"""Multi-round sessions: every session carries a system prompt shared by
all and a history of its own, built (prefilled) in set-up; a turn appends a
question and generates an answer, and both join the session's context.

Open loop (``rate``, turns/s): turns are due on a Poisson schedule and go to
the session that has been idle longest. Closed loop (``"loop": "closed"``,
``pool`` prepared turns): every session is a user who asks the next
question as soon as the last answer is complete, which is how the
reference's multi-round QA drives a server.

Mix parameters: ``sessions``, ``shared_prefix_tokens``, ``history_tokens``,
``question_tokens``, ``output_tokens``, and ``rate`` or ``loop`` + ``pool``.
"""

from __future__ import annotations

from . import common


def plan(mix: dict, seed: int, seconds: float, vocab: int) -> dict:
    closed = mix.get("loop") == "closed"
    n = int(mix["pool"]) if closed else max(int(round(mix["rate"] * seconds)), 1)
    n_sessions = int(mix["sessions"])
    shared = common.tokens(
        int(mix["shared_prefix_tokens"]), vocab, common.rng_for(seed, "shared"))
    h_len = common.quantile_lengths(
        mix["history_tokens"], n_sessions, common.rng_for(seed, "history_len"))
    h_content = common.rng_for(seed, "history")
    sessions = [shared + common.tokens(h, vocab, h_content) for h in h_len]
    q_len = common.quantile_lengths(
        mix["question_tokens"], n, common.rng_for(seed, "question_len"))
    o_len = common.quantile_lengths(
        mix["output_tokens"], n, common.rng_for(seed, "output_len"))
    due = ([0.0] * n if closed else
           common.poisson_due_times(n, seconds, common.rng_for(seed, "due")))
    content = common.rng_for(seed, "content")
    requests = [
        {"due": due[i], "append": common.tokens(q_len[i], vocab, content),
         "max_tokens": o_len[i]}
        for i in range(n)
    ]
    return {"mode": "closed" if closed else "open", "clients": n_sessions,
            "setup_prompts": [], "sessions": sessions, "requests": requests,
            "shared_prefix": shared}
