"""Closed-loop clients: each sends its next request when the last one has
completed, for the whole window. Callers of a batch API look like this.

Mix parameters: ``clients``, ``shared_prefix_tokens`` (may be 0),
``prompt_tokens``, ``output_tokens``, ``pool`` (how many requests are
prepared; more than the window can complete, and the same multiset of
sizes for every seed).
"""

from __future__ import annotations

from . import common


def plan(mix: dict, seed: int, seconds: float, vocab: int) -> dict:
    n = int(mix["pool"])
    shared = common.tokens(
        int(mix.get("shared_prefix_tokens", 0)), vocab,
        common.rng_for(seed, "shared"))
    p_len = common.quantile_lengths(
        mix["prompt_tokens"], n, common.rng_for(seed, "prompt_len"))
    o_len = common.quantile_lengths(
        mix["output_tokens"], n, common.rng_for(seed, "output_len"))
    content = common.rng_for(seed, "content")
    requests = [
        {"due": 0.0, "prompt": shared + common.tokens(p_len[i], vocab, content),
         "max_tokens": o_len[i]}
        for i in range(n)
    ]
    return {"mode": "closed", "clients": int(mix["clients"]),
            "setup_prompts": [shared] if shared else [], "sessions": [],
            "requests": requests, "shared_prefix": shared}
