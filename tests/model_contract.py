"""One harness for what every model class owes the engine: the tiny engine
the model files build, the loop that drives it by hand, and the two
comparisons they make. ``tests/test_nemotron_h.py``, ``test_glm4_moe_lite.py``,
``test_phi4flash.py`` and ``test_qwen3_next.py`` ask the same questions of
their class through these (chunked prefill then chained decode against the
class's reference, chunk sizes, short prompts, packed rows against lone rows,
arrivals joining the chain, preemption by recompute).

A test that does not test an engine setting leaves it to ``DEFAULTS``: a step
program is compiled once for a model and a set of these (``tests/conftest.py``
keeps compiled programs for the session), and a test that reads an engine
without changing it takes the file's module-scoped one.
"""

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams

DEFAULTS = dict(
    max_model_len=256, block_size=8, num_kv_blocks=96, max_num_seqs=4,
    max_prefill_tokens=16, kv_swap=False,
)


def make_engine(model: str, **over) -> LLMEngine:
    return LLMEngine(EngineConfig(**{**DEFAULTS, "model": model, **over}))


def run(eng, prompts, n_tokens, stagger=0, logprobs=5, watch=None):
    """Drive ``eng`` by hand: request i arrives after ``stagger * i`` steps.
    ``watch(seq)`` is called for every sequence that has arrived before
    every step. -> per request ``{"tokens", "logprobs": [{id: lp}], "seq"}``."""
    sp = SamplingParams(max_tokens=n_tokens, temperature=0.0, ignore_eos=True,
                        logprobs=logprobs)
    res, pending, steps = {}, list(enumerate(prompts)), 0
    while pending or eng.has_work():
        while pending and steps >= stagger * pending[0][0]:
            i, p = pending.pop(0)
            res[f"r{i}"] = {"tokens": [], "logprobs": [],
                            "seq": eng.add_request(
                                f"r{i}", prompt_token_ids=list(p), sampling=sp)}
        if watch is not None:
            for r in res.values():
                watch(r["seq"])
        for out in eng.step():
            r = res[out.request_id]
            r["tokens"].extend(out.new_token_ids)
            for lp in out.logprobs or []:
                at = dict(lp["top"])
                at[lp["token_id"]] = lp["logprob"]
                r["logprobs"].append(at)
        steps += 1
        assert steps < 4000, "the engine makes no progress"
    return [res[f"r{i}"] for i in range(len(prompts))]


def assert_same(a, b, tol=1e-3):
    """Two runs of one request: the same tokens, and every reported
    log-probability within ``tol``."""
    assert a["tokens"] == b["tokens"]
    for x, y in zip(a["logprobs"], b["logprobs"]):
        assert all(abs(x[t] - y[t]) < tol for t in x)


def assert_matches_reference(reference, params, prompt, got, tol=2e-3):
    """Every log-probability ``got`` reports is the reference's:
    ``reference(params, prompt, tokens)`` -> one row of log-probabilities
    over the vocabulary for each generated token."""
    rows = reference(params, list(prompt), got["tokens"])
    assert len(got["logprobs"]) == len(got["tokens"]) == len(rows)
    for j, at in enumerate(got["logprobs"]):
        for tid, lp in at.items():
            assert abs(rows[j][tid] - lp) < tol, (j, tid, rows[j][tid], lp)


def assert_dispatch_counts_exported(eng):
    """An engine whose model goes through ``models/moe_dispatch.py`` has run:
    all six of the dispatch's counts come out of ``step_aux`` into the
    engine's stats and from there onto ``/metrics``, and the sixth, the
    expert layers whose held pairs passed the row capacity, reads 0 where
    a step's pairs are one row tile (every tiny test configuration)."""
    from prometheus_client import generate_latest

    from production_stack_tpu.engine.server import EngineMetrics
    from production_stack_tpu.models import moe_dispatch

    assert eng.runner.aux_names[:moe_dispatch.AUX_WIDTH] == moe_dispatch.AUX_NAMES
    metrics, stats = EngineMetrics("m"), eng.stats()
    metrics.refresh(stats)
    text = generate_latest(metrics.registry).decode()
    for key in moe_dispatch.AUX_NAMES:
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(f'pst:{key}{{model_name="m"}}'))
        assert float(line.split()[-1]) == stats[key]
    assert stats["moe_layer_steps_total"] > 0
    assert stats["moe_dispatch_overflow_total"] == 0

