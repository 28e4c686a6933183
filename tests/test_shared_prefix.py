"""Rows of a decode batch behind one prompt (PR 50): the run of leading pages
they hold in common, found on the host to be counted
(``engine/runner.py::shared_prefix_run``) and in the decode kernel's first cell to be read
once (``ops/paged_attention_pallas.py::_find_shared_run``), and an engine whose
requests share a prefix: the same tokens whether the kernel takes the run or
walks every row, and the two counters and the ``pst.step_info`` fields by
what the batches held. The kernel's own tests are in
``tests/test_paged_attention.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from types import SimpleNamespace

from production_stack_tpu.engine.runner import shared_prefix_run
from production_stack_tpu.obs.engine_telemetry import ENGINE_TELEMETRY
from production_stack_tpu.ops import paged_attention_pallas as pap
from production_stack_tpu.ops.attention import decode_sharing_calls
from production_stack_tpu.parallel.mesh import AXIS_DATA, AXIS_TENSOR

from . import model_contract as contract
from .model_contract import run
from .test_paged_attention import program_run

BS = 8


def _tables(rows):
    width = max(len(r) for r in rows)
    return np.array([list(r) + [0] * (width - len(r)) for r in rows], np.int32)


# name: (a table row a batch row, kv_lens, (pages, rows) shared)
RUNS = {
    "share_nothing": ([[3, 4, 5], [6, 7, 8], [9, 10, 11]], [20, 20, 20], (0, 0)),
    "share_k_pages": (
        [[3, 4, 5, 20], [3, 4, 5, 21], [3, 4, 5, 22]], [30, 27, 25], (3, 3)),
    "differ_in_one_row_only": (
        [[3, 4, 5, 20], [3, 4, 5, 21], [3, 9, 5, 22]], [30, 27, 25], (1, 3)),
    # a finished member of a chain keeps its row, with kv_len 0 and whatever
    # table: it does not shorten the run, and it is not counted
    "holds_a_finished_row": (
        [[3, 4, 5, 20], [0, 0, 0, 0], [3, 4, 5, 22]], [30, 0, 25], (3, 2)),
    "holds_a_finished_row_first": (
        [[7, 7, 7, 7], [3, 4, 5, 21], [3, 4, 5, 22]], [0, 27, 25], (3, 2)),
    # a row that joins without the prefix takes the run to 0 for the batch
    "holds_a_joining_row_without_the_prefix": (
        [[3, 4, 5, 20], [3, 4, 5, 21], [12, 13, 14, 15]], [30, 27, 9], (0, 0)),
    # the tables agree on three pages, but row 1 writes into the third
    "never_reaches_a_rows_last_page": (
        [[3, 4, 5, 20], [3, 4, 5], [3, 4, 5, 22]], [30, 17, 25], (2, 3)),
    "a_row_on_its_first_page_shares_nothing": (
        [[3, 4, 5, 20], [3], [3, 4, 5, 22]], [30, 8, 25], (0, 0)),
    "a_full_last_page_is_still_the_rows_own": (
        [[3, 4, 5], [3, 4, 5]], [24, 24], (2, 2)),
    "one_live_row_shares_with_nobody": (
        [[3, 4, 5, 20], [0, 0, 0, 0]], [30, 0], (0, 0)),
    "no_live_row": ([[0, 0], [0, 0]], [0, 0], (0, 0)),
}


@pytest.mark.parametrize("case", list(RUNS))
def test_the_run_the_host_counts_is_the_run_the_kernel_finds(case):
    rows, lens, want = RUNS[case]
    tables, lens = _tables(rows), np.array(lens, np.int32)
    assert shared_prefix_run(tables, lens, BS) == want
    found = program_run(tables, lens, BS)
    assert found[0] == want[0]
    if want[0]:
        assert lens[found[1]] > 0, "the run is read through a live row's table"
        assert all((lens[lens > 0] - 1) // BS >= want[0])


def test_the_run_is_a_runtime_value_in_one_program():
    """Nothing about the run is static and nothing new crosses to the
    device: whatever the tables hold, a decode call is the same program
    (``runner.compiles_in_window`` stays 0 and the program store keeps its
    entries)."""
    rng = np.random.default_rng(0)
    kv = jnp.asarray(rng.standard_normal((1, 40, 2, BS, 2 * 16)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((4, 1, 4, 16)), jnp.float32)
    call = jax.jit(lambda t, n: pap.pallas_paged_attention(
        q, kv, t, n, (n - 1)[:, None], 0, scale=0.25))
    outs = []
    for rows, lens, _ in RUNS.values():
        tables = np.zeros((4, 8), np.int32)
        t = _tables(rows)
        tables[: t.shape[0], : t.shape[1]] = t
        lens = np.array(list(lens) + [0] * (4 - len(lens)), np.int32)
        outs.append(np.asarray(call(jnp.asarray(tables), jnp.asarray(lens))))
    assert all(np.all(np.isfinite(o)) for o in outs)
    assert call._cache_size() == 1


# ----------------------------------------------------------------------------
# An engine whose requests share a prefix
# ----------------------------------------------------------------------------

PREFIX = [(5 * i + 2) % 97 + 1 for i in range(4 * BS)]  # four whole pages
TAILS = [[11, 12, 13], [21, 22, 23, 24, 25], [31], [41, 42, 43, 44, 45, 46, 47]]


def _decode_steps(seen):
    return [m for k, m in seen if k == "decode"]


def _depth(meta) -> int:
    return int(meta["bucket"].split("xn")[1]) if "xn" in meta["bucket"] else 1


def _context(meta) -> int:
    """Context tokens a dispatch's rows (all live) held, a step each:
    ``kv_tokens`` is what they hold after its last."""
    d, rows = _depth(meta), meta["rows"]
    return (meta["kv_tokens"] - rows * (d - 1)) * d + rows * d * (d - 1) // 2


def _serve_behind_a_prefix(model, monkeypatch, walk_rows: bool):
    """One request leaves the prefix's pages in the cache, then four arrive
    behind it together. -> (their tokens, the decode steps' records, the
    counters' moves)."""
    if walk_rows:  # the run forced to 0 in the program: a walk a row
        monkeypatch.setattr(
            pap, "_find_shared_run", lambda *refs: (jnp.int32(0), jnp.int32(0)))
    eng = contract.make_engine(model, attn_impl="pallas")
    assert eng.cfg.enable_prefix_caching
    run(eng, [PREFIX + [9]], 1, logprobs=None)
    seen = []
    monkeypatch.setattr(
        ENGINE_TELEMETRY, "step_info",
        lambda kind, **meta: seen.append((kind, meta)))
    before = eng.stats()
    got = run(eng, [PREFIX + t for t in TAILS], 10, logprobs=None)
    after = eng.stats()
    moved = {k: after[k] - before[k] for k in (
        "decode_context_tokens_total", "decode_shared_tokens_spared_total")}
    return [g["tokens"] for g in got], _decode_steps(seen), moved


@pytest.mark.parametrize("model", ["tiny-llama-debug", "tiny-ouro-debug"],
                         ids=["dense", "two_passes"])
def test_rows_behind_one_prefix_decode_the_same_with_the_run_as_without(
        model, monkeypatch):
    tokens, steps, moved = _serve_behind_a_prefix(model, monkeypatch, False)
    assert all(len(t) == 10 for t in tokens)
    # what the batches held, by the trace's records
    full = [m for m in steps if m["rows"] == 4 and m["shared_rows"] == 4]
    assert full, "four rows behind the prefix decoded together"
    assert all(m["shared_kv_tokens"] == len(PREFIX) for m in full)
    for m in steps:  # kv_tokens means what it meant: every row's context
        assert m["kv_tokens"] >= m["shared_rows"] * m["shared_kv_tokens"]
    assert moved["decode_context_tokens_total"] == sum(
        _context(m) for m in steps)
    assert moved["decode_shared_tokens_spared_total"] == sum(
        max(m["shared_rows"] - 1, 0) * m["shared_kv_tokens"] * _depth(m)
        for m in steps) >= 3 * len(PREFIX) * len(full)
    share = (moved["decode_shared_tokens_spared_total"]
             / moved["decode_context_tokens_total"])
    assert 0.3 < share < 0.75  # three of four readings of 32 in 35-50 a row
    walked, _, moved_walked = _serve_behind_a_prefix(model, monkeypatch, True)
    assert walked == tokens
    assert moved_walked == moved  # the host counts what the tables hold


def test_rows_with_prompts_of_their_own_share_nothing(monkeypatch):
    seen = []
    monkeypatch.setattr(
        ENGINE_TELEMETRY, "step_info",
        lambda kind, **meta: seen.append((kind, meta)))
    eng = contract.make_engine("tiny-llama-debug", attn_impl="pallas")
    run(eng, [[(7 * i + j) % 101 + 1 for i in range(20 + j)] for j in range(4)],
        6, logprobs=None)
    steps = _decode_steps(seen)
    assert steps and all(
        m["shared_kv_tokens"] == 0 and m["shared_rows"] == 0 for m in steps)
    stats = eng.stats()
    assert stats["decode_shared_tokens_spared_total"] == 0
    assert stats["decode_context_tokens_total"] > 0


# name: (impl, mesh axes, rows, heads, head_dim, window, fused write) -> calls
# as the chip lowers them (under the interpreter every shape shares)
_ON_THE_CHIP = {
    "the_looped_cells_shape": (("pallas", {}, 16, 16, 128, 0, False), 1),
    "the_gather_reference_reads_no_page_once": (
        ("gather", {}, 16, 16, 128, 0, False), 0),
    "a_bucket_under_eight_rows": (("pallas", {}, 4, 32, 128, 0, False), 0),
    "heads_not_in_eights": (("pallas", {}, 16, 12, 128, 0, False), 0),
    "heads_of_64_lanes": (("pallas", {}, 16, 40, 64, 0, False), 0),
    "heads_of_256_lanes": (("pallas", {}, 16, 16, 256, 0, False), 1),
    "every_layer_under_a_window": (("pallas", {}, 16, 32, 128, 4096, False), 0),
    "the_fused_write_walks_a_row": (("pallas", {}, 16, 32, 128, 0, True), 0),
    "a_shard_of_rows_a_call": (
        ("pallas", {AXIS_DATA: 2, AXIS_TENSOR: 2}, 16, 32, 128, 0, False), 2),
    "rows_that_do_not_divide_stay_whole": (
        ("pallas", {AXIS_DATA: 3, AXIS_TENSOR: 1}, 16, 32, 128, 0, False), 1),
    "a_shards_heads_not_in_eights": (
        ("pallas", {AXIS_TENSOR: 8}, 16, 32, 128, 0, False), 0),
    "a_shards_rows_under_eight": (
        ("pallas", {AXIS_DATA: 4}, 16, 32, 128, 0, False), 0),
}


@pytest.mark.parametrize("case", list(_ON_THE_CHIP))
def test_the_count_asks_the_rule_the_calls_trace_by(case, monkeypatch):
    (impl, axes, rows, heads, hd, window, fused), want = _ON_THE_CHIP[case]
    monkeypatch.setattr(pap, "pallas_interpret", lambda: False)
    monkeypatch.setenv("PST_FUSED_KV_WRITE", "1" if fused else "0")
    mesh = SimpleNamespace(shape=axes) if axes else None
    assert decode_sharing_calls(impl, mesh, rows, heads, hd, window) == want
    if impl == "pallas" and not fused and not axes:
        # one rule: what the call traces by, and its first cell tests
        assert bool(pap.decode_shares(rows, heads, hd, window)) == bool(want)


def _dispatch(eng, lens, tables, kv_ahead):
    """A decode dispatch of ``kv_ahead + 1`` steps as `_step_info` is told
    of it. -> (its trace record, the two counters' moves)."""
    runner, seen = eng.runner, {}
    seqs = [SimpleNamespace(block_ids=[p for p in row if p]) for row in tables]
    batch = {"kv_lens": np.array(lens + [0], np.int32),  # a padding row
             "block_tables": _tables(tables + [[0]])}
    before = (runner.decode_context_tokens_total,
              runner.decode_shared_tokens_spared_total)
    orig = ENGINE_TELEMETRY.step_info
    ENGINE_TELEMETRY.step_info = lambda kind, **meta: seen.update(meta)
    try:
        runner._step_info("decode", f"b4xn{kv_ahead + 1}", seqs, batch,
                          len(seqs) * (kv_ahead + 1), kv_ahead)
    finally:
        ENGINE_TELEMETRY.step_info = orig
    return seen, (runner.decode_context_tokens_total - before[0],
                  runner.decode_shared_tokens_spared_total - before[1])


@pytest.fixture(scope="module")
def engines():
    return {impl: contract.make_engine("tiny-llama-debug", attn_impl=impl)
            for impl in ("pallas", "gather")}


_SHARED_4 = [[3, 4, 5, 6, 20], [3, 4, 5, 6, 21], [3, 4, 5, 6, 22]]


@pytest.mark.parametrize("case, lens, kv_ahead, want", [
    # (shared tokens, kv_tokens, context, spared)
    ("one_step", [41, 38, 35], 0, (32, 114, 114, 64)),
    # three steps: 114 + 117 + 120 held, the run read 3 times for 3 rows
    ("a_burst_of_three", [41, 38, 35], 2, (32, 120, 351, 192)),
    # row 2 is on its fourth page at the burst's first step and on its
    # fifth at its last: the first step shares three pages, and that is
    # what is counted
    ("the_run_of_the_bursts_first_step", [41, 38, 32], 2, (24, 117, 342, 144)),
    ("a_finished_member", [41, 0, 35], 2, (32, 80, 234, 96)),
])
def test_a_dispatch_counts_what_its_first_step_shares(
        engines, case, lens, kv_ahead, want):
    meta, moved = _dispatch(engines["pallas"], lens, _SHARED_4, kv_ahead)
    live = sum(1 for n in lens if n)
    assert (meta["shared_kv_tokens"], meta["kv_tokens"]) == want[:2]
    assert meta["shared_rows"] == live
    assert moved == want[2:]


def test_nothing_is_counted_as_spared_where_no_call_shares(
        engines, monkeypatch):
    """The gather reference reads every row's pages, and so do calls of a
    shape the phase does not take: the record and the counter say 0
    whatever the tables hold, and the context is counted all the same."""
    meta, moved = _dispatch(engines["gather"], [41, 38, 35], _SHARED_4, 0)
    assert (meta["shared_kv_tokens"], meta["shared_rows"]) == (0, 0)
    assert moved == (114, 0)
    eng = contract.make_engine("tiny-llama-debug", attn_impl="pallas")
    monkeypatch.setattr(pap, "decode_shares", lambda *shape: False)
    meta, moved = _dispatch(eng, [41, 38, 35], _SHARED_4, 0)
    assert (meta["shared_kv_tokens"], meta["shared_rows"]) == (0, 0)
    assert moved == (114, 0)


def test_the_server_exports_the_shared_read_counters(monkeypatch):
    from prometheus_client import generate_latest

    from production_stack_tpu.engine.server import EngineMetrics

    eng = contract.make_engine("tiny-llama-debug", attn_impl="pallas")
    run(eng, [PREFIX + [9]], 1, logprobs=None)
    run(eng, [PREFIX + t for t in TAILS], 4, logprobs=None)
    metrics, stats = EngineMetrics("m"), eng.stats()
    metrics.refresh(stats)
    text = generate_latest(metrics.registry).decode()
    for series, key in (
            ("pst:decode_context_tokens_total", "decode_context_tokens_total"),
            ("pst:decode_shared_tokens_spared_total",
             "decode_shared_tokens_spared_total")):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(series + '{model_name="m"}'))
        assert float(line.split()[-1]) == stats[key] > 0
