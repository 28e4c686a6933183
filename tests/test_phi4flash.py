"""The decoder-hybrid-decoder class (``models/phi4flash.py``: Mamba-1, window
and full differential attention, gated memory units, cross-attention) on the
engine's normal path, against the benchmark's plain reference
(``perf/reference/phi4flash.py``: float32, every layer on every token,
nothing of the program's forward pass), at tiny widths with the published
layer map's shape: Mamba / window at 0-3, Mamba 4 handing on ``m``, full
attention 5, a gated memory unit 6, cross-attention 7; hidden 64, window 16,
pages of 8.

What the benchmark's ``correct`` cannot see is here: rows against each other
(packed prefill, slots), the window group's pages released on the way with
the block table holding freed entries, the allocator's bound, the kernels
against their ``jax.numpy`` forms.
"""

import functools
import json
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import phi4flash as reference
from production_stack_tpu.engine.config import EngineConfig, window_block_count
from production_stack_tpu.engine.kv_manager import BlockAllocator
from production_stack_tpu.engine.sequence import SamplingParams, Sequence
from production_stack_tpu.models.base import ModelConfig
from production_stack_tpu.models.phi4flash import Phi4Flash
from production_stack_tpu.models.registry import PRESETS
from production_stack_tpu.ops import selective_scan as scan

from . import model_contract as contract
from .model_contract import assert_same, run

CFG = PRESETS["tiny-phi4flash-debug"]
HF = {"num_hidden_layers": CFG.num_layers,
      "num_attention_heads": CFG.num_heads,
      "num_key_value_heads": CFG.num_kv_heads,
      "sliding_window": CFG.sliding_window,
      "layer_norm_eps": CFG.layer_norm_eps}
PROMPT = [3, 17, 98, 25, 42, 7, 11, 20, 15, 31, 8, 77, 12, 5, 9, 2, 33, 44, 99,
          100, 101, 64, 65, 1, 90, 13, 14, 6, 120, 50, 51, 52, 53, 54, 55, 56,
          57, 58, 59, 60, 61, 62, 63, 66, 67, 68, 69, 70, 71, 72, 73, 74, 75]


make_engine = functools.partial(
    contract.make_engine, "tiny-phi4flash-debug", enable_prefix_caching=False)


@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.fixture(scope="module")
def params(engine):
    return engine.runner.params


def reference_logprobs(params, ids, n_prompt, n_gen, variant="none"):
    with jax.default_matmul_precision("highest"):
        (lps, gap), = reference.teacher_force(
            types.SimpleNamespace(hf=HF), params,
            [{"tokens": list(ids), "n_prompt": n_prompt,
              "want": [[0]] * n_gen}], variant)
    assert gap is None
    return lps


assert_matches_reference = functools.partial(
    contract.assert_matches_reference,
    lambda params, prompt, tokens: reference_logprobs(
        params, prompt + tokens, len(prompt), len(tokens)))


# ----------------------------------------------------------------------------
# The engine's normal path against the reference's full forward pass
# ----------------------------------------------------------------------------


def test_chunked_prefill_then_decode_with_pages_released(engine, params):
    """53 prompt tokens in chunks of 16 (more than three windows of 16),
    then chained decode through the caches: every reported log-probability
    is the reference's, window-group pages were released on the way and the
    sequence's table of that group holds freed entries."""
    seen = {"released": 0, "held": 0}

    def watch(seq):
        seen["released"] = max(seen["released"], seq.window_released)
        seen["held"] = max(
            seen["held"], len(seq.window_block_ids) - seq.window_released)
        assert all(b == 0 for b in seq.window_block_ids[:seq.window_released])

    got = run(engine, [PROMPT], 8, watch=watch)[0]
    assert len(got["tokens"]) == 8
    assert_matches_reference(params, PROMPT, got)
    assert engine.pipelined_bursts_total > 0, "decode must run chained"
    assert seen["released"] >= 4, "pages below the window must be released"
    assert seen["held"] <= engine.allocator.window_bound(16)
    stats = engine.stats()
    assert stats["window_pages_released_total"] >= 4
    assert stats["window_pages_in_use"] == 0 and stats["kv_pages_in_use"] == 0
    # three chunks of 16 and one of 5; the cross-decoder ran in the last
    # alone, on its one position
    assert stats["prefill_tokens_total"] == len(PROMPT)
    assert stats["cross_decoder_positions_total"] == 1
    assert stats["prefill_bucket_positions_total"] == 3 * 16 + 8  # 5 in a bucket of 8


@pytest.mark.parametrize("chunk", [8, 48, 64])
def test_chunk_size_does_not_change_the_logits(chunk, params):
    """The same prompt in chunks of 8 (every chunk inside one window page),
    48 and whole; the synchronous loop once."""
    eng = make_engine(max_prefill_tokens=chunk, overlap_decode=chunk != 48)
    got = run(eng, [PROMPT], 4)[0]
    assert_matches_reference(params, PROMPT, got)


def test_decode_slides_the_window_and_releases_pages(params):
    """A short prompt and 44 decoded tokens: the window slides over its own
    outputs, pages go back during decode, residency stays at the bound."""
    eng = make_engine()
    held = []
    got = run(eng, [PROMPT[:6]], 44, watch=lambda s: held.append(
        len(s.window_block_ids) - s.window_released))[0]
    assert_matches_reference(params, PROMPT[:6], got)
    assert eng.allocator.window_pages_released >= 3
    assert max(held) <= eng.allocator.window_steady


def test_short_prompts_and_one_token_chunks(params):
    """Prompts shorter than the convolution's tail, and a chunk of one
    token that is a sequence's first (the decode path from zeros)."""
    eng = make_engine(max_prefill_tokens=8)
    prompts = [[5], [9, 2], PROMPT[:9]]
    for p, got in zip(prompts, run(eng, prompts, 5)):
        assert_matches_reference(params, p, got)


def test_packed_rows_of_unequal_length_match_their_lone_runs(params):
    """Five sequences of different lengths arrive two steps apart into
    three rows: packed and padded prefill steps, decode batches that grow
    and shrink, slots and window pages taken again after a finish."""
    prompts = [PROMPT[:n] for n in (37, 5, 53, 18, 26)]
    n_out = 7
    eng = make_engine(max_num_seqs=3, max_prefill_tokens=32)
    together = run(eng, prompts, n_out, stagger=2)
    lone_eng = make_engine(max_num_seqs=3, max_prefill_tokens=32)
    for p, got in zip(prompts, together):
        lone = run(lone_eng, [p], n_out)[0]
        assert_same(got, lone)
        assert_matches_reference(params, p, got)
    assert eng.allocator.state_slots_in_use == 0
    assert eng.allocator.window_pages_in_use == 0


def test_arrivals_join_the_running_chain_through_all_three_caches(params):
    """Eight sequences arrive three steps apart under a chain of four rows
    (one prompt in two chunks): each joins behind its own prefill with no
    drain, its row of ``window_tables`` sent with the rest of the batch; a
    finished member's slot, window pages and global pages come back a burst
    later while the chain runs on. Tokens and log-probabilities are the
    synchronous loop's."""
    prompts = [PROMPT[:n] for n in (37, 5, 53, 18, 26, 11, 44, 9)]
    kw = dict(max_num_seqs=4, min_decode_bucket=4, max_prefill_tokens=32)
    sync = run(make_engine(overlap_decode=False, **kw), prompts, 9, stagger=3)
    eng = make_engine(**kw)
    step, held = eng.step, []

    def checked_step():
        outs = step()
        if not sum(eng.pipeline_breaks.values()):  # the chain never drained
            # slots and window pages are held by running sequences and by
            # members that finished under the burst in flight, no one else
            owners = eng.scheduler.running + [
                s for _, s in eng._burst_deferred]
            assert eng.allocator.state_slots_in_use == len(owners)
            assert eng.allocator.window_pages_in_use == sum(
                len(s.window_block_ids) - s.window_released for s in owners)
            held.append(len(eng._burst_deferred))
        return outs

    eng.step = checked_step
    got = run(eng, prompts, 9, stagger=3)
    assert 0 < max(held) <= 2 and held.count(0) > len(held) // 2
    for a, b in zip(got, sync):
        assert_same(a, b)
    assert eng.chain_kept_prefills_total >= 7
    assert eng.pipeline_breaks["prefill"] == 0
    assert sum(eng.pipeline_breaks.values()) == 1, eng.pipeline_breaks
    assert eng.allocator.state_slots_in_use == 0
    assert eng.allocator.window_pages_in_use == 0
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_preemption_by_recompute_returns_the_same_tokens(params):
    """Twelve global pages: two 40-token prompts admit and one must lose
    its pages of both groups and its slot while decoding; it starts again
    from zeros and gives the tokens of a roomy engine."""
    p1, p2 = PROMPT[:40], PROMPT[5:45]
    tight = make_engine(num_kv_blocks=12, max_model_len=128, max_prefill_tokens=48)
    got = run(tight, [p1, p2], 10)
    assert tight.num_preempted_total > 0, "the test must exercise preemption"
    roomy = run(make_engine(max_prefill_tokens=48), [p1, p2], 10)
    for p, a, b in zip((p1, p2), got, roomy):
        assert a["tokens"] == b["tokens"]
        assert_matches_reference(params, p, a)
    assert tight.allocator.state_slots_in_use == 0
    assert tight.allocator.window_pages_in_use == 0


@pytest.mark.parametrize("variant", reference.VARIANTS[1:])
def test_every_negative_control_moves_the_reference(variant, params):
    ids = PROMPT + PROMPT[:11]
    sound = reference_logprobs(params, ids, len(PROMPT), 12)
    broken = reference_logprobs(params, ids, len(PROMPT), 12, variant)
    moved = np.abs(sound - broken).max()
    # the precision controls move little at these widths, the equations much
    assert moved > (1e-5 if variant in ("state_bf16", "kv_fp8") else 1e-2), moved


# ----------------------------------------------------------------------------
# The model's pieces
# ----------------------------------------------------------------------------


def _step_inputs(model, lens, T, bs=8, slots=3, nb=32):
    B = len(lens)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (B, T)), jnp.int32)
    pos = np.zeros((B, T), np.int32)
    write = np.full((B, T), nb * bs, np.int32)
    W = -(-T // bs)
    tables = np.zeros((B, W), np.int32)
    wtables = np.zeros((B, W), np.int32)
    for i, n in enumerate(lens):
        tables[i] = 1 + i * W + np.arange(W)
        wtables[i] = 2 + (B - i) * W + np.arange(W)
        pos[i, :n] = np.arange(n)
        pos[i, n:] = max(n - 1, 0)
        write[i, :n] = tables[i][np.arange(n) // bs] * bs + np.arange(n) % bs
    cache = model.make_kv_cache(nb, bs, None, state_slots=slots,
                                window_blocks=nb)
    lens = np.asarray(lens, np.int32)
    return dict(
        tokens=tokens, positions=jnp.asarray(pos), write_idx=jnp.asarray(write),
        block_tables=jnp.asarray(tables), kv_lens=jnp.asarray(lens),
        last_idx=jnp.asarray(np.maximum(lens - 1, 0)), cache=cache,
        state_slots=jnp.arange(B, dtype=jnp.int32),
        window_tables=jnp.asarray(wtables))


def test_skipped_cross_decoder_equals_the_unskipped_at_sampled_positions(params):
    """A packed prefill step of rows of 24, 9 and 0 tokens: the logits of
    each row's last position with the cross-decoder on that position alone
    are those of the step that runs every layer on every token."""
    model = Phi4Flash(CFG)
    lens = [24, 9, 0]
    a = _step_inputs(model, lens, 24)
    b = _step_inputs(model, lens, 24)
    cache_in = a.pop("cache"), b.pop("cache")
    args = ("tokens", "positions", "write_idx", "block_tables", "kv_lens",
            "last_idx")
    skipped, ca = model.forward(
        params, *(a[k] for k in args), cache_in[0],
        state_slots=a["state_slots"], window_tables=a["window_tables"])
    whole, cb = model.forward(
        params, *(b[k] for k in args), cache_in[1],
        state_slots=b["state_slots"], window_tables=b["window_tables"],
        all_logits=True)
    for i, n in enumerate(lens[:2]):
        np.testing.assert_allclose(skipped[i], whole[i, n - 1], atol=2e-5)
    # what each step says it ran the cross-decoder on, counted from the batch
    # that branch was handed: a row's one position, or every position
    assert model.AUX_NAMES == ("cross_decoder_positions_total",)
    assert float(model.step_aux(ca)[0]) == len(lens)
    assert float(model.step_aux(cb)[0]) == len(lens) * 24
    for k in set(ca) - {"aux"}:  # the caches the two steps leave are the same
        np.testing.assert_array_equal(ca[k], cb[k])
    # a step none of whose rows is sampled from runs it on none
    c = _step_inputs(model, lens, 24)
    _, cc = model.forward(
        params, *(c[k] for k in args), c.pop("cache"),
        state_slots=c["state_slots"], window_tables=c["window_tables"],
        sample_rows=jnp.zeros(len(lens), bool))
    assert float(model.step_aux(cc)[0]) == 0
    for k in set(ca) - {"aux"}:
        np.testing.assert_array_equal(ca[k], cc[k])


def test_paired_heads_equal_the_four_product_form():
    """Keys stored as pairs ``[k1 | k2]``, queries ``[q1 | 0]`` and ``[0 |
    q2]`` through the paged attention: ``combine`` of that is the
    reference's differential attention written as four products."""
    model = Phi4Flash(CFG)
    rng = np.random.default_rng(2)
    T, bs, D = 21, 8, CFG.hidden_size
    h = jnp.asarray(rng.normal(size=(1, T, D)), jnp.float32)
    lp = {
        "wq": rng.normal(size=(D, CFG.q_size)) / 8,
        "bq": 0.1 * rng.normal(size=(CFG.q_size,)),
        "wkv": rng.normal(size=(D, 2 * CFG.kv_size)) / 8,
        "bkv": 0.1 * rng.normal(size=(2 * CFG.kv_size,)),
        "wo": rng.normal(size=(CFG.q_size, D)) / 8,
        "bo": 0.1 * rng.normal(size=(D,)),
        "subln": 1 + 0.1 * rng.normal(size=(2 * CFG.head_dim,)),
        "ln1_w": np.ones(D), "ln1_b": np.zeros(D),
        **{f"lambda_{n}": 0.3 * rng.normal(size=(CFG.head_dim,))
           for n in ("q1", "k1", "q2", "k2")},
    }
    lp = {k: jnp.asarray(v, jnp.float32) for k, v in lp.items()}
    layer, window = 3, 8
    eps = CFG.layer_norm_eps
    x = h[0]  # the block's input; both sides normalise it first
    hn = reference._ln(x, lp["ln1_w"], lp["ln1_b"], eps)[None]
    tables = jnp.asarray([[4, 2, 5]], jnp.int32)
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    flat = tables[0][pos[0] // bs] * bs + pos[0] % bs
    with jax.default_matmul_precision("highest"):
        pages = model._write_pages(
            lp, hn, jnp.zeros((1, 6, 2, bs, CFG.kv_size), jnp.float32), 0, flat)
        served = model._diff_attention(
            lp, hn, pages, 0, tables, jnp.asarray([T]), pos, layer, "gather",
            window=window)[0]
        kv = reference.keys_values(
            x, lp, pairs=CFG.num_kv_heads // 2, eps=eps, kv_fp8=False)
        plain = reference.diff_attention(
            x, lp, kv, reference.lambda_init(layer),
            q_pairs=CFG.num_heads // 2, window=window, eps=eps,
            lambda_off=False) - x
    np.testing.assert_allclose(served, plain, atol=5e-4, rtol=1e-4)


def test_mamba_chunks_inside_the_convolutions_reach_equal_the_whole(params, monkeypatch):
    """One row in chunks of 5, 2, 1 and 9 positions (boundaries inside the
    convolution's reach of 3) through the interpreted kernels, state and
    tail carried by the slot: the outputs are the whole row's through
    ``scan_reference``, another slot's state stays as it was."""
    model = Phi4Flash(CFG)
    lp = {k: v[0] for k, v in params["layers"]["self_mamba"].items()}
    rng = np.random.default_rng(4)
    T = 17
    x = jnp.asarray(rng.normal(size=(1, T, CFG.hidden_size)), jnp.float32)

    def rows(n, first):
        return (jnp.asarray([1]), jnp.asarray([n]),
                jnp.arange(n)[None] < n, jnp.asarray([not first]))

    def pools():
        c = model.make_kv_cache(1, 8, None, state_slots=2)
        return c["ssm"] + 7.0, c["conv"] + 7.0

    pool, tails = pools()
    whole, y_whole, _, _ = model._mamba(lp, x, pool, tails, 1, rows(T, True))
    monkeypatch.setattr(scan, "use_kernels", lambda: True)
    pool, tails = pools()
    outs, ys, at = [], [], 0
    for n in (5, 2, 1, 9):
        o, y, pool, tails = model._mamba(
            lp, x[:, at:at + n], pool, tails, 1, rows(n, at == 0))
        outs.append(o)
        ys.append(y)
        at += n
    np.testing.assert_allclose(jnp.concatenate(outs, 1), whole, atol=2e-5)
    np.testing.assert_allclose(jnp.concatenate(ys, 1), y_whole, atol=2e-5)
    np.testing.assert_array_equal(pool[1, 0], np.full_like(pool[1, 0], 7.0))
    np.testing.assert_array_equal(pool[0], np.full_like(pool[0], 7.0))


def _scan_inputs(B, T, Di=256, N=16, L=2, S=5, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    return dict(
        pool=f(L, S, N, Di), u=f(B, T, Di), dt=0.1 * jnp.abs(f(B, T, Di)),
        a_t=-jnp.exp(f(N, Di)), bm=f(B, T, N), cm=f(B, T, N),
        d=jnp.ones((Di,), jnp.float32))


@pytest.mark.parametrize("T,lens", [(21, (21, 9, 0)), (8, (8, 8, 3)),
                                    (300, (300, 257, 1))])
def test_prefill_kernel_equals_the_recurrence(T, lens):
    """Packed rows each from their own slot, one from zeros, one that is
    padding; chunks of 256 positions carry the state over (T = 300). Slots
    no row names, and the other layer, stay bit for bit."""
    x = _scan_inputs(3, T, seed=T)
    slots, keep = jnp.asarray([3, 0, 4]), jnp.asarray([1, 0, 1])
    lens = jnp.asarray(lens)
    valid = jnp.arange(T)[None, :] < lens[:, None]
    dt = jnp.where(valid[..., None], x["dt"], 0.0)
    s0 = jnp.where(keep[:, None, None] != 0, x["pool"][1, slots], 0.0)
    y_ref, s_ref = scan.scan_reference(
        s0, x["u"], dt, x["a_t"], x["bm"], x["cm"], x["d"])
    y, pool = scan.selective_scan_prefill(
        x["pool"], 1, slots, keep, lens, x["u"], dt, x["a_t"], x["bm"],
        x["cm"], x["d"])
    np.testing.assert_allclose(
        jnp.where(valid[..., None], y, 0.0),
        jnp.where(valid[..., None], y_ref, 0.0), atol=2e-4, rtol=1e-4)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(pool[1, slots], s_ref, atol=2e-4, rtol=1e-4)
    np.testing.assert_array_equal(pool[0], x["pool"][0])
    np.testing.assert_array_equal(pool[1, jnp.asarray([1, 2])],
                                  x["pool"][1, jnp.asarray([1, 2])])


def test_decode_kernel_equals_the_step_and_leaves_other_slots():
    x = _scan_inputs(3, 1, seed=7)
    slots, keep = jnp.asarray([3, 0, 4]), jnp.asarray([1, 0, 1])
    s0 = jnp.where(keep[:, None, None] != 0, x["pool"][1, slots], 0.0)
    y_ref, s_ref = scan.scan_reference(
        s0, x["u"], x["dt"], x["a_t"], x["bm"], x["cm"], x["d"])
    y, pool = scan.selective_scan_decode(
        x["pool"], 1, slots, keep, x["u"][:, 0], x["dt"][:, 0], x["a_t"],
        x["bm"][:, 0], x["cm"][:, 0], x["d"])
    np.testing.assert_allclose(y, y_ref[:, 0], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(pool[1, slots], s_ref, atol=1e-6, rtol=1e-6)
    np.testing.assert_array_equal(pool[0], x["pool"][0])
    np.testing.assert_array_equal(pool[1, jnp.asarray([1, 2])],
                                  x["pool"][1, jnp.asarray([1, 2])])


# ----------------------------------------------------------------------------
# The cache manager's window group
# ----------------------------------------------------------------------------


def _sequence(n_prompt):
    return Sequence("s", list(range(n_prompt)), SamplingParams(max_tokens=1))


@pytest.mark.parametrize("chunk", [1, 100, 1024])
def test_a_sequence_of_forty_windows_stays_inside_the_bound(chunk):
    """512-token window, 128-token pages: through 40 windows in chunks of
    ``chunk`` a sequence never holds more than ``ceil(512 / 128) + 1`` pages
    between steps, nor more than ``window_bound(chunk)`` while a chunk is
    written; finished, it returns every page and its slot."""
    alloc = BlockAllocator(256, 128, False, state_slots=2, window_blocks=32,
                           window_tokens=512)
    seq = _sequence(40 * 512)
    assert alloc.take_state_slot(seq)
    between, during = 0, 0
    while seq.num_computed_tokens < 40 * 512:
        end = seq.num_computed_tokens + chunk
        alloc.advance_window(seq, end)
        during = max(during, len(seq.window_block_ids) - seq.window_released)
        assert alloc.window_pages_in_use == (
            len(seq.window_block_ids) - seq.window_released)
        # every token the next query may see still has its page
        assert seq.window_released <= max(
            seq.num_computed_tokens - 511, 0) // 128
        seq.num_computed_tokens = end
        alloc.trim_window(seq)
        between = max(
            between, len(seq.window_block_ids) - seq.window_released)
    assert between <= 512 // 128 + 1 < alloc.window_steady
    assert during <= alloc.window_bound(chunk)
    assert alloc.window_pages_released >= 40 * 4 - 5
    alloc.release_sequence(seq)
    assert alloc.window_pages_in_use == 0 and alloc.state_slots_in_use == 0
    assert sorted(alloc.window._free) == list(range(32))
    assert alloc.window_pages_cached == 0  # no hash is kept with the cache off


def test_window_group_exhaustion_is_the_allocators_error():
    from production_stack_tpu.engine.kv_manager import NoFreeBlocksError

    alloc = BlockAllocator(64, 8, False, window_blocks=10, window_tokens=16)
    a, b = _sequence(64), _sequence(64)
    alloc.advance_window(a, 64)  # 8 pages
    with pytest.raises(NoFreeBlocksError):
        alloc.advance_window(b, 24)
    assert b.window_block_ids == []  # all or nothing
    alloc.release_sequence(a)
    alloc.advance_window(b, 24)
    assert len(b.window_block_ids) == 3 and alloc.window_pages_in_use == 3
    # a model without the group: nothing to advance, nothing held
    plain = BlockAllocator(64, 8, False)
    plain.advance_window(b, 64)
    assert plain.window_pages_in_use == 0


def test_the_window_group_is_sized_from_the_model_and_the_engines_limits(engine):
    cfg = EngineConfig(model="tiny-phi4flash-debug", block_size=8,
                       max_num_seqs=4, max_prefill_tokens=16)
    assert window_block_count(cfg, CFG) == 4 * (2 + 2) + 2 * 2
    assert window_block_count(cfg, PRESETS["tiny-llama-debug"]) == 0
    eng = engine
    assert eng.runner.window_blocks == 20
    assert eng.runner.kv_cache["wkv"].shape[:2] == (CFG.num_window_layers, 20)
    assert eng.runner.kv_cache["kv"].shape[:2] == (1, 96)
    assert eng.runner.kv_cache["ssm"].shape == (
        CFG.num_mamba_layers, eng.runner.state_slots + 1, 16, 128)


# ----------------------------------------------------------------------------
# Start-up: what is refused, and the configuration's door
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("over,flag", [
    (dict(enable_prefix_caching=True), "--enable-prefix-caching"),
    (dict(kv_swap=True), "--kv-swap"),
    (dict(cpu_offload_blocks=8), "--cpu-offload-blocks"),
    (dict(remote_kv_url="http://x"), "--remote-kv-url"),
    (dict(kv_role="producer"), "--kv-role"),
    (dict(speculative_ngram=3), "--speculative-ngram"),
    (dict(enable_lora=True), "--enable-lora"),
    (dict(tensor_parallel_size=2), "--tensor-parallel-size"),
    (dict(pipeline_parallel_size=2), "--pipeline-parallel-size"),
    (dict(data_parallel_size=2), "--data-parallel-size"),
    (dict(quantization="int8"), "--quantization"),
    (dict(kv_cache_dtype="float8_e4m3fn"), "--kv-cache-dtype"),
])
def test_refused_at_start_up_by_the_flags_name(over, flag):
    with pytest.raises(ValueError) as e:
        make_engine(**over)
    assert flag in str(e.value)
    assert "tiny-phi4flash-debug" in str(e.value)


def test_released_window_pages_are_a_reason_of_their_own():
    """Without the state-space layers' reasons the property still refuses
    what it must, by the flag's name; its prefix cache is served (the window
    group matches by hash: ``tests/test_mellum.py``)."""
    from production_stack_tpu.engine.config import refuse_unserved

    only = type("Only", (ModelConfig,), {"window_pages": True})()
    for over, flag in ((dict(), "--kv-swap"),
                       (dict(kv_swap=False, cpu_offload_blocks=8),
                        "--cpu-offload-blocks")):
        with pytest.raises(ValueError, match=flag):
            refuse_unserved(EngineConfig(**over), only)
    refuse_unserved(EngineConfig(kv_swap=False), only)


def test_config_door_knows_the_model_type(tmp_path):
    from production_stack_tpu.models.llama import config_from_hf_json

    with open("perf/configs/phi-4-mini-flash.json") as f:
        raw = json.load(f)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(raw))
    cfg = config_from_hf_json(str(path), name="x")
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size) == (32, 2560, 200064)
    assert (cfg.d_inner, cfg.dt_rank, cfg.head_dim) == (5120, 160, 64)
    assert (cfg.num_mamba_layers, cfg.num_window_layers, cfg.cross_pairs) == (9, 8, 7)
    # a token's keys and values: 5,120 B a layer; 8 windows of 5 pages: 26 MB
    assert cfg.page_bytes(128, 2) == 128 * 5120
    assert cfg.window_page_bytes(128, 2) * 5 == 26_214_400
    assert cfg.state_bytes_per_slot() == 9 * (5120 * 16 * 4 + 3 * 5120 * 2)
    shapes = jax.eval_shape(Phi4Flash(cfg).init_params, jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 3.84e9 < n < 3.87e9  # the published 3.8 B, uncut
    raw["num_hidden_layers"] = 30
    path.write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="multiple of 4"):
        config_from_hf_json(str(path))


# ----------------------------------------------------------------------------
# The kernels at the published widths, compiled for a described chip
# ----------------------------------------------------------------------------


@pytest.fixture(scope="module")
def one_chip():
    """A v5e chip that is described, not attached: the TPU's compiler is
    installed here and refuses what the chip's would (interpret mode shows
    neither a tiling fault nor a pool copy)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # The session's compile cache (tests/conftest.py) stays out of these: a
    # program compiled for a described chip is written there but cannot be
    # read back without one (a warning and a second compile each time).
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("B,T", [(64, 1), (1, 1024), (4, 256)])
def test_scan_kernels_compile_for_the_chip_without_a_pool_copy(B, T, one_chip, monkeypatch):
    """Nine layers, 73 slots, 16 states x 5,120 channels: Mosaic takes both
    kernels, and the donated pool is updated in place (no temporary of the
    pool's size)."""
    monkeypatch.setattr(scan, "pallas_interpret", lambda: False)
    L, S, N, Di = 9, 73, 16, 5120
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    rows = sds((B,), jnp.int32)
    if T == 1:
        fn = lambda pool, sl, kp, u, dt, a, bm, cm, d: scan.selective_scan_decode(  # noqa: E731
            pool, 3, sl, kp, u, dt, a, bm, cm, d)
        args = (sds((L, S, N, Di)), rows, rows, sds((B, Di)), sds((B, Di)),
                sds((N, Di)), sds((B, N)), sds((B, N)), sds((Di,)))
    else:
        fn = lambda pool, sl, kp, ln, u, dt, a, bm, cm, d: scan.selective_scan_prefill(  # noqa: E731
            pool, 3, sl, kp, ln, u, dt, a, bm, cm, d)
        args = (sds((L, S, N, Di)), rows, rows, rows, sds((B, T, Di)),
                sds((B, T, Di)), sds((N, Di)), sds((B, T, N)), sds((B, T, N)),
                sds((Di,)))
    with jax.disable_jit(False):
        compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    pool_bytes = L * S * N * Di * 4
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 4


@pytest.mark.parametrize("B,T", [(64, 1), (1, 1024), (4, 256)])
def test_gated_delta_kernels_compile_for_the_chip_without_a_pool_copy(B, T, one_chip, monkeypatch):
    """The gated-delta-rule hybrid's two kernels (``ops/gated_delta.py``,
    tested in ``tests/test_qwen3_next.py``) at the published widths: twelve
    layers, 73 slots, 32 heads of a 128 x 128 state. Kept here because the
    described chip is this file's (one file may load the TPU's library)."""
    from production_stack_tpu.ops import gated_delta as gdn

    monkeypatch.setattr(gdn, "pallas_interpret", lambda: False)
    L, S, H, K, V = 12, 73, 32, 128, 128
    sds = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    rows = sds((B,), jnp.int32)
    if T == 1:
        fn = lambda pool, sl, kp, q, k, v, g, b: gdn.gated_delta_decode(  # noqa: E731
            pool, 5, sl, kp, q, k, v, g, b)
        args = (sds((L, S, H, K, V)), rows, rows, sds((B, H, K)),
                sds((B, H, K)), sds((B, H, V)), sds((B, H)), sds((B, H)))
    else:
        fn = lambda pool, sl, kp, ln, q, k, v, g, b: gdn.gated_delta_prefill(  # noqa: E731
            pool, 5, sl, kp, ln, q, k, v, g, b)
        args = (sds((L, S, H, K, V)), rows, rows, rows, sds((B, T, H, K)),
                sds((B, T, H, K)), sds((B, T, H, V)), sds((B, T, H)),
                sds((B, T, H)))
    with jax.disable_jit(False):
        compiled = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile()
    pool_bytes = L * S * H * K * V * 4
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 4
    assert "tpu_custom_call" in compiled.as_text()


def test_conv_tail_kernel_compiles_for_the_chip_without_a_pool_copy(one_chip, monkeypatch):
    """The gated-delta-rule hybrid's third kernel at the published widths:
    twelve layers, 73 slots, a tail of 3 x 8,192 bf16, 64 rows. Mosaic takes
    it and the donated 43 MB pool is updated in place."""
    from production_stack_tpu.ops import gated_delta as gdn

    monkeypatch.setattr(gdn, "pallas_interpret", lambda: False)
    L, S, taps, C, B = 12, 73, 4, 8192, 64
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    rows = sds((B,), jnp.int32)
    fn = lambda pool, sl, kp, x, w: gdn.conv_tail_decode(  # noqa: E731
        pool, 5, sl, kp, x, w)
    with jax.disable_jit(False):
        compiled = jax.jit(fn, donate_argnums=(0,)).lower(
            sds((L, S) + gdn.tail_shape(taps, C)), rows, rows, sds((B, C)),
            sds((taps, C))).compile()
    pool_bytes = L * S * (taps - 1) * C * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 4
    assert "tpu_custom_call" in compiled.as_text()


def test_the_gated_delta_decode_program_holds_the_tails_pool_in_the_kernel_alone(
        one_chip, monkeypatch):
    """The 64-row decode step of the gated-delta cell's configuration, whole,
    compiled for the described chip. The 43 MB pool of tails fits XLA's fast
    memory, and around a gather and a scatter (or an unconstrained kernel
    operand) it was carried there and back in every DeltaNet layer (PERF.md
    §6, PR 43): no copy of it, no scatter over it and no ``S(1)`` on it may
    stand in the text, and the projection's weights stay as they are stored
    (a reshape of the kernel's row, left to XLA, turned the whole ``w_qkv``
    stack instead: 403 MB of temporaries)."""
    from production_stack_tpu.models import moe_dispatch, qwen3_next
    from production_stack_tpu.ops import gated_delta as gdn
    from production_stack_tpu.ops import paged_attention_pallas as pap

    for mod in (gdn, pap, moe_dispatch):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    with open("perf/configs/qwen3-next-ep8-cut.json") as f:
        cfg = qwen3_next.config_from_hf(json.load(f), "qwen3-next-ep8-cut")
    model = qwen3_next.Qwen3Next(cfg)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: model.make_kv_cache(2048, 128, None, state_slots=72)))
    B = 64
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)

    def step(params, tokens, positions, write_idx, tables, kv_lens, last_idx,
             cache, slots):
        return model.forward(
            params, tokens, positions, write_idx, tables, kv_lens, last_idx,
            cache, state_slots=slots, attn_impl="pallas")

    with jax.disable_jit(False):
        compiled = jax.jit(step, donate_argnums=(7,)).lower(
            params, i32(B, 1), i32(B, 1), i32(B, 1), i32(B, 128), i32(B),
            i32(B), cache, i32(B)).compile()
    pool = "bf16[" + ",".join(map(str, cache["conv"].shape)) + "]"
    assert pool == "bf16[12,73,3,64,128]"  # 43 MB: no padded tile
    text = compiled.as_text()
    plumbing = {"parameter", "get-tuple-element", "tuple", "while", "call",
                "bitcast"}
    held = [
        f"{name} {opcode}" for name, result, opcode in re.findall(
            r"^\s*(?:ROOT )?(%\S+) = (\(.*?\)|\S+) ([\w\-]+)\(", text, re.M)
        if pool in result and (
            re.search(re.escape(pool) + r"\S*S\(1\)", result)
            or opcode not in plumbing and "conv_tail_decode" not in name)]
    assert not held
    assert "conv_tail_decode" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_the_window_mix_decode_program_copies_no_weight_stack(one_chip, monkeypatch):
    """The 32-row decode step of the window / full attention cell's
    configuration, whole, compiled for the described chip (PR 46). Left to
    XLA, the reshape of the query and key projections to heads turned the
    whole ``wq`` and ``wk`` stacks instead of the rows: a copy of 528 + 66 MB
    in every step (1.4 ms of a 18 ms step on the chip, and as much
    temporary memory); ``Mellum._attention`` holds the projections behind an
    ``optimization_barrier``. Neither page group is copied either, and both
    paged kernels and the grouped products are in the text."""
    from production_stack_tpu.models import mellum, moe_dispatch
    from production_stack_tpu.ops import paged_attention_pallas as pap

    for mod in (pap, moe_dispatch):
        monkeypatch.setattr(mod, "pallas_interpret", lambda: False)
    with open("perf/configs/mellum2-ep4-cut.json") as f:
        cfg = mellum.config_from_hf(json.load(f), "mellum2-ep4-cut")
    model = mellum.Mellum(cfg)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: model.make_kv_cache(2560, 128, None, window_blocks=592)))
    B = 32
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)

    def step(params, tokens, positions, write_idx, tables, kv_lens, last_idx,
             cache, window_tables):
        return model.forward(
            params, tokens, positions, write_idx, tables, kv_lens, last_idx,
            cache, window_tables=window_tables, attn_impl="pallas")

    with jax.disable_jit(False):
        compiled = jax.jit(step, donate_argnums=(7,)).lower(
            params, i32(B, 1), i32(B, 1), i32(B, 1), i32(B, 256), i32(B),
            i32(B), cache, i32(B, 256)).compile()
    text = compiled.as_text()
    big = ("bf16[28,", "bf16[448,", "bf16[7,2560,", "bf16[21,592,")
    copies = [
        name for name, result, opcode in re.findall(
            r"^\s*(?:ROOT )?(%\S+) = (\(.*?\)|\S+) ([\w\-]+)\(", text, re.M)
        if opcode in ("copy", "transpose") and result.startswith(big)]
    assert not copies
    assert compiled.memory_analysis().temp_size_in_bytes < 128 << 20
    # 256 pair rows at a quarter share: a capacity of 128, so the held rows'
    # way back and the loop of further rounds are in the program
    for kernel in ("paged_attn_decode", "gmm", "moe_sum_rows"):
        assert kernel in text


@pytest.mark.parametrize("rows,width,tokens", [
    (1920, 2048, 1024), (8448, 1024, 1024), (3072, 2304, 1024),
    (128, 2048, 64), (7680, 2048, 4096)])
def test_the_held_rows_way_back_compiles_for_the_chip(rows, width, tokens,
                                                      one_chip, monkeypatch):
    """``moe_dispatch.sum_rows`` (PR 52) at the three share cells' prefill
    capacities, a decode step's, and a step of 4,096 tokens whose sums take
    two blocks of fast memory: Mosaic takes the float32 weights as a
    prefetched scalar operand and the one-row read-add-write at a traced
    row, and the kernel asks for no more fast memory than it is given."""
    from production_stack_tpu.models import moe_dispatch

    monkeypatch.setattr(moe_dispatch, "pallas_interpret", lambda: False)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    blocks, block = moe_dispatch.sums_blocks(tokens, width)
    assert blocks == (2 if tokens == 4096 else 1)
    with jax.disable_jit(False):
        compiled = jax.jit(moe_dispatch.sum_rows, donate_argnums=(4,)).lower(
            sds((rows, width), jnp.float32), sds((rows,), jnp.int32),
            sds((rows,), jnp.float32), sds((), jnp.int32),
            sds((blocks * block, width), jnp.float32),
            sds((), jnp.int32)).compile()
    assert "moe_sum_rows" in compiled.as_text()


@pytest.mark.parametrize("B,T", [(16, 1), (1, 1024)])
def test_the_looped_step_program_copies_no_weight_stack(B, T, one_chip, monkeypatch):
    """A 16-row decode step and a 1,024-token prefill step of the looped
    dense cell's configuration, whole, compiled for the described chip
    (PR 49): Mosaic takes 32-token pages of 2,048 lanes with one key-value
    head a query head; the program holds one loop of passes around one loop
    of layers; and, left to XLA, the reshape of the query and key
    projections to heads turned the whole bf16 ``wq`` and ``wk`` stacks
    instead of the rows, once a step: 2 x 384 MiB copied and held beside
    15.0 GB of arguments (``Llama.forward`` holds the projections behind an
    ``optimization_barrier`` under the loop)."""
    from production_stack_tpu.models import llama
    from production_stack_tpu.ops import paged_attention_pallas as pap

    monkeypatch.setattr(pap, "pallas_interpret", lambda: False)
    with open("perf/configs/ouro-2.6b.json") as f:
        cfg = llama.config_from_hf(json.load(f), "ouro-2.6b")
    model = llama.Llama(cfg)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.make_kv_cache(192, 32)))
    assert cache.shape == (192, 192, 2, 32, 2048)
    i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)

    def step(params, tokens, positions, write_idx, tables, kv_lens, last_idx,
             cache):
        return model.forward(
            params, tokens, positions, write_idx, tables, kv_lens, last_idx,
            cache, attn_impl="pallas")

    with jax.disable_jit(False):
        compiled = jax.jit(step, donate_argnums=(7,)).lower(
            params, i32(B, T), i32(B, T), i32(B, T), i32(B, 64), i32(B),
            i32(B), cache).compile()
    text = compiled.as_text()
    results = re.findall(
        r"^\s*(?:ROOT )?(%\S+) = (\(.*?\)|\S+) ([\w\-]+)\(", text, re.M)
    big = ("bf16[48,2048,", "bf16[48,5632,", "bf16[192,192,")
    copies = [name for name, result, opcode in results
              if opcode in ("copy", "transpose") and result.startswith(big)]
    assert not copies
    assert sum(1 for _, _, opcode in results if opcode == "while") == 2
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64 << 20
    assert 14.9e9 < memory.argument_size_in_bytes < 15.1e9  # 5.34 + 9.66 GB
    assert "paged_attn_decode" in text or T > 1
