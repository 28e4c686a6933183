"""The gated-delta-rule hybrid (``models/qwen3_next.py``: Gated DeltaNet
layers on matrix-state slots, gated attention with rotary on a quarter of
the lanes, a softmax router over an expert-parallel share) on the engine's
normal path, against the benchmark's plain reference
(``perf/reference/qwen3_next.py``: float32, the delta rule position by
position, nothing of the program's forward pass), at tiny widths: two
periods of three DeltaNet layers and one attention layer, hidden 64, 4 of 16
experts held from expert 4 on, pages of 8.

What the benchmark's ``correct`` cannot see is here: rows against each other
(packed prefill, slots taken again), the state carried from a chunk into the
next, the kernels against the recurrence, the shares against the whole.
"""

import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import qwen3_next as reference
from production_stack_tpu.engine import config as engine_config
from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.models import moe_dispatch
from production_stack_tpu.models.qwen3_next import Qwen3Next
from production_stack_tpu.models.registry import PRESETS
from production_stack_tpu.ops import gated_delta as gdn

from . import model_contract as contract
from .model_contract import assert_same, run

NAME = "tiny-qwen3-next-debug"
CFG = PRESETS[NAME]
HF = {"num_hidden_layers": CFG.num_layers,
      "hidden_size": CFG.hidden_size,
      "full_attention_interval": CFG.full_attention_interval,
      "num_attention_heads": CFG.num_heads,
      "num_key_value_heads": CFG.num_kv_heads,
      "head_dim": CFG.head_dim,
      "partial_rotary_factor": CFG.partial_rotary_factor,
      "rope_theta": CFG.rope_theta,
      "linear_num_key_heads": CFG.linear_num_key_heads,
      "linear_num_value_heads": CFG.linear_num_value_heads,
      "linear_key_head_dim": CFG.linear_key_head_dim,
      "num_experts": CFG.n_routed_experts,
      "num_experts_per_tok": CFG.num_experts_per_tok,
      "norm_topk_prob": CFG.norm_topk_prob,
      "rms_norm_eps": CFG.rms_norm_eps,
      "ep_share": {"first": CFG.expert_first, "of": CFG.router_experts}}
REF_CFG = types.SimpleNamespace(
    hf=HF, raw={"published": {"num_experts": CFG.router_experts}})
PROMPT = [3, 17, 98, 25, 42, 7, 11, 20, 15, 31, 8, 77, 12, 5, 9, 2, 33, 44, 99,
          100, 101, 64, 65, 1, 90, 13, 14, 6, 120, 50, 51, 52, 53, 54, 55, 56,
          57, 58, 59, 60, 61, 62, 63, 66, 67, 68, 69, 70, 71, 72, 73, 74, 75]


make_engine = functools.partial(
    contract.make_engine, NAME, enable_prefix_caching=False)


@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.fixture(scope="module")
def params(engine):
    return engine.runner.params


def reference_logprobs(params, ids, n_prompt, n_gen, variant="none"):
    with jax.default_matmul_precision("highest"):
        (lps, gap), = reference.teacher_force(
            REF_CFG, params,
            [{"tokens": list(ids), "n_prompt": n_prompt,
              "want": [[0]] * n_gen}], variant)
    assert gap.shape == (n_gen,)
    assert variant != "none" or np.all(gap >= 0)
    return lps


assert_matches_reference = functools.partial(
    contract.assert_matches_reference,
    lambda params, prompt, tokens: reference_logprobs(
        params, prompt + tokens, len(prompt), len(tokens)))


# ----------------------------------------------------------------------------
# The engine's normal path against the reference's full forward pass
# ----------------------------------------------------------------------------


def test_chunked_prefill_then_decode_through_slots_and_pages(engine, params):
    """53 prompt tokens in chunks of 16 (the state and the convolution's
    tail carried from one chunk call into the next), then chained decode
    through the slot: every reported log-probability is the reference's."""
    got = run(engine, [PROMPT], 8)[0]
    assert len(got["tokens"]) == 8
    assert_matches_reference(params, PROMPT, got)
    assert engine.pipelined_bursts_total > 0, "decode must run chained"
    stats = engine.stats()
    assert stats["state_slots_in_use"] == 0 and stats["kv_pages_in_use"] == 0
    assert stats["prefill_tokens_total"] == len(PROMPT)
    assert stats["prefill_bucket_positions_total"] == 3 * 16 + 8  # 5 in 8
    # the dispatch's five counts, under the accepted names: every real token
    # of every fetched step routes top_k pairs in each of the eight layers
    assert stats["moe_layer_steps_total"] % CFG.num_layers == 0
    assert stats["moe_pairs_routed_total"] > 0
    assert 0 < stats["moe_pairs_held_total"] < stats["moe_pairs_routed_total"]


def test_the_server_exports_the_dispatch_counts(engine):
    """``pst:moe_dispatch_overflow_total`` beside the accepted five."""
    run(engine, [PROMPT[:20]], 2)
    contract.assert_dispatch_counts_exported(engine)


def test_a_prompt_cut_into_three_chunks_equals_one_chunk(engine, params):
    prompt = PROMPT[:48]
    three = run(engine, [prompt], 4)[0]  # the defaults: chunks of 16
    one = run(make_engine(max_prefill_tokens=64, overlap_decode=False),
              [prompt], 4)[0]
    assert_same(three, one)
    assert_matches_reference(params, prompt, one)


def test_short_prompts_and_one_token_chunks(params):
    """Prompts shorter than the convolution's tail, and a chunk of one
    token that is a sequence's first (the decode path from zeros)."""
    eng = make_engine(max_prefill_tokens=8)
    prompts = [[5], [9, 2], PROMPT[:9]]
    for p, got in zip(prompts, run(eng, prompts, 5)):
        assert_matches_reference(params, p, got)


def test_four_ragged_packed_rows_equal_four_lone_rows(params):
    """Four sequences of different lengths arrive together: packed and
    padded prefill steps, each row from its own slot."""
    prompts = [PROMPT[:n] for n in (37, 5, 53, 18)]
    eng = make_engine(max_prefill_tokens=32)
    together = run(eng, prompts, 6)
    lone_eng = make_engine(max_prefill_tokens=32)
    for p, got in zip(prompts, together):
        assert_same(got, run(lone_eng, [p], 6)[0])
        assert_matches_reference(params, p, got)
    assert eng.allocator.state_slots_in_use == 0


def test_a_freed_slot_is_zero_for_its_next_owner(params):
    """Every slot a sequence can be given holds NaN (state and tail): a new
    sequence starts from zeros all the same."""
    eng = make_engine()
    run(eng, [PROMPT[:20]], 3)
    cache = eng.runner.kv_cache
    poison = lambda a: a.at[:, :-1].set(jnp.nan)  # noqa: E731 — not the scratch
    eng.runner.kv_cache = {**cache, "ssm": poison(cache["ssm"]),
                           "conv": poison(cache["conv"])}
    got = run(eng, [PROMPT[:30]], 5)[0]
    assert_matches_reference(params, PROMPT[:30], got)


def test_a_staggered_many_row_run_equals_the_synchronous_loop(params):
    """Eight sequences arrive three steps apart under a chain of four rows:
    each joins behind its own prefill with no drain, a finished member's
    slot comes back a burst later while the chain runs on. Tokens and
    log-probabilities are the synchronous loop's, request by request."""
    prompts = [PROMPT[:n] for n in (37, 5, 53, 18, 26, 11, 44, 9)]
    kw = dict(max_num_seqs=4, min_decode_bucket=4, max_prefill_tokens=32)
    sync = run(make_engine(overlap_decode=False, **kw), prompts, 9, stagger=3)
    eng = make_engine(**kw)
    got = run(eng, prompts, 9, stagger=3)
    for a, b in zip(got, sync):
        assert_same(a, b)
    assert eng.chain_kept_prefills_total >= 7
    assert eng.pipeline_breaks["prefill"] == 0
    assert eng.allocator.state_slots_in_use == 0
    assert eng.allocator.num_free == eng.allocator.num_blocks
    assert_matches_reference(params, prompts[2], got[2])


def test_preemption_by_recompute_returns_the_same_tokens(params):
    """Twelve pages: two 40-token prompts admit and one must lose its pages
    and its slot while decoding; it starts again from zeros."""
    p1, p2 = PROMPT[:40], PROMPT[5:45]
    tight = make_engine(num_kv_blocks=12, max_model_len=128, max_prefill_tokens=48)
    got = run(tight, [p1, p2], 10)
    assert tight.num_preempted_total > 0, "the test must exercise preemption"
    roomy = run(make_engine(max_prefill_tokens=48), [p1, p2], 10)
    for a, b in zip(got, roomy):
        assert a["tokens"] == b["tokens"]
    assert tight.allocator.state_slots_in_use == 0


@pytest.mark.parametrize("variant", reference.VARIANTS[1:])
def test_every_negative_control_moves_the_reference(variant, params):
    ids = PROMPT + PROMPT[:11]
    sound = reference_logprobs(params, ids, len(PROMPT), 12)
    broken = reference_logprobs(params, ids, len(PROMPT), 12, variant)
    moved = np.abs(sound - broken).max()
    # the precision controls move little at these widths, the equations much
    # (unnormalised keys make the delta rule diverge: nothing finite is left)
    assert not moved <= (1e-5 if variant == "state_bf16" else 1e-2), moved


# ----------------------------------------------------------------------------
# The expert block: a share, and the shares together
# ----------------------------------------------------------------------------


def test_softmax_route_is_the_top_k_of_a_softmax_renormalised():
    u = jax.random.normal(jax.random.PRNGKey(0), (9, 16))
    w_r = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
    ids, w = moe_dispatch.route(u, w_r, None, top_k=3, norm_topk_prob=True,
                                scale=1.0, scoring="softmax")
    p = jax.nn.softmax(jnp.einsum("nd,de->ne", u, w_r, precision="highest"))
    top, want = jax.lax.top_k(p, 3)
    np.testing.assert_array_equal(ids, want)
    np.testing.assert_allclose(w, top / top.sum(-1, keepdims=True), rtol=1e-6)
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        moe_dispatch.route(u, w_r, None, top_k=3, norm_topk_prob=True,
                           scale=1.0, scoring="tanh")


def test_sigmoid_route_is_what_it_was():
    """The accepted classes' router: selection by score + bias, weights by
    score alone (the hybrid and latent cells' paths)."""
    u = jax.random.normal(jax.random.PRNGKey(0), (9, 16))
    w_r = jax.random.normal(jax.random.PRNGKey(1), (16, 12))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (12,))
    ids, w = moe_dispatch.route(u, w_r, bias, top_k=3, norm_topk_prob=True,
                                scale=2.5)
    s = jax.nn.sigmoid(jnp.einsum("nd,de->ne", u, w_r, precision="highest"))
    _, want = jax.lax.top_k(s + bias, 3)
    np.testing.assert_array_equal(ids, want)
    chosen = jnp.take_along_axis(s, want, -1)
    np.testing.assert_allclose(
        w, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)


# 23 tokens: one row tile, the plain program; 200: a capacity, rounds traced
@pytest.mark.parametrize("tokens", [23, 200])
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_whole(tokens, params):
    """Four ranks of 4 of the 16 experts, one router: the routed parts add
    up (with the shared expert once) to the uncut reference's expert block."""
    import dataclasses

    D, Fe, E = CFG.hidden_size, CFG.moe_intermediate_size, CFG.router_experts
    held = CFG.n_routed_experts
    x = jax.random.normal(jax.random.PRNGKey(3), (tokens, D))
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    whole = {"w1": jax.random.normal(ks[0], (E, D, 2 * Fe)) / np.sqrt(D),
             "w2": jax.random.normal(ks[1], (E, Fe, D)) / np.sqrt(Fe)}
    mp = {k: v[0] for k, v in params["layers"]["moe"].items()
          if k not in whole}
    u = reference._norm(x, mp["norm"], CFG.rms_norm_eps)
    valid = jnp.ones((tokens,), bool)
    total = Qwen3Next(CFG).shared_expert(mp, u)
    for first in range(0, E, held):
        model = Qwen3Next(dataclasses.replace(CFG, expert_first=first))
        banks = {k: v[first:first + held] for k, v in whole.items()}
        part, stats = model.routed(mp, banks, 0, u, valid)
        assert stats[0] == tokens * CFG.num_experts_per_tok
        assert stats[5] == 0  # no share passed its capacity
        total = total + part
    with jax.default_matmul_precision("highest"):
        want, _ = reference.moe(
            x, {**mp, **whole}, top_k=CFG.num_experts_per_tok, first=0,
            renorm=True, eps=CFG.rms_norm_eps, variant="none")
    np.testing.assert_allclose(total, want, atol=2e-4, rtol=2e-4)


# ----------------------------------------------------------------------------
# The kernels, interpreted, against the recurrence position by position
# ----------------------------------------------------------------------------


def _delta_inputs(B, T, H=2, K=128, V=128, L=2, S=5, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, T, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, K)))
    v = jax.random.normal(ks[2], (B, T, H, V))
    # a token's decay between 0.2 and 0.9999, as the configuration's
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H), minval=-9.0, maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    pool = jax.random.normal(ks[5], (L, S, H, K, V))
    return pool, q, k, v, g, beta


@pytest.mark.parametrize("T,lens", [(150, (150, 70, 0)), (64, (64, 64, 3)),
                                    (200, (129, 1, 200))])
def test_prefill_kernel_equals_the_recurrence(T, lens):
    """Ragged packed rows each from its own slot: one continued from a
    non-zero state, one fresh over a slot that holds another's state, one
    row of padding; chunks past a row's length are not walked; other slots
    and the other layer are left as they were."""
    pool, q, k, v, g, beta = _delta_inputs(3, T)
    lens = jnp.asarray(lens, jnp.int32)
    valid = (jnp.arange(T)[None] < lens[:, None])[..., None]
    g, beta = jnp.where(valid, g, 0.0), jnp.where(valid, beta, 0.0)
    slots, keep = jnp.array([3, 1, 4]), jnp.array([1, 0, 1])
    s0 = jnp.where(keep[:, None, None, None] != 0, pool[1, slots], 0.0)
    o_ref, s_ref = gdn.delta_reference(s0, q, k, v, g, beta)
    o, pool2 = jax.jit(gdn.gated_delta_prefill)(
        pool, jnp.int32(1), slots, keep, lens, q, k, v, g, beta)
    for b, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(o[b, :n], o_ref[b, :n], atol=2e-5)
        np.testing.assert_allclose(pool2[1, slots[b]], s_ref[b], atol=2e-5)
    np.testing.assert_array_equal(pool2[0], pool[0])
    np.testing.assert_array_equal(pool2[1, jnp.array([0, 2])],
                                  pool[1, jnp.array([0, 2])])


def test_decode_kernel_equals_the_step_and_leaves_other_slots():
    pool, q, k, v, g, beta = _delta_inputs(3, 1, H=4, K=16, V=16, seed=1)
    slots, keep = jnp.array([3, 1, 4]), jnp.array([1, 0, 1])
    s0 = jnp.where(keep[:, None, None, None] != 0, pool[1, slots], 0.0)
    o_ref, s_ref = gdn.delta_reference(s0, q, k, v, g, beta)
    o, pool2 = jax.jit(gdn.gated_delta_decode)(
        pool, jnp.int32(1), slots, keep, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
        beta[:, 0])
    np.testing.assert_allclose(o, o_ref[:, 0], atol=1e-5)
    np.testing.assert_allclose(pool2[1, slots], s_ref, atol=1e-5)
    np.testing.assert_array_equal(pool2[0], pool[0])
    np.testing.assert_array_equal(pool2[1, 0], pool[1, 0])


def _tail_rows(case):
    """-> (slots, keep, true_len) of a decode step's rows over a pool of 74
    slots whose last is the scratch."""
    scratch = 73
    if case == "continued":
        return [5], [1], [1]
    if case == "fresh":  # over a slot that holds another sequence's tail
        return [2], [0], [1]
    if case == "padding":  # two real rows, three of padding at the scratch
        return [7, scratch, 0, scratch, scratch], [1, 0, 0, 0, 0], [1, 0, 1, 0, 0]
    rng = np.random.default_rng(0)  # 64 rows: 50 real in any order, 14 padding
    slots = rng.permutation(scratch)[:64]
    real = rng.permutation(64) < 50
    keep = (rng.random(64) < 0.7) & real
    return np.where(real, slots, scratch), keep, real


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("case", ["continued", "fresh", "padding", "ragged64"])
def test_conv_tail_kernel_equals_the_gather_shift_and_scatter(case, dtype):
    """The decode step's convolution on the tails' pool, interpreted, against
    the ``jax.numpy`` path it replaces: each real row's tail bit for bit and
    its sum to rounding, every slot no row of the step owns and every other
    layer bit for bit. (The scratch slot is nobody's: the kernel shifts it
    where the ``jax.numpy`` path leaves a padding row's tail as it read it.)"""
    L, S, taps, C, li = 3, 74, 4, 2 * gdn.LANES, 1
    slots, keep, lens = (jnp.asarray(a, jnp.int32) for a in _tail_rows(case))
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 3)
    pool = jax.random.normal(
        ks[0], (L, S) + gdn.tail_shape(taps, C)).astype(dtype)
    x = jax.random.normal(ks[1], (slots.shape[0], C)).astype(dtype)
    w = jax.random.normal(ks[2], (taps, C)).astype(dtype)
    want, pool_ref = gdn.conv_tail_reference(
        pool, li, slots, keep, lens, x[:, None], w)
    got, pool2 = jax.jit(gdn.conv_tail_decode)(
        pool, jnp.int32(li), slots, keep, x, w)
    real = np.asarray(lens) > 0
    np.testing.assert_allclose(got[real], want[real, 0], atol=1e-6, rtol=1e-6)
    settled = np.setdiff1d(np.arange(S), [] if real.all() else [S - 1])
    np.testing.assert_array_equal(pool2[li, settled], pool_ref[li, settled])
    untouched = np.setdiff1d(np.arange(S), np.asarray(slots))
    np.testing.assert_array_equal(pool2[li, untouched], pool[li, untouched])
    np.testing.assert_array_equal(pool2[0], pool[0])
    np.testing.assert_array_equal(pool2[2], pool[2])
    # the newest row of a real row's tail is its row of this step
    np.testing.assert_array_equal(
        pool2[li, slots[real], -1].reshape(-1, C), x[real])


def test_the_tails_pool_is_whole_tiles_a_tap_and_refuses_other_widths():
    """A slot's tap is ``[channels / 128, 128]``: 64 sublanes of bf16 at the
    published widths, no padded tile; channels that are no whole lanes keep
    one row (the CPU path's) and the kernel says so."""
    assert gdn.tail_shape(4, 8192) == (3, 64, 128)
    assert gdn.tail_shape(4, 96) == (3, 1, 96)
    cache = Qwen3Next(CFG).make_kv_cache(4, 8, None, state_slots=3)
    assert cache["conv"].shape == (
        CFG.num_state_layers, 4) + gdn.tail_shape(4, CFG.conv_dim)
    pool = jnp.zeros((1, 2) + gdn.tail_shape(4, 96))
    with pytest.raises(ValueError, match="whole 128-lane tiles"):
        gdn.conv_tail_decode(pool, 0, jnp.zeros(1, jnp.int32),
                             jnp.ones(1, jnp.int32), jnp.zeros((1, 96)),
                             jnp.zeros((4, 96)))


def test_the_model_on_the_interpreted_kernels_equals_the_recurrence(monkeypatch):
    """One DeltaNet layer of the class at 128-wide heads, prefill then two
    decode steps, on the kernels (interpreted: the chunked prefill, the
    decode step and the convolution's tail) and on the recurrence."""
    import dataclasses

    cfg = dataclasses.replace(
        CFG, linear_key_head_dim=128, linear_value_head_dim=128,
        linear_num_key_heads=1, linear_num_value_heads=2)
    model = Qwen3Next(cfg)
    full = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    lp = {k: v[0] for k, v in full["layers"]["delta"].items()}
    cache = model.make_kv_cache(4, 8, None, state_slots=3)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.hidden_size))
    lens = jnp.array([24, 13])
    valid = jnp.arange(24)[None] < lens[:, None]
    rows = (jnp.array([2, 0]), lens, valid, jnp.array([False, False]))

    def both(x, pool, tails, rows):
        monkeypatch.setattr(gdn, "use_kernels", lambda: False)
        want = model._delta(lp, x, pool, tails, 1, rows)
        monkeypatch.setattr(gdn, "use_kernels", lambda: True)
        got = model._delta(lp, x, pool, tails, 1, rows)
        return want, got

    want, got = both(x, cache["ssm"], cache["conv"], rows)
    for b, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(got[0][b, :n], want[0][b, :n], atol=1e-4)
    np.testing.assert_allclose(got[1], want[1], atol=1e-4)
    np.testing.assert_array_equal(got[2], want[2])
    step = (rows[0], jnp.array([1, 1]), jnp.ones((2, 1), bool),
            jnp.array([True, True]))
    calls = []
    kernel = gdn.conv_tail_decode
    monkeypatch.setattr(gdn, "conv_tail_decode",
                        lambda *a: calls.append(1) or kernel(*a))
    want2, got2 = both(x[:, :1], want[1], want[2], step)
    assert calls == [1]  # the decode step's convolution went through it
    np.testing.assert_allclose(got2[0], want2[0], atol=1e-4)
    np.testing.assert_allclose(got2[1], want2[1], atol=1e-4)
    np.testing.assert_array_equal(got2[2], want2[2])
    # and a step with a row of padding at the scratch slot (the pool's last)
    step = (jnp.array([2, 3]), jnp.array([1, 0]),
            jnp.array([[True], [False]]), jnp.array([True, False]))
    want3, got3 = both(x[:, 1:2], want2[1], want2[2], step)
    np.testing.assert_allclose(got3[0][0], want3[0][0], atol=1e-4)
    np.testing.assert_allclose(got3[1][:, :3], want3[1][:, :3], atol=1e-4)
    np.testing.assert_array_equal(got3[2][:, :3], want3[2][:, :3])


# ----------------------------------------------------------------------------
# Start-up: what is refused, the pools' sizes, the configuration's door
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
    (dict(expert_parallel_size=2), "--expert-parallel-size"),
    (dict(data_parallel_size=2), "--data-parallel-size"),
    (dict(quantization="int8"), "--quantization"),
    (dict(kv_cache_dtype="float8_e4m3fn"), "--kv-cache-dtype"),
])
def test_refused_at_start_up_by_the_flags_name(over, flag):
    """Every refusal of the class's properties, from the one table, with its
    sentence."""
    kw = dict(model=NAME, enable_prefix_caching=False, kv_swap=False)
    kw.update(over)
    with pytest.raises(ValueError) as e:
        engine_config.refuse_unserved(EngineConfig(**kw), CFG)
    (why,) = [w for on, f, w in engine_config._refusals(EngineConfig(**kw))
              if f == flag]
    prop = "recurrent" if "recurrent" in why else "wide_head_pages"
    assert str(e.value) == (
        f"{flag} is not served for model {NAME!r}, which "
        f"{engine_config._HAS[prop]}: {why[prop]}")
    engine_config.refuse_unserved(
        EngineConfig(model=NAME, enable_prefix_caching=False, kv_swap=False),
        CFG)


def test_one_byte_pages_are_refused_for_the_wide_heads_alone():
    """Not for the state: the hybrid class keeps its one-byte pages."""
    fp8 = dict(kv_cache_dtype="float8_e4m3fn", enable_prefix_caching=False,
               kv_swap=False)
    engine_config.refuse_unserved(
        EngineConfig(**fp8), PRESETS["tiny-nemotron-h-debug"])
    engine_config.refuse_unserved(
        EngineConfig(kv_cache_dtype="float8_e4m3fn"), PRESETS["tiny-llama-debug"])
    with pytest.raises(ValueError, match="256-wide"):
        engine_config.refuse_unserved(EngineConfig(**fp8), CFG)


def test_config_door_knows_the_model_type_and_the_arithmetic(tmp_path):
    from production_stack_tpu.models.llama import config_from_hf_json

    with open("perf/configs/qwen3-next-ep8-cut.json") as f:
        raw = json.load(f)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(raw))
    cfg = config_from_hf_json(str(path), name="x")
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size) == (16, 2048, 18992)
    assert (cfg.periods, cfg.num_kv_layers, cfg.num_state_layers) == (4, 4, 12)
    assert (cfg.n_routed_experts, cfg.router_experts, cfg.expert_first) == (
        64, 512, 0)
    assert (cfg.key_dim, cfg.value_dim, cfg.conv_dim, cfg.rotary_dim) == (
        2048, 4096, 8192, 64)
    # a slot: 12 layers x 2 MiB of state + 12 x 3 x 8,192 x 2 B of tails
    assert cfg.state_bytes_per_slot() == 12 * (2 * 2**20 + 3 * 8192 * 2)
    assert 25.7e6 < cfg.state_bytes_per_slot() < 25.9e6
    shapes = jax.eval_shape(Qwen3Next(cfg).init_params, jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    delta, attn, moe = (shapes["layers"][k] for k in ("delta", "attn", "moe"))
    assert round(count(delta) / 12 / 1e6, 2) == 33.72
    assert round(count(attn) / 4 / 1e6, 2) == 27.27
    banks = count(moe["w1"]) + count(moe["w2"])
    assert round(banks / 16 / 1e6, 1) == 201.3
    assert round((count(moe) - banks) / 16 / 1e6, 2) == 4.20
    assert round((count(shapes["embed"]) + count(shapes["lm_head"])) / 1e6, 1) == 77.8
    assert 3.875e9 < count(shapes) < 3.885e9  # 7.76 GB at 2 B a parameter
    # the whole model by the same count: 79.7 B
    whole = {**raw, **raw["published"]}
    whole.pop("ep_share")
    path.write_text(json.dumps(whole))
    full = config_from_hf_json(str(path))
    n = count(jax.eval_shape(Qwen3Next(full).init_params, jax.random.PRNGKey(0)))
    assert 79.5e9 < n < 79.9e9
    raw["num_hidden_layers"] = 18
    path.write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="whole periods"):
        config_from_hf_json(str(path))


def test_explicit_pages_are_checked_against_what_the_slots_leave(monkeypatch):
    """On a chip an explicit --num-kv-blocks that cannot fit beside the
    weights and the slots is an error that names the three; one that fits is
    taken as it is (on the CPU nothing is checked)."""
    with open("perf/configs/qwen3-next-ep8-cut.json") as f:
        raw = json.load(f)
    from production_stack_tpu.models.qwen3_next import config_from_hf

    cfg = config_from_hf(raw, "x")
    dev = types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite",
        memory_stats=lambda: {"bytes_limit": 16_909_336_064})
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    eng = EngineConfig(model="x", block_size=128, max_num_seqs=64,
                       num_kv_blocks=2048)
    weights = 7_760_000_000
    assert engine_config.resolve_num_kv_blocks(eng, cfg, weights) == 2048
    eng.num_kv_blocks = 8192  # 8.6 GB of pages beside 7.76 + 1.88
    with pytest.raises(ValueError, match="--num-kv-blocks 8192"):
        engine_config.resolve_num_kv_blocks(eng, cfg, weights)
    eng.num_kv_blocks = None  # sized from what is left: pages of 1 MiB
    n = engine_config.resolve_num_kv_blocks(eng, cfg, weights)
    left = int(16_909_336_064 * 0.9) - weights - 73 * cfg.state_bytes_per_slot()
    assert n == left // 2**20
