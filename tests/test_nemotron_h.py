"""The hybrid model class (state-space + attention + latent-MoE blocks,
``models/nemotron_h.py``) on the engine's normal path, against an independent
float32 oracle written out in numpy below (plain loops over positions and
experts; nothing of the program's forward pass), at tiny widths: hidden 64,
pattern ``MEM*E``, 16 experts top 3 of which this engine holds 4, latent 16,
vocabulary 128.

The benchmark's ``correct`` compares lone requests (PERF.md §7); what guards
rows against each other — slots mixed up, padded rows advancing a state, a
slot reused after a finish — is here.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import moe_dispatch
from production_stack_tpu.models.nemotron_h import NemotronH
from production_stack_tpu.models.registry import PRESETS
from production_stack_tpu.ops import ssm

from . import model_contract as contract
from .model_contract import assert_same, run

CFG = PRESETS["tiny-nemotron-h-debug"]
KIND = {"M": "mamba", "*": "attn", "E": "moe"}
PROMPT = [3, 17, 98, 25, 42, 7, 11, 20, 15, 31, 8, 77, 12, 5, 9, 2, 33, 44, 99,
          100, 101, 64, 65, 1, 90, 13, 14, 6, 120, 50, 51, 52, 53, 54, 55, 56,
          57, 58, 59, 60, 61, 62, 63, 66, 67, 68, 69, 70, 71, 72, 73, 74, 75]


# ----------------------------------------------------------------------------
# The oracle: numpy, float32, one position and one expert at a time
# ----------------------------------------------------------------------------


def _rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _softplus(x):
    return np.log1p(np.exp(-np.abs(x))) + np.maximum(x, 0.0)


def oracle_mamba(cfg, lw, u, s0=None):
    """u [T, D] -> (out [T, D], last state [H, P, N])."""
    T = u.shape[0]
    H, P, G, N, K = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                     cfg.ssm_state_size, cfg.conv_kernel)
    di, C = cfg.d_inner, cfg.conv_dim
    proj = u @ lw["w_in"]
    z, xbc, dt = proj[:, :di], proj[:, di:di + C], proj[:, di + C:]
    padded = np.concatenate([np.zeros((K - 1, C), np.float32), xbc])
    conv = np.stack([
        sum(padded[t + k] * lw["conv_w"][k] for k in range(K)) + lw["conv_b"]
        for t in range(T)])
    xbc = _silu(conv)
    xs = xbc[:, :di].reshape(T, H, P)
    bm = xbc[:, di:di + G * N].reshape(T, G, N)
    cm = xbc[:, di + G * N:].reshape(T, G, N)
    dt = _softplus(dt + lw["dt_bias"])
    a = -np.exp(lw["A_log"])
    s = np.zeros((H, P, N), np.float32) if s0 is None else s0.copy()
    y = np.zeros((T, H, P), np.float32)
    for t in range(T):
        for h in range(H):
            g = h // (H // G)
            s[h] = np.exp(dt[t, h] * a[h]) * s[h] + dt[t, h] * np.outer(
                xs[t, h], bm[t, g])
            y[t, h] = s[h] @ cm[t, g] + lw["D"][h] * xs[t, h]
    y = y.reshape(T, di) * _silu(z)
    yg = y.reshape(T, G, di // G)
    yg = yg / np.sqrt(np.mean(yg * yg, -1, keepdims=True) + cfg.rms_norm_eps)
    return (yg.reshape(T, di) * lw["gate_norm"]) @ lw["w_out"], s


def oracle_attention(cfg, lw, u):
    T = u.shape[0]
    q = (u @ lw["wq"]).reshape(T, cfg.num_heads, cfg.head_dim)
    k = (u @ lw["wk"]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    v = (u @ lw["wv"]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    out = np.zeros((T, cfg.num_heads, cfg.head_dim), np.float32)
    rep = cfg.num_heads // cfg.num_kv_heads
    for h in range(cfg.num_heads):
        sc = q[:, h] @ k[:, h // rep].T / np.sqrt(cfg.head_dim)
        sc = np.where(np.tril(np.ones((T, T), bool)), sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        out[:, h] = (p / p.sum(-1, keepdims=True)) @ v[:, h // rep]
    return out.reshape(T, -1) @ lw["wo"]


def oracle_route(cfg, lw, u):
    """-> (ids [T, K], weights [T, K]): chosen by score + bias, weighed by
    the score alone, renormalised and scaled."""
    s = 1.0 / (1.0 + np.exp(-(u @ lw["w_router"])))
    ids = np.argsort(-(s + lw["router_bias"]), axis=-1, kind="stable")[
        :, :cfg.num_experts_per_tok]
    w = np.take_along_axis(s, ids, -1)
    w = w / w.sum(-1, keepdims=True) * cfg.routed_scaling_factor
    return ids, w


def oracle_moe(cfg, lw, u, first, w1, w2):
    """The layer over the experts ``first .. first + len(w1)``; the whole
    layer when handed all of them. -> (routed part, shared part)."""
    ids, w = oracle_route(cfg, lw, u)
    lat = u @ lw["w_latent_down"]
    acc = np.zeros_like(lat)
    for t in range(u.shape[0]):
        for e, we in zip(ids[t], w[t]):
            if first <= e < first + len(w1):
                a = np.maximum(lat[t] @ w1[e - first], 0.0) ** 2
                acc[t] += we * (a @ w2[e - first])
    shared = (np.maximum(u @ lw["w_shared_up"], 0.0) ** 2) @ lw["w_shared_down"]
    return acc @ lw["w_latent_up"], shared


def oracle_logits(cfg, params, ids):
    """Full forward over the whole sequence: logits [T, V]."""
    x = params["embed"][np.asarray(ids)]
    seen = {}
    for c in cfg.pattern:
        kind = KIND[c]
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        lw = {k: v[i] for k, v in params["layers"][kind].items()}
        u = _rms(x, lw["norm"], cfg.rms_norm_eps)
        if kind == "mamba":
            out, _ = oracle_mamba(cfg, lw, u)
        elif kind == "attn":
            out = oracle_attention(cfg, lw, u)
        else:
            routed, shared = oracle_moe(
                cfg, lw, u, cfg.expert_first, lw["w1"], lw["w2"])
            out = routed + shared
        x = x + out
    return _rms(x, params["final_norm"], cfg.rms_norm_eps) @ params["lm_head"].T


def _log_softmax(x):
    x = x - x.max(-1, keepdims=True)
    return x - np.log(np.exp(x).sum(-1, keepdims=True))


# ----------------------------------------------------------------------------
# Fixtures
# ----------------------------------------------------------------------------


make_engine = functools.partial(
    contract.make_engine, "tiny-nemotron-h-debug", enable_prefix_caching=False)


def slots_into(seen):
    """A ``watch`` for ``run``: the state slots each request held, by id."""
    def watch(seq):
        if seq.state_slot is not None:
            seen.setdefault(seq.request_id, set()).add(seq.state_slot)
    return watch


@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.fixture(scope="module")
def params(engine):
    return jax.tree.map(lambda a: np.asarray(a, np.float32),
                        jax.device_get(engine.runner.params))


def oracle_rows(params, prompt, tokens):
    lps = _log_softmax(oracle_logits(CFG, params, prompt + tokens))
    return lps[len(prompt) - 1:len(prompt) - 1 + len(tokens)]


assert_matches_oracle = functools.partial(
    contract.assert_matches_reference, oracle_rows)


# ----------------------------------------------------------------------------
# (a) (b) the engine's normal path against the full forward pass
# ----------------------------------------------------------------------------


def test_chunked_prefill_then_decode_matches_full_forward(engine, params):
    """(a) 53 prompt tokens in chunks of 16 through pages and slots, then
    chained decode steps: every reported log-probability is the oracle's."""
    got = run(engine, [PROMPT], 8)[0]
    assert len(got["tokens"]) == 8
    assert_matches_oracle(params, PROMPT, got)
    assert engine.pipelined_bursts_total > 0, "decode must run chained"


def test_the_server_exports_the_dispatch_counts(engine):
    """``pst:moe_dispatch_overflow_total`` beside the accepted five."""
    run(engine, [PROMPT[:20]], 2)
    contract.assert_dispatch_counts_exported(engine)


@pytest.mark.parametrize("chunk", [16, 48, 64])
def test_chunk_size_does_not_change_the_logits(chunk, params):
    """(b) the same prompt in chunks of 16, 48 and whole."""
    eng = make_engine(max_prefill_tokens=chunk, overlap_decode=chunk != 48)
    got = run(eng, [PROMPT], 4)[0]
    assert_matches_oracle(params, PROMPT, got)


def test_short_prompts_and_one_token_chunks(params):
    """Prompts shorter than the convolution's tail, and a chunk of one
    token that is a sequence's first (the decode kernel from zeros)."""
    eng = make_engine(max_prefill_tokens=8)
    prompts = [[5], [9, 2], PROMPT[:9]]
    for p, got in zip(prompts, run(eng, prompts, 5)):
        assert_matches_oracle(params, p, got)


# ----------------------------------------------------------------------------
# (c) rows sharing steps, slots reused
# ----------------------------------------------------------------------------


def test_staggered_sequences_match_their_lone_runs(params):
    """(c) five sequences of different lengths arrive two steps apart into
    three rows: packed and padded prefill steps, decode batches that grow
    and shrink, a slot taken again after a finish. Each matches its lone
    run and the oracle."""
    prompts = [PROMPT[:n] for n in (37, 5, 53, 18, 26)]
    n_out = 7
    eng = make_engine(max_num_seqs=3, max_prefill_tokens=32)
    seen = {}
    together = run(eng, prompts, n_out, stagger=2, watch=slots_into(seen))
    slots = [min(seen[f"r{i}"]) for i in range(len(prompts))]
    assert all(len(s) == 1 for s in seen.values())
    assert len(set(slots)) < len(slots), "a slot must have been reused"
    lone_eng = make_engine(max_num_seqs=3, max_prefill_tokens=32)
    for p, got in zip(prompts, together):
        lone = run(lone_eng, [p], n_out)[0]
        assert_same(got, lone)
        assert_matches_oracle(params, p, got)
    assert eng.allocator.state_slots_in_use == 0


def test_arrivals_join_the_running_chain_on_state_slots(params):
    """Eight sequences arrive three steps apart under a chain of four rows
    (one prompt in two chunks): each joins behind its own prefill, which
    wrote its slot before the chained step reads it, with no drain; a
    finished member's slot and pages come back a burst later and are taken
    again (six slots, eight sequences) while the chain runs on. Tokens and
    log-probabilities are the synchronous loop's."""
    prompts = [PROMPT[:n] for n in (37, 5, 53, 18, 26, 11, 44, 9)]
    kw = dict(max_num_seqs=4, min_decode_bucket=4, max_prefill_tokens=32)
    sync = run(make_engine(overlap_decode=False, **kw), prompts, 9, stagger=3)
    eng = make_engine(**kw)
    step, held = eng.step, []

    def checked_step():
        outs = step()
        if not sum(eng.pipeline_breaks.values()):  # the chain never drained
            # a slot is held by a running sequence or by a member that
            # finished under the burst in flight, and by nothing else
            assert eng.allocator.state_slots_in_use == (
                eng.scheduler.num_running + len(eng._burst_deferred))
            held.append(len(eng._burst_deferred))
        return outs

    eng.step = checked_step
    seen = {}
    got = run(eng, prompts, 9, stagger=3, watch=slots_into(seen))
    assert 0 < max(held) <= 2 and held.count(0) > len(held) // 2
    for a, b in zip(got, sync):
        assert_same(a, b)
    slots = [min(seen[f"r{i}"]) for i in range(len(prompts))]
    assert len(set(slots)) < len(slots), "a slot must have been reused"
    assert eng.chain_kept_prefills_total >= 7
    assert eng.pipeline_breaks["prefill"] == 0
    assert sum(eng.pipeline_breaks.values()) == 1, eng.pipeline_breaks
    assert eng.allocator.state_slots_in_use == 0
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_slot_wait_leaves_the_request_queued():
    """More live sequences than slots: admission waits, counts the wait,
    and every request still completes."""
    eng = make_engine(max_num_seqs=2)
    eng.allocator._free_slots = eng.allocator._free_slots[:1]  # one slot only
    out = run(eng, [PROMPT[:10], PROMPT[:12]], 3, logprobs=None)
    assert [len(r["tokens"]) for r in out] == [3, 3]
    assert eng.allocator.state_slot_waits > 0
    assert eng.stats()["state_slot_waits_total"] > 0


# ----------------------------------------------------------------------------
# (d) (e) the scan and the kernel
# ----------------------------------------------------------------------------


def _ssm_inputs(B, T, H=8, P=16, G=2, N=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)))
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.7))
    bm = jax.random.normal(ks[3], (B, T, G, N))
    cm = jax.random.normal(ks[4], (B, T, G, N))
    s0 = jax.random.normal(ks[5], (B, H, P, N))
    return x, dt, a, bm, cm, s0


@pytest.mark.parametrize("chunk,T", [(8, 37), (16, 16), (128, 50)])
def test_chunked_scan_equals_the_sequential_recurrence(chunk, T):
    """(d) from a non-zero initial state, with rows that end early (their
    ``dt`` is 0 past the end: the state must stay where the row ended)."""
    x, dt, a, bm, cm, s0 = _ssm_inputs(3, T)
    lens = jnp.array([T, T // 2, 3])
    dt = dt * (jnp.arange(T)[None, :, None] < lens[:, None, None])
    y, s_last = ssm.ssd_chunked(x, dt, a, bm, cm, s0, chunk=chunk)
    s, ys, at_len = s0, [], [None] * 3
    for t in range(T):
        yt, s = ssm.ssm_step(s, x[:, t], dt[:, t], a, bm[:, t], cm[:, t])
        ys.append(yt)
        for b in range(3):
            if t + 1 == int(lens[b]):
                at_len[b] = s[b]
    np.testing.assert_allclose(y, jnp.stack(ys, 1), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(s_last, jnp.stack(at_len), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("G", [1, 2])
def test_decode_kernel_equals_the_step_and_leaves_other_slots(G):
    """(e) ``ssm_decode`` interpreted: the row's state is read by slot,
    stepped and written back in place; every other slot and layer is
    bit for bit what it was; a decay of 0 starts from zeros. One group of
    heads a grid step (``G`` 1) and two."""
    L, slots, B, H, P, N = 2, 6, 3, 8, 16, 16
    x, dt, a, bm, cm, _ = _ssm_inputs(B, 1, G=G)
    pool_plain = jax.random.normal(jax.random.PRNGKey(9), (L, slots, H, P, N))
    slot_of = jnp.array([4, 0, 2])
    fresh = jnp.array([False, True, False])
    decay = jnp.where(fresh[:, None], 0.0, jnp.exp(dt[:, 0] * a))
    s_in = jnp.where(fresh[:, None, None, None], 0.0, pool_plain[1, slot_of])
    want_y, want_s = ssm.ssm_step(s_in, x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0])
    pool = ssm.pack_state(pool_plain, G)
    before = np.asarray(pool)
    y, pool = jax.jit(
        lambda p: ssm.ssm_decode(
            p, jnp.int32(1), slot_of, decay, dt[:, 0, :, None] * x[:, 0],
            bm[:, 0], cm[:, 0], n_groups=G)
    )(pool)
    np.testing.assert_allclose(y, want_y, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(
        ssm.unpack_state(pool[1, slot_of], P), want_s, atol=1e-5, rtol=1e-5)
    untouched = np.ones((L, slots), bool)
    untouched[1, np.asarray(slot_of)] = False
    assert np.array_equal(np.asarray(pool)[untouched], before[untouched])


def test_state_layout_round_trip():
    s = jax.random.normal(jax.random.PRNGKey(2), (3, 8, 16, 16))
    packed = ssm.pack_state(s, 2)
    assert packed.shape == (3,) + ssm.packed_shape(8, 16, 16, 2)
    assert np.array_equal(ssm.unpack_state(packed, 16), s)
    # published widths: two heads share a 128-lane tile
    assert ssm.packed_shape(128, 64, 128, 8) == (64, 128, 128)


# ----------------------------------------------------------------------------
# (f) (g) the expert layer and its share
# ----------------------------------------------------------------------------


def _moe_layer(seed=3, n_tokens=9):
    """A whole 16-expert layer's weights (float32 numpy) and its input."""
    whole = dataclasses.replace(CFG, n_routed_experts=16, expert_first=0)
    p = NemotronH(whole).init_params(jax.random.PRNGKey(seed))
    lw = {k: np.asarray(v[0], np.float32) for k, v in p["layers"]["moe"].items()}
    u = np.asarray(jax.random.normal(
        jax.random.PRNGKey(seed + 1), (n_tokens, CFG.hidden_size)), np.float32)
    return lw, u


@pytest.mark.parametrize("tokens", [9, 150])
def test_the_four_shares_add_up_to_the_whole_layer(tokens):
    """(f) each of the four ranks' routed parts, passed through the latent
    up-projection, summed, with the shared expert counted once, is what the
    oracle gives for the uncut 16-expert layer: at 9 tokens, whose pairs
    are one row tile (the plain program), and at 150, where a share works
    on one row capacity a round."""
    lw, u = _moe_layer(n_tokens=tokens)
    pairs = tokens * CFG.num_experts_per_tok
    assert (moe_dispatch.capacity(pairs, 4, 16) < pairs) == (tokens == 150)
    want_routed, want_shared = oracle_moe(CFG, lw, u, 0, lw["w1"], lw["w2"])
    total = np.zeros_like(want_routed)
    valid = jnp.ones(u.shape[0], bool)
    held_pairs = 0.0
    for rank in range(4):
        cfg = dataclasses.replace(CFG, n_routed_experts=4, expert_first=4 * rank)
        model = NemotronH(cfg)
        share = {**lw, "w1": lw["w1"][4 * rank:4 * rank + 4],
                 "w2": lw["w2"][4 * rank:4 * rank + 4]}
        share = {k: jnp.asarray(v) for k, v in share.items()}
        acc, stats = model.routed_latent(share, jnp.asarray(u), valid)
        part = np.asarray(acc) @ lw["w_latent_up"]
        # the rank's own part is the oracle's over its four experts
        own, _ = oracle_moe(CFG, lw, u, 4 * rank, share["w1"], share["w2"])
        np.testing.assert_allclose(part, own, atol=2e-4, rtol=2e-4)
        total += part
        assert float(stats[0]) == u.shape[0] * CFG.num_experts_per_tok
        held_pairs += float(stats[1])
        # experts that got a pair: at most the four held, at least one where
        # a pair is held, at most the busiest times their number; one layer
        assert float(stats[1]) <= float(stats[2]) * float(stats[3])
        assert 0 < float(stats[3]) <= 4 and float(stats[4]) == 1.0
        assert float(stats[5]) == 0.0  # no share passed its capacity
        out, _ = model._moe(share, jnp.asarray(u), valid)
        np.testing.assert_allclose(
            np.asarray(out), own + want_shared, atol=2e-4, rtol=2e-4)
    assert held_pairs == u.shape[0] * CFG.num_experts_per_tok
    np.testing.assert_allclose(total, want_routed, atol=5e-4, rtol=5e-4)


def test_padding_tokens_route_nowhere():
    lw, u = _moe_layer()
    cfg = dataclasses.replace(CFG, n_routed_experts=16, expert_first=0)
    share = {k: jnp.asarray(v) for k, v in lw.items()}
    valid = jnp.arange(u.shape[0]) < 4
    acc, stats = NemotronH(cfg).routed_latent(share, jnp.asarray(u), valid)
    assert float(stats[0]) == float(stats[1]) == 4 * CFG.num_experts_per_tok
    assert not np.asarray(acc)[4:].any()


def test_selection_is_by_score_plus_bias_and_weights_by_score():
    """(g) a bias that lifts a low-scoring expert into the top k changes
    who is chosen; its weight is still its score's share."""
    lw, u = _moe_layer()
    model = NemotronH(dataclasses.replace(CFG, n_routed_experts=16, expert_first=0))
    s = 1.0 / (1.0 + np.exp(-(u @ lw["w_router"])))
    worst = int(np.argmin(s[0]))
    lw["router_bias"] = np.zeros(16, np.float32)
    lw["router_bias"][worst] = 10.0
    ids, w = model.route({k: jnp.asarray(v) for k, v in lw.items()}, jnp.asarray(u))
    ids, w = np.asarray(ids), np.asarray(w)
    assert (ids == worst).any(axis=1).all(), "the bias must select it"
    want_ids, want_w = oracle_route(CFG, lw, u)
    assert np.array_equal(np.sort(ids, -1), np.sort(want_ids, -1))
    for t in range(u.shape[0]):
        chosen = s[t][ids[t]]
        np.testing.assert_allclose(
            w[t], chosen / chosen.sum() * CFG.routed_scaling_factor, rtol=1e-5)
        got = dict(zip(ids[t].tolist(), w[t].tolist()))
        for e, we in zip(want_ids[t], want_w[t]):
            assert abs(got[int(e)] - we) < 1e-5
    assert np.allclose(w.sum(-1), CFG.routed_scaling_factor, rtol=1e-5)


# ----------------------------------------------------------------------------
# (h) what is refused, by the flag's name
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("over,flag", [
    (dict(enable_prefix_caching=True), "--enable-prefix-caching"),
    (dict(kv_swap=True), "--kv-swap"),
    (dict(cpu_offload_blocks=8), "--cpu-offload-blocks"),
    (dict(kv_role="producer"), "--kv-role"),
    (dict(speculative_ngram=3), "--speculative-ngram"),
    (dict(enable_lora=True), "--enable-lora"),
    (dict(tensor_parallel_size=2), "--tensor-parallel-size"),
    (dict(quantization="int8"), "--quantization"),
])
def test_refused_at_start_up_by_the_flags_name(over, flag):
    with pytest.raises(ValueError) as e:
        make_engine(**over)
    assert flag in str(e.value) and "recurrent" in str(e.value)


def test_config_door_knows_the_model_type(tmp_path):
    import json

    from production_stack_tpu.models.llama import config_from_hf_json

    with open("perf/configs/nemotron-3-super-ep4-cut.json") as f:
        raw = json.load(f)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(raw))
    cfg = config_from_hf_json(str(path), name="x")
    assert cfg.num_layers == 11 and cfg.pattern == "MEMEMEM*EME"
    assert (cfg.n_routed_experts, cfg.router_experts, cfg.expert_first) == (128, 512, 0)
    assert cfg.num_kv_layers == 1 and cfg.count("mamba") == 5
    assert cfg.state_bytes_per_slot() == 5 * (4 << 20) + 5 * 3 * 10240 * 2
    raw["hybrid_override_pattern"] = "ME-"
    raw["num_hidden_layers"] = 3
    path.write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="block kinds"):
        config_from_hf_json(str(path))


# ----------------------------------------------------------------------------
# (i) preemption is by recompute
# ----------------------------------------------------------------------------


def test_preemption_by_recompute_returns_the_same_tokens(params):
    """(i) 12 pages of 8 tokens: two 40-token prompts admit and one must
    lose its pages and its slot while decoding; it starts again from zeros
    and gives the tokens of a roomy engine. (A pool one page smaller makes
    the recompute-only scheduler trade the two back and forth for ever,
    with any model: its admission counts the prompt's pages, the recompute
    needs the outputs' too.)"""
    p1, p2 = PROMPT[:40], PROMPT[5:45]
    tight = make_engine(num_kv_blocks=12, max_model_len=128, max_prefill_tokens=48)
    got = run(tight, [p1, p2], 10)
    assert tight.num_preempted_total > 0, "the test must exercise preemption"
    roomy = run(make_engine(max_prefill_tokens=48), [p1, p2], 10)
    for p, a, b in zip((p1, p2), got, roomy):
        assert a["tokens"] == b["tokens"]
        assert_matches_oracle(params, p, a)
    assert tight.allocator.state_slots_in_use == 0
