"""The latent-attention mixture of experts (``models/glm4_moe_lite.py``) on
the engine's normal path against the benchmark's plain reference
(``perf/reference/glm4_moe_lite.py``: float32, expanded attention, a dense
loop over the experts; nothing of the program's forward pass), at tiny
widths: hidden 64, one dense layer and two expert layers of 8 gated experts
top 2, four heads over a latent row of 24 + 8, vocabulary 128, float32.

Tolerances: the served model and the reference are both float32 here, so a
log-probability differs by summation order alone (blocks, online softmax,
absorbed against expanded products): 2e-3 holds every case with a decade of
room (observed below 2e-4); an expert flip cannot hide in it (a flipped
expert moves a log-probability by 1e-2 and more at these widths).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import config as configs
from perf.reference import glm4_moe_lite as ref
from production_stack_tpu.engine.config import EngineConfig, resolve_num_kv_blocks
from production_stack_tpu.models import moe_dispatch
from production_stack_tpu.models.glm4_moe_lite import Glm4MoeLite
from production_stack_tpu.models.registry import PRESETS
from production_stack_tpu.ops import mla_attention as mla

from . import model_contract as contract
from .model_contract import assert_same, run

NAME = "tiny-glm4-moe-lite-debug"
CFG = PRESETS[NAME]
TOL = 2e-3
PROMPT = [3, 17, 98, 25, 42, 7, 11, 20, 15, 31, 8, 77, 12, 5, 9, 2, 33, 44, 99,
          100, 101, 64, 65, 1, 90, 13, 14, 6, 120, 50, 51, 52, 53, 54, 55, 56,
          57, 58, 59, 60, 61, 62, 63, 66, 67, 68, 69, 70, 71, 72, 73, 74, 75]
# The preset under the published key names, as the reference reads them.
HF = {
    "model_type": "glm4_moe_lite", "vocab_size": CFG.vocab_size,
    "hidden_size": CFG.hidden_size, "num_hidden_layers": CFG.num_layers,
    "first_k_dense_replace": CFG.first_k_dense,
    "intermediate_size": CFG.intermediate_size,
    "num_attention_heads": CFG.num_heads, "q_lora_rank": CFG.q_lora_rank,
    "kv_lora_rank": CFG.kv_lora_rank, "qk_nope_head_dim": CFG.qk_nope_head_dim,
    "qk_rope_head_dim": CFG.qk_rope_head_dim, "v_head_dim": CFG.v_head_dim,
    "rope_theta": CFG.rope_theta, "n_routed_experts": CFG.n_routed_experts,
    "num_experts_per_tok": CFG.num_experts_per_tok,
    "moe_intermediate_size": CFG.moe_intermediate_size,
    "n_shared_experts": CFG.n_shared_experts,
    "routed_scaling_factor": CFG.routed_scaling_factor,
    "norm_topk_prob": CFG.norm_topk_prob, "rms_norm_eps": CFG.rms_norm_eps,
}
REF_CFG = configs.Config(
    name=NAME, path="", hf=HF, engine_flags=(), weights_seed=0, check={},
    reference="glm4_moe_lite", raw={})


make_engine = functools.partial(contract.make_engine, NAME)


@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.fixture(scope="module")
def params(engine):
    return engine.runner.params


def reference_logprobs(params, prompt, tokens):
    seq = {"tokens": list(prompt) + list(tokens), "n_prompt": len(prompt),
           "want": [[0]] * len(tokens)}
    return ref.teacher_force(REF_CFG, params, [seq], "none")[0][0]


assert_matches_reference = functools.partial(
    contract.assert_matches_reference, reference_logprobs, tol=TOL)


# ----------------------------------------------------------------------------
# The engine's normal path against the reference's full forward pass
# ----------------------------------------------------------------------------


def test_chunked_prefill_then_decode_through_the_latent_pages(engine, params):
    """53 prompt tokens in chunks of 16 written to the pages and attended
    absorbed, then chained decode steps through the cache (absorbed): every
    reported log-probability is the reference's full forward pass's."""
    got = run(engine, [PROMPT], 8)[0]
    assert len(got["tokens"]) == 8
    assert_matches_reference(params, PROMPT, got)
    assert engine.pipelined_bursts_total > 0, "decode must run chained"
    stats = engine.stats()
    # counted on the steps whose result is fetched: the prompt's last chunk
    assert stats["mla_prefill_steps_absorbed_total"] == 1
    assert stats["mla_prefill_steps_expanded_total"] == 0
    assert stats["moe_pairs_held_total"] == stats["moe_pairs_routed_total"] > 0


def test_the_server_exports_the_dispatch_counts(engine):
    """``pst:moe_dispatch_overflow_total`` beside the accepted five."""
    run(engine, [PROMPT[:20]], 2)
    contract.assert_dispatch_counts_exported(engine)


def test_prefill_in_one_chunk_takes_the_expanded_path(params):
    """The whole prompt as one chunk of 64 positions: long enough at these
    widths (the rule's threshold is 47) to pay for expanding the context."""
    eng = make_engine(max_prefill_tokens=64)
    got = run(eng, [PROMPT], 4)[0]
    assert_matches_reference(params, PROMPT, got)
    stats = eng.stats()
    assert stats["mla_prefill_steps_expanded_total"] == 1
    assert stats["mla_prefill_steps_absorbed_total"] == 0


def test_short_prompts_and_one_token_chunks(engine, params):
    prompts = [[5], [9, 2], PROMPT[:9]]
    for p, got in zip(prompts, run(engine, prompts, 5)):
        assert_matches_reference(params, p, got)


def test_decode_through_the_kernel_matches_the_reference(params):
    """The same path with ``mla_decode`` (interpreted here) in the decode
    step, as the chip runs it."""
    eng = make_engine(attn_impl="pallas", max_model_len=128, num_kv_blocks=48)
    got = run(eng, [PROMPT[:21], PROMPT[:9]], 4)
    for p, g in zip((PROMPT[:21], PROMPT[:9]), got):
        assert_matches_reference(params, p, g)


# ----------------------------------------------------------------------------
# The prefix cache over latent pages; rows sharing steps; preemption
# ----------------------------------------------------------------------------


def test_a_prefix_cache_hit_on_latent_pages_gives_the_same_logits(engine, params):
    """The second request finds the first's whole pages (48 of 53 tokens at
    8 a page), prefills the rest over them, and reports what the first, which
    found nothing, reported; a turn that extends the first answer finds its
    pages too and matches the reference's full forward pass."""
    doc = PROMPT[::-1]
    hits = engine.stats()["prefix_cache_hits_total"]
    first = run(engine, [doc], 6)[0]
    assert engine.stats()["prefix_cache_hits_total"] == hits
    again = run(engine, [doc], 6)[0]
    assert engine.stats()["prefix_cache_hits_total"] - hits == 48
    assert_same(first, again)
    assert_matches_reference(params, doc, again)
    turn = doc + first["tokens"] + [4, 19, 88]
    hits = engine.stats()["prefix_cache_hits_total"]
    got = run(engine, [turn], 5)[0]
    assert engine.stats()["prefix_cache_hits_total"] - hits >= 56
    assert_matches_reference(params, turn, got)


def test_staggered_sequences_leave_no_trace_in_each_other(engine, params):
    """Six sequences of different lengths arrive two steps apart into four
    rows: packed and padded prefill steps, decode batches that grow and
    shrink (padded rows, a row taken again after a finish), pages found in
    the prefix cache. Each matches the reference's lone forward pass."""
    prompts = [PROMPT[n:] + PROMPT[:n] for n in (37, 5, 52, 18, 26, 44)]
    prompts = [p[:n] for p, n in zip(prompts, (37, 5, 53, 18, 26, 9))]
    for p, got in zip(prompts, run(engine, prompts, 7, stagger=2)):
        assert_matches_reference(params, p, got)


def test_arrivals_join_the_running_chain_on_latent_pages(params):
    """Eight sequences arrive three steps apart under a chain of eight rows
    (prompts of up to four chunks, pages found in the prefix cache): each joins
    behind its own prefill with no drain, and a finished member's pages come
    back a burst later while the chain runs on. Tokens and log-probabilities
    are the synchronous loop's."""
    prompts = [PROMPT[n:] + PROMPT[:n] for n in (37, 5, 52, 18, 26, 44, 0, 37)]
    prompts = [p[:n] for p, n in zip(prompts, (37, 5, 53, 18, 26, 9, 30, 50))]
    kw = dict(max_num_seqs=8, min_decode_bucket=8)
    sync = run(make_engine(overlap_decode=False, **kw), prompts, 9, stagger=3)
    eng = make_engine(**kw)
    got = run(eng, prompts, 9, stagger=3)
    for a, b in zip(got, sync):
        assert_same(a, b)
    assert eng.chain_kept_prefills_total >= 7
    assert eng.pipeline_breaks["prefill"] == 0
    assert sum(eng.pipeline_breaks.values()) == 1, eng.pipeline_breaks
    assert eng.allocator.num_free == eng.allocator.num_blocks


def test_preemption_by_recompute_returns_the_same_tokens(params):
    """12 pages of 8 tokens: two 40-token prompts admit and one must lose
    its pages while decoding; it is prefilled again and still gives what the
    reference gives for a lone sequence."""
    p1, p2 = PROMPT[:40], PROMPT[5:45]
    tight = make_engine(num_kv_blocks=12, max_model_len=128,
                        max_prefill_tokens=48, enable_prefix_caching=False)
    got = run(tight, [p1, p2], 10)
    assert tight.num_preempted_total > 0, "the test must exercise preemption"
    for p, a in zip((p1, p2), got):
        assert len(a["tokens"]) == 10
        assert_matches_reference(params, p, a)


# ----------------------------------------------------------------------------
# Both prefill paths; the rule; the kernel
# ----------------------------------------------------------------------------


def _prefill_inputs(n_cached=24, n_fresh=16, bs=8, nb=16):
    """A row with ``n_cached`` tokens in its pages and ``n_fresh`` to
    prefill, beside a padding row."""
    model = Glm4MoeLite(CFG)
    params = model.init_params(jax.random.PRNGKey(0))
    cache = model.make_kv_cache(nb, bs)
    ids = np.asarray(PROMPT[: n_cached + n_fresh], np.int32)
    W = 8
    tables = np.zeros((2, W), np.int32)
    tables[0] = np.arange(1, W + 1)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def step(cache, lo, hi, path):
        T = hi - lo
        pos = np.arange(lo, hi, dtype=np.int32)
        tokens = np.stack([ids[lo:hi], np.zeros(T, np.int32)])
        write = np.stack([tables[0][pos // bs] * bs + pos % bs,
                          np.full(T, nb * bs, np.int32)])
        return model.forward(
            params, jnp.asarray(tokens), jnp.asarray(np.stack([pos, pos * 0])),
            jnp.asarray(write), jnp.asarray(tables),
            jnp.asarray([hi, 0], jnp.int32), jnp.asarray([T - 1, 0], jnp.int32),
            cache, all_logits=True, prefill_path=path)

    _, cache = step(cache, 0, n_cached, "absorbed")
    return step, cache, n_cached, n_cached + n_fresh


def test_both_prefill_paths_give_the_same_logits():
    """One chunk over one cached context, expanded and absorbed: the same
    logits (float32: summation order alone), the same rows written, and
    each path counted under its own name."""
    step, cache, lo, hi = _prefill_inputs()
    exp_logits, exp_cache = step(cache, lo, hi, "expanded")
    abs_logits, abs_cache = step(cache, lo, hi, "absorbed")
    np.testing.assert_allclose(
        np.asarray(exp_logits[0]), np.asarray(abs_logits[0]), atol=2e-4, rtol=2e-4)
    # the rows written are the same but for the summation order above them
    np.testing.assert_allclose(
        np.asarray(exp_cache["kv"]), np.asarray(abs_cache["kv"]), atol=1e-5)
    names = Glm4MoeLite.AUX_NAMES
    counted = dict(zip(names, np.asarray(exp_cache["aux"])))
    assert counted["mla_prefill_steps_expanded_total"] == 1
    assert counted["mla_prefill_steps_absorbed_total"] == 0
    counted = dict(zip(names, np.asarray(abs_cache["aux"])))
    assert counted["mla_prefill_steps_absorbed_total"] == 1
    # the cached row is [c_kv | k_rope | 0 ...] in whole lane tiles
    row = np.asarray(exp_cache["kv"])[0, 1, 0, 0]
    assert row.shape == (128,) and row[:32].any() and not row[32:].any()


def test_the_prefill_rule_follows_the_chunk_against_the_expansion():
    """Expanded once the chunk's saving a context token, (2 rank + rope) -
    (nope + rope + v) a head and query, passes the expansion's (nope + v) x
    rank: 398 positions at the published widths. No flag reads into it."""
    published = Glm4MoeLite(dataclasses.replace(
        CFG, kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256))
    assert [published.prefill_path(t) for t in (64, 256, 398, 399, 512, 1024)] == [
        "absorbed", "absorbed", "absorbed", "expanded", "expanded", "expanded"]
    tiny = Glm4MoeLite(CFG)
    assert tiny.prefill_path(16) == "absorbed" and tiny.prefill_path(64) == "expanded"


def test_mla_decode_kernel_reads_live_pages_alone():
    """The kernel (interpreted) against the ``jax.numpy`` absorbed path:
    ragged lengths on each side of a page and of a chunk, an empty row, dead
    table entries pointing at a page of NaN, another layer of NaN."""
    rng = np.random.default_rng(0)
    H, rank, rope, bs, W = 4, 128, 64, 8, 12
    lanes = mla.latent_lanes(rank, rope)
    lens = np.asarray([1, 8, 0, 37, 9, 96], np.int32)
    B = len(lens)
    nb = B * W + 2
    kv = np.zeros((nb, 1, bs, lanes), np.float32)
    kv[..., :rank + rope] = rng.standard_normal((nb, 1, bs, rank + rope))
    kv[1] = np.nan
    stack = jnp.stack([jnp.full(kv.shape, np.nan, jnp.float32), jnp.asarray(kv)])
    tables = (rng.permutation(B * W) + 2).reshape(B, W)
    dead = np.arange(W)[None] >= -(-lens // bs)[:, None]
    q = jnp.asarray(rng.standard_normal((B, H, rank + rope)), jnp.float32)
    got = np.asarray(mla.mla_decode(
        q, stack, jnp.asarray(np.where(dead, 1, tables).astype(np.int32)),
        jnp.asarray(lens), jnp.int32(1), rank=rank, scale=0.125,
        chunk_tokens=32, fold_tokens=16))
    want = np.asarray(mla.absorbed_attention(
        q[:, None], jnp.asarray(kv)[None], 0,
        jnp.asarray(np.where(dead, 0, tables).astype(np.int32)),
        jnp.asarray(lens), jnp.asarray(np.maximum(lens - 1, 0))[:, None],
        rank=rank, scale=0.125))[:, 0]
    assert np.isfinite(got).all()
    live = lens > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=2e-5)
    assert not got[~live].any()


# ----------------------------------------------------------------------------
# The expert share, tied to the model
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("tokens", [11, 300])
def test_eight_shares_add_up_to_the_uncut_references_layer(tokens):
    """At 64 experts top 4: eight shares of 8 experts each, through the
    dispatch the mixture-of-experts classes call, with the shared expert
    counted once, add up to what the reference gives for the whole layer:
    at 11 tokens (one row tile: the plain program) and at 300, where a
    share works on one row capacity of 256 of its 1,280 rows a round."""
    assert (moe_dispatch.capacity(tokens * 4, 8, 64) == 256) == (tokens == 300)
    whole = dataclasses.replace(
        CFG, n_routed_experts=64, router_experts=64, num_experts_per_tok=4)
    p = Glm4MoeLite(whole).init_params(jax.random.PRNGKey(3))
    mp = {k: v[0] for k, v in p["layers"]["moe"].items()}
    norm = jnp.ones((CFG.hidden_size,), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (tokens, CFG.hidden_size), jnp.float32)
    want, gap = ref.moe(
        x, norm, mp, top_k=4, first=0, scale=CFG.routed_scaling_factor,
        renorm=True, eps=CFG.rms_norm_eps, softmax=False)
    assert float(gap.min()) > 0
    u = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + CFG.rms_norm_eps)
    valid = jnp.ones(x.shape[0], bool)

    @jax.jit
    def share(first, w1, w2):  # one rank's layer, told which experts it holds
        model = Glm4MoeLite(dataclasses.replace(
            whole, n_routed_experts=8, expert_first=first))
        return model.routed(mp, {"w1": w1, "w2": w2}, 0, u, valid)

    total = np.zeros(want.shape, np.float32)
    held_pairs = 0.0
    for rank in range(8):
        lo = 8 * rank
        part, stats = share(jnp.int32(lo), mp["w1"][lo:lo + 8], mp["w2"][lo:lo + 8])
        total += np.asarray(part)
        counts = dict(zip(moe_dispatch.AUX_NAMES, np.asarray(stats)))
        assert counts["moe_pairs_routed_total"] == x.shape[0] * 4
        assert 0 < counts["moe_experts_touched_total"] <= 8
        assert counts["moe_layer_steps_total"] == 1
        assert counts["moe_dispatch_overflow_total"] == 0
        held_pairs += counts["moe_pairs_held_total"]
    assert held_pairs == x.shape[0] * 4
    # the last rank's own part is the reference's over its eight experts
    own, _ = ref.moe(
        x, norm, {**mp, "w1": mp["w1"][56:], "w2": mp["w2"][56:],
                  "w_shared_gate": mp["w_shared_gate"] * 0},
        top_k=4, first=56, scale=CFG.routed_scaling_factor, renorm=True,
        eps=CFG.rms_norm_eps, softmax=False)
    np.testing.assert_allclose(np.asarray(part), np.asarray(own),
                               atol=2e-4, rtol=2e-4)
    total += np.asarray(Glm4MoeLite(whole).shared_expert(mp, u))  # once
    np.testing.assert_allclose(total, np.asarray(want), atol=5e-4, rtol=5e-4)


# ----------------------------------------------------------------------------
# Sizes, the door, and what is refused
# ----------------------------------------------------------------------------


def test_the_pool_is_sized_by_the_models_own_page():
    """A page is one padded latent row a token and layer, not 2 x KH x hd."""
    model = Glm4MoeLite(CFG)
    cache = model.make_kv_cache(6, 8)
    assert cache["kv"].shape == (CFG.num_layers, 6, 1, 8, 128)
    assert CFG.page_bytes(8, 4) == cache["kv"].nbytes // 6
    cfg = EngineConfig(model=NAME, block_size=8, max_model_len=256)
    assert resolve_num_kv_blocks(cfg, CFG, 0) == (512 << 20) // CFG.page_bytes(8, 4)


@pytest.mark.parametrize("over,flag", [
    (dict(kv_swap=True), "--kv-swap"),
    (dict(cpu_offload_blocks=8), "--cpu-offload-blocks"),
    (dict(remote_kv_url="http://x"), "--remote-kv-url"),
    (dict(kv_role="producer"), "--kv-role"),
    (dict(enable_lora=True), "--enable-lora"),
    (dict(tensor_parallel_size=2), "--tensor-parallel-size"),
    (dict(quantization="int8"), "--quantization"),
    (dict(kv_cache_dtype="float8_e4m3fn"), "--kv-cache-dtype"),
])
def test_refused_at_start_up_by_the_flags_name(over, flag):
    with pytest.raises(ValueError) as e:
        make_engine(**over)
    assert flag in str(e.value) and "latents" in str(e.value)


def test_config_door_knows_the_model_type(tmp_path):
    import json

    from production_stack_tpu.models.llama import config_from_hf_json

    with open("perf/configs/glm-4.7-flash-pp6-cut.json") as f:
        raw = json.load(f)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(raw))
    cfg = config_from_hf_json(str(path), name="x")
    assert (cfg.num_layers, cfg.first_k_dense, cfg.num_moe_layers) == (8, 1, 7)
    assert (cfg.n_routed_experts, cfg.router_experts, cfg.expert_first) == (64, 64, 0)
    assert cfg.head_dim == 256 and cfg.cache_lanes == 640 and cfg.latent_pages
    # 576 stored numbers a token and layer, in rows of 640 lanes of bf16
    assert cfg.page_bytes(128, 2) == 8 * 128 * 640 * 2
    raw["ep_share"] = {"first": 56, "of": 64}
    raw["n_routed_experts"] = 8
    path.write_text(json.dumps(raw))
    cfg = config_from_hf_json(str(path))
    assert (cfg.n_routed_experts, cfg.router_experts, cfg.expert_first) == (8, 64, 56)
    raw["n_group"] = 2
    path.write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="group-limited"):
        config_from_hf_json(str(path))
