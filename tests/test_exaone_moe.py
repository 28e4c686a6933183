"""The ``exaone_moe`` class (window and full attention mixed, a leading dense
layer, sigmoid-routed expert layers with a selection bias and a shared
expert, a multi-token-prediction module) on the engine's normal path at a
tiny size, against ``perf/reference/exaone_moe.py``: main logits and the
draft module's alike, prefill then decode through both page groups. The
verify-and-draft step itself is ``tests/test_mtp_step.py``'s."""

import dataclasses
import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.reference import exaone_moe as reference
from production_stack_tpu.engine import config as engine_config
from production_stack_tpu.engine.config import EngineConfig, window_block_count
from production_stack_tpu.models.exaone_moe import (
    AUX_NAMES, MTP_AUX_NAMES, ExaoneMoe, ExaoneMoeConfig)
from production_stack_tpu.models.registry import MODEL_TYPES, PRESETS

from . import model_contract as contract
from .model_contract import assert_same, run

NAME = "tiny-exaone-moe-debug"
CFG = PRESETS[NAME]
HF = {"num_hidden_layers": CFG.num_layers,
      "hidden_size": CFG.hidden_size,
      "layer_types": list(CFG.layer_types),
      "mlp_layer_types": list(CFG.mlp_layer_types),
      "sliding_window": CFG.sliding_window,
      "num_attention_heads": CFG.num_heads,
      "num_key_value_heads": CFG.num_kv_heads,
      "head_dim": CFG.head_dim,
      "rope_parameters": {"rope_type": "default", "rope_theta": CFG.rope_theta},
      "num_experts": CFG.n_routed_experts,
      "num_experts_per_tok": CFG.num_experts_per_tok,
      "norm_topk_prob": CFG.norm_topk_prob,
      "routed_scaling_factor": CFG.routed_scaling_factor,
      "rms_norm_eps": CFG.rms_norm_eps,
      "ep_share": {"first": CFG.expert_first, "of": CFG.router_experts}}
REF_CFG = types.SimpleNamespace(
    hf=HF, raw={"published": {"num_experts": CFG.router_experts}})
PROMPT = [3, 17, 98, 25, 42, 7, 11, 20, 15, 31, 8, 77, 12, 5, 9, 2, 33, 44, 99,
          100, 101, 64, 65, 1, 90, 13, 14, 6, 120, 50, 51, 52, 53, 54, 55, 56,
          57, 58, 59, 60, 61, 62, 63, 66, 67, 68, 69, 70, 71, 72, 73, 74, 75]

make_engine = functools.partial(contract.make_engine, NAME)


@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.fixture(scope="module")
def uncached():
    return make_engine(enable_prefix_caching=False)


@pytest.fixture(scope="module")
def params(engine):
    return engine.runner.params


def reference_logprobs(params, ids, n_prompt, n_gen, variant="none"):
    with jax.default_matmul_precision("highest"):
        (lps, gap), = reference.teacher_force(
            REF_CFG, params,
            [{"tokens": list(ids), "n_prompt": n_prompt,
              "want": [[0]] * n_gen}], variant)
    assert gap.shape == (n_gen,) and np.all(gap >= 0)
    return lps


assert_matches_reference = functools.partial(
    contract.assert_matches_reference,
    lambda params, prompt, tokens: reference_logprobs(
        params, prompt + tokens, len(prompt), len(tokens)))


def test_chunked_prefill_then_decode_through_both_groups(engine, params):
    """53 prompt tokens (more than three windows) in chunks of 16, then
    chained decode (the draft off): the window layers read their own group
    while its pages below the window go, the full layer the global group,
    without a positional embedding; every log-probability is the
    reference's."""
    got = run(engine, [PROMPT], 8)[0]
    assert len(got["tokens"]) == 8
    assert_matches_reference(params, PROMPT, got)
    assert engine.pipelined_bursts_total > 0, "decode must run chained"
    stats = engine.stats()
    assert stats["window_pages_released_total"] >= 2
    assert stats["window_pages_in_use"] == 0 and stats["kv_pages_in_use"] == 0
    # four expert layers a step (the draft module's runs only with the
    # draft on), every expert held
    assert stats["moe_layer_steps_total"] % 4 == 0
    assert stats["moe_pairs_held_total"] == stats["moe_pairs_routed_total"] > 0
    assert stats["kv_slot_layers"] == 2  # the full layer and the MTP layer
    assert all(stats[k] == 0 for k in MTP_AUX_NAMES)


def test_the_server_exports_the_dispatch_counts(engine):
    run(engine, [PROMPT[:20]], 2)
    contract.assert_dispatch_counts_exported(engine)
    assert engine.runner.aux_names == AUX_NAMES


def test_a_prompt_cut_into_three_chunks_equals_one_chunk(uncached, params):
    a = run(uncached, [PROMPT[:40]], 6)[0]
    b = run(make_engine(enable_prefix_caching=False, max_prefill_tokens=64),
            [PROMPT[:40]], 6)[0]
    assert_same(a, b)


def test_a_cached_prefix_serves_both_groups(engine, uncached):
    """The same prompt again: served from the cache of both groups, the
    same log-probabilities as without a cache."""
    run(engine, [PROMPT], 4)
    again = run(engine, [PROMPT], 4)[0]
    assert again["seq"].num_cached_prompt_tokens >= 32
    assert_same(again, run(uncached, [PROMPT], 4)[0])


def mtp_rows(model, params, tokens, *, block=8, chunks=(16,), shifted=True,
             adopt=None):
    """The draft module's log-probabilities from the model's own paged path,
    driven by hand: ``tokens`` prefilled in ``chunks`` through both groups
    and the module's pages, row ``i`` for position ``i`` and token ``i + 1``.
    ``adopt``: (cache, tokens already in it): continue from a filled cache as
    a prefix hit does: one position back under the slot rule (whose first
    slot past the hit needs that position's state), at the hit itself
    without it (every slot below the hit is then taken as it is)."""
    cfg = model.cfg
    T = len(tokens)
    nb = 40
    cache = model.make_kv_cache(nb, block, None, nb) if adopt is None else adopt[0]
    start = 0 if adopt is None else adopt[1] - (1 if shifted else 0)
    table = jnp.arange(1, 33, dtype=jnp.int32)[None]  # page i+1 for block i
    slot = lambda p: int(table[0, p // block]) * block + p % block  # noqa: E731
    out = []
    sizes = list(chunks)
    while start < T - 1:
        n = min(sizes.pop(0) if len(sizes) > 1 else sizes[0], T - 1 - start)
        pos = np.arange(start, start + n)
        tok = jnp.asarray([tokens[start:start + n]], jnp.int32)
        nxt = jnp.asarray([tokens[start + 1:start + n + 1]], jnp.int32)
        w = np.array([slot(p) for p in pos])
        if adopt is not None and start == adopt[1] - 1:
            w[0] = nb * block  # the position a hit computes again: dropped
        ahead = 1 if shifted else 0
        logits, hidden, cache = model.forward(
            params, tok, jnp.asarray([pos], jnp.int32),
            jnp.asarray([w], jnp.int32), table,
            jnp.asarray([start + n], jnp.int32),
            jnp.asarray([n - 1], jnp.int32), cache, window_tables=table,
            attn_impl="gather", return_hidden=True)
        draft, cache = model.mtp_forward(
            params, hidden, nxt, jnp.asarray([pos], jnp.int32),
            jnp.asarray([[slot(p + ahead) for p in pos]], jnp.int32), table,
            jnp.asarray([start + n + ahead], jnp.int32),
            jnp.asarray([n - 1], jnp.int32), cache, attn_impl="gather",
            all_logits=True, shifted=shifted)
        out.append(np.asarray(jax.nn.log_softmax(draft[0], axis=-1)))
        start += n
    return np.concatenate(out), cache


def test_the_draft_modules_logits_are_the_references(params):
    """Prefill in chunks through the module's pages one slot ahead, the last
    chunk a single position (a decode step's shape): every row is the
    reference's ``mtp_logits``."""
    model = ExaoneMoe(CFG)
    ids = PROMPT[:42]
    got, _ = mtp_rows(model, params, ids, chunks=(16, 16, 8, 1))
    with jax.default_matmul_precision("highest"):
        want = reference.mtp_logits(REF_CFG, params, ids, list(range(41)))
    np.testing.assert_allclose(got, want, atol=2e-3)
    with jax.default_matmul_precision("highest"):
        other = reference.mtp_logits(
            REF_CFG, params, ids, list(range(41)), "mtp_hidden_unnormed")
    assert np.abs(other - want).max() > 0.05


@pytest.mark.parametrize("shifted", [True, False])
def test_the_slot_rule_under_a_prefix_hit(params, shifted):
    """Two requests share 16 tokens (two pages) and part at the next. The
    second takes the first's pages and goes on from them: with the module's
    entries one slot ahead it reads what a cold prefill reads; with the
    shift taken out the last slot of the second page was made from the
    first request's next token, and the logits move."""
    model = ExaoneMoe(CFG)
    first = PROMPT[:30]
    second = PROMPT[:16] + [88] + PROMPT[17:30]
    _, cache = mtp_rows(model, params, first, shifted=shifted)
    hit, _ = mtp_rows(model, params, second, shifted=shifted, adopt=(cache, 16))
    cold, _ = mtp_rows(model, params, second, shifted=shifted)
    err = np.abs(hit - cold[15 if shifted else 16:]).max()
    if shifted:
        assert err < 1e-4
    else:
        assert err > 1e-2


@pytest.mark.parametrize("variant", reference.VARIANTS[1:])
def test_every_negative_control_moves_the_reference(variant, params):
    ids = PROMPT + PROMPT[:20]
    with jax.default_matmul_precision("highest"):
        if variant == "mtp_hidden_unnormed":
            rows = list(range(60, 70))
            base = reference.mtp_logits(REF_CFG, params, ids, rows)
            other = reference.mtp_logits(REF_CFG, params, ids, rows, variant)
        else:
            base = reference_logprobs(params, ids, 60, 10)
            other = reference_logprobs(params, ids, 60, 10, variant)
    assert np.abs(other - base).max() > 1e-3


@pytest.mark.parametrize("tokens", [23, 200])
def test_the_eight_shares_routed_parts_and_one_shared_expert_are_the_whole(
        tokens, params):
    """Eight ranks of 2 of 16 experts, one router with its bias: the routed
    parts, and the shared expert counted once, add up to the uncut
    reference's expert block."""
    E, held = 16, 2
    cfg = dataclasses.replace(CFG, router_experts=E, n_routed_experts=held)
    D, Fe = cfg.hidden_size, cfg.moe_intermediate_size
    x = jax.random.normal(jax.random.PRNGKey(3), (tokens, D))
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    own = params["layers"]["l1"]["moe"]
    whole = {"w1": jax.random.normal(ks[0], (E, D, 2 * Fe)) / np.sqrt(D),
             "w2": jax.random.normal(ks[1], (E, Fe, D)) / np.sqrt(Fe)}
    mp = {"norm": own["norm"],
          "w_router": jax.random.normal(ks[2], (D, E)) / np.sqrt(D),
          "router_bias": 0.1 * jax.random.normal(ks[3], (E,))}
    shared = {k: own[k] for k in ("w_shared_gate", "w_shared_up",
                                  "w_shared_down")}
    u = reference._rms(x, mp["norm"], cfg.rms_norm_eps)
    valid = jnp.ones((tokens,), bool)
    total = reference._swiglu(u, *shared.values())
    for first in range(0, E, held):
        model = ExaoneMoe(dataclasses.replace(cfg, expert_first=first))
        part, stats = model.routed(
            {**mp, **{k: v[first:first + held] for k, v in whole.items()}},
            u, valid)
        assert stats[0] == tokens * cfg.num_experts_per_tok
        assert stats[1] < stats[0]
        total = total + part
    with jax.default_matmul_precision("highest"):
        want, _ = reference.moe(
            x, {**mp, **whole, **shared}, top_k=cfg.num_experts_per_tok,
            first=0, renorm=True, eps=cfg.rms_norm_eps,
            scale=cfg.routed_scaling_factor, bias=True, with_shared=True)
    np.testing.assert_allclose(total, want, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("over,flag", [
    (dict(kv_swap=True), "--kv-swap"),
    (dict(speculative_ngram=3), "--speculative-ngram"),
    (dict(tensor_parallel_size=2), "--tensor-parallel-size"),
    (dict(quantization="int8"), "--quantization"),
    (dict(kv_cache_dtype="float8_e4m3fn"), "--kv-cache-dtype"),
])
def test_refused_at_start_up_by_the_flags_name(over, flag):
    kw = dict(model=NAME, kv_swap=False)
    kw.update(over)
    with pytest.raises(ValueError, match=flag):
        engine_config.refuse_unserved(EngineConfig(**kw), CFG)
    engine_config.refuse_unserved(
        EngineConfig(model=NAME, kv_swap=False, speculative_mtp=1), CFG)


def test_config_door_knows_the_model_type_and_the_arithmetic(tmp_path):
    from production_stack_tpu.models.llama import config_from_hf_json

    assert MODEL_TYPES["exaone_moe"][1:] == (ExaoneMoeConfig, ExaoneMoe)
    with open("perf/configs/k-exaone-ep8-cut.json") as f:
        raw = json.load(f)
    path = tmp_path / "config.json"
    path.write_text(json.dumps(raw))
    cfg = config_from_hf_json(str(path), name="x")
    assert isinstance(cfg, ExaoneMoeConfig) and cfg.window_pages
    assert (cfg.num_layers, cfg.hidden_size, cfg.vocab_size) == (5, 6144, 19200)
    assert (cfg.num_full_layers, cfg.num_window_layers, cfg.num_kv_layers,
            cfg.mtp_layers, cfg.num_sparse_layers) == (1, 4, 2, 1, 5)
    assert (cfg.n_routed_experts, cfg.router_experts, cfg.expert_first,
            cfg.num_experts_per_tok) == (16, 128, 0, 8)
    assert (cfg.sliding_window, cfg.q_size, cfg.kv_size,
            cfg.routed_scaling_factor) == (128, 8192, 1024, 2.5)
    # a token: 4,096 B a layer; a 128-token page 1 MiB over the full layer
    # and the MTP layer, 2 MiB over the four window layers
    assert cfg.page_bytes(128, 2) == 2 * 4096 * 128 == 1 << 20
    assert cfg.window_page_bytes(128, 2) == 4 * 4096 * 128 == 2 << 20
    eng = EngineConfig(model="x", block_size=128, max_num_seqs=64,
                       max_prefill_tokens=1024)
    assert window_block_count(eng, cfg) == 64 * (1 + 2 + 1) + 2 * 8 == 272
    shapes = jax.eval_shape(ExaoneMoe(cfg).init_params, jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    layers = shapes["layers"]
    assert round(count(layers["l1"]["attn"]) / 1e6, 2) == 113.25
    assert round(count(layers["l0"]["dense"]) / 1e6, 2) == 339.74
    moe = layers["l1"]["moe"]
    assert round((count(moe["w1"]) + count(moe["w2"])) / 1e6, 2) == 603.98
    assert round(sum(count(moe[k]) for k in (
        "w_shared_gate", "w_shared_up", "w_shared_down")) / 1e6, 2) == 37.75
    assert round(count(moe["w_router"]) / 1e6, 2) == 0.79
    assert round(count(layers["l1"]) / 1e6, 1) == 755.8  # a sparse layer held
    assert round(count(layers["l0"]) / 1e6, 1) == 453.0
    assert round(count(layers["mtp"]) / 1e6, 1) == 831.3
    assert round((count(shapes["embed"]) + count(shapes["lm_head"])) / 1e6, 1) == 235.9
    assert 4.540e9 < count(shapes) < 4.548e9  # 9.09 GB at 2 B a parameter
    # the whole model by the same count: 236.6 B and 5.1 B of MTP
    whole = {**raw, "num_hidden_layers": 48, "num_experts": 128,
             "vocab_size": 153600,
             "layer_types": (raw["layer_types"][:4] * 12),
             "mlp_layer_types": ["dense"] + ["sparse"] * 47}
    whole.pop("ep_share")
    path.write_text(json.dumps(whole))
    full = jax.eval_shape(
        ExaoneMoe(config_from_hf_json(str(path))).init_params,
        jax.random.PRNGKey(0))
    mtp = count(full["layers"]["mtp"])
    assert 5.05e9 < mtp < 5.15e9
    assert 236.3e9 < count(full) - mtp < 236.9e9
    for key, value, match in (
            ("scoring_func", "softmax", "sigmoid"),
            ("n_group", 4, "grouped router"),
            ("mtp_layer_types", ["sliding_attention"], "full-attention draft"),
            ("num_nextn_predict_layers", 2, "0 or 1"),
            ("layer_types", ["full_attention"] * 5, "both layer types")):
        path.write_text(json.dumps({**raw, key: value}))
        with pytest.raises(ValueError, match=match):
            config_from_hf_json(str(path))
