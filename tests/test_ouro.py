"""A looped stack on the dense class (``models/llama.py`` with ``ut_steps``
> 1; ``model_type: ouro``): the layer scan inside a rolled loop of passes,
pass ``t`` layer ``l`` on cache slot ``t x layers + l``, the final norm at
the end of every pass, on the engine's normal path against the benchmark's
plain reference (``perf/reference/ouro.py``: float32, every sequence whole,
nothing of the program's forward pass), at tiny widths: three layers run
twice (preset ``tiny-ouro-debug``) and four times, hidden 64, four heads
each with its own keys and values, pages of 8.

What the benchmark's ``correct`` cannot see is here: rows against each other,
a prefix-cache hit that has to bring the pages of every pass, a preemption,
what is refused for such a model and what was held to the reference
instead (quantised leaves, tensor parallelism, swap, n-gram drafts).
"""

import dataclasses
import functools
import hashlib
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf import config as configs
from perf.reference import ouro as reference
from production_stack_tpu.engine.config import EngineConfig, refuse_unserved
from production_stack_tpu.engine.precompile import compile_cache_key
from production_stack_tpu.models import registry
from production_stack_tpu.models.llama import (
    Llama, LlamaConfig, config_from_hf, load_hf_params)
from production_stack_tpu.models.registry import PRESETS
from production_stack_tpu.obs.engine_telemetry import ENGINE_TELEMETRY

from . import model_contract as contract
from .model_contract import assert_same, run

NAME = "tiny-ouro-debug"
CFG = PRESETS[NAME]
# the same widths and weights under four passes, and under one
FOUR = dataclasses.replace(CFG, ut_steps=4, name="tiny-ouro-4-debug")
ONE = dataclasses.replace(CFG, ut_steps=1, name="tiny-ouro-1-debug")
PRESETS[FOUR.name], PRESETS[ONE.name] = FOUR, ONE
PROMPT = [3, 17, 98, 25, 42, 7, 11, 20, 15, 31, 8, 77, 12, 5, 9, 2, 33, 44, 99,
          100, 101, 64, 65, 1, 90, 13, 14, 6, 120, 50, 51, 52, 53, 54, 55, 56,
          57, 58, 59, 60, 61, 62, 63, 66, 67, 68, 69, 70, 71, 72, 73, 74, 75]
OTHER = [(7 * i + 3) % 127 + 1 for i in range(120)]

make_engine = functools.partial(contract.make_engine, NAME)


def hf_of(cfg: LlamaConfig) -> dict:
    return {"model_type": "ouro", "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.hidden_size,
            "intermediate_size": cfg.intermediate_size,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
            "total_ut_steps": cfg.ut_steps, "early_exit_threshold": 1,
            "tie_word_embeddings": False, "use_sliding_window": False,
            "max_position_embeddings": cfg.max_position_embeddings,
            "eos_token_id": 0}


def ref_cfg(cfg: LlamaConfig, **flags):
    return types.SimpleNamespace(hf=hf_of(cfg), flag=flags.get)


@pytest.fixture(scope="module")
def engine():
    return make_engine()


@pytest.fixture(scope="module")
def uncached():
    """The same engine with no prefix cache: what every hit must equal."""
    return make_engine(enable_prefix_caching=False)


@pytest.fixture(scope="module")
def params(engine):
    return engine.runner.params


def reference_logprobs(params, ids, n_prompt, n_gen, variant="none", cfg=CFG,
                       **flags):
    (lps, gap), = reference.teacher_force(
        ref_cfg(cfg, **flags), params,
        [{"id": "t", "tokens": list(ids), "n_prompt": n_prompt,
          "want": [[0]] * n_gen}], variant)
    assert gap is None and lps.shape == (n_gen, cfg.vocab_size)
    return lps


def matches_reference(params, prompt, got, cfg=CFG, tol=2e-3):
    contract.assert_matches_reference(
        lambda p, pr, toks: reference_logprobs(
            p, pr + toks, len(pr), len(toks), cfg=cfg),
        params, prompt, got, tol)


# ----------------------------------------------------------------------------
# The engine's normal path against the reference's full forward pass
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [CFG, FOUR], ids=["two_passes", "four_passes"])
def test_chunked_prefill_then_chained_decode_is_the_reference(cfg, engine):
    """53 prompt tokens in chunks of 16, then chained decode: every pass
    writes and reads its own slots of the same pages, and every reported
    log-probability is the reference's. The exit gate is computed beside:
    at threshold 1 no position leaves before the last pass."""
    eng = engine if cfg is CFG else contract.make_engine(cfg.name)
    cache = eng.runner.kv_cache
    assert cache.shape[0] == cfg.ut_steps * cfg.num_layers == cfg.num_kv_layers
    got = run(eng, [PROMPT], 8)[0]
    assert len(got["tokens"]) == 8
    matches_reference(eng.runner.params, PROMPT, got, cfg)
    assert 0 < reference.LAST["min_stay"] < 1
    assert eng.pipelined_bursts_total > 0, "decode must run chained"
    stats = eng.stats()
    assert stats["kv_slot_layers"] == cfg.num_kv_layers
    assert stats["decode_layer_passes_total"] == (
        stats["decode_dispatches_total"] * cfg.ut_steps * cfg.num_layers)
    assert stats["prefill_layer_passes_total"] >= 4 * cfg.num_kv_layers
    assert stats["kv_pages_in_use"] == 0


def test_every_pass_wrote_its_own_slots(engine):
    """After a run no cache slot of any pass is empty where the first page
    was written, and the passes' slots of one layer differ."""
    run(engine, [PROMPT[:24]], 2)
    cache = np.asarray(engine.runner.kv_cache)  # [6, nb, 2, bs, KH*hd]
    written = np.abs(cache).sum(axis=(2, 3, 4)) > 0  # [slots, nb]
    assert written.any(axis=1).all()
    page = int(np.argmax(written[0]))
    assert not np.allclose(cache[0, page], cache[CFG.num_layers, page])


def test_a_prompt_cut_into_three_chunks_equals_one_chunk(uncached, params):
    prompt = PROMPT[:48]
    three = run(uncached, [prompt], 4)[0]  # the defaults: chunks of 16
    one = run(make_engine(max_prefill_tokens=64, overlap_decode=False,
                          enable_prefix_caching=False), [prompt], 4)[0]
    assert_same(three, one)
    matches_reference(params, prompt, one)


def test_four_ragged_packed_rows_equal_four_lone_rows(params):
    prompts = [PROMPT[:n] for n in (37, 5, 53, 18)]
    kw = dict(max_prefill_tokens=32, enable_prefix_caching=False)
    together = run(make_engine(**kw), prompts, 6)
    lone = make_engine(**kw)
    for p, got in zip(prompts, together):
        assert_same(got, run(lone, [p], 6)[0])
        matches_reference(params, p, got)


def test_a_staggered_many_row_run_equals_the_synchronous_loop(params):
    """Eight sequences arrive three steps apart under a chain of four rows:
    each joins behind its own prefill with no drain."""
    prompts = [(PROMPT + OTHER)[i:i + n] for i, n in enumerate(
        (37, 5, 53, 18, 26, 11, 44, 9))]
    kw = dict(max_num_seqs=4, min_decode_bucket=4, max_prefill_tokens=32)
    sync = run(make_engine(overlap_decode=False, **kw), prompts, 9, stagger=3)
    eng = make_engine(**kw)
    got = run(eng, prompts, 9, stagger=3)
    for a, b in zip(got, sync):
        assert_same(a, b)
    assert eng.chain_kept_prefills_total >= 7
    assert eng.allocator.num_free == eng.allocator.num_blocks
    matches_reference(params, prompts[2], got[2])


def test_preemption_by_recompute_returns_the_reference(uncached, params):
    """Twelve pages: two 40-token prompts admit and one must lose its pages
    (of every pass: a page is all six slots) while decoding; it starts
    again and still reports the reference's log-probabilities."""
    p1, p2 = PROMPT[:40], OTHER[:40]
    tight = make_engine(num_kv_blocks=12, max_model_len=128)
    got = run(tight, [p1, p2], 10)
    assert tight.num_preempted_total > 0, "the test must exercise preemption"
    for p, a in zip((p1, p2), got):
        assert a["tokens"] == run(uncached, [p], 10)[0]["tokens"]
        matches_reference(params, p, a)


def test_a_prefix_cache_hit_brings_the_pages_of_every_pass(uncached, params):
    """A second prompt behind a shared 24-token prefix, then a session's
    next turn: the hit skips the cached tokens in every pass, so the
    continuation is right only if the pages hold all six slots. The logits
    are the uncached engine's and the reference's full forward pass."""
    eng = make_engine()
    shared = PROMPT[:24]
    first = run(eng, [shared + OTHER[:21]], 6)[0]
    assert first["seq"].num_cached_prompt_tokens == 0
    prompt = shared + OTHER[60:77]
    got = run(eng, [prompt], 6)[0]
    assert got["seq"].num_cached_prompt_tokens == 24
    assert_same(got, run(uncached, [prompt], 6)[0])
    matches_reference(params, prompt, got)
    turn = prompt + got["tokens"] + OTHER[100:109]
    nxt = run(eng, [turn], 8)[0]
    assert nxt["seq"].num_cached_prompt_tokens == 40
    matches_reference(params, turn, nxt)


def test_one_pass_of_the_same_weights_is_the_dense_class(params):
    """``ut_steps`` 1 over the same leaves: the plain four-norm stack, one
    layer of pages a layer, the reference's ``one_pass``."""
    eng = contract.make_engine(ONE.name)
    assert eng.runner.kv_cache.shape[0] == ONE.num_layers
    assert not ONE.looped and eng.runner.passes == 1
    for a, b in zip(jax.tree.leaves(eng.runner.params), jax.tree.leaves(params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    got = run(eng, [PROMPT], 6)[0]
    contract.assert_matches_reference(
        lambda p, pr, toks: reference_logprobs(
            p, pr + toks, len(pr), len(toks), "one_pass"), params, PROMPT, got)


# The lowered text of ``tiny-llama-debug``'s decode step (``Llama.forward`` at
# two rows, one token each, a 64-page cache), digested at the parent commit
# (4b73982, before ``ut_steps`` existed): a plain stack traces no loop and
# no slot arithmetic, so the dense cell's step programs are the parent's.
PARENT_DECODE_STEP_SHA256 = (
    "d5c52c944ac2001edc386cce33c0258eb15ba123dd3c6b7a3104c57f19a10ad6")


def lowered_decode_step(cfg) -> str:
    model = registry.model_for(cfg)
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.make_kv_cache(64, 8))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    step = jax.jit(functools.partial(model.forward, attn_impl="gather"),
                   donate_argnums=(7,))
    return step.lower(shapes, i32(2, 1), i32(2, 1), i32(2, 1), i32(2, 32),
                      i32(2), i32(2), cache).as_text()


def test_a_plain_stack_lowers_to_the_parents_program():
    text = lowered_decode_step(PRESETS["tiny-llama-debug"])
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_DECODE_STEP_SHA256
    looped = lowered_decode_step(CFG)
    # one rolled loop around one layer scan: a while inside a while, and the
    # layer's projection traced once, not once a pass
    assert looped.count("stablehlo.while") == 2
    assert text.count("stablehlo.while") == 1


# ----------------------------------------------------------------------------
# Every control of the reference is held out
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("variant", reference.VARIANTS[1:])
def test_a_control_of_the_reference_is_not_what_the_engine_serves(
        variant, engine, params):
    """Chunked prefill (chunks of 16 over 53 tokens) then 8 decode steps
    against each control: none is within the tolerance the reference
    itself is held to."""
    got = run(engine, [PROMPT], 8)[0]
    rows = reference_logprobs(
        params, PROMPT + got["tokens"], len(PROMPT), 8, variant,
        **{"--max-num-batched-tokens": "16"})
    worst = max(abs(rows[j][tid] - lp)
                for j, at in enumerate(got["logprobs"]) for tid, lp in at.items())
    assert worst > 2e-2, (variant, worst)


def test_the_cache_sharing_controls_differ_only_where_a_step_reads_back():
    """``shared_kv_last`` prefills exactly, so the first generated position
    is the reference's and the later ones are not; ``slot_by_layer`` under
    chunks of 16 is already off at the first."""
    params = Llama(CFG).init_params(jax.random.PRNGKey(0))
    ids = (PROMPT + OTHER)[:60]
    base = reference_logprobs(params, ids, 52, 8)
    shared = reference_logprobs(params, ids, 52, 8, "shared_kv_last")
    by_layer = reference_logprobs(params, ids, 52, 8, "slot_by_layer",
                                  **{"--max-num-batched-tokens": "16"})
    assert np.abs(shared[0] - base[0]).max() < 1e-4
    assert np.abs(shared[1:] - base[1:]).max() > 1e-2
    assert np.abs(by_layer[0] - base[0]).max() > 1e-2


def test_the_exit_gate_is_the_papers_distribution():
    """Three passes, two positions: ``p_t = lambda_t prod_{s<t}(1 -
    lambda_s)``, the rest at the last pass; a sigmoid that rounds to 1
    leaves at its pass."""
    h = np.zeros((3, 2, 4), np.float32)
    h[0, :, 0], h[1, :, 0] = [0.0, 40.0], [np.log(3.0), 0.0]
    w, b = jnp.asarray([1.0, 0, 0, 0]), jnp.float32(0.0)
    p, stay, exit_pass = reference.exit_distribution(jnp.asarray(h), w, b)
    np.testing.assert_allclose(p[:, 0], [0.5, 0.375, 0.125], rtol=1e-6)
    np.testing.assert_allclose(p.sum(0), [1.0, 1.0], rtol=1e-6)
    assert stay[0] == pytest.approx(0.125) and stay[1] == 0.0
    assert exit_pass.tolist() == [2, 0]


# ----------------------------------------------------------------------------
# The config door, the arithmetic, the loader
# ----------------------------------------------------------------------------


def test_the_config_door_knows_the_published_model_and_its_arithmetic():
    cfg = configs.load("perf/configs/ouro-2.6b.json")
    assert cfg.hf["model_type"] == "ouro" and cfg.raw["reduced"] == []
    model_cfg = configs.program_model_config(cfg)
    assert type(model_cfg) is LlamaConfig
    assert (model_cfg.ut_steps, model_cfg.num_layers) == (4, 48)
    assert model_cfg.num_kv_layers == 192 and model_cfg.looped
    assert model_cfg.post_block_norms and not model_cfg.norm_unit_offset
    assert not model_cfg.attention_bias and not model_cfg.qk_norm
    assert model_cfg.sliding_window == 0 and not model_cfg.tie_word_embeddings
    assert model_cfg.rope_theta == 1e6 and model_cfg.rms_norm_eps == 1e-6
    # a token: K and V of 16 heads of 128 in bf16, over 192 slots
    assert model_cfg.page_bytes(32, 2) == 32 * 1_572_864 == 48 * 2**20
    shapes = jax.eval_shape(
        registry.model_for(model_cfg).init_params, jax.random.PRNGKey(0))
    leaves = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    a_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert a_layer == 51_388_416
    assert leaves == 48 * a_layer + 2 * 49152 * 2048 + 2048 + 2049
    assert leaves == 2_667_974_657  # the published 2.6 B; 5.336 GB in bf16
    assert shapes["exit_gate_w"].shape == (2048,)
    assert shapes["exit_gate_b"].shape == ()
    assert "passes" not in compile_cache_key(EngineConfig(), model_cfg)
    assert compile_cache_key(EngineConfig(), model_cfg) != compile_cache_key(
        EngineConfig(), dataclasses.replace(model_cfg, ut_steps=1))


def test_per_token_exit_and_a_window_are_refused_by_name():
    hf = hf_of(CFG)
    assert config_from_hf(hf).ut_steps == 2
    with pytest.raises(ValueError, match="early_exit_threshold 0.9"):
        config_from_hf({**hf, "early_exit_threshold": 0.9})
    with pytest.raises(ValueError, match="use_sliding_window"):
        config_from_hf({**hf, "use_sliding_window": True})
    # no other model_type reads the loop's keys
    assert config_from_hf({**hf, "model_type": "llama"}).ut_steps == 1


def test_a_checkpoint_with_the_published_leaf_names_loads(tmp_path):
    from safetensors.numpy import save_file

    cfg = config_from_hf({**hf_of(CFG), "vocab_size": 96}, name="ckpt")
    rng = np.random.default_rng(7)
    D, F, Q = cfg.hidden_size, cfg.intermediate_size, cfg.q_size
    tensors = {"model.embed_tokens.weight": rng.normal(size=(96, D)),
               "model.norm.weight": rng.normal(size=(D,)),
               "lm_head.weight": rng.normal(size=(96, D)),
               "model.early_exit_gate.weight": rng.normal(size=(1, D)),
               "model.early_exit_gate.bias": rng.normal(size=(1,))}
    norms = {"input_layernorm": "attn_norm",
             "input_layernorm_2": "post_attn_norm",
             "post_attention_layernorm": "mlp_norm",
             "post_attention_layernorm_2": "post_mlp_norm"}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        for name, shape in (("self_attn.q_proj", (Q, D)),
                            ("self_attn.k_proj", (cfg.kv_size, D)),
                            ("self_attn.v_proj", (cfg.kv_size, D)),
                            ("self_attn.o_proj", (D, Q)),
                            ("mlp.gate_proj", (F, D)), ("mlp.up_proj", (F, D)),
                            ("mlp.down_proj", (D, F))):
            tensors[p + name + ".weight"] = rng.normal(size=shape)
        for name in norms:
            tensors[p + name + ".weight"] = rng.normal(size=(D,))
    tensors = {k: np.asarray(v, np.float32) for k, v in tensors.items()}
    save_file(tensors, str(tmp_path / "model.safetensors"))
    params = load_hf_params(cfg, str(tmp_path))
    shapes = jax.eval_shape(Llama(cfg).init_params, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda x: x.shape, params) == jax.tree.map(
        lambda x: x.shape, shapes)
    close = functools.partial(np.testing.assert_allclose, rtol=1e-2, atol=1e-2)
    for hf_name, ours in norms.items():
        close(np.asarray(params["layers"][ours][2], np.float32),
              tensors[f"model.layers.2.{hf_name}.weight"])
    close(np.asarray(params["layers"]["w_down"][1], np.float32),
          tensors["model.layers.1.mlp.down_proj.weight"].T)
    close(np.asarray(params["exit_gate_w"], np.float32),
          tensors["model.early_exit_gate.weight"][0])
    close(float(params["exit_gate_b"]),
          float(tensors["model.early_exit_gate.bias"][0]))
    # and the directory is served on the normal path: ``--model <dir>`` whose
    # config.json says ``model_type: ouro``
    (tmp_path / "config.json").write_text(json.dumps(
        {**hf_of(CFG), "vocab_size": 96, "torch_dtype": "float32"}))
    eng = contract.make_engine(str(tmp_path))
    assert eng.runner.kv_cache.shape[0] == 6 and eng.model_cfg.looped
    prompt = [t % 96 for t in PROMPT[:21]]
    got = run(eng, [prompt], 4)[0]
    served = dataclasses.replace(cfg, dtype="float32")
    contract.assert_matches_reference(
        lambda p, pr, toks: reference_logprobs(
            p, pr + toks, len(pr), len(toks), cfg=served),
        eng.runner.params, prompt, got, tol=5e-3)


# ----------------------------------------------------------------------------
# What is refused for a looped stack, and what was held to the reference
# ----------------------------------------------------------------------------


@pytest.mark.parametrize("flag,over", [
    ("--pipeline-parallel-size", {"pipeline_parallel_size": 2}),
    ("--enable-lora", {"enable_lora": True}),
    ("--cpu-offload-blocks", {"cpu_offload_blocks": 8}),
    ("--remote-kv-url", {"remote_kv_url": "http://localhost:1"}),
    ("--kv-role", {"kv_role": "producer"}),
    ("--data-parallel-size", {"data_parallel_size": 2}),
    ("--sequence-parallel-size", {"sequence_parallel_size": 2}),
    ("--kv-cache-dtype", {"kv_cache_dtype": "float8_e4m3fn"}),
])
def test_a_flag_not_held_to_the_reference_is_refused_by_name(flag, over):
    cfg = EngineConfig(model=NAME, kv_swap=False, **over)
    with pytest.raises(ValueError) as e:
        refuse_unserved(cfg, CFG)
    assert flag in str(e.value) and "several times a step" in str(e.value)
    refuse_unserved(cfg, ONE)  # the same widths as a plain stack: served
    if flag == "--pipeline-parallel-size":
        assert "a stage would be visited once a pass" in str(e.value)
        with pytest.raises(ValueError, match="--pipeline-parallel-size"):
            make_engine(**over)


def test_what_the_looped_class_serves_is_not_refused():
    refuse_unserved(EngineConfig(
        model=NAME, enable_prefix_caching=True, kv_swap=True,
        speculative_ngram=3, quantization="int4", tensor_parallel_size=2), CFG)


def test_the_embeddings_path_refuses_a_looped_stack(params):
    with pytest.raises(ValueError, match="looped stack"):
        Llama(CFG).encode(params, jnp.zeros((1, 4), jnp.int32),
                          jnp.asarray([4]))


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_quantised_leaves_under_the_loop_are_the_reference(mode):
    """The reference widens the same stored leaves; a layer's quantised
    matrices are read once a pass by the layer's index, not the slot's."""
    eng = make_engine(quantization=mode)
    got = run(eng, [PROMPT], 6)[0]
    matches_reference(eng.runner.params, PROMPT, got, tol=5e-3)


def test_tensor_parallelism_under_the_loop_is_the_reference(params):
    eng = make_engine(tensor_parallel_size=2)
    got = run(eng, [PROMPT], 6)[0]
    matches_reference(params, PROMPT, got)


def test_swapped_out_pages_come_back_with_every_pass(uncached, params):
    """Swap in place of recompute: a parked sequence's tail pages go to the
    host and come back framed by the cache's leading dimension, all six
    slots of them."""
    p1, p2 = PROMPT[:40], OTHER[:40]
    tight = make_engine(num_kv_blocks=12, max_model_len=128, kv_swap=True,
                        swap_quantum_tokens=0)
    got = run(tight, [p1, p2], 10)
    assert tight.swapper.swap_out_total > 0, "the test must exercise swap"
    for p, a in zip((p1, p2), got):
        assert a["tokens"] == run(uncached, [p], 10)[0]["tokens"]
        matches_reference(params, p, a)


def test_ngram_drafts_verify_through_every_pass(params):
    """A repetitive prompt drafts; the verify step scores every draft
    position in one forward pass (``all_logits``) through both passes, and
    the output is the plain greedy one and the reference's."""
    prompt = ([5, 9, 13, 21] * 10)[:38]
    spec = make_engine(speculative_ngram=3)
    # a row that asks for log-probabilities is not drafted for
    got = run(spec, [prompt], 12, logprobs=None)[0]
    plain = run(make_engine(), [prompt], 12)[0]
    assert got["tokens"] == plain["tokens"]
    assert spec.spec_proposed_total > 0
    matches_reference(params, prompt, plain)


# ----------------------------------------------------------------------------
# Tracing
# ----------------------------------------------------------------------------


def test_a_step_says_its_passes_and_a_plain_stack_says_nothing(monkeypatch):
    seen = []
    monkeypatch.setattr(
        ENGINE_TELEMETRY, "step_info",
        lambda kind, **meta: seen.append((kind, meta)))
    run(make_engine(), [PROMPT[:20]], 3)
    assert {k for k, _ in seen} == {"prefill", "decode"}
    assert all(meta["passes"] == 2 for _, meta in seen)
    seen.clear()
    run(contract.make_engine("tiny-llama-debug"), [PROMPT[:20]], 3)
    assert seen and all("passes" not in meta for _, meta in seen)


def test_the_server_exports_the_loops_counters(engine):
    from prometheus_client import generate_latest

    from production_stack_tpu.engine.server import EngineMetrics

    run(engine, [PROMPT[:20]], 3)
    metrics, stats = EngineMetrics("m"), engine.stats()
    metrics.refresh(stats)
    text = generate_latest(metrics.registry).decode()
    for series, key in (
            ("pst:decode_layer_passes_total", "decode_layer_passes_total"),
            ("pst:prefill_layer_passes_total", "prefill_layer_passes_total"),
            ("pst:kv_slot_layers", "kv_slot_layers")):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(series + '{model_name="m"}'))
        assert float(line.split()[-1]) == stats[key] > 0
    assert stats["kv_slot_layers"] == 6
