"""Gemma-family correctness (Gemma-1 GeGLU/norm/embedding conventions,
Gemma-2 softcaps, post-block norms, alternating sliding-window layers), and
the four-norm block's second user: a looped stack (``tiny-ouro-debug``: the
same block without the unit offset, run ``ut_steps`` = 2 times over one set
of weights), which the naive reference here follows by ``cfg.ut_steps``.

Same ring-1 strategy as ``test_engine_core``: an independent naive
full-attention reference reimplements the Gemma math directly (no shared
attention/paging code), and the engine's paged path — prefill chunks,
batched decode, sliding-window masks across page boundaries — must
reproduce it token-for-token under greedy sampling.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.engine import LLMEngine
from production_stack_tpu.engine.sequence import SamplingParams
from production_stack_tpu.models.llama import (
    Llama,
    _layer_window,
    config_from_hf_json,
)
from production_stack_tpu.models.registry import PRESETS


def naive_forward(cfg, params, token_ids):
    """Logits [T, V] via full attention, fp32 — all Gemma knobs honored."""
    x = params["embed"][jnp.asarray(token_ids)]
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.hidden_size), x.dtype)
    T = x.shape[0]
    pos = jnp.arange(T)
    half = cfg.head_dim // 2
    freqs = 1.0 / (cfg.rope_theta ** (jnp.arange(half) / half))
    ang = pos[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)

    def rope(v):
        v1, v2 = v[..., :half], v[..., half:]
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([v1 * c - v2 * s, v2 * c + v1 * s], axis=-1)

    def rms(v, w):
        v32 = v.astype(jnp.float32)
        normed = v32 * jax.lax.rsqrt(
            jnp.mean(v32 * v32, -1, keepdims=True) + cfg.rms_norm_eps
        )
        if cfg.norm_unit_offset:
            return normed * (1.0 + w)
        return normed * w

    def act(v):
        if cfg.hidden_act == "gelu_tanh":
            return jax.nn.gelu(v, approximate=True)
        return jax.nn.silu(v)

    def cap(s, c):
        return jnp.tanh(s / c) * c if c else s

    lp = params["layers"]
    for _ in range(cfg.ut_steps):  # a looped stack: the final norm every pass
        for i in range(cfg.num_layers):
            h = rms(x, lp["attn_norm"][i])
            q = (h @ lp["wq"][i]).reshape(T, cfg.num_heads, cfg.head_dim)
            k = (h @ lp["wk"][i]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
            v = (h @ lp["wv"][i]).reshape(T, cfg.num_kv_heads, cfg.head_dim)
            q, k = rope(q), rope(k)
            G = cfg.num_heads // cfg.num_kv_heads
            k = jnp.repeat(k, G, axis=1)
            v = jnp.repeat(v, G, axis=1)
            scores = jnp.einsum("thd,shd->hts", q, k) * cfg.attn_scale
            scores = cap(scores, cfg.attn_logit_softcap)
            mask = pos[None, :] <= pos[:, None]
            win = int(_layer_window(cfg, i))
            if win:
                mask = mask & (pos[None, :] > pos[:, None] - win)
            scores = jnp.where(mask[None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            attn = jnp.einsum("hts,shd->thd", probs, v).reshape(T, -1)
            o = attn @ lp["wo"][i]
            if cfg.post_block_norms:
                o = rms(o, lp["post_attn_norm"][i])
            x = x + o
            h = rms(x, lp["mlp_norm"][i])
            ff = (act(h @ lp["w_gate"][i]) * (h @ lp["w_up"][i])) @ lp["w_down"][i]
            if cfg.post_block_norms:
                ff = rms(ff, lp["post_mlp_norm"][i])
            x = x + ff
        x = rms(x, params["final_norm"])
    unembed = params.get("lm_head", params["embed"])
    return cap(x @ unembed.T, cfg.final_logit_softcap)


def naive_greedy(cfg, params, prompt_ids, n_tokens):
    ids = list(prompt_ids)
    out = []
    for _ in range(n_tokens):
        logits = naive_forward(cfg, params, ids)
        nxt = int(jnp.argmax(logits[-1]))
        out.append(nxt)
        ids.append(nxt)
    return out


def make_engine(model, **over):
    kw = dict(
        model=model,
        max_model_len=256,
        block_size=8,
        num_kv_blocks=128,
        max_num_seqs=8,
        max_prefill_tokens=64,
    )
    kw.update(over)
    return LLMEngine(EngineConfig(**kw))


def run_greedy(eng, rid, prompt, n):
    eng.add_request(
        rid, prompt_token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True),
    )
    toks = []
    while eng.has_work():
        for out in eng.step():
            toks.extend(out.new_token_ids)
    return toks


# Long enough that decode positions cross the gemma2 sliding window (16)
# and span several 8-token pages.
PROMPT = [3, 17, 98, 255, 42, 7, 11, 200, 150, 31, 8, 77, 123, 9, 54, 201,
          33, 4, 90, 18, 61, 240, 5, 66]


def test_layer_window_pattern():
    cfg = PRESETS["tiny-gemma2-debug"]
    # pattern 2: even layers local, odd layers global.
    assert [int(_layer_window(cfg, i)) for i in range(4)] == [16, 0, 16, 0]
    cfg1 = PRESETS["tiny-gemma-debug"]
    assert int(_layer_window(cfg1, 0)) == 0  # no sliding window configured


@pytest.mark.parametrize(
    "model", ["tiny-gemma-debug", "tiny-gemma2-debug", "tiny-ouro-debug"])
def test_engine_greedy_matches_naive(model):
    eng = make_engine(model)
    cfg = PRESETS[model]
    params = jax.device_get(eng.runner.params)
    prompt = [t % cfg.vocab_size for t in PROMPT]
    expected = naive_greedy(cfg, params, prompt, 12)
    got = run_greedy(eng, "g0", prompt, 12)
    assert got == expected


def test_gemma2_chunked_prefill_matches():
    """Prefill split into 8-token chunks must agree with the naive reference
    (window masks must hold across chunk and page boundaries)."""
    eng = make_engine("tiny-gemma2-debug", max_prefill_tokens=8)
    cfg = PRESETS["tiny-gemma2-debug"]
    params = jax.device_get(eng.runner.params)
    expected = naive_greedy(cfg, params, PROMPT, 6)
    got = run_greedy(eng, "g1", PROMPT, 6)
    assert got == expected


def test_the_looped_four_norm_block_in_chunks_matches_naive():
    """The loop at ``ut_steps`` 2 through 8-token prefill chunks: a later
    chunk reads the earlier ones' pages of both passes."""
    eng = make_engine("tiny-ouro-debug", max_prefill_tokens=8)
    cfg = PRESETS["tiny-ouro-debug"]
    assert cfg.ut_steps == 2 and cfg.post_block_norms
    assert not cfg.norm_unit_offset
    params = jax.device_get(eng.runner.params)
    tokens = [t % cfg.vocab_size for t in PROMPT]
    assert run_greedy(eng, "o1", tokens, 6) == naive_greedy(
        cfg, params, tokens, 6)


def test_gemma2_tensor_parallel_matches():
    eng = make_engine("tiny-gemma2-debug", tensor_parallel_size=2)
    cfg = PRESETS["tiny-gemma2-debug"]
    params = jax.device_get(eng.runner.params)
    expected = naive_greedy(cfg, params, PROMPT, 8)
    got = run_greedy(eng, "g2", PROMPT, 8)
    assert got == expected


def test_gemma2_pipeline_parallel_matches():
    """pp=2 on the 4-layer gemma2 debug model: each stage holds 2 layers —
    one local(window) + one global — so the global-layer-index fix for the
    window pattern is load-bearing here."""
    eng = make_engine("tiny-gemma2-debug", pipeline_parallel_size=2)
    cfg = PRESETS["tiny-gemma2-debug"]
    params = jax.device_get(eng.runner.params)
    expected = naive_greedy(cfg, params, PROMPT, 8)
    got = run_greedy(eng, "g3", PROMPT, 8)
    assert got == expected


def test_hf_gemma2_config_parsing(tmp_path):
    hf = {
        "model_type": "gemma2",
        "vocab_size": 1000,
        "hidden_size": 128,
        "intermediate_size": 256,
        "num_hidden_layers": 4,
        "num_attention_heads": 8,
        "num_key_value_heads": 4,
        "head_dim": 16,
        "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6,
        "max_position_embeddings": 8192,
        "hidden_activation": "gelu_pytorch_tanh",
        "query_pre_attn_scalar": 224,
        "attn_logit_softcapping": 50.0,
        "final_logit_softcapping": 30.0,
        "sliding_window": 4096,
        "eos_token_id": 1,
        "bos_token_id": 2,
    }
    p = tmp_path / "config.json"
    p.write_text(json.dumps(hf))
    cfg = config_from_hf_json(str(p), name="g2")
    assert cfg.hidden_act == "gelu_tanh"
    assert cfg.norm_unit_offset and cfg.embed_scale and cfg.tie_word_embeddings
    assert cfg.query_pre_attn_scalar == 224
    assert cfg.attn_logit_softcap == 50.0
    assert cfg.final_logit_softcap == 30.0
    assert cfg.post_block_norms
    assert cfg.sliding_window == 4096 and cfg.sliding_window_pattern == 2
    assert cfg.attn_scale == pytest.approx(224 ** -0.5)


def test_hf_gemma2_load_roundtrip(tmp_path):
    """Gemma-2 checkpoint layout (4 norms/layer, tied embeddings, no
    lm_head) loads into the right param slots."""
    from safetensors.numpy import save_file

    from production_stack_tpu.models.llama import load_hf_params

    hf = {
        "model_type": "gemma2",
        "vocab_size": 256,
        "hidden_size": 32,
        "intermediate_size": 64,
        "num_hidden_layers": 2,
        "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "head_dim": 8,
        "query_pre_attn_scalar": 8,
        "attn_logit_softcapping": 50.0,
        "final_logit_softcapping": 30.0,
        "sliding_window": 16,
        "hidden_activation": "gelu_pytorch_tanh",
        "eos_token_id": 1,
    }
    (tmp_path / "config.json").write_text(json.dumps(hf))
    cfg = config_from_hf_json(str(tmp_path / "config.json"), name="g2t")

    rng = np.random.default_rng(7)
    D, qs, kvs = 32, 32, 16
    tensors = {
        "model.embed_tokens.weight": rng.normal(size=(256, D)),
        "model.norm.weight": rng.normal(size=(D,)),
    }
    for i in range(2):
        p = f"model.layers.{i}."
        tensors[p + "self_attn.q_proj.weight"] = rng.normal(size=(qs, D))
        tensors[p + "self_attn.k_proj.weight"] = rng.normal(size=(kvs, D))
        tensors[p + "self_attn.v_proj.weight"] = rng.normal(size=(kvs, D))
        tensors[p + "self_attn.o_proj.weight"] = rng.normal(size=(D, qs))
        tensors[p + "mlp.gate_proj.weight"] = rng.normal(size=(64, D))
        tensors[p + "mlp.up_proj.weight"] = rng.normal(size=(64, D))
        tensors[p + "mlp.down_proj.weight"] = rng.normal(size=(D, 64))
        tensors[p + "input_layernorm.weight"] = rng.normal(size=(D,))
        tensors[p + "post_attention_layernorm.weight"] = rng.normal(size=(D,))
        tensors[p + "pre_feedforward_layernorm.weight"] = rng.normal(size=(D,))
        tensors[p + "post_feedforward_layernorm.weight"] = rng.normal(size=(D,))
    tensors = {k: np.asarray(v, np.float32) for k, v in tensors.items()}
    save_file(tensors, str(tmp_path / "model.safetensors"))

    params = load_hf_params(cfg, str(tmp_path))
    lyr = params["layers"]
    assert "lm_head" not in params  # tied
    for ours, hf_name in [
        ("attn_norm", "input_layernorm"),
        ("post_attn_norm", "post_attention_layernorm"),
        ("mlp_norm", "pre_feedforward_layernorm"),
        ("post_mlp_norm", "post_feedforward_layernorm"),
    ]:
        np.testing.assert_allclose(
            np.asarray(lyr[ours][1], np.float32),
            tensors[f"model.layers.1.{hf_name}.weight"],
            rtol=1e-2, atol=1e-2,  # stored bf16
        )


def test_hf_mistral_sliding_window_parsing(tmp_path):
    hf = {
        "model_type": "mistral",
        "vocab_size": 1000,
        "hidden_size": 128,
        "intermediate_size": 256,
        "num_hidden_layers": 2,
        "num_attention_heads": 8,
        "num_key_value_heads": 4,
        "head_dim": 16,
        "sliding_window": 4096,
        "eos_token_id": 2,
    }
    p = tmp_path / "config.json"
    p.write_text(json.dumps(hf))
    cfg = config_from_hf_json(str(p), name="m")
    # Mistral v0.1: every layer local.
    assert cfg.sliding_window == 4096 and cfg.sliding_window_pattern == 1
    assert cfg.hidden_act == "silu" and not cfg.norm_unit_offset
