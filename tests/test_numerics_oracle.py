"""Model-level numerics oracle: every family vs an independent numpy ref.

The reference stack inherits model correctness from vLLM; this repo owns
its own (VERDICT r4 #6). Each test runs the production forward
(models/llama.py `Llama.forward` with real paging inputs / models/bert.py)
at tiny scale in float32 and pins full-sequence logits against
`tests/numpy_reference.py` — written from the architectures' published
conventions, sharing no code with the package — so an architecture-level
bug (rope scaling, GQA head mapping, softcap placement, window pattern,
router renormalization) cannot hide in both implementations.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from production_stack_tpu.models.llama import (
    Llama,
    LlamaConfig,
    quantize_tree,
)
from production_stack_tpu.models.registry import get_model_config

from .numpy_reference import (
    dequant_tree,
    ref_bert_forward,
    ref_decoder_forward,
)

pytestmark = pytest.mark.fast

T = 24  # sequence length exercised (crosses page boundaries at bs=8)


def _variant(base: str, **kw) -> LlamaConfig:
    cfg = get_model_config(base)
    return dataclasses.replace(cfg, **kw, dtype="float32")


FAMILIES = {
    # Plain Llama (GQA via tiny preset's MHA; rope, SwiGLU, untied head).
    "llama": _variant("tiny-llama-debug"),
    # Llama-3.1: rope scaling ramp active well below T.
    "llama31-rope-scaled": _variant(
        "tiny-llama-debug",
        rope_scaling_factor=8.0,
        rope_low_freq_factor=1.0,
        rope_high_freq_factor=4.0,
        rope_original_max_position=16,
    ),
    # GQA proper: 8 query heads over 2 kv heads.
    "llama-gqa": _variant("tiny-llama-debug", num_kv_heads=2),
    # Mistral v0.1: sliding window on every layer.
    "mistral": _variant(
        "tiny-llama-debug", sliding_window=8, sliding_window_pattern=1,
        name="tiny-mistral-debug",
    ),
    # Qwen2: attention biases.
    "qwen2": _variant(
        "tiny-llama-debug", attention_bias=True, name="tiny-qwen2-debug"
    ),
    # Qwen3: per-head q/k RMSNorm.
    "qwen3": _variant("tiny-qwen3-debug"),
    # Mixtral: sparse MoE (4 experts, top-2, renormalized).
    "mixtral": _variant("tiny-mixtral-debug"),
    # Gemma 1: GeGLU, (1+w) norms, sqrt(D)-scaled embeddings, tied head.
    "gemma": _variant("tiny-gemma-debug"),
    # Gemma 2: softcaps, post-block norms, alternating sliding windows,
    # query_pre_attn_scalar.
    "gemma2": _variant("tiny-gemma2-debug"),
    # A looped stack: three layers run twice over one set of weights (cache
    # slots pass x layers + layer), the four-norm block without the unit
    # offset, the final norm closing every pass.
    "ouro": _variant("tiny-ouro-debug"),
}


def _run_model(cfg: LlamaConfig, params, token_ids, kv_dtype=None):
    """Production forward at [1, T] with a real paged-cache setup; returns
    full-sequence logits [T, V] (float32)."""
    model = Llama(cfg)
    nb, bs = 16, 8
    toks = jnp.asarray(np.asarray(token_ids)[None], jnp.int32)
    tt = toks.shape[1]
    positions = jnp.arange(tt, dtype=jnp.int32)[None]
    write_idx = jnp.arange(tt, dtype=jnp.int32)[None]  # pages 0..2
    tables = jnp.arange(nb, dtype=jnp.int32)[None]
    kv_lens = jnp.full((1,), tt, jnp.int32)
    last_idx = jnp.full((1,), tt - 1, jnp.int32)
    cache = model.make_kv_cache(nb, bs, kv_dtype)
    logits, _ = model.forward(
        params, toks, positions, write_idx, tables, kv_lens, last_idx,
        cache, attn_impl="gather", all_logits=True,
    )
    return np.asarray(logits[0], np.float32)


def _agree(got, want, label, atol_scale=2e-3):
    """Full-sequence agreement: tight numeric tolerance + argmax match."""
    assert got.shape == want.shape, (label, got.shape, want.shape)
    scale = float(np.max(np.abs(want))) or 1.0
    np.testing.assert_allclose(
        got, want, atol=atol_scale * scale, rtol=2e-3,
        err_msg=f"{label}: logits diverge from the independent reference",
    )
    assert np.array_equal(got.argmax(-1), want.argmax(-1)), (
        f"{label}: argmax token disagrees with the independent reference"
    )


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_family_matches_numpy_reference(family):
    cfg = FAMILIES[family]
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(42))
    rng = np.random.default_rng(3)
    token_ids = rng.integers(1, cfg.vocab_size - 1, size=T).tolist()

    got = _run_model(cfg, params, token_ids)
    ref = ref_decoder_forward(
        cfg, jax.tree.map(lambda x: np.asarray(x, np.float32), params),
        token_ids,
    )
    _agree(got, ref, family)


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_quantized_llama_matches_dequantized_reference(mode):
    """Quantized serving must equal float math over the EXACTLY dequantized
    weights (quantization changes the weights, not the architecture)."""
    cfg = FAMILIES["llama-gqa"]
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(7))
    qparams = quantize_tree(jax.tree.map(lambda x: x, params), mode=mode)
    rng = np.random.default_rng(5)
    token_ids = rng.integers(1, cfg.vocab_size - 1, size=T).tolist()

    got = _run_model(cfg, qparams, token_ids)
    ref = ref_decoder_forward(cfg, dequant_tree(qparams), token_ids)
    _agree(got, ref, f"llama-{mode}")


@pytest.mark.parametrize("mode", ["int8", "int4"])
def test_quantized_moe_matches_dequantized_reference(mode):
    cfg = FAMILIES["mixtral"]
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(9))
    qparams = quantize_tree(jax.tree.map(lambda x: x, params), mode=mode)
    rng = np.random.default_rng(6)
    token_ids = rng.integers(1, cfg.vocab_size - 1, size=T).tolist()

    got = _run_model(cfg, qparams, token_ids)
    ref = ref_decoder_forward(cfg, dequant_tree(qparams), token_ids)
    _agree(got, ref, f"mixtral-{mode}")


def test_fp8_kv_matches_rounded_reference():
    """fp8-e4m3 KV cache must equal the reference with K/V round-tripped
    through e4m3 after rope — same rounding, same math."""
    cfg = FAMILIES["llama-gqa"]
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(11))
    rng = np.random.default_rng(8)
    token_ids = rng.integers(1, cfg.vocab_size - 1, size=T).tolist()

    got = _run_model(cfg, params, token_ids, kv_dtype="float8_e4m3fn")

    def kv_quant(x):
        return x.astype(ml_dtypes.float8_e4m3fn).astype(np.float32)

    ref = ref_decoder_forward(
        cfg, jax.tree.map(lambda x: np.asarray(x, np.float32), params),
        token_ids, kv_quant=kv_quant,
    )
    # fp8 rounding amplifies small logit differences; the bar is agreement
    # with the SAME rounding applied, at a slightly looser tolerance.
    _agree(got, ref, "llama-fp8kv", atol_scale=5e-3)


def test_bert_matches_numpy_reference():
    from production_stack_tpu.models.bert import BERT_PRESETS, BertClassifier

    cfg = BERT_PRESETS["tiny-bert-debug"]
    model = BertClassifier(cfg)
    params = model.init_params(jax.random.PRNGKey(13))
    rng = np.random.default_rng(12)
    B, tt = 3, 20
    tokens = rng.integers(2, cfg.vocab_size - 1, size=(B, tt))
    lengths = np.asarray([20, 14, 9])
    for i, ln in enumerate(lengths):
        tokens[i, ln:] = cfg.pad_token_id
    type_ids = np.zeros((B, tt), np.int64)
    type_ids[:, 10:] = 1  # segment B

    got = np.asarray(
        model.forward(
            params,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(lengths, jnp.int32),
            jnp.asarray(type_ids, jnp.int32),
        )
    )
    ref = ref_bert_forward(cfg, params, tokens, lengths, type_ids)
    np.testing.assert_allclose(got, ref, atol=1e-3, rtol=1e-3)
