"""Independent pure-numpy reference forwards for every served model family.

The model-level numerics oracle (VERDICT r4 #6): the reference stack
inherits correctness from vLLM's battle-tested model zoo; this repo must
establish its own. These implementations are written directly from the
architectures' published conventions (HF modeling semantics: rotate-half
rope, llama3 rope scaling ramp, GQA head grouping, Gemma (1+w) norms and
sqrt(D) embedding scale, Gemma-2 logit softcaps and alternating sliding
windows, Qwen3 per-head q/k RMSNorm, Mixtral top-k renormalized routing,
RoBERTa classification heads) in plain numpy — deliberately sharing NO code
with production_stack_tpu — so an architecture-level bug (rope scaling,
head mapping, softcap placement, window pattern) cannot hide in both.
"""

from __future__ import annotations

import math

import numpy as np


# ---------------------------------------------------------------------------
# Weight dequantization (numpy-side inverse of the packed formats)
# ---------------------------------------------------------------------------


def dequant_tree(params):
    """Return a float32 copy of a (possibly int8/int4-quantized) param tree.

    int8 leaves carry a per-output-channel ``*_qs`` sibling; int4 leaves are
    nibble-packed along the contraction axis with group scales in ``*_q4s``.
    """
    def deq_layer(layers, key):
        w = np.asarray(layers[key])
        if key + "_q4s" in layers:
            s = np.asarray(layers[key + "_q4s"], np.float32)
            lo = ((w.astype(np.int8) << 4) >> 4).astype(np.float32)
            hi = (w.astype(np.int8) >> 4).astype(np.float32)
            full = np.stack([lo, hi], axis=-2)  # [..., in/2, 2, out]
            shape = full.shape[:-3] + (full.shape[-3] * 2, full.shape[-1])
            full = full.reshape(shape)
            G = s.shape[-2]
            g = shape[-2] // G
            full = full.reshape(shape[:-2] + (G, g, shape[-1]))
            full = full * s[..., :, None, :]
            return full.reshape(shape)
        if key + "_qs" in layers:
            s = np.asarray(layers[key + "_qs"], np.float32)
            return w.astype(np.float32) * s[..., None, :]
        return w.astype(np.float32)

    out = {"layers": {}}
    for k, v in params.items():
        if k == "layers":
            continue
        if k.endswith("_qs") or k.endswith("_q4s"):
            continue
        if k + "_qs" in params:  # embed / lm_head: per-row scale (axis -1)
            s = np.asarray(params[k + "_qs"], np.float32)
            out[k] = np.asarray(v, np.float32) * s[:, None]
        else:
            out[k] = np.asarray(v, np.float32)
    for k, v in params["layers"].items():
        if k.endswith("_qs") or k.endswith("_q4s") or k.startswith("lora_"):
            continue
        if k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
            out["layers"][k] = deq_layer(params["layers"], k)
        else:
            out["layers"][k] = np.asarray(v, np.float32)
    return out


# ---------------------------------------------------------------------------
# Decoder families (llama / mistral / qwen2 / qwen3 / mixtral / gemma 1+2)
# ---------------------------------------------------------------------------


def _rms(x, w, eps, unit_offset=False):
    normed = x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps)
    return normed * (1.0 + w) if unit_offset else normed * w


def _rope_tables(positions, cfg):
    half = cfg.head_dim // 2
    freqs = 1.0 / (cfg.rope_theta ** (np.arange(half, dtype=np.float64) / half))
    if cfg.rope_scaling_factor:
        # Llama-3.1 "llama3" scaling: long wavelengths fully scaled, short
        # kept, smooth ramp between the low/high frequency-factor bounds of
        # the original training context.
        wavelen = 2.0 * math.pi / freqs
        low_w = cfg.rope_original_max_position / cfg.rope_low_freq_factor
        high_w = cfg.rope_original_max_position / cfg.rope_high_freq_factor
        smooth = (
            cfg.rope_original_max_position / wavelen - cfg.rope_low_freq_factor
        ) / (cfg.rope_high_freq_factor - cfg.rope_low_freq_factor)
        smooth = np.clip(smooth, 0.0, 1.0)
        scaled = (1.0 - smooth) * freqs / cfg.rope_scaling_factor + smooth * freqs
        freqs = np.where(
            wavelen > low_w,
            freqs / cfg.rope_scaling_factor,
            np.where(wavelen < high_w, freqs, scaled),
        )
    ang = np.asarray(positions, np.float64)[:, None] * freqs  # [T, half]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _apply_rope(x, cos, sin):
    """HF rotate-half; x [T, H, hd]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _softcap(x, cap):
    return np.tanh(x / cap) * cap if cap else x


def _act(cfg):
    if cfg.hidden_act == "gelu_tanh":
        return lambda v: 0.5 * v * (
            1.0 + np.tanh(math.sqrt(2.0 / math.pi) * (v + 0.044715 * v**3))
        )
    return lambda v: v / (1.0 + np.exp(-v))  # silu


def _layer_window(cfg, li):
    if not cfg.sliding_window:
        return 0  # global
    pat = cfg.sliding_window_pattern
    if pat <= 1:
        return cfg.sliding_window
    return 0 if (li + 1) % pat == 0 else cfg.sliding_window


def _mlp(cfg, lp, li, h):
    act = _act(cfg)
    if not cfg.num_experts:
        g = h @ lp["w_gate"][li]
        u = h @ lp["w_up"][li]
        return (act(g) * u) @ lp["w_down"][li]
    # Mixtral sparse MoE: fp32 router, top-k, renormalized combine.
    logits = h @ lp["w_router"][li]  # [T, E]
    z = logits - logits.max(-1, keepdims=True)
    probs = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    K = cfg.num_experts_per_tok
    ids = np.argsort(-probs, axis=-1, kind="stable")[:, :K]  # [T, K]
    w = np.take_along_axis(probs, ids, axis=-1)
    w = w / w.sum(-1, keepdims=True)
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        for k in range(K):
            e = ids[t, k]
            ht = h[t]
            ff = (act(ht @ lp["w_gate"][li, e]) * (ht @ lp["w_up"][li, e]))
            out[t] += w[t, k] * (ff @ lp["w_down"][li, e])
    return out


def ref_decoder_forward(cfg, params, token_ids, kv_quant=None):
    """Full-sequence logits [T, V], float32/float64 math throughout.

    ``params`` must be a float tree (run :func:`dequant_tree` first for
    quantized checkpoints). ``kv_quant``: a callable applied to each
    layer's K and V after rope (e.g. an fp8-e4m3 round-trip) to mirror a
    quantized KV cache.
    """
    T = len(token_ids)
    D = cfg.hidden_size
    x = params["embed"][np.asarray(token_ids)]  # [T, D]
    if cfg.embed_scale:
        x = x * np.float32(math.sqrt(D))
    positions = np.arange(T)
    cos, sin = _rope_tables(positions, cfg)
    lp = params["layers"]
    offset = cfg.norm_unit_offset
    G = cfg.num_heads // cfg.num_kv_heads
    scale = 1.0 / math.sqrt(cfg.query_pre_attn_scalar or cfg.head_dim)

    # A looped stack (``ut_steps`` > 1) runs the same layers that many times,
    # the final norm closing every pass; a plain stack is one pass.
    for _ in range(cfg.ut_steps):
        for li in range(cfg.num_layers):
            h = _rms(x, lp["attn_norm"][li], cfg.rms_norm_eps, offset)
            q = h @ lp["wq"][li]
            k = h @ lp["wk"][li]
            v = h @ lp["wv"][li]
            if "bq" in lp:
                q, k, v = q + lp["bq"][li], k + lp["bk"][li], v + lp["bv"][li]
            q = q.reshape(T, cfg.num_heads, cfg.head_dim)
            k = k.reshape(T, cfg.num_kv_heads, cfg.head_dim)
            v = v.reshape(T, cfg.num_kv_heads, cfg.head_dim)
            if "q_norm" in lp:  # Qwen3: per-head RMS over hd, pre-rope
                q = _rms(q, lp["q_norm"][li], cfg.rms_norm_eps)
                k = _rms(k, lp["k_norm"][li], cfg.rms_norm_eps)
            q = _apply_rope(q, cos, sin)
            k = _apply_rope(k, cos, sin)
            if kv_quant is not None:
                k, v = kv_quant(k), kv_quant(v)
            # GQA: query head hq reads kv head hq // G.
            kq = np.repeat(k, G, axis=1)  # [T, H, hd]
            vq = np.repeat(v, G, axis=1)
            scores = np.einsum("thd,shd->hts", q, kq) * scale
            scores = _softcap(scores, cfg.attn_logit_softcap)
            mask = positions[None, :] <= positions[:, None]  # causal [T, S]
            win = _layer_window(cfg, li)
            if win:
                mask = mask & (positions[None, :] > positions[:, None] - win)
            scores = np.where(mask[None], scores, -1e30)
            z = scores - scores.max(-1, keepdims=True)
            probs = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
            attn = np.einsum("hts,shd->thd", probs, vq).reshape(T, -1)
            o = attn @ lp["wo"][li]
            if cfg.post_block_norms:
                o = _rms(o, lp["post_attn_norm"][li], cfg.rms_norm_eps, offset)
            x = x + o
            h = _rms(x, lp["mlp_norm"][li], cfg.rms_norm_eps, offset)
            ff = _mlp(cfg, lp, li, h)
            if cfg.post_block_norms:
                ff = _rms(ff, lp["post_mlp_norm"][li], cfg.rms_norm_eps, offset)
            x = x + ff

        x = _rms(x, params["final_norm"], cfg.rms_norm_eps, offset)
    head = params.get("lm_head", params["embed"])
    logits = x @ head.T
    return _softcap(logits, cfg.final_logit_softcap)


# ---------------------------------------------------------------------------
# BERT/RoBERTa cross-encoder
# ---------------------------------------------------------------------------


def _ln(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / np.sqrt(var + eps) * w + b


def ref_bert_forward(cfg, params, tokens, lengths, type_ids=None):
    """Relevance logits [B] — RoBERTa sequence-classification semantics."""
    erf = np.vectorize(math.erf)  # exact gelu (bert uses non-approximate)

    tokens = np.asarray(tokens)
    B, T = tokens.shape
    H, hd = cfg.num_heads, cfg.head_dim
    positions = np.arange(T)[None, :] + cfg.position_offset
    valid = np.arange(T)[None, :] < np.asarray(lengths)[:, None]
    if type_ids is None:
        type_ids = np.zeros((B, T), np.int64)
    type_ids = np.minimum(type_ids, cfg.type_vocab_size - 1)
    def to_np(v):
        return (
            {kk: to_np(vv) for kk, vv in v.items()}
            if isinstance(v, dict)
            else np.asarray(v, np.float32)
        )

    p = {k: to_np(v) for k, v in params.items() if k != "layers"}
    lp = to_np(params["layers"])
    x = (
        p["word_emb"][tokens]
        + p["pos_emb"][np.minimum(positions, cfg.max_position_embeddings - 1)]
        + p["type_emb"][type_ids]
    )
    x = _ln(x, p["emb_ln_w"], p["emb_ln_b"], cfg.layer_norm_eps)
    for li in range(cfg.num_layers):
        q = (x @ lp["wq"][li] + lp["bq"][li]).reshape(B, T, H, hd)
        k = (x @ lp["wk"][li] + lp["bk"][li]).reshape(B, T, H, hd)
        v = (x @ lp["wv"][li] + lp["bv"][li]).reshape(B, T, H, hd)
        scores = np.einsum("bthd,bshd->bhts", q, k) / math.sqrt(hd)
        scores = np.where(valid[:, None, None, :], scores, -1e30)
        z = scores - scores.max(-1, keepdims=True)
        probs = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
        attn = np.einsum("bhts,bshd->bthd", probs, v).reshape(B, T, -1)
        a = attn @ lp["wo"][li] + lp["bo"][li]
        x = _ln(x + a, lp["attn_ln"]["w"][li], lp["attn_ln"]["b"][li],
                cfg.layer_norm_eps)
        f = x @ lp["w1"][li] + lp["b1"][li]
        f = 0.5 * f * (1.0 + erf(f / math.sqrt(2.0)))  # exact gelu
        f = f @ lp["w2"][li] + lp["b2"][li]
        x = _ln(x + f, lp["mlp_ln"]["w"][li], lp["mlp_ln"]["b"][li],
                cfg.layer_norm_eps)
    cls = x[:, 0]
    h = np.tanh(cls @ p["cls_dense_w"] + p["cls_dense_b"])
    logits = h @ p["cls_out_w"] + p["cls_out_b"]
    col = 1 if cfg.num_labels == 2 else 0
    return logits[:, col]
