"""The plain reference of the ``mellum`` architecture (window and full
attention mixed, every layer a mixture-of-experts block), written out from
the published configuration's keys and the Qwen3-MoE modelling file's
equations, which those keys are (``max_window_layers``,
``use_sliding_window``, ``norm_topk_prob``, ``num_experts``,
``moe_intermediate_size``), in ``jax.numpy``: float32, matmul precision
"highest", attention over the whole sequence in query blocks (no cache, no
kernel, no batching), the expert block as a dense loop over the experts the
configuration **holds** with the router over all the published experts. The
interface is in ``perf/reference/__init__.py``.

``h = x + Attn_t(N(x))``, ``y = h + MoE(N(h))`` with ``N(x; w) = x /
sqrt(mean(x^2) + eps) w`` and ``t = layer_types[l]``; a final ``N`` before
the untied head.

**Attention**: ``q``, ``k``, ``v`` without bias; ``q <- N_head(q)``, ``k <-
N_head(k)``; rotate-half rotary embedding over every lane with the inverse
frequencies of ``t`` (``rope_parameters[t]``): ``default`` is ``theta^(-2i /
head)``; ``yarn`` blends ``f`` and ``f / factor`` a frequency by the linear
ramp between the whole dimensions that turn ``beta_fast`` and ``beta_slow``
times in ``original_max_position_embeddings`` positions, and scales ``cos``
and ``sin`` by ``attention_factor``; causal softmax attention at
``head^-1/2``, grouped; a ``sliding_attention`` query at ``p`` sees keys
``p - sliding_window + 1 .. p``.

**Expert block**: ``p = softmax(W_r x)`` over all the published experts,
the top ``num_experts_per_tok``, renormalised over the chosen; expert ``e``:
``down_e(silu(gate_e x) up_e x)``. The sum runs over the held experts only
(``num_experts`` from ``ep_share.first`` on): one rank's share.

``gap`` is the smallest distance between the k-th and (k+1)-th router logit
over the layers. The weights stay in the engine's dtype on the device and
are widened a layer (the expert banks: an expert) at a time.

Negative controls, each one piece of the mathematics broken: ``window_off``
(every layer sees the whole context), ``yarn_off`` (the full layers on the
default frequencies, unscaled), ``yarn_scale_off`` (``attention_factor``
1), ``qk_norm_off``, ``renorm_off``, ``router_sigmoid``. Precision controls,
each the nearest precision below what the configuration states:
``weights_fp8`` (every projection and expert matrix, bfloat16 as served,
rounded to float8 e4m3's three mantissa bits; router, norms, embedding and
head stay), ``kv_fp8`` (the rotated keys and the values rounded likewise:
what one-byte pages would hold).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perf import config as configs
from perf.reference import model as shared
from perf.reference import weights as common

VARIANTS = ("none", "window_off", "yarn_off", "yarn_scale_off", "qk_norm_off",
            "renorm_off", "router_sigmoid", "weights_fp8", "kv_fp8")
# The matrices ``weights_fp8`` rounds.
MATRICES = ("wq", "wk", "wv", "wo", "w1", "w2")
SLIDING, FULL = "sliding_attention", "full_attention"
_HI = jax.lax.Precision.HIGHEST
_Q_BLOCK = 1024  # query rows a block: its scores are [block, T] a head
_MAX_LEN = 65536


def _pad_len(n: int) -> int:
    """Padded at the end, which a causal model never looks at: a few sizes
    below a query block, whole pairs of blocks above."""
    for size in (256, 512, _Q_BLOCK):
        if n <= size:
            return size
    size = -(-n // (2 * _Q_BLOCK)) * (2 * _Q_BLOCK)
    if size > _MAX_LEN:
        raise ValueError(f"sequence of {n} tokens is beyond the reference's sizes")
    return size


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=_HI,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(
        jnp.float32)


def _fp8(x):
    """Rounded to three mantissa bits (a convert pair would be folded away
    on the chip); the dtype stays."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)


def weights(cfg):
    from production_stack_tpu.models import registry

    return common.engine_params(
        registry.model_for(configs.program_model_config(cfg)),
        cfg.weights_seed, cfg.flag("--quantization"))


def inv_freq(rope: dict, head: int, default_theta: float = 10000.0):
    """(inverse frequencies ``[head / 2]`` float64, the scale of ``cos`` and
    ``sin``) of one entry of ``rope_parameters``."""
    theta = float(rope.get("rope_theta", default_theta))
    half = head // 2
    f = theta ** (-np.arange(half, dtype=np.float64) / half)
    if rope.get("rope_type", "default") == "default":
        return f, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}: default or yarn")
    factor = float(rope["factor"])
    original = int(rope["original_max_position_embeddings"])

    def turning(rotations):  # the dimension that turns so often in `original`
        return (head * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turning(float(rope.get("beta_fast") or 32))), 0)
    high = min(math.ceil(turning(float(rope.get("beta_slow") or 1))), head - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0, 1)
    scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return f / factor * ramp + f * (1.0 - ramp), float(scale)


def rope_tables(n_positions: int, inv, scale: float):
    """cos/sin [T, head / 2], angles in float64 on the host."""
    ang = np.arange(n_positions, dtype=np.float64)[:, None] * inv[None, :]
    return (jnp.asarray((np.cos(ang) * scale).astype(np.float32)),
            jnp.asarray((np.sin(ang) * scale).astype(np.float32)))


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "window", "eps", "qk_norm", "kv_fp8"))
def attention(x, cos, sin, lw, *, n_heads, n_kv, window, eps, qk_norm, kv_fp8):
    """x [T, D] float32 -> the attention block's output [T, D]. ``window``
    0: every earlier key; else the last ``window`` keys, the query's own
    among them."""
    T = x.shape[0]
    h = _rms(x, lw["norm"], eps)
    q = _mm(h, lw["wq"]).reshape(T, n_heads, -1)
    k = _mm(h, lw["wk"]).reshape(T, n_kv, -1)
    v = _mm(h, lw["wv"]).reshape(T, n_kv, -1)
    if qk_norm:
        q, k = _rms(q, lw["q_norm"], eps), _rms(k, lw["k_norm"], eps)

    def rope(t):  # rotate-half: (t1, t2) -> (t1 c - t2 s, t2 c + t1 s)
        half = t.shape[-1] // 2
        t1, t2 = t[..., :half], t[..., half:]
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], axis=-1)

    q, k = rope(q), rope(k)
    if kv_fp8:  # what the pages would hold one precision down
        k, v = _fp8(k), _fp8(v)
    rep = n_heads // n_kv
    qb = min(_Q_BLOCK, T)
    key_pos = jnp.arange(T)
    scale = 1.0 / math.sqrt(q.shape[-1])

    def head(args):
        q_h, i = args  # [T, head], the head's index
        k_h = jax.lax.dynamic_index_in_dim(k, i // rep, axis=1, keepdims=False)
        v_h = jax.lax.dynamic_index_in_dim(v, i // rep, axis=1, keepdims=False)

        def block(start):
            rows = jax.lax.dynamic_slice_in_dim(q_h, start, qb)
            s = jnp.einsum("td,sd->ts", rows, k_h, precision=_HI,
                           preferred_element_type=jnp.float32) * scale
            q_pos = start + jnp.arange(qb)
            seen = key_pos[None, :] <= q_pos[:, None]
            if window:
                seen &= key_pos[None, :] > q_pos[:, None] - window
            s = jnp.where(seen, s, -jnp.inf)
            return jnp.matmul(jax.nn.softmax(s, -1), v_h, precision=_HI,
                              preferred_element_type=jnp.float32)

        return jax.lax.map(block, jnp.arange(0, T, qb)).reshape(T, -1)

    o = jax.lax.map(head, (q.transpose(1, 0, 2), jnp.arange(n_heads)))
    return _mm(o.transpose(1, 0, 2).reshape(T, -1), lw["wo"])


@functools.partial(jax.jit, static_argnames=(
    "top_k", "first", "renorm", "eps", "softmax"))
def moe(x, lw, *, top_k, first, renorm, eps, softmax):
    """-> (out [T, D], gap [T])."""
    u = _rms(x, lw["norm"], eps)
    logits = _mm(u, lw["w_router"])  # [T, all experts]
    s = jax.nn.softmax(logits, -1) if softmax else jax.nn.sigmoid(logits)
    ordered = jnp.sort(logits, axis=-1)[:, ::-1]
    gap = ordered[:, top_k - 1] - ordered[:, top_k]
    w, ids = jax.lax.top_k(s, top_k)
    if renorm:
        w = w / jnp.sum(w, -1, keepdims=True)
    held = lw["w1"].shape[0]
    width = lw["w2"].shape[1]

    def one(e, acc):
        weight = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)  # [T]
        a = _mm(u, jax.lax.dynamic_index_in_dim(lw["w1"], e, keepdims=False))
        y = _mm(jax.nn.silu(a[:, :width]) * a[:, width:],
                jax.lax.dynamic_index_in_dim(lw["w2"], e, keepdims=False))
        return acc + weight[:, None] * y

    return jax.lax.fori_loop(0, held, one, jnp.zeros_like(u)), gap


def teacher_force(cfg, params, sequences, variant: str) -> list:
    """One sequence at a time: its hidden states [T, D] are all that is
    held while the layers are walked."""
    hf = cfg.hf
    eps = float(hf.get("rms_norm_eps", 1e-6))
    n_heads = hf["num_attention_heads"]
    head = hf.get("head_dim") or hf["hidden_size"] // n_heads
    types = list(hf["layer_types"])
    window = 0 if variant == "window_off" else int(hf["sliding_window"])
    ropes = dict(hf.get("rope_parameters") or {})
    if variant == "yarn_off":
        ropes[FULL] = {"rope_type": "default",
                       "rope_theta": ropes[FULL]["rope_theta"]}
    elif variant == "yarn_scale_off":
        ropes[FULL] = dict(ropes[FULL], attention_factor=1.0)
    freqs = {t: inv_freq(ropes.get(t) or {}, head) for t in (SLIDING, FULL)}
    share = hf.get("ep_share") or {}
    all_experts = int((cfg.raw.get("published") or {}).get(
        "num_experts", hf["num_experts"]))
    layers = params["layers"]
    router_width = layers["moe"]["w_router"].shape[-1]
    if router_width != all_experts:
        raise ValueError(
            f"the served router scores {router_width} experts, the "
            f"configuration publishes {all_experts}")
    final_norm, lm_head = common.head_weights(params)

    def layer_weights(kind, i):
        lw = {k: v[i] for k, v in layers[kind].items()}
        if variant == "weights_fp8":
            lw = {k: _fp8(v) if k in MATRICES else v for k, v in lw.items()}
        return lw

    out = []
    for s in sequences:
        T = _pad_len(len(s["tokens"]))
        ids = np.zeros(T, np.int32)
        ids[: len(s["tokens"])] = s["tokens"]
        x = common.embed_rows(params, jnp.asarray(ids))
        tables = {t: rope_tables(T, *freqs[t]) for t in freqs}
        gaps = np.full(T, np.inf, np.float32)
        for i, t in enumerate(types):
            x = x + attention(
                x, *tables[t], layer_weights("attn", i), n_heads=n_heads,
                n_kv=hf.get("num_key_value_heads", n_heads),
                window=window if t == SLIDING else 0, eps=eps,
                qk_norm=variant != "qk_norm_off", kv_fp8=variant == "kv_fp8")
            ffn, gap = moe(
                x, layer_weights("moe", i), top_k=hf["num_experts_per_tok"],
                first=int(share.get("first", 0)),
                renorm=(bool(hf.get("norm_topk_prob", True))
                        and variant != "renorm_off"),
                eps=eps, softmax=variant != "router_sigmoid")
            x = x + ffn
            gaps = np.minimum(gaps, np.asarray(gap))
        n_prompt, n_gen = s["n_prompt"], len(s["want"])
        rows = jnp.arange(n_prompt - 1, n_prompt - 1 + n_gen)
        lps = np.asarray(shared.head_logprobs(x[rows], final_norm, lm_head, eps=eps))
        out.append((lps, gaps[n_prompt - 1: n_prompt - 1 + n_gen]))
        del x
    return out
