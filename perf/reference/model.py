"""The plain reference: a Mistral / Mixtral decoder written out from the
published equations in ``jax.numpy``, float32, matmul precision "highest".

No kernels, no cache, no batching, and no import from
``production_stack_tpu``: RMSNorm, rotary embedding in the HF rotate-half
convention, grouped-query causal attention computed in query blocks, SwiGLU,
and for Mixtral the router (softmax over all experts, top-k, renormalise).
One layer's weights are handed in at a time by the caller
(:mod:`perf.reference.mistral`), which owns where they come from.

``variant`` deliberately breaks one piece of mathematics, for the negative
controls that show the check has power: ``no_renorm`` (top-k weights not
renormalised), ``rope_1e4`` (rotary base 10,000 instead of the config's).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

VARIANTS = ("none", "no_renorm", "rope_1e4")
_Q_BLOCK = 512
_HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI, preferred_element_type=jnp.float32)


def rope_tables(n_positions: int, head_dim: int, theta: float):
    """cos/sin [T, head_dim/2], angles computed in float64 on the host."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = np.arange(n_positions, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, cos, sin):
    # x [T, H, hd]; HF rotate-half: (x1, x2) -> (x1 c - x2 s, x2 c + x1 s)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, n_rep: int):
    """Causal GQA attention, queries in blocks against the whole context.
    q [T, H, hd], k/v [T, KH, hd] -> [T, H*hd]."""
    T, H, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    kq = jnp.repeat(k, n_rep, axis=1)  # query head h reads kv head h // n_rep
    vq = jnp.repeat(v, n_rep, axis=1)
    key_pos = jnp.arange(T)
    outs = []
    for start in range(0, T, _Q_BLOCK):
        qb = q[start:start + _Q_BLOCK]
        scores = jnp.einsum(
            "thd,shd->hts", qb, kq, precision=_HI,
            preferred_element_type=jnp.float32,
        ) * scale
        q_pos = start + jnp.arange(qb.shape[0])
        mask = key_pos[None, :] <= q_pos[:, None]
        scores = jnp.where(mask[None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum(
            "hts,shd->thd", probs, vq, precision=_HI,
            preferred_element_type=jnp.float32,
        ).reshape(qb.shape[0], H * hd))
    return jnp.concatenate(outs, axis=0)


def _swiglu(h, w_gate, w_up, w_down):
    g = _mm(h, w_gate.astype(jnp.float32))
    u = _mm(h, w_up.astype(jnp.float32))
    return _mm(jax.nn.silu(g) * u, w_down.astype(jnp.float32))


def _expert(lw, name: str, e: int):
    """Expert ``e``'s matrix: from this layer's bank [E, in, out], or, where
    the caller handed the whole stacked bank [L, E, in, out] and ``li``, one
    slice of it (so that no layer-sized copy is made)."""
    w = lw[name]
    if w.ndim == 3:
        return w[e]
    return jax.lax.dynamic_slice(
        w, (lw["li"], e, 0, 0), (1, 1) + w.shape[2:])[0, 0]


def _moe(h, lw, top_k: int, renorm: bool):
    """Mixtral sparse MLP. Returns (out [T, D], gap [T]): ``gap`` is the
    distance between the k-th and (k+1)-th router logit, i.e. how far the
    token is from routing to a different expert set."""
    logits = _mm(h, lw["w_router"].astype(jnp.float32))  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, top_k)
    if renorm:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    ordered = jnp.sort(logits, axis=-1)[:, ::-1]
    gap = ordered[:, top_k - 1] - ordered[:, top_k]
    n_experts = logits.shape[-1]
    out = jnp.zeros_like(h)
    for e in range(n_experts):  # every expert over every token, masked
        weight = jnp.sum(jnp.where(top_e == e, top_w, 0.0), axis=-1)  # [T]
        y = _swiglu(h, *(_expert(lw, name, e)
                         for name in ("w_gate", "w_up", "w_down")))
        out = out + weight[:, None] * y
    return out, gap


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "top_k", "eps", "renorm"))
def layer(x, cos, sin, lw, *, n_heads, n_kv, top_k, eps, renorm):
    """One decoder layer over one whole sequence. x [T, D] float32.
    Returns (x, gap): gap is [T], +inf for a dense layer."""
    T = x.shape[0]
    h = _rms(x, lw["attn_norm"], eps)
    q = _mm(h, lw["wq"]).reshape(T, n_heads, -1)
    k = _mm(h, lw["wk"]).reshape(T, n_kv, -1)
    v = _mm(h, lw["wv"]).reshape(T, n_kv, -1)
    q, k = _rope(q, cos, sin), _rope(k, cos, sin)
    x = x + _mm(_attention(q, k, v, n_heads // n_kv), lw["wo"])
    h = _rms(x, lw["mlp_norm"], eps)
    if "w_router" in lw:
        ff, gap = _moe(h, lw, top_k, renorm)
    else:
        ff = _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"])
        gap = jnp.full((T,), jnp.inf, jnp.float32)
    return x + ff, gap


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logprobs(x_rows, final_norm, lm_head, *, eps):
    """log-softmax over the vocabulary for the given rows. lm_head [V, D]."""
    h = _rms(x_rows, final_norm, eps)
    logits = jnp.einsum(
        "td,vd->tv", h, lm_head, precision=_HI,
        preferred_element_type=jnp.float32,
    )
    return jax.nn.log_softmax(logits, axis=-1)


def pad_len(n: int) -> int:
    """Sequence lengths are padded (at the end, which a causal model never
    looks at) to a few sizes so that a new seed seldom compiles."""
    for size in (256, 512, 768, 1024, 1536, 2048, 3072, 4096, 6144, 8192,
                 12288, 16384, 24576, 32768):
        if n <= size:
            return size
    raise ValueError(f"sequence of {n} tokens is beyond the reference's sizes")
