"""The plain reference of the ``phi4flash`` decoder-hybrid-decoder (SambaY
with differential attention, arXiv:2507.06607), written out from the
equations in ``jax.numpy``: float32, matmul precision "highest", a plain
loop over the layers, the state-space recurrence a ``lax.scan`` over
positions (no chunking, no cache, no kernel), attention over the whole
sequence in blocks of queries, differential attention as its four products,
every layer on every token. The interface is in
``perf/reference/__init__.py``.

Block ``l`` of ``n``: ``h = x + mixer_l(LN1(x))``, ``out = h + fc2(silu(g) *
u)`` with ``[g, u] = fc1(LN2(h))``; LayerNorm with weight and bias; after
the last block a LayerNorm and the tied embedding as head. The mixer:

- ``l < n/2`` even, and ``l = n/2`` — Mamba-1: ``[u, z] = in_proj(x)``; ``u
  <- silu(conv(u) + b)`` (causal, depthwise, the kernel's last tap on the
  current row); ``[dt_r, B, C] = x_proj(u)``; ``dt = softplus(dt_proj(dt_r) +
  b_dt)``; ``s_t = exp(dt_t A) s_{t-1} + (dt_t u_t) B_t^T``, ``y_t = s_t C_t
  + D u_t``, ``A = -exp(A_log)``; ``out_proj(y * silu(z))``. Layer ``n/2``'s
  ``y`` is the memory ``m``.
- ``l < n/2`` odd — differential attention, a query at ``t`` sees ``t -
  window + 1 .. t``. ``l = n/2 + 1`` — the same, full causal.
- ``l >= n/2 + 2`` even — gated memory unit ``out_proj(m * silu(in_proj(x)))``.
- ``l >= n/2 + 2`` odd — differential cross-attention: its own query, layer
  ``n/2 + 1``'s keys and values, full causal.

Differential attention: ``q`` as ``[pairs, 2, head]`` (``q1``, ``q2``), ``k``
and ``v`` likewise, query pair ``a`` on key-value pair ``a // rep``; ``Att =
softmax(q k^T / sqrt(head)) v``; ``o1 = [Att(q1, k1, v1) | Att(q1, k1,
v2)]``, ``o2 = [Att(q2, k2, v1) | Att(q2, k2, v2)]``; ``lam = exp(lq1 . lk1) -
exp(lq2 . lk2) + lambda_init(l)``, ``lambda_init(l) = 0.8 - 0.6 exp(-0.3
l)``; ``RMSNorm(o1 - lam o2) * (1 - lambda_init(l))``; ``out_proj``. No
positional encoding anywhere.

``gap`` is ``None``: the model has no router. The weights stay in the
engine's dtype on the device and are widened a layer at a time.

Negative controls: ``window_off`` (the window layers see everything),
``lambda_off`` (``lam = 0``), ``memory_stale`` (a gated memory unit reads
``m`` of the position before). Precision controls, each the nearest
precision below what the configuration states: ``weights_fp8`` (every
layer's matrices rounded to float8 e4m3's three mantissa bits; norms,
biases, the convolution, ``A``, ``D``, the lambdas and the embedding stay),
``state_bf16`` (the float32 recurrent state rounded to bfloat16 after every
position), ``kv_fp8`` (the full-attention layer's keys and values, which
every cross-attention layer reads, rounded to three mantissa bits).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perf import config as configs
from perf.reference import weights as common

VARIANTS = ("none", "window_off", "lambda_off", "memory_stale", "weights_fp8",
            "state_bf16", "kv_fp8")
# The matrices ``weights_fp8`` rounds.
MATRICES = ("fc1", "fc2", "in_proj", "x_proj", "dt_proj", "out_proj", "wq",
            "wkv", "wo", "gmu_in", "gmu_out")
_HI = jax.lax.Precision.HIGHEST
_PAD = 128
_QUERIES = 512  # queries a block of attention scores
_BLOCK = 24  # sequences whose hidden states, memory and keys are held at once


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=_HI,
                      preferred_element_type=jnp.float32)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32))


def _fp8(x):
    """Rounded to three mantissa bits (a convert pair would be folded away
    on the chip); the dtype stays."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)


def weights(cfg):
    from production_stack_tpu.models import registry

    return common.engine_params(
        registry.model_for(configs.program_model_config(cfg)),
        cfg.weights_seed, cfg.flag("--quantization"))


@functools.partial(jax.jit, static_argnames=("eps",))
def mlp(x, lw, *, eps):
    gu = _mm(_ln(x, lw["ln2_w"], lw["ln2_b"], eps), lw["fc1"])
    half = gu.shape[-1] // 2
    return x + _mm(jax.nn.silu(gu[:, :half]) * gu[:, half:], lw["fc2"])


@functools.partial(jax.jit, static_argnames=("eps", "state_bf16"))
def mamba(x, lw, *, eps, state_bf16):
    """x [T, D] float32 -> (x + the mixer's output, the scan's y [T, Di])."""
    T = x.shape[0]
    h = _ln(x, lw["ln1_w"], lw["ln1_b"], eps)
    K, d_inner = lw["conv_w"].shape
    n_state = lw["A_log"].shape[0]
    rank = lw["dt_proj"].shape[0]
    uz = _mm(h, lw["in_proj"])
    u, z = uz[:, :d_inner], uz[:, d_inner:]
    padded = jnp.concatenate([jnp.zeros((K - 1, d_inner), jnp.float32), u])
    u = jax.nn.silu(lw["conv_b"].astype(jnp.float32) + sum(
        padded[k:k + T] * lw["conv_w"][k].astype(jnp.float32)
        for k in range(K)))
    dbc = _mm(u, lw["x_proj"])
    dt_r, bm, cm = (dbc[:, :rank], dbc[:, rank:rank + n_state],
                    dbc[:, rank + n_state:])
    dt = jax.nn.softplus(_mm(dt_r, lw["dt_proj"]) + lw["dt_bias"])  # [T, Di]
    a = -jnp.exp(lw["A_log"]).T  # [Di, N]

    def step(s, inp):
        u_t, dt_t, b_t, c_t = inp
        s = (jnp.exp(dt_t[:, None] * a) * s
             + (dt_t * u_t)[:, None] * b_t[None, :])
        if state_bf16:  # a convert pair would be folded away on the chip
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.sum(s * c_t[None, :], axis=-1)

    _, y = jax.lax.scan(
        step, jnp.zeros((d_inner, n_state), jnp.float32), (u, dt, bm, cm))
    y = y + lw["D"] * u
    return x + _mm(y * jax.nn.silu(z), lw["out_proj"]), y


@functools.partial(jax.jit, static_argnames=("pairs", "eps", "kv_fp8"))
def keys_values(x, lw, *, pairs, eps, kv_fp8):
    """-> (k1, k2, v1, v2), each [T, pairs, head], of the layer's own input."""
    T = x.shape[0]
    h = _ln(x, lw["ln1_w"], lw["ln1_b"], eps)
    kv = _mm(h, lw["wkv"]) + lw["bkv"].astype(jnp.float32)
    if kv_fp8:
        kv = _fp8(kv)
    half = kv.shape[-1] // 2
    k = kv[:, :half].reshape(T, pairs, 2, -1)
    v = kv[:, half:].reshape(T, pairs, 2, -1)
    return k[:, :, 0], k[:, :, 1], v[:, :, 0], v[:, :, 1]


@functools.partial(jax.jit, static_argnames=(
    "q_pairs", "window", "eps", "lambda_off"))
def diff_attention(x, lw, kv, init, *, q_pairs, window, eps, lambda_off):
    """x [T, D] -> x + the mixer's output. ``kv`` = (k1, k2, v1, v2) of this
    layer or of the one it reads; ``init`` the layer's ``lambda_init``;
    ``window`` 0 = full causal."""
    T = x.shape[0]
    h = _ln(x, lw["ln1_w"], lw["ln1_b"], eps)
    q = (_mm(h, lw["wq"]) + lw["bq"].astype(jnp.float32)).reshape(
        T, q_pairs, 2, -1)
    head = q.shape[-1]
    rep = q_pairs // kv[0].shape[1]
    k1, k2, v1, v2 = (jnp.repeat(a, rep, axis=1) for a in kv)
    lam = 0.0 if lambda_off else (
        jnp.exp(jnp.sum(lw["lambda_q1"] * lw["lambda_k1"]))
        - jnp.exp(jnp.sum(lw["lambda_q2"] * lw["lambda_k2"])) + init)
    keys = jnp.arange(T)

    def att(qb, k, at):
        """Probabilities [pairs, block, T] of the queries from ``at`` on."""
        s = jnp.einsum("thd,shd->hts", qb, k, precision=_HI,
                       preferred_element_type=jnp.float32) / math.sqrt(head)
        t = at + jnp.arange(qb.shape[0])
        see = keys[None, :] <= t[:, None]
        if window:
            see = see & (keys[None, :] > t[:, None] - window)
        return jax.nn.softmax(jnp.where(see[None], s, -jnp.inf), axis=-1)

    def pv(p, v):
        return jnp.einsum("hts,shd->thd", p, v, precision=_HI,
                          preferred_element_type=jnp.float32)

    outs = []
    for at in range(0, T, _QUERIES):
        p1 = att(q[at:at + _QUERIES, :, 0], k1, at)
        p2 = att(q[at:at + _QUERIES, :, 1], k2, at)
        o1 = jnp.concatenate([pv(p1, v1), pv(p1, v2)], axis=-1)
        o2 = jnp.concatenate([pv(p2, v1), pv(p2, v2)], axis=-1)
        d = o1 - lam * o2  # [block, pairs, 2 head]
        d = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + eps)
        outs.append(d * lw["subln"].astype(jnp.float32) * (1.0 - init))
    o = jnp.concatenate(outs).reshape(T, -1)
    return x + _mm(o, lw["wo"]) + lw["bo"].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "stale"))
def gated_memory(x, lw, m, *, eps, stale):
    if stale:  # the memory of the position before
        m = jnp.concatenate([jnp.zeros_like(m[:1]), m[:-1]])
    h = _ln(x, lw["ln1_w"], lw["ln1_b"], eps)
    return x + _mm(m * jax.nn.silu(_mm(h, lw["gmu_in"])), lw["gmu_out"])


@functools.partial(jax.jit, static_argnames=("eps",))
def head_logprobs(x_rows, w, b, embed, *, eps):
    logits = jnp.einsum(
        "td,vd->tv", _ln(x_rows, w, b, eps), embed, precision=_HI,
        preferred_element_type=jnp.float32)
    return jax.nn.log_softmax(logits, axis=-1)


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def layer_map(n: int) -> list:
    """``(kind, group, index in the group's stack or None)`` of each block."""
    half = n // 2
    out = []
    for l in range(n):
        if l < half:
            out.append(("mamba", "self_mamba", l // 2) if l % 2 == 0
                       else ("window", "self_attn", l // 2))
        elif l == half:
            out.append(("mamba", "mid_mamba", None))
        elif l == half + 1:
            out.append(("full", "mid_attn", None))
        else:
            i = (l - half - 2) // 2
            out.append(("gmu", "gmu", i) if l % 2 == 0 else ("cross", "cross", i))
    return out


def teacher_force(cfg, params, sequences, variant: str) -> list:
    """In blocks of ``_BLOCK`` sequences: a block's hidden states, its memory
    ``m`` and the full-attention layer's keys and values are all held while
    the layers are walked once (92 MB a 3,000-token sequence)."""
    return [r for at in range(0, len(sequences), _BLOCK)
            for r in _block(cfg, params, sequences[at:at + _BLOCK], variant)]


def _block(cfg, params, sequences, variant: str) -> list:
    hf = cfg.hf
    eps = float(hf.get("layer_norm_eps", 1e-5))
    n = int(hf["num_hidden_layers"])
    q_pairs = hf["num_attention_heads"] // 2
    kv_pairs = hf.get("num_key_value_heads", hf["num_attention_heads"]) // 2
    window = 0 if variant == "window_off" else int(hf["sliding_window"])
    xs = []
    for s in sequences:
        padded = -(-len(s["tokens"]) // _PAD) * _PAD
        ids = np.zeros(padded, np.int32)
        ids[: len(s["tokens"])] = s["tokens"]
        xs.append(common.embed_rows(params, jnp.asarray(ids)))
    memory = [None] * len(sequences)
    shared_kv = [None] * len(sequences)
    for l, (kind, group, i) in enumerate(layer_map(n)):
        lw = params["layers"][group]
        if i is not None:
            lw = {k: v[i] for k, v in lw.items()}
        if variant == "weights_fp8":
            lw = {k: _fp8(v) if k in MATRICES else v for k, v in lw.items()}
        for j in range(len(sequences)):
            x = xs[j]
            if kind == "mamba":
                x, y = mamba(x, lw, eps=eps, state_bf16=variant == "state_bf16")
                if group == "mid_mamba":
                    memory[j] = y
            elif kind in ("window", "full"):
                kv = keys_values(
                    x, lw, pairs=kv_pairs, eps=eps,
                    kv_fp8=kind == "full" and variant == "kv_fp8")
                if kind == "full":
                    shared_kv[j] = kv
                x = diff_attention(
                    x, lw, kv, lambda_init(l), q_pairs=q_pairs,
                    window=window if kind == "window" else 0, eps=eps,
                    lambda_off=variant == "lambda_off")
            elif kind == "gmu":
                x = gated_memory(x, lw, memory[j], eps=eps,
                                 stale=variant == "memory_stale")
            else:
                x = diff_attention(
                    x, lw, shared_kv[j], lambda_init(l), q_pairs=q_pairs,
                    window=0, eps=eps, lambda_off=variant == "lambda_off")
            xs[j] = mlp(x, lw, eps=eps)
        del lw
    name = "lm_head" if "lm_head" in params else "embed"
    embed = params[name].astype(jnp.float32)
    out = []
    for j, s in enumerate(sequences):
        n_prompt, n_gen = s["n_prompt"], len(s["want"])
        rows = jnp.arange(n_prompt - 1, n_prompt - 1 + n_gen)
        lps = np.asarray(head_logprobs(
            xs[j][rows], params["final_norm"], params["final_norm_b"], embed,
            eps=eps))
        out.append((lps, None))
    return out
