"""The plain reference of the ``nemotron_h`` hybrid (Mamba-2 / attention /
LatentMoE blocks behind one pre-norm residual each, in the order of
``hybrid_override_pattern``), written out from the published equations in
``jax.numpy``: float32, matmul precision "highest", the state-space
recurrence as a plain ``lax.scan`` over positions (no chunking, no cache, no
kernel), attention over the whole sequence, the expert layer as a dense loop
over the experts the configuration **holds** with the router over all the
published experts. The interface is in ``perf/reference/__init__.py``.

An ``M`` block: ``[z | xBC | dt] = W_in u``; ``xBC <- silu(conv(xBC) + b)``
(causal, depthwise, the kernel's last tap on the current row); ``[x | B | C]
= xBC``; ``dt <- softplus(dt + dt_bias)``; ``S_t = exp(dt_t A) S_{t-1} +
dt_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``, ``A = -exp(A_log)``; ``y <- y
silu(z)``, RMS-normalised within each of the ``n_groups`` groups of
channels, times the norm weight; ``W_out y``. A ``*`` block: causal
grouped-query attention, **no rotary embedding**. An ``E`` block: ``s =
sigmoid(W_r u)``; the ``top_k`` largest of ``s + b`` are chosen; their
weights are ``s`` (not ``s + b``) over their sum, times
``routed_scaling_factor``; ``out = W_up sum_e w_e W2_e relu(W1_e l)^2 +
W2_s relu(W1_s u)^2`` with ``l = W_down u``. The sum runs over the held
experts only (``n_routed_experts`` from ``ep_share.first`` on, of the
``published.n_routed_experts`` the router scores): one rank's share.

``gap`` is the smallest distance between the k-th and (k+1)-th of ``s + b``
over the ``E`` layers. The weights stay in the engine's dtype on the device
and are widened a layer (the expert banks: an expert) at a time.

Negative controls: ``softmax_router`` (scores by softmax over the experts),
``routed_scale_1`` (routed sum not scaled), ``norm_ungrouped`` (the gated
norm over all channels at once). Precision controls, each the nearest
precision below what the configuration states: ``state_bf16`` (the float32
recurrent state rounded to bfloat16 after every position), ``weights_fp8``
(every projection and expert matrix, bfloat16 as served, rounded to float8
e4m3's three mantissa bits; the exponent is left its range, as a scale a
channel would leave it; router, norms, convolution, embedding and head
stay), ``kv_fp8`` (keys and values rounded so before attention).
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

VARIANTS = ("none", "softmax_router", "routed_scale_1", "norm_ungrouped",
            "state_bf16", "weights_fp8", "kv_fp8")
# The matrices ``weights_fp8`` rounds.
MATRICES = ("w_in", "w_out", "wq", "wk", "wv", "wo", "w_latent_down",
            "w_latent_up", "w1", "w2", "w_shared_up", "w_shared_down")
KIND = {"M": "mamba", "*": "attn", "E": "moe"}
_HI = jax.lax.Precision.HIGHEST
_PAD = 128
_BLOCK = 72


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=_HI,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(jnp.float32)


def _fp8(x):
    """Rounded to three mantissa bits (a convert pair would be folded away
    on the chip); the dtype stays."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)


def weights(cfg):
    from production_stack_tpu.models import registry

    return common.engine_params(
        registry.model_for(configs.program_model_config(cfg)),
        cfg.weights_seed, cfg.flag("--quantization"))


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "state", "eps", "ungrouped", "state_bf16"))
def mamba(x, lw, *, heads, groups, state, eps, ungrouped, state_bf16):
    """x [T, D] float32 -> the mixer's output [T, D]."""
    T = x.shape[0]
    h = _rms(x, lw["norm"], eps)
    K, conv_dim = lw["conv_w"].shape
    d_inner = conv_dim - 2 * groups * state
    pd = d_inner // heads
    proj = _mm(h, lw["w_in"])
    z, xbc, dt = (proj[:, :d_inner], proj[:, d_inner:d_inner + conv_dim],
                  proj[:, d_inner + conv_dim:])
    padded = jnp.concatenate([jnp.zeros((K - 1, conv_dim), jnp.float32), xbc])
    conv = lw["conv_b"].astype(jnp.float32) + sum(
        padded[k:k + T] * lw["conv_w"][k].astype(jnp.float32) for k in range(K))
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_inner].reshape(T, heads, pd)
    bm = xbc[:, d_inner:d_inner + groups * state].reshape(T, groups, state)
    cm = xbc[:, d_inner + groups * state:].reshape(T, groups, state)
    rep = heads // groups
    bm, cm = jnp.repeat(bm, rep, axis=1), jnp.repeat(cm, rep, axis=1)  # [T, H, N]
    dt = jax.nn.softplus(dt + lw["dt_bias"])  # [T, H]
    a = -jnp.exp(lw["A_log"])

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if state_bf16:  # a convert pair would be folded away on the chip
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(
        step, jnp.zeros((heads, pd, state), jnp.float32), (xs, bm, cm, dt))
    y = y + lw["D"][:, None] * xs
    y = y.reshape(T, d_inner) * jax.nn.silu(z)
    g = 1 if ungrouped else groups
    yg = y.reshape(T, g, d_inner // g)
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True) + eps)
    y = yg.reshape(T, d_inner) * lw["gate_norm"].astype(jnp.float32)
    return _mm(y, lw["w_out"])


@functools.partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps", "kv_fp8"))
def attention(x, lw, *, n_heads, n_kv, eps, kv_fp8):
    T = x.shape[0]
    h = _rms(x, lw["norm"], eps)
    q = _mm(h, lw["wq"]).reshape(T, n_heads, -1)
    k = _mm(h, lw["wk"]).reshape(T, n_kv, -1)
    v = _mm(h, lw["wv"]).reshape(T, n_kv, -1)
    if kv_fp8:
        k, v = _fp8(k), _fp8(v)
    rep = n_heads // n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k, precision=_HI,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    pos = jnp.arange(T)
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v,
                     precision=_HI, preferred_element_type=jnp.float32)
    return _mm(out.reshape(T, -1), lw["wo"])


@functools.partial(jax.jit, static_argnames=(
    "top_k", "first", "scale", "renorm", "eps", "softmax"))
def moe(x, lw, *, top_k, first, scale, renorm, eps, softmax):
    """-> (out [T, D], gap [T])."""
    u = _rms(x, lw["norm"], eps)
    logits = _mm(u, lw["w_router"])  # [T, all experts]
    s = jax.nn.softmax(logits, -1) if softmax else jax.nn.sigmoid(logits)
    choice = s + lw["router_bias"]
    ordered = jnp.sort(choice, axis=-1)[:, ::-1]
    gap = ordered[:, top_k - 1] - ordered[:, top_k]
    _, ids = jax.lax.top_k(choice, top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if renorm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * scale
    lat = _mm(u, lw["w_latent_down"])
    held = lw["w1"].shape[0]

    def one(e, acc):
        weight = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)  # [T]
        a = _mm(lat, jax.lax.dynamic_index_in_dim(lw["w1"], e, keepdims=False))
        y = _mm(jnp.square(jax.nn.relu(a)),
                jax.lax.dynamic_index_in_dim(lw["w2"], e, keepdims=False))
        return acc + weight[:, None] * y

    routed = jax.lax.fori_loop(0, held, one, jnp.zeros_like(lat))
    shared_out = _mm(jnp.square(jax.nn.relu(_mm(u, lw["w_shared_up"]))),
                     lw["w_shared_down"])
    return _mm(routed, lw["w_latent_up"]) + shared_out, gap


def teacher_force(cfg, params, sequences, variant: str) -> list:
    """In blocks of ``_BLOCK`` sequences: a block's hidden states are all
    held while the layers are walked once (19 MB a 1,024-token sequence)."""
    return [r for at in range(0, len(sequences), _BLOCK)
            for r in _block(cfg, params, sequences[at:at + _BLOCK], variant)]


def _block(cfg, params, sequences, variant: str) -> list:
    hf = cfg.hf
    pattern = hf["hybrid_override_pattern"]
    eps = float(hf.get("layer_norm_epsilon", hf.get("rms_norm_eps", 1e-5)))
    n_heads = hf["num_attention_heads"]
    share = hf.get("ep_share") or {}
    all_experts = int((cfg.raw.get("published") or {}).get(
        "n_routed_experts", hf["n_routed_experts"]))
    router_width = params["layers"]["moe"]["w_router"].shape[-1]
    if router_width != all_experts:
        raise ValueError(
            f"the served router scores {router_width} experts, the "
            f"configuration publishes {all_experts}")
    xs, gaps = [], []
    for s in sequences:
        padded = -(-len(s["tokens"]) // _PAD) * _PAD
        ids = np.zeros(padded, np.int32)
        ids[: len(s["tokens"])] = s["tokens"]
        xs.append(common.embed_rows(params, jnp.asarray(ids)))
        gaps.append(np.full(padded, np.inf, np.float32))
    seen = {}
    for c in pattern:
        kind = KIND[c]
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        lw = {k: v[i] for k, v in params["layers"][kind].items()}
        if variant == "weights_fp8":
            lw = {k: _fp8(v) if k in MATRICES else v for k, v in lw.items()}
        for j in range(len(sequences)):
            if kind == "mamba":
                out = mamba(
                    xs[j], lw, heads=hf["mamba_num_heads"],
                    groups=hf["n_groups"], state=hf["ssm_state_size"], eps=eps,
                    ungrouped=variant == "norm_ungrouped",
                    state_bf16=variant == "state_bf16")
            elif kind == "attn":
                out = attention(
                    xs[j], lw, n_heads=n_heads,
                    n_kv=hf.get("num_key_value_heads", n_heads), eps=eps,
                    kv_fp8=variant == "kv_fp8")
            else:
                out, gap = moe(
                    xs[j], lw, top_k=hf["num_experts_per_tok"],
                    first=int(share.get("first", 0)),
                    scale=1.0 if variant == "routed_scale_1"
                    else float(hf.get("routed_scaling_factor", 1.0)),
                    renorm=bool(hf.get("norm_topk_prob", True)), eps=eps,
                    softmax=variant == "softmax_router")
                gaps[j] = np.minimum(gaps[j], np.asarray(gap))
            xs[j] = xs[j] + out
        del lw
    final_norm, lm_head = common.head_weights(params)
    out = []
    for j, s in enumerate(sequences):
        n_prompt, n_gen = s["n_prompt"], len(s["want"])
        rows = jnp.arange(n_prompt - 1, n_prompt - 1 + n_gen)
        lps = np.asarray(shared.head_logprobs(
            xs[j][rows], final_norm, lm_head, eps=eps))
        out.append((lps, gaps[j][n_prompt - 1: n_prompt - 1 + n_gen]))
    return out
