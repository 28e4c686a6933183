"""The plain reference of the ``qwen3_next`` gated-delta-rule hybrid, written
out from the published equations (the ``transformers`` modelling file
``modeling_qwen3_next.py``; Gated Delta Networks, arXiv:2412.06464) in
``jax.numpy``: float32, matmul precision "highest", the delta rule as a plain
``lax.scan`` over positions (no chunking, no cache, no kernel), attention
over the whole sequence, the expert block as a dense loop over the experts
the configuration **holds** with the router over all the published experts.
The interface is in ``perf/reference/__init__.py``.

Layer ``i`` is full attention when ``(i + 1) % full_attention_interval ==
0``, Gated DeltaNet otherwise; every layer's MLP is the expert block. ``N(x;
w) = x / sqrt(mean(x^2) + eps) (1 + w)`` (zero-centred weight); ``h = x +
Mixer(N(x; w1))``, ``y = h + MoE(N(h; w2))``; a final ``N`` before the head.

**Gated attention**: ``q_proj`` gives a query and an output gate a head;
``q <- N_head(q)``, ``k <- N_head(k)``; rotary on the first
``partial_rotary_factor`` of the lanes (half-split pairing), the rest
untouched; causal softmax attention at ``head_dim^-1/2``, grouped; ``out =
o_proj(attn * sigmoid(gate))``.

**Gated DeltaNet**: ``[q | k | v]``, ``z`` and ``[b | a]`` are projections
of the input; ``[q | k | v]`` goes through a depthwise causal convolution
(no bias) and ``silu``; ``q``, ``k`` are repeated to the value heads and
L2-normalised a head (``x / sqrt(sum x^2 + 1e-6)``), ``q <- q
key_dim^-1/2``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
dt_bias)``. With ``S [key, value]`` zero at the start::

    S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S <- S + k_t u^T;  o_t = S^T q_t

``o <- o / sqrt(mean(o^2) + eps) w_o silu(z)`` a value head (``w_o``
plain), then ``out_proj``.

**Expert block**: ``p = softmax(W_r x)`` over all the published experts,
the top ``num_experts_per_tok``, renormalised over the chosen; expert ``e``:
``down_e(silu(gate_e x) up_e x)``; plus ``sigmoid(x . w_sg) down_s(silu(gate_s
x) up_s x)``. The sum runs over the held experts only (``num_experts`` from
``ep_share.first`` on): one rank's share.

``gap`` is the smallest distance between the k-th and (k+1)-th router logit
over the layers. The weights stay in the engine's dtype on the device and
are widened a layer (the expert banks: an expert) at a time.

Negative controls, each one piece of the mathematics broken: ``decay_off``
(``g = 0``), ``beta_one``, ``qk_l2norm_off``, ``out_gate_off`` (neither
``sigmoid(gate)`` nor ``silu(z)``), ``rotary_full`` (every lane rotated),
``norm_plain`` (``w`` for ``1 + w``), ``sigmoid_router``,
``shared_gate_off``. Precision controls, each the nearest precision below
what the configuration states: ``state_bf16`` (the float32 state rounded to
bfloat16 after every position), ``weights_fp8`` (every projection and expert
matrix, bfloat16 as served, rounded to float8 e4m3's three mantissa bits;
router, norms, convolution, embedding and head stay).
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

VARIANTS = ("none", "decay_off", "beta_one", "qk_l2norm_off", "out_gate_off",
            "rotary_full", "norm_plain", "sigmoid_router", "shared_gate_off",
            "state_bf16", "weights_fp8")
# The matrices ``weights_fp8`` rounds.
MATRICES = ("w_qkv", "w_z", "w_ba", "w_out", "wq", "wq_gate", "wk", "wv",
            "wo", "w1", "w2", "w_shared_gate", "w_shared_up", "w_shared_down")
_HI = jax.lax.Precision.HIGHEST
_PAD = 128
_BLOCK = 24


def _mm(a, b):
    return jnp.matmul(a, b.astype(jnp.float32), precision=_HI,
                      preferred_element_type=jnp.float32)


def _norm(x, w, eps, plain=False):
    w = w.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        w if plain else 1.0 + w)


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
    "key_heads", "value_heads", "key_dim", "eps", "variant"))
def delta_net(x, lw, *, key_heads, value_heads, key_dim, eps, variant):
    """x [T, D] float32 -> the mixer's output [T, D]."""
    T = x.shape[0]
    h = _norm(x, lw["norm"], eps, variant == "norm_plain")
    qkv, z, ba = _mm(h, lw["w_qkv"]), _mm(h, lw["w_z"]), _mm(h, lw["w_ba"])
    K, conv_dim = lw["conv_w"].shape
    padded = jnp.concatenate([jnp.zeros((K - 1, conv_dim), jnp.float32), qkv])
    qkv = jax.nn.silu(sum(
        padded[j:j + T] * lw["conv_w"][j].astype(jnp.float32)
        for j in range(K)))
    kd = key_heads * key_dim
    q = qkv[:, :kd].reshape(T, key_heads, key_dim)
    k = qkv[:, kd:2 * kd].reshape(T, key_heads, key_dim)
    v = qkv[:, 2 * kd:].reshape(T, value_heads, -1)
    rep = value_heads // key_heads
    q, k = jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1)
    if variant != "qk_l2norm_off":
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q * key_dim ** -0.5
    b, a = ba[:, :value_heads], ba[:, value_heads:]
    beta = jnp.ones_like(b) if variant == "beta_one" else jax.nn.sigmoid(b)
    g = -jnp.exp(lw["A_log"]) * jax.nn.softplus(a + lw["dt_bias"])
    if variant == "decay_off":
        g = jnp.zeros_like(g)

    def step(s, inp):  # s [H, K, V]
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[:, None, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t, precision=_HI))
        s = s + k_t[:, :, None] * u[:, None, :]
        if variant == "state_bf16":  # a convert pair would be folded away
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=_HI)

    _, o = jax.lax.scan(
        step, jnp.zeros((value_heads, key_dim, v.shape[-1]), jnp.float32),
        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    o = (o * lw["o_norm"].astype(jnp.float32)).reshape(T, -1)
    if variant != "out_gate_off":
        o = o * jax.nn.silu(z)
    return _mm(o, lw["w_out"])


@functools.partial(jax.jit, static_argnames=(
    "n_heads", "n_kv", "rotary", "eps", "variant"))
def attention(x, cos, sin, lw, *, n_heads, n_kv, rotary, eps, variant):
    """x [T, D] float32 -> the mixer's output [T, D]. ``cos``/``sin`` [T,
    rotary / 2]."""
    T = x.shape[0]
    plain = variant == "norm_plain"
    h = _norm(x, lw["norm"], eps, plain)
    q = _mm(h, lw["wq"]).reshape(T, n_heads, -1)
    gate = _mm(h, lw["wq_gate"])
    k = _mm(h, lw["wk"]).reshape(T, n_kv, -1)
    v = _mm(h, lw["wv"]).reshape(T, n_kv, -1)
    q, k = _norm(q, lw["q_norm"], eps, plain), _norm(k, lw["k_norm"], eps, plain)

    def rope(t):  # the first ``rotary`` lanes, half-split pairing
        half = rotary // 2
        t1, t2, rest = t[..., :half], t[..., half:rotary], t[..., rotary:]
        c, s = cos[:, None, :], sin[:, None, :]
        return jnp.concatenate(
            [t1 * c - t2 * s, t2 * c + t1 * s, rest], axis=-1)

    q, k = rope(q), rope(k)
    rep = n_heads // n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k, precision=_HI,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(q.shape[-1])
    pos = jnp.arange(T)
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    out = jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, -1), v,
                     precision=_HI, preferred_element_type=jnp.float32)
    out = out.reshape(T, -1)
    if variant != "out_gate_off":
        out = out * jax.nn.sigmoid(gate)
    return _mm(out, lw["wo"])


@functools.partial(jax.jit, static_argnames=(
    "top_k", "first", "renorm", "eps", "variant"))
def moe(x, lw, *, top_k, first, renorm, eps, variant):
    """-> (out [T, D], gap [T])."""
    u = _norm(x, lw["norm"], eps, variant == "norm_plain")
    logits = _mm(u, lw["w_router"])  # [T, all experts]
    s = (jax.nn.sigmoid(logits) if variant == "sigmoid_router"
         else jax.nn.softmax(logits, -1))
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

    routed = jax.lax.fori_loop(0, held, one, jnp.zeros_like(u))
    shared_out = _mm(
        jax.nn.silu(_mm(u, lw["w_shared_gate"])) * _mm(u, lw["w_shared_up"]),
        lw["w_shared_down"])
    if variant != "shared_gate_off":
        shared_out = shared_out * jax.nn.sigmoid(
            _mm(u, lw["w_shared_sig"][:, None]))
    return routed + shared_out, gap


def teacher_force(cfg, params, sequences, variant: str) -> list:
    """In blocks of ``_BLOCK`` sequences: a block's hidden states are all
    held while the layers are walked once (18 MB a 2,176-token sequence)."""
    return [r for at in range(0, len(sequences), _BLOCK)
            for r in _block(cfg, params, sequences[at:at + _BLOCK], variant)]


def _block(cfg, params, sequences, variant: str) -> list:
    hf = cfg.hf
    eps = float(hf.get("rms_norm_eps", 1e-6))
    interval = int(hf.get("full_attention_interval", 4))
    n_heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // n_heads
    rotary = head_dim if variant == "rotary_full" else int(
        head_dim * float(hf.get("partial_rotary_factor", 0.25)))
    share = hf.get("ep_share") or {}
    all_experts = int((cfg.raw.get("published") or {}).get(
        "num_experts", hf["num_experts"]))
    layers = params["layers"]
    router_width = layers["moe"]["w_router"].shape[-1]
    if router_width != all_experts:
        raise ValueError(
            f"the served router scores {router_width} experts, the "
            f"configuration publishes {all_experts}")
    xs, gaps, ropes = [], [], {}
    for s in sequences:
        padded = -(-len(s["tokens"]) // _PAD) * _PAD
        ids = np.zeros(padded, np.int32)
        ids[: len(s["tokens"])] = s["tokens"]
        xs.append(common.embed_rows(params, jnp.asarray(ids)))
        gaps.append(np.full(padded, np.inf, np.float32))
        if padded not in ropes:
            ropes[padded] = shared.rope_tables(
                padded, rotary, float(hf.get("rope_theta", 1e7)))

    def rounded(lw):
        if variant != "weights_fp8":
            return lw
        return {k: _fp8(v) if k in MATRICES else v for k, v in lw.items()}

    for li in range(hf["num_hidden_layers"]):
        p, j = divmod(li, interval)
        if j < interval - 1:
            lw = rounded({k: v[p * (interval - 1) + j]
                          for k, v in layers["delta"].items()})
            mixer = functools.partial(
                delta_net, lw=lw, key_heads=hf["linear_num_key_heads"],
                value_heads=hf["linear_num_value_heads"],
                key_dim=hf["linear_key_head_dim"], eps=eps, variant=variant)
        else:
            lw = rounded({k: v[p] for k, v in layers["attn"].items()})
            mixer = lambda x, lw=lw: attention(  # noqa: E731
                x, *ropes[x.shape[0]], lw, n_heads=n_heads,
                n_kv=hf.get("num_key_value_heads", n_heads), rotary=rotary,
                eps=eps, variant=variant)
        mw = rounded({k: v[li] for k, v in layers["moe"].items()})
        for i in range(len(sequences)):
            h = xs[i] + mixer(xs[i])
            out, gap = moe(
                h, mw, top_k=hf["num_experts_per_tok"],
                first=int(share.get("first", 0)),
                renorm=bool(hf.get("norm_topk_prob", True)), eps=eps,
                variant=variant)
            gaps[i] = np.minimum(gaps[i], np.asarray(gap))
            xs[i] = h + out
        del lw, mw
    final_norm, lm_head = common.head_weights(params)
    if variant != "norm_plain":
        final_norm = 1.0 + final_norm
    out = []
    for i, s in enumerate(sequences):
        n_prompt, n_gen = s["n_prompt"], len(s["want"])
        rows = jnp.arange(n_prompt - 1, n_prompt - 1 + n_gen)
        lps = np.asarray(shared.head_logprobs(
            xs[i][rows], final_norm, lm_head, eps=eps))
        out.append((lps, gaps[i][n_prompt - 1: n_prompt - 1 + n_gen]))
    return out
