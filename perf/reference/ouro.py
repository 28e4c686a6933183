"""The looped-stack reference (``model_type: ouro``; the "LoopLM" of *Scaling
Latent Reasoning via Looped Language Models*, ByteDance) as ``run.py`` asks
for one (the interface is in ``perf/reference/__init__.py``): float32
``jax.numpy`` at matmul precision ``highest`` over the weights the engine
serves, every sequence whole, no kernels, no cache, no batching, nothing of
the program's forward pass.

The equations (L layers, T passes = ``total_ut_steps``):

- ``h = E[tokens]``. For pass ``t = 0..T-1``: for layer ``l = 0..L-1``:
  ``h <- Block_l(h)``, the keys and values of pass ``t`` layer ``l`` being
  those a cache would hold in **slot ``t x L + l``**; after layer ``L-1``,
  ``h <- RMSNorm_final(h)``, and that normalised ``h`` is both what the next
  pass starts from and pass ``t``'s output ``h_t``. Every pass uses the same
  L layers of weights and the same final norm; only the slots differ:
  ``T x L`` layers of keys and values under ``L`` layers of weights.
- ``Block_l(x)``: ``a = Attn(RMSNorm_in(x))``; ``x <- x + RMSNorm_in2(a)``;
  ``m = SwiGLU(RMSNorm_post(x))``; ``x <- x + RMSNorm_post2(m)``: four norms
  a layer, the output of each sub-block normalised before it joins the
  residual. ``Attn``: ``q``, ``k``, ``v``, ``o`` without bias, no per-head
  norm, rotate-half rotary embedding over all lanes of a head at
  ``rope_theta``, causal, scale ``head_dim^-1/2``.
- Exit gate: ``lambda_t = sigmoid(w_g . h_t + b_g)``, ``p_t = lambda_t x
  prod_{s<t}(1 - lambda_s)`` for ``t < T-1``, ``p_{T-1} = prod_{s<T-1}(1 -
  lambda_s)``; a token leaves at the first pass whose cumulative ``sum_{s<=t}
  p_s >= q``. At the served ``q = early_exit_threshold = 1`` that is the last
  pass unless a sigmoid rounds to 1: the logits are ``W_head . h_{T-1}``.
  :func:`teacher_force` computes the gate at every compared position and
  **raises** if one would leave earlier (``LAST["min_stay"]`` keeps the
  smallest ``1 - CDF_{T-2}`` it saw).

Negative controls: ``one_pass`` (T = 1); ``shared_kv_last`` (the paper's
cache-sharing approximation: the prompt is prefilled exactly, then every
pass of a decode step reads the *last* pass's keys and values of the earlier
positions: a quarter of the cache); ``slot_by_layer`` (what a cache of L
layers would hold: every step, prefill chunks of the deployment's token
budget included, reads the last pass's keys and values of what earlier
steps wrote, its own pass's for its own positions); ``no_loop_norm`` (the
final norm only at the end, not between passes); ``no_post_norms`` (the
sub-blocks' output norms skipped); ``weights_fp8`` (every projection matrix
rounded to float8 e4m3's three mantissa bits: the nearest precision below
the stated bf16; norms, embedding and head stay).
"""

from __future__ import annotations

import functools
import math
import sys
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from perf import config as configs
from perf.reference import model as ref
from perf.reference import weights as common

VARIANTS = ("none", "one_pass", "shared_kv_last", "slot_by_layer",
            "no_loop_norm", "no_post_norms", "weights_fp8")
# The matrices ``weights_fp8`` rounds.
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
_HI = jax.lax.Precision.HIGHEST
_Q_BLOCK = 512
# The smallest 1 - CDF_{T-2} of the last call that computed the gate.
LAST = {"min_stay": None}


class Hyper(NamedTuple):
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    passes: int
    loop_norm: bool
    post_norms: bool
    fp8: bool


def weights(cfg):
    from production_stack_tpu.models import llama as prog

    return common.engine_params(
        prog.Llama(configs.program_model_config(cfg)), cfg.weights_seed,
        cfg.flag("--quantization"))


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI, preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, cos, sin):
    # x [T, H, hd]; rotate-half: (x1, x2) -> (x1 c - x2 s, x2 c + x1 s)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _widen(sl, fp8: bool):
    """One layer's slices of the stored leaves, in float32."""
    out = {}
    for name, leaf in sl.items():
        if name.endswith(("_qs", "_q4s")):
            continue
        if name in MATRICES:
            w = common.matmul_leaf(leaf, sl.get(name + "_q4s"),
                                   sl.get(name + "_qs"))
            out[name] = _fp8(w) if fp8 else w
        else:
            out[name] = leaf.astype(jnp.float32)
    return out


def _attend(q, k, v, q_pos, n_rep: int):
    """Causal attention of queries at positions ``q_pos`` over keys at
    positions 0..S-1, queries in blocks. q [T, H, hd], k/v [S, KH, hd]."""
    T, H, hd = q.shape
    scale = 1.0 / math.sqrt(hd)
    kq, vq = jnp.repeat(k, n_rep, axis=1), jnp.repeat(v, n_rep, axis=1)
    key_pos = jnp.arange(k.shape[0])
    outs = []
    for at in range(0, T, _Q_BLOCK):
        qb = q[at:at + _Q_BLOCK]
        scores = jnp.einsum("thd,shd->hts", qb, kq, precision=_HI,
                            preferred_element_type=jnp.float32) * scale
        mask = key_pos[None, :] <= q_pos[at:at + _Q_BLOCK, None]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hts,shd->thd", probs, vq, precision=_HI,
                               preferred_element_type=jnp.float32
                               ).reshape(qb.shape[0], H * hd))
    return jnp.concatenate(outs, axis=0)


@functools.partial(jax.jit, static_argnames=("hp",))
def _segment(layers, final_norm, x, cos, sin, k_last, v_last, start, *, hp):
    """All passes over one block of positions ``start .. start + Tb - 1``
    (``x [Tb, D]`` their embeddings, ``cos`` / ``sin`` their rotary rows).

    ``k_last`` / ``v_last`` are None for the reference itself: the block is
    the whole sequence and every pass reads its own keys and values. For the
    cache-sharing controls they are ``[L, S, KH x hd]``, the **last** pass's
    keys and values of the positions below ``start``: every pass reads those
    for the earlier positions and its own for the block's, and the last
    pass's are written back at the block's rows.

    -> (``h_t`` of every pass ``[passes, Tb, D]``, ``k_last``, ``v_last``)."""
    Tb = x.shape[0]
    H, KH, hd, eps = hp.heads, hp.kv_heads, hp.head_dim, hp.eps
    q_pos = start + jnp.arange(Tb)

    def block(emit, x, sl):
        lw, kl, vl = sl
        w = _widen(lw, hp.fp8)
        h = _rms(x, w["attn_norm"], eps)
        q = _rope(_mm(h, w["wq"]).reshape(Tb, H, hd), cos, sin)
        k = _rope(_mm(h, w["wk"]).reshape(Tb, KH, hd), cos, sin)
        v = _mm(h, w["wv"]).reshape(Tb, KH, hd)
        if kl is None:
            kf, vf = k, v
        else:
            kf = jax.lax.dynamic_update_slice(
                kl, k.reshape(Tb, KH * hd), (start, 0))
            vf = jax.lax.dynamic_update_slice(
                vl, v.reshape(Tb, KH * hd), (start, 0))
        a = _mm(_attend(q, kf.reshape(-1, KH, hd), vf.reshape(-1, KH, hd),
                        q_pos, H // KH), w["wo"])
        x = x + (_rms(a, w["post_attn_norm"], eps) if hp.post_norms else a)
        h = _rms(x, w["mlp_norm"], eps)
        m = _mm(jax.nn.silu(_mm(h, w["w_gate"])) * _mm(h, w["w_up"]),
                w["w_down"])
        x = x + (_rms(m, w["post_mlp_norm"], eps) if hp.post_norms else m)
        return x, ((kf, vf) if emit and kl is not None else None)

    outs, written = [], None
    for t in range(hp.passes):
        last = t == hp.passes - 1  # the pass whose keys and values stay
        x, written = jax.lax.scan(
            functools.partial(block, last), x, (layers, k_last, v_last))
        if hp.loop_norm or last:
            x = _rms(x, final_norm, eps)
        outs.append(x)
    k_new, v_new = written if written is not None else (None, None)
    return jnp.stack(outs), k_new, v_new


@jax.jit
def _head(h_rows, lm_head):
    """log-softmax of ``W_head . h`` (``h`` is the last pass's output, which
    the final norm has already closed). lm_head [V, D]."""
    return jax.nn.log_softmax(jnp.einsum(
        "td,vd->tv", h_rows, lm_head, precision=_HI,
        preferred_element_type=jnp.float32), axis=-1)


def exit_distribution(h_passes, gate_w, gate_b):
    """The exit gate over pass outputs ``h_passes [T, n, D]`` -> (``p [T,
    n]`` the probability of leaving at each pass, ``stay [n]`` = ``1 -
    CDF_{T-2}``, ``exit_pass [n]`` at threshold 1: the first pass whose
    cumulative probability reaches 1, which is the last unless a product of
    ``1 - lambda`` has rounded to 0)."""
    lam = jax.nn.sigmoid(
        jnp.einsum("tnd,d->tn", h_passes, gate_w, precision=_HI) + gate_b)
    lam = np.asarray(lam, np.float32)
    T = lam.shape[0]
    before = np.cumprod(np.concatenate(
        [np.ones_like(lam[:1]), 1.0 - lam[:-1]]), axis=0)  # prod_{s<t}
    p = np.concatenate([lam[:-1] * before[:-1], before[-1:]])
    left = before[1:] <= 0.0  # [T-1, n]: CDF_t >= 1 for t < T-1
    exit_pass = np.where(left.any(0), left.argmax(0), T - 1)
    return p, before[-1], exit_pass


def _hyper(hf: dict, variant: str) -> Hyper:
    heads = hf["num_attention_heads"]
    return Hyper(
        heads=heads, kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        eps=float(hf.get("rms_norm_eps", 1e-6)),
        passes=1 if variant == "one_pass" else int(hf.get("total_ut_steps", 1)),
        loop_norm=variant != "no_loop_norm",
        post_norms=variant != "no_post_norms",
        fp8=variant == "weights_fp8")


def _steps(cfg, variant: str, n_prompt: int, n_rows: int) -> list:
    """[(start, end)] of the steps a cache-sharing control walks: the prompt
    (whole for ``shared_kv_last``, whose prefill keeps every pass's slots;
    in chunks of the deployment's prefill budget for ``slot_by_layer``),
    then one decode step a generated token up to row ``n_rows - 1``."""
    chunk = n_prompt
    if variant == "slot_by_layer":
        flag = getattr(cfg, "flag", lambda name: None)
        chunk = int(flag("--max-num-batched-tokens") or 1024)
    bounds = list(range(0, n_prompt, chunk)) + list(range(n_prompt, n_rows + 1))
    return list(zip(bounds[:-1], bounds[1:]))


def _pad_block(n: int) -> int:
    return 1 if n == 1 else ref.pad_len(n)


def _shared_cache_passes(cfg, variant, layers, final_norm, x_all, cos, sin,
                         n_prompt, n_rows, hp):
    """``h_t`` of every pass ``[passes, n_rows, D]`` for a control that
    shares cache slots between passes (:func:`_steps`)."""
    L = next(iter(layers.values())).shape[0]
    room = ref.pad_len(n_rows) + ref.pad_len(min(n_prompt, 1024))
    kv = jnp.zeros((L, room, hp.kv_heads * hp.head_dim), jnp.float32)
    k_last, v_last, rows = kv, kv, []
    for start, end in _steps(cfg, variant, n_prompt, n_rows):
        pad = _pad_block(end - start)
        take = jnp.minimum(start + jnp.arange(pad), x_all.shape[0] - 1)
        outs, k_last, v_last = _segment(
            layers, final_norm, x_all[take], cos[take], sin[take], k_last,
            v_last, jnp.int32(start), hp=hp)
        rows.append(outs[:, :end - start])
    return jnp.concatenate(rows, axis=1)


def teacher_force(cfg, params, sequences, variant: str) -> list:
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}: {VARIANTS}")
    hp = _hyper(cfg.hf, variant)
    theta = float(cfg.hf["rope_theta"])
    layers = {k: v for k, v in params["layers"].items()
              if not k.startswith("lora_")}
    final_norm, lm_head = common.head_weights(params)
    gate_w = params.get("exit_gate_w")
    out, stays = [], []
    for s in sequences:
        n_prompt, n_gen = s["n_prompt"], len(s["want"])
        n_rows = n_prompt - 1 + n_gen  # the rows a logit is compared at end here
        padded = ref.pad_len(len(s["tokens"]))
        ids = np.zeros(padded, np.int32)
        ids[: len(s["tokens"])] = s["tokens"]
        x = common.embed_rows(params, jnp.asarray(ids))
        cos, sin = (jnp.asarray(t)
                    for t in ref.rope_tables(padded, hp.head_dim, theta))
        if variant in ("shared_kv_last", "slot_by_layer"):
            h = _shared_cache_passes(cfg, variant, layers, final_norm, x, cos,
                                     sin, n_prompt, n_rows, hp)
        else:
            h, _, _ = _segment(layers, final_norm, x, cos, sin, None, None,
                               jnp.int32(0), hp=hp)
        h = h[:, n_prompt - 1: n_rows]  # [passes, n_gen, D]
        if gate_w is not None and hp.passes > 1 and hp.loop_norm:
            _, stay, exit_pass = exit_distribution(
                h, gate_w.astype(jnp.float32),
                params["exit_gate_b"].astype(jnp.float32))
            if (exit_pass < hp.passes - 1).any():
                raise ValueError(
                    f"sequence {s.get('id')}: a position would leave at pass "
                    f"{int(exit_pass.min())} of {hp.passes} at threshold 1 "
                    f"(smallest 1 - CDF {float(stay.min())!r}): the served "
                    "path takes every pass, so this check set cannot be "
                    "compared")
            stays.append(float(stay.min()))
        out.append((np.asarray(_head(h[-1], lm_head)), None))
    if stays:
        LAST["min_stay"] = min(stays)
        print(f"[reference] ouro {variant}: every position leaves at the last "
              f"of {hp.passes} passes at threshold 1; smallest 1 - CDF at the "
              f"pass before: {LAST['min_stay']:.6g}", file=sys.stderr)
    return out
