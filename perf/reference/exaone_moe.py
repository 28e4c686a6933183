"""The plain reference of the ``exaone_moe`` architecture (window and full
attention mixed, a leading dense layer, expert layers with a shared expert,
a multi-token-prediction module), written out from the published
configuration's keys in ``jax.numpy``: float32, matmul precision "highest",
attention over the whole sequence in query blocks (no cache, no kernel, no
batching), the expert block as a dense loop over the experts the
configuration **holds** with the router over all the published experts. The
interface is in ``perf/reference/__init__.py``; beside it ``mtp_logits``,
the draft module's logits, which ``correct`` cannot see
(``scripts/tpu_mtp_check.py`` holds the served path to them).

Main layer ``l``: ``h = x + Attn_t(N(x))``, ``y = h + MLP_m(N(h))`` with
``N(x; w) = x / sqrt(mean(x^2) + eps) w``, ``t = layer_types[l]``, ``m =
mlp_layer_types[l]``; a final ``N`` (``h^``) before the untied head.

**Attention**: ``q``, ``k``, ``v`` without bias; causal softmax attention at
``head^-1/2``, grouped. ``sliding_attention``: a query at ``p`` sees keys ``p
- sliding_window + 1 .. p``; rotate-half rotary embedding over every lane,
``theta^(-2i / head)``. ``full_attention``: every earlier key. *Assumed* (no
key of the published config says; the EXAONE-4 family's modelling and the
model card are the source): ``q <- N_head(q)``, ``k <- N_head(k)`` before the
rotary embedding (``qk_norm``); **no positional embedding on
``full_attention`` layers** (``nope_full``); the pre-norm placement above
(``pre_norm``).

**Dense block** (``mlp_layer_types[l] == "dense"``): ``down(silu(gate x) up
x)``. **Sparse block**: ``s = sigmoid(W_r x)`` over all the published
experts; the ``num_experts_per_tok`` largest of ``s + b`` (*assumed*: the
selection bias ``b``, ``router_bias``; the keys are DeepSeek-V3's, whose
router has it); weights ``s`` of the chosen, divided by their sum
(``norm_topk_prob``), times ``routed_scaling_factor``; ``sum_e w_e
Expert_e(x) + Shared(x)``. The sum runs over the held experts only
(``num_experts`` from ``ep_share.first`` on): one rank's share; the shared
expert is whole.

**MTP module** (DeepSeek-V3 technical report, section 2.2): ``u_i = W_eh
[N_e(Emb(t_{i+1})) ; N_h(h^_i)]``, ``z_i = Layer_mtp(u)_i`` (one more
``full_attention`` sparse layer over ``u_0 .. u_i``, its own router and
experts), ``Head(N(z_i))`` predicts ``t_{i+2}``; ``Emb`` and ``Head`` are the
main model's. *Assumed*: the order inside the concatenation
(``mtp_concat_order``), ``h^`` taken after the final norm
(``mtp_hidden_normed``), the module's block being sparse
(``mtp_block_sparse``). **Index convention**: the served path keeps
``u_i``'s keys and values at cache slot ``i + 1`` (slot 0 empty and masked),
so that a page's content is a function of the tokens it is hashed by; here
there is no cache and ``u_i`` sits at row ``i``: the same mathematics.

``gap`` is the smallest distance between the k-th and (k+1)-th of ``s + b``
over the expert layers.

Negative controls, each one piece of the mathematics broken: ``window_off``,
``rope_on_full`` (the full layers rotated like the window layers),
``qk_norm_off``, ``bias_off`` (selection by ``s`` alone), ``scale_off``
(``routed_scaling_factor`` 1), ``shared_off``, ``renorm_off``; for
``mtp_logits`` also ``mtp_hidden_unnormed`` (``h^`` taken before the final
norm). Precision controls, each the nearest precision below what the
configuration states: ``weights_fp8`` (every projection and expert matrix
rounded to float8 e4m3's three mantissa bits; router, norms, embedding and
head stay), ``kv_fp8`` (keys and values rounded likewise).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perf import config as configs
from perf.reference import model as shared
from perf.reference import weights as common
from perf.reference.mellum import _fp8, _mm, _pad_len, _rms, attention, rope_tables

VARIANTS = ("none", "window_off", "rope_on_full", "qk_norm_off", "bias_off",
            "scale_off", "shared_off", "renorm_off", "weights_fp8", "kv_fp8",
            "mtp_hidden_unnormed")
# The matrices ``weights_fp8`` rounds.
MATRICES = ("wq", "wk", "wv", "wo", "w1", "w2", "w_gate", "w_up", "w_down",
            "w_shared_gate", "w_shared_up", "w_shared_down", "w_eh")
SLIDING, FULL = "sliding_attention", "full_attention"


def weights(cfg):
    from production_stack_tpu.models import registry

    return common.engine_params(
        registry.model_for(configs.program_model_config(cfg)),
        cfg.weights_seed, cfg.flag("--quantization"))


def _swiglu(u, gate, up, down):
    return _mm(jax.nn.silu(_mm(u, gate)) * _mm(u, up), down)


@functools.partial(jax.jit, static_argnames=("eps",))
def dense(x, lw, *, eps):
    u = _rms(x, lw["norm"], eps)
    return _swiglu(u, lw["w_gate"], lw["w_up"], lw["w_down"])


@functools.partial(jax.jit, static_argnames=(
    "top_k", "first", "renorm", "eps", "scale", "bias", "with_shared"))
def moe(x, lw, *, top_k, first, renorm, eps, scale, bias, with_shared):
    """-> (out [T, D], gap [T])."""
    u = _rms(x, lw["norm"], eps)
    s = jax.nn.sigmoid(_mm(u, lw["w_router"]))  # [T, all experts]
    pick = s + lw["router_bias"] if bias else s
    ordered = jnp.sort(pick, axis=-1)[:, ::-1]
    gap = ordered[:, top_k - 1] - ordered[:, top_k]
    _, ids = jax.lax.top_k(pick, top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if renorm:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * scale
    held = lw["w1"].shape[0]
    width = lw["w2"].shape[1]

    def one(e, acc):
        weight = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)  # [T]
        a = _mm(u, jax.lax.dynamic_index_in_dim(lw["w1"], e, keepdims=False))
        y = _mm(jax.nn.silu(a[:, :width]) * a[:, width:],
                jax.lax.dynamic_index_in_dim(lw["w2"], e, keepdims=False))
        return acc + weight[:, None] * y

    out = jax.lax.fori_loop(0, held, one, jnp.zeros_like(u))
    if with_shared:
        out = out + _swiglu(u, lw["w_shared_gate"], lw["w_shared_up"],
                            lw["w_shared_down"])
    return out, gap


class _Walk:
    """The configuration's equations bound to one variant: the layers of the
    main stack and of the MTP module over one sequence's hidden states."""

    def __init__(self, cfg, params, variant: str):
        hf = cfg.hf
        self.hf, self.params, self.variant = hf, params, variant
        self.eps = float(hf.get("rms_norm_eps", 1e-5))
        self.n_heads = hf["num_attention_heads"]
        self.n_kv = hf.get("num_key_value_heads", self.n_heads)
        self.head = hf.get("head_dim") or hf["hidden_size"] // self.n_heads
        self.window = 0 if variant == "window_off" else int(hf["sliding_window"])
        rope = hf.get("rope_parameters") or {}
        theta = float(rope.get("rope_theta", hf.get("rope_theta", 10000.0)))
        half = self.head // 2
        self.inv = theta ** (-np.arange(half, dtype=np.float64) / half)
        share = hf.get("ep_share") or {}
        self.first = int(share.get("first", 0))
        all_experts = int((cfg.raw.get("published") or {}).get(
            "num_experts", hf["num_experts"]))
        width = params["layers"]["l1" if "l1" in params["layers"] else "mtp"][
            "moe"]["w_router"].shape[-1]
        if width != all_experts:
            raise ValueError(
                f"the served router scores {width} experts, the "
                f"configuration publishes {all_experts}")
        self.final_norm, self.lm_head = common.head_weights(params)

    def leaves(self, layer: str, block: str) -> dict:
        lw = self.params["layers"][layer][block]
        if self.variant == "weights_fp8":
            lw = {k: _fp8(v) if k in MATRICES else v for k, v in lw.items()}
        return lw

    def tables(self, T: int) -> dict:
        """cos/sin by layer type: the window layers' rotation, the full
        layers' identity (no positional embedding) unless ``rope_on_full``."""
        half = self.head // 2
        turn = rope_tables(T, self.inv, 1.0)
        still = (jnp.ones((T, half), jnp.float32),
                 jnp.zeros((T, half), jnp.float32))
        return {SLIDING: turn,
                FULL: turn if self.variant == "rope_on_full" else still}

    def attn(self, x, tables, layer: str, kind: str):
        return attention(
            x, *tables[kind], self.leaves(layer, "attn"),
            n_heads=self.n_heads, n_kv=self.n_kv,
            window=self.window if kind == SLIDING else 0, eps=self.eps,
            qk_norm=self.variant != "qk_norm_off",
            kv_fp8=self.variant == "kv_fp8")

    def sparse(self, x, layer: str):
        hf, v = self.hf, self.variant
        return moe(
            x, self.leaves(layer, "moe"), top_k=hf["num_experts_per_tok"],
            first=self.first,
            renorm=bool(hf.get("norm_topk_prob", True)) and v != "renorm_off",
            eps=self.eps,
            scale=1.0 if v == "scale_off"
            else float(hf.get("routed_scaling_factor", 1.0)),
            bias=v != "bias_off", with_shared=v != "shared_off")

    def main(self, ids: np.ndarray):
        """-> (the stack's output before the final norm [T, D], gaps [T])."""
        T = ids.shape[0]
        x = common.embed_rows(self.params, jnp.asarray(ids))
        tables = self.tables(T)
        gaps = np.full(T, np.inf, np.float32)
        kinds = self.hf.get("mlp_layer_types") or []
        for i, t in enumerate(self.hf["layer_types"]):
            x = x + self.attn(x, tables, f"l{i}", t)
            if kinds[i] == "dense":
                x = x + dense(x, self.leaves(f"l{i}", "dense"), eps=self.eps)
            else:
                ffn, gap = self.sparse(x, f"l{i}")
                x = x + ffn
                gaps = np.minimum(gaps, np.asarray(gap))
        return x, gaps

    def mtp(self, x, ids: np.ndarray):
        """The module's output before its last norm, row ``i`` for position
        ``i`` and the token after it (the last row of ``x`` has none: its
        row pairs with token 0 and means nothing)."""
        T = ids.shape[0]
        io = self.leaves("mtp", "io")
        h = x if self.variant == "mtp_hidden_unnormed" else _rms(
            x, self.final_norm, self.eps)
        nxt = np.concatenate([ids[1:], ids[:1] * 0])
        e = common.embed_rows(self.params, jnp.asarray(nxt))
        u = _mm(jnp.concatenate(
            [_rms(e, io["enorm"], self.eps), _rms(h, io["hnorm"], self.eps)],
            axis=-1), io["w_eh"])
        u = u + self.attn(u, self.tables(T), "mtp", FULL)
        ffn, _ = self.sparse(u, "mtp")
        return u + ffn, io["final_norm"]


def teacher_force(cfg, params, sequences, variant: str) -> list:
    """One sequence at a time: its hidden states [T, D] are all that is
    held while the layers are walked."""
    walk = _Walk(cfg, params, variant)
    out = []
    for s in sequences:
        T = _pad_len(len(s["tokens"]))
        ids = np.zeros(T, np.int32)
        ids[: len(s["tokens"])] = s["tokens"]
        x, gaps = walk.main(ids)
        n_prompt, n_gen = s["n_prompt"], len(s["want"])
        rows = jnp.arange(n_prompt - 1, n_prompt - 1 + n_gen)
        lps = np.asarray(shared.head_logprobs(
            x[rows], walk.final_norm, walk.lm_head, eps=walk.eps))
        out.append((lps, gaps[n_prompt - 1: n_prompt - 1 + n_gen]))
        del x
    return out


def mtp_logits(cfg, params, tokens, rows, variant: str = "none") -> np.ndarray:
    """The draft module's log-probabilities ``[len(rows), vocab]`` over one
    sequence ``tokens``: row ``i`` of ``rows`` is position ``i`` paired with
    token ``i + 1``, and predicts token ``i + 2`` (``i <= len(tokens) - 2``)."""
    walk = _Walk(cfg, params, variant)
    T = _pad_len(len(tokens))
    ids = np.zeros(T, np.int32)
    ids[: len(tokens)] = tokens
    x, _ = walk.main(ids)
    z, last_norm = walk.mtp(x, ids)
    return np.asarray(shared.head_logprobs(
        z[jnp.asarray(rows)], last_norm.astype(jnp.float32), walk.lm_head,
        eps=walk.eps))
