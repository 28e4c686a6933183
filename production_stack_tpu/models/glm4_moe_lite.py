"""Latent-attention mixture of experts (``model_type: glm4_moe_lite``).

``h <- h + Attn_l(RMSNorm(h))``, ``h <- h + FFN_l(RMSNorm(h))``, a final
RMSNorm and an untied head.

**Attention (MLA), every layer.** ``c_q = RMSNorm(W_dq u)``; ``[q_nope_h |
q_rope_h] = W_uq c_q``; ``[c_kv | k_r] = W_dkv u``, ``c_kv <- RMSNorm(c_kv)``,
``k_rope = RoPE(k_r)`` — one for all heads; ``q_rope_h <- RoPE(q_rope_h)``
(every rotary dimension, pairs in halves); ``score_h(t, s) = (q_nope_h(t) .
W_uk_h c_kv(s) + q_rope_h(t) . k_rope(s)) / sqrt(nope + rope)``, causal
softmax, ``o_h = W_uv_h sum_s p c_kv(s)``, ``out = W_o [o_1 .. o_H]``. **A
token's cache row in a layer is ``[c_kv | k_rope]``** (after the norm and the
rotation), padded to whole lane tiles, in pages of one row a token
(:mod:`production_stack_tpu.ops.mla_attention`). A decode step runs the
absorbed form in the ``mla_decode`` kernel; a prefill chunk writes its rows
and then attends *expanded* (keys and values rebuilt from the latents, a
block at a time) or *absorbed*, by :meth:`Glm4MoeLite.prefill_path`: what the
two cost for the chunk the step holds, no flag.

**FFN.** The first ``first_k_dense_replace`` layers: a dense SwiGLU. The
rest: ``noaux_tc`` router (sigmoid scores, the top-k of ``score + bias``,
weights the scores renormalised and scaled), gated SwiGLU experts at full
width through the dispatch the hybrid class shares
(:mod:`production_stack_tpu.models.moe_dispatch`), one shared expert. **The
layer is told which experts it holds** (``n_routed_experts`` of
``router_experts`` from ``expert_first`` on; ``ep_share`` absent = all).

Parameters are stacked by kind: every layer's attention leaves
(``layers.attn``), the leading dense MLPs (``layers.dense``) and the expert
layers (``layers.moe``). The dense layers are walked, the expert layers
scanned; a layer's expert banks are read in place out of the stack (the
grouped products are given the stack as one bank of ``layers x experts``
groups of which only this layer's are non-empty: a slice handed to a Pallas
call would be copied first, 0.8 GB a bank).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import xxhash

from ..ops import mla_attention as mla
from . import base, moe_dispatch

Params = Dict[str, Any]

# What a step reports beside its tokens: the dispatch's five counts, and
# which way a prefill step attended (a decode step reports neither).
AUX_NAMES = moe_dispatch.AUX_NAMES + (
    "mla_prefill_steps_expanded_total", "mla_prefill_steps_absorbed_total")
AUX_WIDTH = len(AUX_NAMES)


@dataclasses.dataclass(frozen=True)
class Glm4MoeLiteConfig(base.ModelConfig):
    vocab_size: int = 154880
    hidden_size: int = 2048
    num_layers: int = 47
    first_k_dense: int = 1
    intermediate_size: int = 10240
    # attention (MLA)
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    # experts: ``n_routed_experts`` held of ``router_experts`` scored
    n_routed_experts: int = 64
    router_experts: int = 64
    expert_first: int = 0
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 202752
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    name: str = "glm4-moe-lite"
    eos_token_ids: Tuple[int, ...] = (2,)
    bos_token_id: Optional[int] = None

    # What the engine asks of any model config.
    latent_pages = True  # a page is one latent row a token, not K and V
    num_kv_heads = 1  # one shared row a token: nothing to shard by head

    @property
    def head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def cache_lanes(self) -> int:
        return mla.latent_lanes(self.kv_lora_rank, self.qk_rope_head_dim)

    def page_bytes(self, block_size: int, itemsize: int,
                   tp: int = 1, pp: int = 1) -> int:
        """Bytes of one page over every layer, as the pool stores it."""
        return self.num_layers * block_size * self.cache_lanes * itemsize


def config_from_hf(hf: dict, name: str = "") -> Glm4MoeLiteConfig:
    """The ``glm4_moe_lite`` keys of an HF ``config.json``. Beside them an
    expert-parallel share, as ``models/nemotron_h.py`` reads it:
    ``n_routed_experts`` is what this engine holds, ``ep_share`` = ``{"first":
    i, "of": n}`` says of how many the router is (absent: it holds all)."""
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing (n_group > 1) is not built")
    if hf.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(f"topk_method {hf['topk_method']!r}: noaux_tc only")
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {hf['hidden_act']!r}: silu only")
    if hf.get("rope_scaling"):
        raise ValueError("rope_scaling is not built for glm4_moe_lite")
    if float(hf.get("partial_rotary_factor", 1)) != 1.0:
        raise ValueError("partial_rotary_factor other than 1 is not built")
    if hf.get("attention_bias"):
        raise ValueError("attention_bias is not built for glm4_moe_lite")
    if not hf.get("q_lora_rank"):
        raise ValueError("glm4_moe_lite without q_lora_rank is not built")
    held = hf["n_routed_experts"]
    share = hf.get("ep_share") or {"first": 0, "of": held}
    first, of = int(share["first"]), int(share["of"])
    if not 0 <= first <= of - held:
        raise ValueError(
            f"ep_share {share}: {held} experts from {first} do not lie "
            f"within {of}")
    dense = int(hf.get("first_k_dense_replace", 0))
    if not 0 <= dense < hf["num_hidden_layers"]:
        raise ValueError(
            f"first_k_dense_replace {dense} leaves no expert layer of "
            f"{hf['num_hidden_layers']}")
    eos = hf.get("eos_token_id", 2)
    return Glm4MoeLiteConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        first_k_dense=dense,
        intermediate_size=hf["intermediate_size"],
        num_heads=hf["num_attention_heads"],
        q_lora_rank=hf["q_lora_rank"],
        kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        n_routed_experts=held,
        router_experts=of,
        expert_first=first,
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        n_shared_experts=int(hf.get("n_shared_experts", 1)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        max_position_embeddings=hf.get("max_position_embeddings", 4096),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        dtype=hf.get("torch_dtype") or "bfloat16",
        name=name or hf.get("_name_or_path", "glm4_moe_lite"),
        eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
        bos_token_id=hf.get("bos_token_id"),
    )


def init_leaf(name: str, shape, dtype, key: jax.Array, fan_in: int) -> jax.Array:
    """One layer's leaf: norms 1, a small non-zero selection bias (so that
    selecting by ``score + bias`` and weighing by ``score`` differ), every
    matrix normal with std ``1/sqrt(fan_in)``."""
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    if name == "router_bias":
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


class Glm4MoeLite(base.Model):
    """Stateless model functions bound to a config (the runner's model
    object, as :class:`production_stack_tpu.models.llama.Llama` is)."""

    AUX_NAMES = AUX_NAMES  # rows the runner appends to a step's packed tokens
    TOKEN_BUDGET = True  # forward takes the step's bound on real tokens

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------

    def leaf_shapes(self) -> Dict[str, Dict[str, tuple]]:
        """Per kind, each leaf's per-layer (shape, dtype, fan-in)."""
        c = self.cfg
        d, f32 = c.jdtype, jnp.dtype(jnp.float32)
        D, H, F, Fe = (c.hidden_size, c.num_heads, c.intermediate_size,
                       c.moe_intermediate_size)
        Fs = Fe * c.n_shared_experts
        r, rq = c.kv_lora_rank, c.q_lora_rank
        return {
            "attn": {
                "attn_norm": ((D,), d, 0),
                "w_dq": ((D, rq), d, D),
                "q_norm": ((rq,), d, 0),
                "w_uq": ((rq, H * c.head_dim), d, rq),
                "w_dkv": ((D, r + c.qk_rope_head_dim), d, D),
                "kv_norm": ((r,), d, 0),
                # W_ukv by head, its key part transposed for the absorbed form
                "w_uk": ((H, c.qk_nope_head_dim, r), d, r),
                "w_uv": ((H, r, c.v_head_dim), d, r),
                "wo": ((H * c.v_head_dim, D), d, H * c.v_head_dim),
                "mlp_norm": ((D,), d, 0),
            },
            "dense": {
                "w_gate": ((D, F), d, D),
                "w_up": ((D, F), d, D),
                "w_down": ((F, D), d, F),
            },
            "moe": {
                "w_router": ((D, c.router_experts), f32, D),
                "router_bias": ((c.router_experts,), f32, 0),
                # gate | up of every held expert, one bank
                "w1": ((c.n_routed_experts, D, 2 * Fe), d, D),
                "w2": ((c.n_routed_experts, Fe, D), d, Fe),
                "w_shared_gate": ((D, Fs), d, D),
                "w_shared_up": ((D, Fs), d, D),
                "w_shared_down": ((Fs, D), d, Fs),
            },
        }

    def layer_counts(self) -> Dict[str, int]:
        c = self.cfg
        return {"attn": c.num_layers, "dense": c.first_k_dense,
                "moe": c.num_moe_layers}

    def init_params(self, rng: jax.Array) -> Params:
        """Random initialisation, a leaf and a layer at a time under a
        ``lax.map`` (each layer its own key): no temporary is larger than
        one layer's leaf in float32, and the stack is written in place."""
        c = self.cfg

        def key_of(name: str) -> jax.Array:
            return jax.random.fold_in(
                rng, xxhash.xxh32(name.encode()).intdigest() & 0x7FFF_FFFF)

        layers: Params = {}
        for kind, leaves in self.leaf_shapes().items():
            n = self.layer_counts()[kind]
            if not n:
                continue
            layers[kind] = {
                leaf: jax.lax.map(
                    lambda i, leaf=leaf, shape=shape, dtype=dtype, fan=fan,
                    key=key_of(f"{kind}.{leaf}"): init_leaf(
                        leaf, shape, dtype, jax.random.fold_in(key, i), fan),
                    jnp.arange(n))
                for leaf, (shape, dtype, fan) in leaves.items()
            }
        V, D, d = c.vocab_size, c.hidden_size, c.jdtype
        params: Params = {
            "embed": base.init_leaf("embed", (V, D), d, key_of("embed")),
            "layers": layers,
            "final_norm": jnp.ones((D,), d),
        }
        if not c.tie_word_embeddings:
            params["lm_head"] = base.init_leaf(
                "lm_head", (V, D), d, key_of("lm_head"))
        return params

    # ------------------------------------------------------------------
    # Per-request state: pages of latents
    # ------------------------------------------------------------------

    def make_kv_cache(
        self, num_blocks: int, block_size: int, dtype: Optional[str] = None,
    ) -> Dict[str, jax.Array]:
        """``kv``: ``[L, nb, 1, bs, lanes]``, one ``[c_kv | k_rope | 0]``
        row a token (``ops/mla_attention.py``). ``aux``: what the last step
        reported (:meth:`step_aux`)."""
        c = self.cfg
        d = jnp.dtype(dtype) if dtype else c.jdtype
        return {
            "kv": jnp.zeros(
                (c.num_layers, num_blocks, 1, block_size, c.cache_lanes), d),
            "aux": jnp.zeros((AUX_WIDTH,), jnp.float32),
        }

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def prefill_path(self, chunk_tokens: int) -> str:
        """Which way a prefill step of ``chunk_tokens`` query positions a
        row attends. Against a context of S tokens the absorbed form costs
        ``T S H (2 rank + rope)`` multiply-adds; the expanded form ``T S H
        (nope + rope + v)`` and, once a context token whatever T is, its
        expansion ``S H (nope + v) rank``. S and H cancel: expanded where
        the chunk is long enough to pay for the expansion (398 positions at
        the published widths; the padded chunk is what the step computes,
        so that is what is compared)."""
        c = self.cfg
        absorbed = 2 * c.kv_lora_rank + c.qk_rope_head_dim
        expanded = c.qk_nope_head_dim + c.qk_rope_head_dim + c.v_head_dim
        expansion = (c.qk_nope_head_dim + c.v_head_dim) * c.kv_lora_rank
        return ("expanded"
                if chunk_tokens * (absorbed - expanded) > expansion
                else "absorbed")

    def forward(
        self,
        params: Params,
        tokens: jax.Array,  # [B, T]
        positions: jax.Array,  # [B, T]
        write_idx: jax.Array,  # [B, T] flat page slot (nb*bs = dropped)
        block_tables: jax.Array,  # [B, W]
        kv_lens: jax.Array,  # [B] valid kv length after this step's writes
        last_idx: jax.Array,  # [B] index in T of each row's last real token
        cache: Dict[str, jax.Array],
        *,
        token_budget: Optional[int] = None,  # most real tokens a step holds
        attn_impl: str = "auto",
        all_logits: bool = False,
        prefill_path: Optional[str] = None,  # tests and timing: force a way
        **_unused,  # lora_idx, lora_scale, moe_impl, pp_size, mesh: refused
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """One engine step; ``Llama.forward``'s contract."""
        cfg = self.cfg
        B, T = tokens.shape
        true_len = jnp.where(kv_lens > 0, last_idx + 1, 0).astype(jnp.int32)
        valid = (jnp.arange(T, dtype=jnp.int32)[None, :]
                 < true_len[:, None]).reshape(-1)
        path = "decode" if T == 1 else (
            prefill_path or self.prefill_path(T))
        half = cfg.qk_rope_head_dim // 2
        freqs = 1.0 / (cfg.rope_theta ** (
            jnp.arange(0, half, dtype=jnp.float32) / half))
        angles = positions.astype(jnp.float32)[..., None] * freqs
        rope = (jnp.cos(angles), jnp.sin(angles))
        step = dict(
            flat_write=write_idx.reshape(-1), block_tables=block_tables,
            kv_lens=kv_lens, positions=positions, rope=rope, path=path,
            attn_impl=attn_impl)

        x = base._embed_lookup(params, tokens, cfg)
        layers = params["layers"]
        kv = cache["kv"]
        for i in range(cfg.first_k_dense):
            ap = {k: v[i] for k, v in layers["attn"].items()}
            x, kv = self._attention(ap, x, kv, i, step)
            with jax.named_scope("dense_mlp"):
                x = x + self._swiglu(
                    base._rms_norm(x, ap["mlp_norm"], cfg.rms_norm_eps),
                    *(layers["dense"][w][i]
                      for w in ("w_gate", "w_up", "w_down"))).astype(x.dtype)

        # The expert layers, scanned. The banks stay whole and closed over:
        # a layer reads its experts in place, as groups of one big bank.
        moe = layers["moe"]
        n_moe, held = cfg.num_moe_layers, cfg.n_routed_experts
        banks = {w: moe[w].reshape((n_moe * held,) + moe[w].shape[2:])
                 for w in ("w1", "w2")}
        scanned = (
            {k: v for k, v in moe.items() if k not in banks},
            jnp.arange(n_moe, dtype=jnp.int32),
        )

        def layer(carry, xs):
            x, kv, aux = carry
            mp, j = xs
            # The attention leaves are stacked over every layer: indexed
            # here, since the stack less its dense layers handed to the scan
            # would be copied first (0.3 GB a step).
            li = cfg.first_k_dense + j
            ap = {k: jax.lax.dynamic_index_in_dim(v, li, keepdims=False)
                  for k, v in layers["attn"].items()}
            x, kv = self._attention(ap, x, kv, li, step)
            u = base._rms_norm(x, ap["mlp_norm"], cfg.rms_norm_eps)
            out, stats = self._moe(
                mp, banks, j * held, u.reshape(B * T, -1), valid, token_budget)
            x = x + out.reshape(B, T, -1).astype(x.dtype)
            return (x, kv, aux + stats), None

        (x, kv, moe_aux), _ = jax.lax.scan(
            layer, (x, kv, jnp.zeros((moe_dispatch.AUX_WIDTH,), jnp.float32)),
            scanned)
        aux = jnp.concatenate([moe_aux, jnp.asarray(
            [path == "expanded", path == "absorbed"], jnp.float32)])

        x = base._rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = params["lm_head" if "lm_head" in params else "embed"]
        if all_logits:
            logits = jnp.einsum(
                "btd,vd->btv", x, head, preferred_element_type=jnp.float32)
        else:
            last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
            logits = jnp.einsum(
                "bd,vd->bv", last, head, preferred_element_type=jnp.float32)
        return logits, {"kv": kv, "aux": aux}

    # -- attention -------------------------------------------------------

    def _attention(self, ap, x, kv, li, step):
        """``x + Attn(RMSNorm(x))`` and the cache with this step's rows."""
        cfg = self.cfg
        B, T, _ = x.shape
        H, r = cfg.num_heads, cfg.kv_lora_rank
        nope, rd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        f32, eps = jnp.float32, cfg.rms_norm_eps
        scale = 1.0 / math.sqrt(cfg.head_dim)
        cos, sin = step["rope"]
        h = base._rms_norm(x, ap["attn_norm"], eps)
        with jax.named_scope("mla_q"):
            c_q = jnp.einsum("btd,dr->btr", h, ap["w_dq"],
                             preferred_element_type=f32).astype(h.dtype)
            c_q = base._rms_norm(c_q, ap["q_norm"], eps)
            q = jnp.einsum("btr,re->bte", c_q, ap["w_uq"],
                           preferred_element_type=f32
                           ).astype(h.dtype).reshape(B, T, H, nope + rd)
            q_nope = q[..., :nope]
            q_rope = base._apply_rope(q[..., nope:], cos, sin)
        with jax.named_scope("mla_kv_down"):
            ckr = jnp.einsum("btd,de->bte", h, ap["w_dkv"],
                             preferred_element_type=f32).astype(h.dtype)
            c_kv = base._rms_norm(ckr[..., :r], ap["kv_norm"], eps)
            k_rope = base._apply_rope(ckr[..., None, r:], cos, sin)[:, :, 0]
            rows = jnp.concatenate([c_kv, k_rope], axis=-1).reshape(B * T, r + rd)
            rows = jnp.pad(rows, ((0, 0), (0, kv.shape[-1] - r - rd)))
            kv = mla.write_rows(kv, li, step["flat_write"], rows)

        where = dict(cache=kv, layer=li, block_tables=step["block_tables"],
                     kv_lens=step["kv_lens"])
        if step["path"] == "expanded":
            with jax.named_scope("mla_attn_prefill"):
                o = mla.expanded_attention(
                    q_nope, q_rope, ap["w_uk"], ap["w_uv"],
                    positions=step["positions"], scale=scale, **where,
                ).astype(h.dtype)
        else:
            with jax.named_scope("mla_absorb"):
                q_abs = jnp.concatenate([
                    jnp.einsum("bthn,hnc->bthc", q_nope, ap["w_uk"],
                               preferred_element_type=f32).astype(h.dtype),
                    q_rope], axis=-1)
            if T == 1 and step["attn_impl"] == "pallas":
                o_lat = mla.mla_decode(
                    q_abs[:, 0], rank=r, scale=scale, **where)[:, None]
            else:
                with jax.named_scope("mla_attn_prefill"):
                    o_lat = mla.absorbed_attention(
                        q_abs, positions=step["positions"], rank=r,
                        scale=scale, **where)
            with jax.named_scope("mla_absorb"):
                o = jnp.einsum("bthc,hcv->bthv", o_lat.astype(h.dtype),
                               ap["w_uv"], preferred_element_type=f32
                               ).astype(h.dtype)
        out = jnp.einsum("bte,ed->btd", o.reshape(B, T, H * cfg.v_head_dim),
                         ap["wo"], preferred_element_type=f32)
        return x + out.astype(x.dtype), kv

    # -- FFN ---------------------------------------------------------------

    @staticmethod
    def _swiglu(u, w_gate, w_up, w_down):
        f32 = jnp.float32
        g = jnp.einsum("...d,df->...f", u, w_gate, preferred_element_type=f32)
        a = jnp.einsum("...d,df->...f", u, w_up, preferred_element_type=f32)
        return jnp.einsum("...f,fd->...d", (jax.nn.silu(g) * a).astype(u.dtype),
                          w_down, preferred_element_type=f32)

    def routed(self, mp, banks, bank_first, u: jax.Array, valid: jax.Array,
               token_budget: Optional[int] = None):
        """This share's part of the routed sum ``[N, D]`` float32 and the
        dispatch's counts (``moe_dispatch.routed_experts`` with this class's
        expert body: gated SwiGLU at full width, gate and up one product).
        ``banks``: ``w1``, ``w2`` as ``[groups, k, n]`` with this layer's
        experts from group ``bank_first`` on."""
        cfg = self.cfg
        Fe = cfg.moe_intermediate_size

        def body(xs, gmm):
            a = gmm(xs, banks["w1"])
            a = (jax.nn.silu(a[:, :Fe]) * a[:, Fe:]).astype(u.dtype)
            return gmm(a, banks["w2"])

        return moe_dispatch.routed_experts(
            u, u, valid, mp["w_router"], mp["router_bias"], body,
            top_k=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor, held=cfg.n_routed_experts,
            expert_first=cfg.expert_first, token_budget=token_budget,
            bank_experts=banks["w1"].shape[0], bank_first=bank_first)

    def shared_expert(self, mp, u: jax.Array) -> jax.Array:
        with jax.named_scope("moe_shared"):
            return self._swiglu(u, mp["w_shared_gate"], mp["w_shared_up"],
                                mp["w_shared_down"])

    def _moe(self, mp, banks, bank_first, u, valid, token_budget=None):
        routed, stats = self.routed(mp, banks, bank_first, u, valid, token_budget)
        return routed + self.shared_expert(mp, u), stats
