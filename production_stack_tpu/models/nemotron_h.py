"""Hybrid decoder (``model_type: nemotron_h``): state-space, attention and
latent mixture-of-experts blocks behind one residual each, in the order a
pattern string gives.

``h <- h + Mixer_l(RMSNorm_l(h))`` for each character of
``hybrid_override_pattern``:

- ``M`` — Mamba-2 (:mod:`production_stack_tpu.ops.ssm`): one input
  projection ``[z | xBC | dt]``, a causal depthwise convolution over the
  last ``conv_kernel`` rows of ``xBC``, the selective state-space
  recurrence, a gate, an RMS norm within each group, one output projection.
- ``*`` — grouped-query attention **without rotary embedding** (the
  state-space layers carry position), through the paged KV cache and the
  attention kernels ``Llama`` uses.
- ``E`` — LatentMoE: sigmoid router scores over *all* experts, the top-k of
  ``score + bias`` chosen, weighted by ``score`` renormalised and scaled;
  experts are ungated ``relu^2`` MLPs in a latent space between a shared
  down- and up-projection; one shared expert at full width. **The layer
  holds a share**: ``n_routed_experts`` of the ``router_experts`` the router
  scores, from ``expert_first`` on. Pairs routed to experts it does not hold
  are dropped before the grouped products (``moe_dispatch.py``, which the
  latent-attention class calls too); the latent up-projection is
  applied to the partial sum (its peers' partial sums add up to the whole,
  ``tests/test_nemotron_h.py``) and the shared expert is added whole. There
  is no exchange here, and nothing stands in for the other ranks.

Two kinds of per-request state (``make_kv_cache``): pages of keys and values
for the attention layers alone, and for every Mamba layer one *slot* per
sequence holding the recurrent state (float32) and the convolution's tail.
Every step program is told each row's slot (``state_slots``); a row that is
padding points at the scratch slot, the pool's last. A prefill chunk starts
from its slot's state (from zeros at position 0) and leaves the state and
tail as they are at the row's true length, so padded positions never enter.

Parameters are grouped by kind and stacked within a kind (the expert banks,
which a Pallas call takes whole, are one array a layer: ``UNSTACKED``); the
forward pass walks the pattern.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import xxhash

from ..ops import ssm
from ..ops.attention import paged_attention
from . import base, moe_dispatch
from .moe_dispatch import AUX_NAMES, AUX_WIDTH

Params = Dict[str, Any]

KINDS = {"M": "mamba", "*": "attn", "E": "moe"}
# Leaves kept one array a layer, not stacked over a kind's layers: a Pallas
# call takes its operands whole, and a layer's slice of a stack would be
# copied to a fresh buffer first (705 MB a bank at published widths).
UNSTACKED = ("w1", "w2")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(base.ModelConfig):
    vocab_size: int = 131072
    hidden_size: int = 4096
    pattern: str = "MEM*E"
    # Mamba-2
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention (no rotary embedding)
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # LatentMoE: ``n_routed_experts`` held of ``router_experts`` scored
    n_routed_experts: int = 512
    router_experts: int = 512
    expert_first: int = 0
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    name: str = "nemotron-h"
    eos_token_ids: Tuple[int, ...] = (2,)
    bos_token_id: Optional[int] = 1

    # What the engine asks of any model config.
    recurrent = True  # has per-sequence state beside the paged KV

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    def count(self, kind: str) -> int:
        return sum(1 for c in self.pattern if KINDS[c] == kind)

    @property
    def num_kv_layers(self) -> int:
        """Layers that hold pages: the KV pool is sized from these."""
        return self.count("attn")

    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    def state_bytes_per_slot(self) -> int:
        """Recurrent state and tail of one sequence over every Mamba layer."""
        s = self.d_inner * self.ssm_state_size * 4
        tail = (self.conv_kernel - 1) * self.conv_dim * self.jdtype.itemsize
        return self.count("mamba") * (s + tail)


def config_from_hf(hf: dict, name: str = "") -> NemotronHConfig:
    """The ``nemotron_h`` keys of an HF ``config.json``. Beside them, an
    expert-parallel share: ``n_routed_experts`` is what this engine holds,
    ``ep_share`` = ``{"first": i, "of": n}`` says of how many the router is
    and where the held ones start (absent: it holds them all)."""
    pattern = hf["hybrid_override_pattern"]
    unknown = sorted(set(pattern) - set(KINDS))
    if unknown:
        raise ValueError(
            f"hybrid_override_pattern has block kinds {unknown}; this model "
            f"class runs {sorted(KINDS)} (a dense MLP block '-' is not built)"
        )
    if len(pattern) != hf["num_hidden_layers"]:
        raise ValueError(
            f"hybrid_override_pattern has {len(pattern)} blocks, "
            f"num_hidden_layers says {hf['num_hidden_layers']}"
        )
    if hf.get("mlp_hidden_act", "relu2") != "relu2":
        raise ValueError(f"mlp_hidden_act {hf['mlp_hidden_act']!r}: relu2 only")
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing (n_group > 1) is not built")
    held = hf["n_routed_experts"]
    share = hf.get("ep_share") or {"first": 0, "of": held}
    first, of = int(share["first"]), int(share["of"])
    if not 0 <= first <= of - held:
        raise ValueError(
            f"ep_share {share}: {held} experts from {first} do not lie "
            f"within {of}"
        )
    heads = hf["num_attention_heads"]
    eos = hf.get("eos_token_id", 2)
    return NemotronHConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        pattern=pattern,
        mamba_num_heads=hf["mamba_num_heads"],
        mamba_head_dim=hf["mamba_head_dim"],
        n_groups=hf["n_groups"],
        ssm_state_size=hf["ssm_state_size"],
        conv_kernel=hf["conv_kernel"],
        chunk_size=hf.get("chunk_size", 128),
        time_step_min=hf.get("time_step_min", 0.001),
        time_step_max=hf.get("time_step_max", 0.1),
        time_step_floor=hf.get("time_step_floor", 1e-4),
        num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        n_routed_experts=held,
        router_experts=of,
        expert_first=first,
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        moe_latent_size=hf["moe_latent_size"],
        moe_shared_expert_intermediate_size=hf[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        rms_norm_eps=hf.get("layer_norm_epsilon", hf.get("rms_norm_eps", 1e-5)),
        max_position_embeddings=hf.get("max_position_embeddings", 4096),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        dtype=hf.get("torch_dtype") or "bfloat16",
        name=name or hf.get("_name_or_path", "nemotron_h"),
        eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
        bos_token_id=hf.get("bos_token_id"),
    )


def init_leaf(name: str, shape, dtype, key: jax.Array,
              time_step=(0.001, 0.1, 1e-4)) -> jax.Array:
    """One leaf's random init by its name (``<kind>.<leaf>`` or a top-level
    name). The state-space leaves follow the published initialisation
    (``time_step``: ``time_step_min``, ``_max``, ``_floor``); the rest is
    ``models/base.py::init_leaf``'s."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf == "A_log":  # A = -exp(A_log) in -[1, 16]
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if leaf == "dt_bias":  # softplus(dt_bias) log-uniform in [min, max]
        lo, hi, floor = time_step
        dt = jnp.exp(
            jax.random.uniform(key, shape, jnp.float32)
            * (math.log(hi) - math.log(lo)) + math.log(lo)
        )
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "D":
        return jnp.ones(shape, jnp.float32)
    if leaf == "router_bias":  # small and non-zero: selects, never weighs
        return 0.02 * jax.random.normal(key, shape, jnp.float32)
    if leaf == "conv_b":
        return jnp.zeros(shape, dtype)
    if leaf == "conv_w":  # [K, C]: fan-in is the kernel's length
        return (
            jax.random.normal(key, shape, jnp.float32) / math.sqrt(shape[-2])
        ).astype(dtype)
    return base.init_leaf(leaf, shape, dtype, key)



class NemotronH(base.Model):
    """Stateless model functions bound to a config (the runner's model
    object, as :class:`production_stack_tpu.models.llama.Llama` is)."""

    AUX_NAMES = AUX_NAMES  # rows the runner appends to a step's packed tokens

    def __init__(self, cfg: NemotronHConfig):
        self.cfg = cfg
        # index of each block within its kind
        seen: Dict[str, int] = {}
        self.blocks = []
        for c in cfg.pattern:
            kind = KINDS[c]
            self.blocks.append((kind, seen.get(kind, 0)))
            seen[kind] = seen.get(kind, 0) + 1

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------

    def leaf_shapes(self) -> Dict[str, Dict[str, tuple]]:
        """Per kind, each leaf's per-layer shape and dtype."""
        c = self.cfg
        d, f32 = c.jdtype, jnp.dtype(jnp.float32)
        D, H = c.hidden_size, c.mamba_num_heads
        in_w = 2 * c.d_inner + 2 * c.n_groups * c.ssm_state_size + H
        return {
            "mamba": {
                "norm": ((D,), d),
                "w_in": ((D, in_w), d),
                "conv_w": ((c.conv_kernel, c.conv_dim), d),
                "conv_b": ((c.conv_dim,), d),
                "dt_bias": ((H,), f32),
                "A_log": ((H,), f32),
                "D": ((H,), f32),
                "gate_norm": ((c.d_inner,), d),
                "w_out": ((c.d_inner, D), d),
            },
            "attn": {
                "norm": ((D,), d),
                "wq": ((D, c.q_size), d),
                "wk": ((D, c.kv_size), d),
                "wv": ((D, c.kv_size), d),
                "wo": ((c.q_size, D), d),
            },
            "moe": {
                "norm": ((D,), d),
                "w_router": ((D, c.router_experts), f32),
                "router_bias": ((c.router_experts,), f32),
                "w_latent_down": ((D, c.moe_latent_size), d),
                "w_latent_up": ((c.moe_latent_size, D), d),
                "w1": ((c.n_routed_experts, c.moe_latent_size,
                        c.moe_intermediate_size), d),
                "w2": ((c.n_routed_experts, c.moe_intermediate_size,
                        c.moe_latent_size), d),
                "w_shared_up": ((D, c.moe_shared_expert_intermediate_size), d),
                "w_shared_down": ((c.moe_shared_expert_intermediate_size, D), d),
            },
        }

    def init_params(self, rng: jax.Array) -> Params:
        """Random initialisation, a leaf and a layer at a time (each its own
        key, so that no temporary is larger than one layer's leaf)."""
        c = self.cfg
        time_step = (c.time_step_min, c.time_step_max, c.time_step_floor)

        def key_of(name: str) -> jax.Array:
            return jax.random.fold_in(
                rng, xxhash.xxh32(name.encode()).intdigest() & 0x7FFF_FFFF)

        layers: Params = {}
        for kind, leaves in self.leaf_shapes().items():
            n = c.count(kind)
            if not n:
                continue
            per_layer = {
                leaf: [
                    init_leaf(f"{kind}.{leaf}", shape, dtype,
                              jax.random.fold_in(key_of(f"{kind}.{leaf}"), i),
                              time_step)
                    for i in range(n)
                ]
                for leaf, (shape, dtype) in leaves.items()
            }
            layers[kind] = {
                leaf: tuple(per) if leaf in UNSTACKED else jnp.stack(per)
                for leaf, per in per_layer.items()
            }
        V, D, d = c.vocab_size, c.hidden_size, c.jdtype
        params: Params = {
            "embed": init_leaf("embed", (V, D), d, key_of("embed")),
            "layers": layers,
            "final_norm": jnp.ones((D,), d),
        }
        if not c.tie_word_embeddings:
            params["lm_head"] = init_leaf("lm_head", (V, D), d, key_of("lm_head"))
        return params

    # ------------------------------------------------------------------
    # Per-request state: pages for attention, slots for the state space
    # ------------------------------------------------------------------

    def make_kv_cache(
        self, num_blocks: int, block_size: int, dtype: Optional[str] = None,
        state_slots: int = 1,
    ) -> Dict[str, jax.Array]:
        """``kv``: pages of the attention layers alone, in ``Llama``'s page
        layout. ``ssm`` / ``conv``: one slot a sequence and one more, the
        last, that padding rows write to. ``aux``: what the last step
        reported (:meth:`step_aux`)."""
        c = self.cfg
        d = jnp.dtype(dtype) if dtype else c.jdtype
        n_m = c.count("mamba")
        return {
            "kv": jnp.zeros(
                (c.num_kv_layers, num_blocks, 2, block_size, c.kv_size), d),
            "ssm": jnp.zeros(
                (n_m, state_slots + 1) + ssm.packed_shape(
                    c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size,
                    c.n_groups),
                jnp.float32),
            "conv": jnp.zeros(
                (n_m, state_slots + 1, c.conv_kernel - 1, c.conv_dim), c.jdtype),
            "aux": jnp.zeros((AUX_WIDTH,), jnp.float32),
        }

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

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
        state_slots: jax.Array,  # [B] each row's slot (padding: any)
        token_budget: Optional[int] = None,  # most real tokens a step holds
        attn_impl: str = "auto",
        all_logits: bool = False,
        **_unused,  # lora_idx, lora_scale, moe_impl, pp_size, mesh: refused
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """One engine step; ``Llama.forward``'s contract plus the slots."""
        cfg = self.cfg
        B, T = tokens.shape
        scratch = cache["ssm"].shape[1] - 1
        real = kv_lens > 0
        slots = jnp.where(real, state_slots, scratch).astype(jnp.int32)
        real = real & (slots != scratch)
        # A decode step (T == 1) has one real token a row; a prefill chunk
        # as many as last_idx says.
        true_len = jnp.where(real, last_idx + 1, 0).astype(jnp.int32)
        valid = jnp.arange(T, dtype=jnp.int32)[None, :] < true_len[:, None]
        fresh = positions[:, 0] == 0  # a sequence's first chunk: from zeros

        x = base._embed_lookup(params, tokens, cfg)
        kv, pool, tails = cache["kv"], cache["ssm"], cache["conv"]
        aux = jnp.zeros((AUX_WIDTH,), jnp.float32)
        layers = params["layers"]
        for kind, i in self.blocks:
            lp = {k: v[i] for k, v in layers[kind].items()}
            h = base._rms_norm(x, lp["norm"], cfg.rms_norm_eps)
            if kind == "mamba":
                with jax.named_scope("ssm_mixer"):
                    out, pool, tails = self._mamba(
                        lp, h, pool, tails, i, slots, true_len, valid, fresh)
            elif kind == "attn":
                out, kv = self._attention(
                    lp, h, kv, i, write_idx.reshape(-1), block_tables,
                    kv_lens, positions, attn_impl)
            else:
                out, stats = self._moe(
                    lp, h.reshape(B * T, -1), valid.reshape(-1), token_budget)
                out = out.reshape(B, T, -1)
                aux = aux + stats
            x = x + out.astype(x.dtype)

        x = base._rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = params["lm_head" if "lm_head" in params else "embed"]
        if all_logits:
            logits = jnp.einsum(
                "btd,vd->btv", x, head, preferred_element_type=jnp.float32)
        else:
            last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
            logits = jnp.einsum(
                "bd,vd->bv", last, head, preferred_element_type=jnp.float32)
        return logits, {"kv": kv, "ssm": pool, "conv": tails, "aux": aux}

    # -- M ----------------------------------------------------------------

    def _mamba(self, lp, h, pool, tails, li, slots, true_len, valid, fresh):
        cfg = self.cfg
        B, T, _ = h.shape
        H, Pd, G, N = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                       cfg.ssm_state_size)
        di, K = cfg.d_inner, cfg.conv_kernel
        f32 = jnp.float32
        proj = jnp.einsum(
            "btd,de->bte", h, lp["w_in"], preferred_element_type=f32
        ).astype(h.dtype)
        z, xbc, dt = jnp.split(proj, [di, di + cfg.conv_dim], axis=-1)

        # Causal depthwise convolution over [tail | this step's rows].
        tail = tails[li, slots]  # [B, K-1, C]
        tail = jnp.where(fresh[:, None, None], jnp.zeros_like(tail), tail)
        window = jnp.concatenate([tail, xbc], axis=1)  # [B, K-1+T, C]
        conv = lp["conv_b"].astype(f32)
        for k in range(K):
            conv = conv + window[:, k:k + T].astype(f32) * lp["conv_w"][k].astype(f32)
        xbc_act = jax.nn.silu(conv).astype(h.dtype)
        # The tail at the row's true length: rows [len, len + K - 1) of the
        # window are positions len - (K - 1) .. len - 1.
        new_tail = jax.vmap(
            lambda w, n: jax.lax.dynamic_slice_in_dim(w, n, K - 1, axis=0)
        )(window, true_len)
        tails = tails.at[li, slots].set(new_tail)

        xs, bm, cm = jnp.split(xbc_act, [di, di + G * N], axis=-1)
        xs = xs.reshape(B, T, H, Pd)
        bm = bm.reshape(B, T, G, N)
        cm = cm.reshape(B, T, G, N)
        dt = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"])  # [B, T, H]
        dt = jnp.where(valid[..., None], dt, 0.0)  # padded: state untouched
        a = -jnp.exp(lp["A_log"])  # [H]

        if T == 1:
            decay = jnp.where(fresh[:, None], 0.0, jnp.exp(dt[:, 0] * a))
            y, pool = ssm.ssm_decode(
                pool, jnp.int32(li), slots, decay,
                dt[:, 0, :, None] * xs[:, 0].astype(f32),
                bm[:, 0].astype(f32), cm[:, 0].astype(f32), n_groups=G,
            )
            y = y[:, None]  # [B, 1, H, P]
        else:
            with jax.named_scope("ssm_prefill"):
                s0 = ssm.unpack_state(pool[li, slots], Pd)
                s0 = jnp.where(fresh[:, None, None, None], 0.0, s0)
                y, s_new = ssm.ssd_chunked(
                    xs, dt, a, bm, cm, s0, chunk=cfg.chunk_size)
                pool = pool.at[li, slots].set(ssm.pack_state(s_new, G))
        y = y + lp["D"][:, None] * xs.astype(f32)
        y = y.reshape(B, T, di) * jax.nn.silu(z.astype(f32))
        # RMS norm within each group of d_inner / G channels.
        yg = y.reshape(B, T, G, di // G)
        yg = yg * jax.lax.rsqrt(
            jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        y = yg.reshape(B, T, di).astype(h.dtype) * lp["gate_norm"]
        out = jnp.einsum(
            "bte,ed->btd", y, lp["w_out"], preferred_element_type=f32)
        return out, pool, tails

    # -- * ----------------------------------------------------------------

    def _attention(self, lp, h, kv_all, li, flat_write, block_tables, kv_lens,
                   positions, attn_impl):
        cfg = self.cfg
        B, T, _ = h.shape
        f32 = jnp.float32
        q, k, v = (
            jnp.einsum("btd,de->bte", h, lp[w], preferred_element_type=f32)
            .astype(h.dtype) for w in ("wq", "wk", "wv"))
        # One scatter over the flattened row view, as Llama.forward does: the
        # drop sentinel (nb*bs) maps out of the whole array.
        n_l, nb, _, bs, _ = kv_all.shape
        idx_k = jnp.where(
            flat_write >= nb * bs, n_l * nb * 2 * bs,
            (li * nb + flat_write // bs) * (2 * bs) + flat_write % bs)
        kvd = jnp.concatenate(
            [k.reshape(B * T, cfg.kv_size), v.reshape(B * T, cfg.kv_size)]
        ).astype(kv_all.dtype)
        kv_all = (
            kv_all.reshape(n_l * nb * 2 * bs, cfg.kv_size)
            .at[jnp.concatenate([idx_k, idx_k + bs])].set(kvd, mode="drop")
            .reshape(kv_all.shape)
        )
        attn = paged_attention(
            q.reshape(B, T, cfg.num_heads, cfg.head_dim), kv_all, block_tables,
            kv_lens, positions, li, scale=1.0 / math.sqrt(cfg.head_dim),
            impl=attn_impl,
        ).reshape(B, T, cfg.q_size).astype(h.dtype)
        out = jnp.einsum("bte,ed->btd", attn, lp["wo"], preferred_element_type=f32)
        return out, kv_all

    # -- E ----------------------------------------------------------------

    def route(self, lp, u: jax.Array):
        """Router over all ``router_experts``: ``(ids [N, K], weights [N, K])``.
        Selection is by ``score + bias``; the weights are the scores alone."""
        cfg = self.cfg
        return moe_dispatch.route(
            u, lp["w_router"], lp["router_bias"],
            top_k=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor)

    def routed_latent(self, lp, u: jax.Array, valid: jax.Array,
                      token_budget: Optional[int] = None):
        """This share's part of the routed sum, in the latent space
        ``[N, latent]`` float32, and the step's ``[AUX_WIDTH]`` counts
        (``moe_dispatch.routed_experts`` with this class's expert body:
        ungated ``relu^2`` between two grouped products, in the latent)."""
        cfg = self.cfg
        with jax.named_scope("moe_latent"):
            lat = jnp.einsum(
                "nd,dl->nl", u, lp["w_latent_down"],
                preferred_element_type=jnp.float32,
            ).astype(u.dtype)

        def body(xs, gmm):
            a = gmm(xs, lp["w1"])
            return gmm(jnp.square(jax.nn.relu(a)).astype(u.dtype), lp["w2"])

        return moe_dispatch.routed_experts(
            u, lat, valid, lp["w_router"], lp["router_bias"], body,
            top_k=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor, held=cfg.n_routed_experts,
            expert_first=cfg.expert_first, token_budget=token_budget)

    def shared_expert(self, lp, u: jax.Array) -> jax.Array:
        f32 = jnp.float32
        with jax.named_scope("moe_shared"):
            a = jnp.einsum("nd,df->nf", u, lp["w_shared_up"],
                           preferred_element_type=f32)
            a = jnp.square(jax.nn.relu(a)).astype(u.dtype)
            return jnp.einsum("nf,fd->nd", a, lp["w_shared_down"],
                              preferred_element_type=f32)

    def _moe(self, lp, u: jax.Array, valid: jax.Array,
             token_budget: Optional[int] = None):
        acc, stats = self.routed_latent(lp, u, valid, token_budget)
        with jax.named_scope("moe_latent"):
            routed = jnp.einsum(
                "nl,ld->nd", acc.astype(u.dtype), lp["w_latent_up"],
                preferred_element_type=jnp.float32)
        return routed + self.shared_expert(lp, u), stats
