"""Gated-delta-rule hybrid (``model_type: qwen3_next``): periods of
``full_attention_interval - 1`` Gated DeltaNet layers and one gated
full-attention layer, every layer followed by a mixture-of-experts block.

``h = x + Mixer(N(x; w1))``, ``y = h + MoE(N(h; w2))`` with the
zero-centred norm ``N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` in
float32; a final ``N`` before the untied head. Layer ``i`` is full attention
when ``(i + 1) % full_attention_interval == 0``.

- **Gated DeltaNet** (:mod:`production_stack_tpu.ops.gated_delta`): ``[q | k
  | v]`` and ``z`` and ``[b | a]`` from three projections of the input (the
  checkpoint's ``in_proj_qkvz`` and ``in_proj_ba`` interleave them by key
  head; the leaves here are split, a loader's permutation); a causal
  depthwise convolution of kernel ``linear_conv_kernel_dim`` over ``[q | k |
  v]``, no bias, then ``silu``; ``q``, ``k`` repeated to the value heads,
  L2-normalised a head, ``q`` scaled by ``key_head_dim^-1/2``; ``beta =
  sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``; the delta rule on
  a ``[key, value]`` state a head; ``o`` RMS-normalised a head (plain
  weight), times ``silu(z)``; one output projection.
- **Gated attention**: ``q_proj`` yields a query and an output gate a head
  (two leaves here); queries and keys normalised a head (zero-centred);
  rotary on the first ``partial_rotary_factor`` of the lanes, the rest
  untouched; grouped causal attention through the paged KV cache and the
  kernels ``Llama`` uses; ``o_proj(attn * sigmoid(gate))``.
- **Expert block**: softmax over **all** ``router_experts`` in float32, the
  top ``num_experts_per_tok``, renormalised over the chosen
  (``norm_topk_prob``); gated SwiGLU experts; a shared expert behind
  ``sigmoid(x . w_sg)``. **The layer holds a share**: ``n_routed_experts``
  of the ``router_experts`` the router scores, from ``expert_first`` on
  (``models/moe_dispatch.py``, which the hybrid and the latent-attention
  classes call too). Pairs routed elsewhere are dropped before the grouped
  products; nothing stands in for the other ranks.

Two kinds of per-request memory (``make_kv_cache``): pages of keys and
values for the attention layers alone, and for every DeltaNet layer one
*slot* a sequence holding the ``[value_heads, key, value]`` float32 state
and the convolution's tail: the last ``linear_conv_kernel_dim - 1``
pre-activation rows of ``[q | k | v]``, each row folded to whole ``[sublane,
lane]`` tiles (``gated_delta.tail_shape``), so that a slot's tail is one
contiguous piece a kernel can fetch by the slot. A row that is padding
points at the scratch slot, the pool's last. Three kernels own the two
pools in place: the chunked delta rule of a prefill step, and of a decode
step the delta rule's one position and the convolution over ``[tail |
row]`` with the tail's shift; a prefill step's convolution, and everything
on the CPU, is ``jax.numpy`` on the same buffers.

The multi-token-prediction layer of the published checkpoints is a draft
head beside the model; it is not served (``engine/spec.py`` drafts n-grams).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import xxhash

from ..ops import gated_delta as gdn
from ..ops.attention import paged_attention
from . import base, moe_dispatch
from .moe_dispatch import AUX_NAMES, AUX_WIDTH

Params = Dict[str, Any]

_F32 = ("A_log", "dt_bias", "w_router")
_BANKS = ("w1", "w2")


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig(base.ModelConfig):
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    # gated attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # Gated DeltaNet
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # expert block: ``n_routed_experts`` held of ``router_experts`` scored
    n_routed_experts: int = 512
    router_experts: int = 512
    expert_first: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    name: str = "qwen3-next"
    eos_token_ids: Tuple[int, ...] = (151645, 151643)
    bos_token_id: Optional[int] = None

    # What the engine asks of any model config.
    recurrent = True  # has per-sequence state beside the paged KV
    wide_head_pages = True  # 256 lanes a head: one-byte pages are not proven

    def __post_init__(self):
        n = self.full_attention_interval
        if n < 2 or self.num_layers % n:
            raise ValueError(
                f"num_hidden_layers {self.num_layers} is not whole periods of "
                f"full_attention_interval {n} (one attention layer closes "
                "each)")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                "linear_num_value_heads is no multiple of linear_num_key_heads")

    @property
    def periods(self) -> int:
        return self.num_layers // self.full_attention_interval

    @property
    def num_kv_layers(self) -> int:
        """Layers that hold pages: the KV pool is sized from these."""
        return self.periods

    @property
    def num_state_layers(self) -> int:
        """Layers that hold a state slot a sequence."""
        return self.num_layers - self.periods

    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_dim + self.value_dim

    def state_bytes_per_slot(self) -> int:
        """Matrix state and tail of one sequence over every DeltaNet layer."""
        s = (self.linear_num_value_heads * self.linear_key_head_dim
             * self.linear_value_head_dim * 4)
        tail = ((self.linear_conv_kernel_dim - 1) * self.conv_dim
                * self.jdtype.itemsize)
        return self.num_state_layers * (s + tail)


def config_from_hf(hf: dict, name: str = "") -> Qwen3NextConfig:
    """The ``qwen3_next`` keys of an HF ``config.json``. Beside them, an
    expert-parallel share: ``num_experts`` is what this engine holds,
    ``ep_share`` = ``{"first": i, "of": n}`` says of how many the router is
    and where the held ones start (absent: it holds them all)."""
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {hf['hidden_act']!r}: silu only")
    if hf.get("decoder_sparse_step", 1) != 1 or hf.get("mlp_only_layers"):
        raise ValueError(
            "decoder_sparse_step != 1 / mlp_only_layers: a dense MLP layer "
            "is not built for qwen3_next")
    if hf.get("rope_scaling") or hf.get("use_sliding_window"):
        raise ValueError("rope_scaling / use_sliding_window are not built")
    if hf.get("attention_bias"):
        raise ValueError("attention_bias is not built for qwen3_next")
    held = hf["num_experts"]
    share = hf.get("ep_share") or {"first": 0, "of": held}
    first, of = int(share["first"]), int(share["of"])
    if not 0 <= first <= of - held:
        raise ValueError(
            f"ep_share {share}: {held} experts from {first} do not lie "
            f"within {of}")
    heads = hf["num_attention_heads"]
    eos = hf.get("eos_token_id", 151645)
    return Qwen3NextConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=hf["num_hidden_layers"],
        full_attention_interval=hf.get("full_attention_interval", 4),
        num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        partial_rotary_factor=float(hf.get("partial_rotary_factor", 0.25)),
        rope_theta=float(hf.get("rope_theta", 1e7)),
        linear_num_key_heads=hf["linear_num_key_heads"],
        linear_num_value_heads=hf["linear_num_value_heads"],
        linear_key_head_dim=hf["linear_key_head_dim"],
        linear_value_head_dim=hf["linear_value_head_dim"],
        linear_conv_kernel_dim=hf.get("linear_conv_kernel_dim", 4),
        n_routed_experts=held,
        router_experts=of,
        expert_first=first,
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        shared_expert_intermediate_size=hf["shared_expert_intermediate_size"],
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        max_position_embeddings=hf.get("max_position_embeddings", 4096),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        dtype=hf.get("torch_dtype") or "bfloat16",
        name=name or hf.get("_name_or_path", "qwen3_next"),
        eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
        bos_token_id=hf.get("bos_token_id"),
    )


def init_leaf(name: str, shape, dtype, key: jax.Array) -> jax.Array:
    """One leaf's random init by its name. Norm weights normal(0, 0.1): the
    zero-centred ``1 + w`` and the plain ``w`` then differ; ``A_log = log
    U(0, 16)`` as the modelling file; ``dt_bias`` the inverse softplus of a
    log-uniform draw in [0.001, 0.1] (the Gated-DeltaNet initialiser: a
    token's decay ``exp(g)`` then spans about 0.2-0.9999, so state is
    carried); matrices normal with std ``fan_in^-1/2``."""
    if "norm" in name:
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if name == "A_log":
        return jnp.log(jax.random.uniform(
            key, shape, jnp.float32, 1e-4, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(
            jax.random.uniform(key, shape, jnp.float32)
            * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    # [.., in, out] (conv_w [K, C]: the kernel's length); rows of [V, D] and
    # the shared expert's gate [D] (D -> 1) contract their last axis
    fan_in = shape[-1] if name in ("embed", "lm_head", "w_shared_sig") else shape[-2]
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def _norm(x, w, eps):
    """The zero-centred RMS norm, in float32; the result in ``x``'s dtype."""
    return base._rms_norm(x, w, eps, unit_offset=True)


def _mm(x, w):
    return jnp.einsum("...d,de->...e", x, w, preferred_element_type=jnp.float32)


class Qwen3Next(base.Model):
    """Stateless model functions bound to a config (the runner's model
    object, as :class:`production_stack_tpu.models.llama.Llama` is)."""

    AUX_NAMES = AUX_NAMES  # rows the runner appends to a step's packed tokens

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------

    def leaf_shapes(self) -> Dict[str, Dict[str, tuple]]:
        """Per kind, each leaf's per-layer shape. ``delta``: the DeltaNet
        mixers in layer order; ``attn``: the attention mixers, one a period;
        ``moe``: every layer's expert block."""
        c = self.cfg
        D, Fe, Fs = (c.hidden_size, c.moe_intermediate_size,
                     c.shared_expert_intermediate_size)
        Hv = c.linear_num_value_heads
        return {
            "delta": {
                "norm": (D,),
                "w_qkv": (D, c.conv_dim),
                "w_z": (D, c.value_dim),
                "w_ba": (D, 2 * Hv),
                "conv_w": (c.linear_conv_kernel_dim, c.conv_dim),
                "A_log": (Hv,),
                "dt_bias": (Hv,),
                "o_norm": (c.linear_value_head_dim,),
                "w_out": (c.value_dim, D),
            },
            "attn": {
                "norm": (D,),
                "wq": (D, c.q_size),
                "wq_gate": (D, c.q_size),
                "wk": (D, c.kv_size),
                "wv": (D, c.kv_size),
                "q_norm": (c.head_dim,),
                "k_norm": (c.head_dim,),
                "wo": (c.q_size, D),
            },
            "moe": {
                "norm": (D,),
                "w_router": (D, c.router_experts),
                # gate | up of every held expert, one bank
                "w1": (c.n_routed_experts, D, 2 * Fe),
                "w2": (c.n_routed_experts, Fe, D),
                "w_shared_gate": (D, Fs),
                "w_shared_up": (D, Fs),
                "w_shared_down": (Fs, D),
                "w_shared_sig": (D,),
            },
        }

    def layer_counts(self) -> Dict[str, int]:
        c = self.cfg
        return {"delta": c.num_state_layers, "attn": c.periods,
                "moe": c.num_layers}

    def init_params(self, rng: jax.Array) -> Params:
        """Random initialisation, a leaf and a layer at a time under a
        ``lax.map`` (each layer its own key): no temporary is larger than
        one layer's leaf in float32."""
        c = self.cfg
        d = c.jdtype

        def key_of(name: str) -> jax.Array:
            return jax.random.fold_in(
                rng, xxhash.xxh32(name.encode()).intdigest() & 0x7FFF_FFFF)

        layers: Params = {}
        for kind, leaves in self.leaf_shapes().items():
            n = self.layer_counts()[kind]
            layers[kind] = {
                leaf: jax.lax.map(
                    lambda i, leaf=leaf, shape=shape,
                    dtype=jnp.float32 if leaf in _F32 else d,
                    key=key_of(f"{kind}.{leaf}"): init_leaf(
                        leaf, shape, dtype, jax.random.fold_in(key, i)),
                    jnp.arange(n))
                for leaf, shape in leaves.items()}
        V, D = c.vocab_size, c.hidden_size
        params: Params = {
            "embed": init_leaf("embed", (V, D), d, key_of("embed")),
            "layers": layers,
            "final_norm": init_leaf("final_norm", (D,), d, key_of("final_norm")),
        }
        if not c.tie_word_embeddings:
            params["lm_head"] = init_leaf("lm_head", (V, D), d, key_of("lm_head"))
        return params

    # ------------------------------------------------------------------
    # Per-request memory: pages for attention, slots for the delta rule
    # ------------------------------------------------------------------

    def make_kv_cache(
        self, num_blocks: int, block_size: int, dtype: Optional[str] = None,
        state_slots: int = 1,
    ) -> Dict[str, jax.Array]:
        """``kv``: pages of the attention layers alone, in ``Llama``'s page
        layout. ``ssm``: the kernels' pool, ``[layers, slots, H, K, V]``
        float32; ``conv``: a slot's tail, ``[layers, slots, taps - 1,
        conv_dim / 128, 128]`` (3 x 64 x 128 bf16 at the published widths:
        whole tiles, nothing padded). One slot a sequence and one more, the
        last, that padding rows write to. ``aux``: what the last step
        reported (:meth:`step_aux`)."""
        c = self.cfg
        d = jnp.dtype(dtype) if dtype else c.jdtype
        n = c.num_state_layers
        return {
            "kv": jnp.zeros(
                (c.num_kv_layers, num_blocks, 2, block_size, c.kv_size), d),
            "ssm": jnp.zeros(
                (n, state_slots + 1, c.linear_num_value_heads,
                 c.linear_key_head_dim, c.linear_value_head_dim), jnp.float32),
            "conv": jnp.zeros(
                (n, state_slots + 1)
                + gdn.tail_shape(c.linear_conv_kernel_dim, c.conv_dim),
                c.jdtype),
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
        true_len = jnp.where(real, last_idx + 1, 0).astype(jnp.int32)
        valid = jnp.arange(T, dtype=jnp.int32)[None, :] < true_len[:, None]
        keep = positions[:, 0] != 0  # a sequence's first chunk: from zeros
        rows = (slots, true_len, valid, keep)

        half = cfg.rotary_dim // 2
        freqs = 1.0 / (cfg.rope_theta ** (
            jnp.arange(0, half, dtype=jnp.float32) / half))
        angles = positions.astype(jnp.float32)[..., None] * freqs
        step = dict(
            flat_write=write_idx.reshape(-1), block_tables=block_tables,
            kv_lens=kv_lens, positions=positions,
            rope=(jnp.cos(angles), jnp.sin(angles)), attn_impl=attn_impl)

        layers = params["layers"]
        n_delta = cfg.full_attention_interval - 1
        held = cfg.n_routed_experts
        moe = layers["moe"]
        # The banks stay whole and closed over: a layer reads its experts in
        # place, as groups of one big bank. So do the other stacks: a layer's
        # leaves are indexed where they are used (a period's slice handed to
        # the inner scan would be copied first, 0.2 GB a period).
        banks = {w: moe[w].reshape((cfg.num_layers * held,) + moe[w].shape[2:])
                 for w in _BANKS}
        at = lambda stack, i: {  # noqa: E731
            k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
            for k, v in stack.items() if k not in _BANKS}
        flat_valid = valid.reshape(-1)

        def experts(layer, x, aux):
            mp = at(moe, layer)
            u = _norm(x, mp["norm"], cfg.rms_norm_eps)
            out, stats = self._moe(
                mp, banks, layer * held, u.reshape(B * T, -1), flat_valid,
                token_budget)
            return x + out.reshape(B, T, -1).astype(x.dtype), aux + stats

        def period(carry, p):
            x, kv, pool, tails, aux = carry

            def delta_layer(carry, j):
                x, pool, tails, aux = carry
                out, pool, tails = self._delta(
                    at(layers["delta"], p * n_delta + j), x, pool, tails,
                    p * n_delta + j, rows)
                x, aux = experts(
                    p * (n_delta + 1) + j, x + out.astype(x.dtype), aux)
                return (x, pool, tails, aux), None

            (x, pool, tails, aux), _ = jax.lax.scan(
                delta_layer, (x, pool, tails, aux),
                jnp.arange(n_delta, dtype=jnp.int32))
            with jax.named_scope("gated_attn"):
                out, kv = self._attention(at(layers["attn"], p), x, kv, p, step)
            x, aux = experts(
                p * (n_delta + 1) + n_delta, x + out.astype(x.dtype), aux)
            return (x, kv, pool, tails, aux), None

        x = base._embed_lookup(params, tokens, cfg)
        (x, kv, pool, tails, aux), _ = jax.lax.scan(
            period,
            (x, cache["kv"], cache["ssm"], cache["conv"],
             jnp.zeros((AUX_WIDTH,), jnp.float32)),
            jnp.arange(cfg.periods, dtype=jnp.int32))

        x = _norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = params["lm_head" if "lm_head" in params else "embed"]
        if all_logits:
            logits = jnp.einsum(
                "btd,vd->btv", x, head, preferred_element_type=jnp.float32)
        else:
            last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
            logits = jnp.einsum(
                "bd,vd->bv", last, head, preferred_element_type=jnp.float32)
        return logits, {"kv": kv, "ssm": pool, "conv": tails, "aux": aux}

    # -- Gated DeltaNet ----------------------------------------------------

    def delta_inputs(self, lp, qkv, ba):
        """What the delta rule takes, from the convolved and activated ``qkv
        [B, T, conv_dim]`` float32 and ``ba [B, T, 2 Hv]`` float32: ``(q, k
        [B, T, Hv, K], v [B, T, Hv, V], g, beta [B, T, Hv])``."""
        cfg = self.cfg
        B, T, _ = qkv.shape
        Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        K, V = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        q, k, v = jnp.split(qkv, [cfg.key_dim, 2 * cfg.key_dim], axis=-1)

        def heads(x):  # L2-normalised a head, repeated to the value heads
            x = x.reshape(B, T, Hk, K)
            x = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
            return jnp.repeat(x, Hv // Hk, axis=2)

        b, a = jnp.split(ba, 2, axis=-1)
        g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(a + lp["dt_bias"])
        return (heads(q) * K ** -0.5, heads(k), v.reshape(B, T, Hv, V), g,
                jax.nn.sigmoid(b))

    def _delta(self, lp, x, pool, tails, li, rows):
        """-> (the mixer's output [B, T, D] float32, pool, tails)."""
        cfg = self.cfg
        slots, true_len, valid, keep = rows
        B, T, _ = x.shape
        f32 = jnp.float32
        h = _norm(x, lp["norm"], cfg.rms_norm_eps)
        with jax.named_scope("gdn_proj"):
            qkv = _mm(h, lp["w_qkv"]).astype(h.dtype)
            z = _mm(h, lp["w_z"])
            ba = _mm(h, lp["w_ba"])

        with jax.named_scope("gdn_conv"):
            # Causal depthwise convolution over [tail | this step's rows].
            if T == 1 and gdn.use_kernels():
                # a real row's true length is 1, a padding row's slot the
                # scratch: every row's tail shifts by its row, in place
                conv, tails = gdn.conv_tail_decode(
                    tails, li, slots, keep, qkv[:, 0], lp["conv_w"])
                conv = conv[:, None]
            else:
                conv, tails = gdn.conv_tail_reference(
                    tails, li, slots, keep, true_len, qkv, lp["conv_w"])
            q, k, v, g, beta = self.delta_inputs(lp, jax.nn.silu(conv), ba)
            # padded positions leave the state as it is
            g = jnp.where(valid[..., None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)

        if not gdn.use_kernels():
            s0 = jnp.where(keep[:, None, None, None], pool[li, slots], 0.0)
            o, s = gdn.delta_reference(s0, q, k, v, g, beta)
            pool = pool.at[li, slots].set(s)
        elif T == 1:
            with jax.named_scope("gdn_decode"):
                o, pool = gdn.gated_delta_decode(
                    pool, li, slots, keep, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                    beta[:, 0])
            o = o[:, None]
        else:
            with jax.named_scope("gdn_prefill"):
                o, pool = gdn.gated_delta_prefill(
                    pool, li, slots, keep, true_len, q, k, v, g, beta)
        # RMS norm a head (plain weight), gated by silu(z)
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps)
        o = o * lp["o_norm"].astype(f32)
        o = o.reshape(B, T, cfg.value_dim) * jax.nn.silu(z)
        with jax.named_scope("gdn_proj"):
            return _mm(o.astype(h.dtype), lp["w_out"]), pool, tails

    # -- gated attention ----------------------------------------------------

    def rotate(self, x, cos, sin):
        """Rotary embedding on the first ``rotary_dim`` lanes of ``x [B, T,
        H, head_dim]`` (half-split pairing), the rest untouched."""
        r = self.cfg.rotary_dim
        return jnp.concatenate(
            [base._apply_rope(x[..., :r], cos, sin), x[..., r:]], axis=-1)

    def _attention(self, lp, x, kv_all, li, step):
        """-> (the mixer's output [B, T, D] float32, the cache with this
        step's rows)."""
        cfg = self.cfg
        B, T, _ = x.shape
        eps = cfg.rms_norm_eps
        cos, sin = step["rope"]
        h = _norm(x, lp["norm"], eps)
        q, gate, k, v = (_mm(h, lp[w]).astype(h.dtype)
                         for w in ("wq", "wq_gate", "wk", "wv"))
        q = _norm(q.reshape(B, T, cfg.num_heads, cfg.head_dim), lp["q_norm"], eps)
        k = _norm(k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim),
                  lp["k_norm"], eps)
        q, k = self.rotate(q, cos, sin), self.rotate(k, cos, sin)
        # One scatter over the flattened row view, as Llama.forward does: the
        # drop sentinel (nb*bs) maps out of the whole array.
        flat_write = step["flat_write"]
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
            q, kv_all, step["block_tables"], step["kv_lens"],
            step["positions"], li, scale=1.0 / math.sqrt(cfg.head_dim),
            impl=step["attn_impl"],
        ).reshape(B, T, cfg.q_size)
        gated = attn.astype(jnp.float32) * jax.nn.sigmoid(
            gate.astype(jnp.float32))
        return _mm(gated.astype(h.dtype), lp["wo"]), kv_all

    # -- expert block --------------------------------------------------------

    def routed(self, mp, banks, bank_first, u: jax.Array, valid: jax.Array,
               token_budget: Optional[int] = None):
        """This share's part of the routed sum ``[N, D]`` float32 and the
        dispatch's counts: softmax scores over all ``router_experts``, the
        top k, renormalised over the chosen. ``banks``: ``w1``, ``w2`` as ``[groups, k, n]``
        with this layer's experts from group ``bank_first`` on."""
        cfg = self.cfg
        Fe = cfg.moe_intermediate_size

        def body(xs, gmm):
            a = gmm(xs, banks["w1"])
            a = (jax.nn.silu(a[:, :Fe]) * a[:, Fe:]).astype(u.dtype)
            return gmm(a, banks["w2"])

        return moe_dispatch.routed_experts(
            u, u, valid, mp["w_router"], None, body,
            top_k=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
            scale=1.0, scoring="softmax", held=cfg.n_routed_experts,
            expert_first=cfg.expert_first, token_budget=token_budget,
            bank_experts=banks["w1"].shape[0], bank_first=bank_first)

    def shared_expert(self, mp, u: jax.Array) -> jax.Array:
        f32 = jnp.float32
        with jax.named_scope("moe_shared"):
            g, a = _mm(u, mp["w_shared_gate"]), _mm(u, mp["w_shared_up"])
            y = _mm((jax.nn.silu(g) * a).astype(u.dtype), mp["w_shared_down"])
            sig = jax.nn.sigmoid(jnp.einsum(
                "nd,d->n", u, mp["w_shared_sig"], preferred_element_type=f32))
            return sig[:, None] * y

    def _moe(self, mp, banks, bank_first, u, valid, token_budget=None):
        routed, stats = self.routed(mp, banks, bank_first, u, valid, token_budget)
        return routed + self.shared_expert(mp, u), stats
