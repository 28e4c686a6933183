"""Decoder-hybrid-decoder (``model_type: phi4flash``): a self-decoder of
state-space and window-attention layers, one full-attention layer whose
pages are the only ones kept for the whole context, and a cross-decoder of
gated memory units and cross-attention layers that read what the
self-decoder left (SambaY with differential attention, arXiv:2507.06607).

Block ``l`` of ``n`` (``n`` a multiple of 4), LayerNorm twice:
``h = x + mixer_l(LN1(x))``, ``out = h + fc2(silu(g) * u)`` with ``[g, u] =
fc1(LN2(h))``. The mixer by layer:

- ``l < n/2``, even — **Mamba-1** (:mod:`production_stack_tpu.ops.selective_scan`):
  ``[u, z] = in_proj(x)``; ``u = silu(conv(u) + b)`` causal, per channel;
  ``[dt_r, B, C] = x_proj(u)``; ``dt = softplus(dt_proj(dt_r) + b_dt)``; the
  recurrence; ``out_proj(y * silu(z))``.
- ``l < n/2``, odd — **differential attention over a sliding window**.
- ``l = n/2`` — the Mamba layer that also hands its ``y`` (before the gate)
  to the cross-decoder as the memory ``m``.
- ``l = n/2 + 1`` — **differential attention, full causal**.
- ``l >= n/2 + 2``, even — **gated memory unit**: ``out_proj(m *
  silu(in_proj(x)))``, ``m`` of the same position. No state of its own.
- ``l >= n/2 + 2``, odd — **differential cross-attention**: a query and an
  output projection only; keys and values are layer ``n/2 + 1``'s.

No positional encoding: the state-space layers carry position.

**Differential attention on the paged kernels.** A token's keys are stored
as ``num_kv_heads / 2`` heads of ``[k1 | k2]`` (twice the head width on the
lanes), its values as ``[v1 | v2]``, and a layer queries with
``2 x num_heads / 2`` heads ``[q1 | 0]`` and ``[0 | q2]`` at scale
``1 / sqrt(head)``: the kernels' outputs are then ``o1 = [Att(q1, k1, v1) |
Att(q1, k1, v2)]`` and ``o2`` likewise, and ``RMSNorm(o1 - lam * o2) * (1 -
lambda_init)`` is a few lines here (``tests/test_phi4flash.py`` proves the
mapping equal to the four-product form).

**Three kinds of per-request memory** (``make_kv_cache``): ``kv``, pages of
the one full-attention layer, which grow with the context; ``wkv``, pages of
the window layers in a group of their own with its own block table
(``window_tables``), whose pages below the window the cache manager releases
as a sequence advances (the kernels neither fetch nor fold them); ``ssm`` /
``conv``, one slot a sequence for every Mamba layer.

**A prefill step skips the cross-decoder.** Layers up to ``n/2`` and layer
``n/2 + 1``'s key and value projection run on every token of a chunk; that
layer's attention and MLP and everything after run on each row's last
position alone (the one a token may be sampled from), with ``m`` gathered
from the same position, and not at all in a step none of whose rows ends
its prompt (``sample_rows``). A decode step runs every layer.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import xxhash

from ..ops import selective_scan as scan
from ..ops.attention import paged_attention
from . import base

Params = Dict[str, Any]

# What a step reports beside its tokens (``step_aux``; the runner sums the
# fetched steps' rows under these names): the positions a prefill step ran
# the cross-decoder on, counted where it runs from the batch it is handed.
AUX_NAMES = ("cross_decoder_positions_total",)

_F32 = ("dt_bias", "A_log", "D", "lambda_q1", "lambda_k1", "lambda_q2",
        "lambda_k2")


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig(base.ModelConfig):
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    # Mamba-1
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"
    name: str = "phi4flash"
    eos_token_ids: Tuple[int, ...] = (199999,)
    bos_token_id: Optional[int] = 199999

    # What the engine asks of any model config.
    recurrent = True  # has per-sequence state beside the paged KV
    window_pages = True  # a group of pages released below the window

    def __post_init__(self):
        if self.num_layers % 4 or self.num_layers < 8:
            raise ValueError(
                f"num_hidden_layers {self.num_layers}: the layer map needs a "
                "multiple of 4, at least 8")
        if self.num_heads % 2 or self.num_kv_heads % 2 or (
                self.num_heads // 2) % (self.num_kv_heads // 2):
            raise ValueError(
                "differential attention pairs heads: num_attention_heads and "
                "num_key_value_heads even, the pairs a multiple of each other")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return -(-self.hidden_size // 16)

    @property
    def q_size(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_size(self) -> int:
        return self.num_kv_heads * self.head_dim

    # layer counts by kind
    @property
    def self_pairs(self) -> int:
        """(Mamba, window attention) pairs of the self-decoder."""
        return self.num_layers // 4

    @property
    def cross_pairs(self) -> int:
        """(gated memory unit, cross-attention) pairs."""
        return self.num_layers // 4 - 1

    @property
    def num_mamba_layers(self) -> int:
        return self.self_pairs + 1

    @property
    def paged_query_shape(self) -> "tuple[int, int]":
        """A query head spans its key-value pair's two heads
        (``paired_queries``)."""
        return self.num_heads, 2 * self.head_dim

    @property
    def num_window_layers(self) -> int:
        return self.self_pairs

    num_kv_layers = 1  # the one full-attention layer

    def lambda_init(self, layer):
        return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))

    # -- what the cache manager sizes its groups from ---------------------

    def page_bytes(self, block_size: int, itemsize: int,
                   tp: int = 1, pp: int = 1) -> int:
        """A page of the global group: the full-attention layer's keys and
        values of ``block_size`` tokens."""
        return 2 * block_size * self.kv_size * itemsize

    def window_page_bytes(self, block_size: int, itemsize: int) -> int:
        """A page of the window group, over every window layer."""
        return self.num_window_layers * self.page_bytes(block_size, itemsize)

    def state_bytes_per_slot(self) -> int:
        """Recurrent state and tail of one sequence over every Mamba layer."""
        s = self.d_inner * self.d_state * 4
        tail = (self.d_conv - 1) * self.d_inner * self.jdtype.itemsize
        return self.num_mamba_layers * (s + tail)


def config_from_hf(hf: dict, name: str = "") -> Phi4FlashConfig:
    """The ``phi4flash`` keys of an HF ``config.json``. What the published
    file leaves to the modelling file (``d_state``, ``d_conv``, ``expand``)
    may be given beside them and defaults to the modelling file's."""
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {hf['hidden_act']!r}: silu only")
    if hf.get("mlp_bias") or hf.get("lm_head_bias"):
        raise ValueError("mlp_bias / lm_head_bias are not built")
    if hf.get("mb_per_layer", 2) != 2:
        raise ValueError(
            f"mb_per_layer {hf['mb_per_layer']}: the layer map is built for "
            "a state-space layer every second layer")
    if not hf.get("sliding_window"):
        raise ValueError("phi4flash needs a sliding_window")
    eos = hf.get("eos_token_id", 199999)
    return Phi4FlashConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        sliding_window=int(hf["sliding_window"]),
        mb_per_layer=hf.get("mb_per_layer", 2),
        d_state=hf.get("mamba_d_state", 16),
        d_conv=hf.get("mamba_d_conv", 4),
        expand=hf.get("mamba_expand", 2),
        layer_norm_eps=hf.get("layer_norm_eps", 1e-5),
        max_position_embeddings=hf.get("max_position_embeddings", 4096),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", True)),
        dtype=hf.get("torch_dtype") or "bfloat16",
        name=name or hf.get("_name_or_path", "phi4flash"),
        eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
        bos_token_id=hf.get("bos_token_id"),
    )


def init_leaf(name: str, shape, dtype, key: jax.Array) -> jax.Array:
    """One leaf's random init by its name. Norm weights 1; every bias small
    and non-zero (a dropped bias then shows); the state-space leaves so that
    state is carried over many positions (``A = -(1..N)`` a channel, ``dt``
    log-uniform in [0.001, 0.1]); the lambda vectors normal(0, 0.1)."""
    if name in ("ln1_w", "ln2_w", "subln"):
        return jnp.ones(shape, dtype)
    if name == "A_log":  # [N, Di]: log(1..N) down the states
        n = jnp.arange(1, shape[0] + 1, dtype=jnp.float32)
        return jnp.broadcast_to(jnp.log(n)[:, None], shape)
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name == "dt_bias":  # softplus(dt_bias) log-uniform in [0.001, 0.1]
        dt = jnp.exp(
            jax.random.uniform(key, shape, jnp.float32)
            * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name.startswith("lambda_"):
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    if name.startswith("b") or name.endswith("_b"):
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    fan_in = shape[-1] if name == "embed" else shape[-2]
    return (
        jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)
    ).astype(dtype)


def _layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def _mm(x, w):
    return jnp.einsum("btd,de->bte", x, w, preferred_element_type=jnp.float32)


class Phi4Flash(base.Model):
    """Stateless model functions bound to a config (the runner's model
    object, as :class:`production_stack_tpu.models.llama.Llama` is)."""

    # A prefill step runs the cross-decoder on each row's last position alone:
    # the runner says which rows a token is sampled from (``sample_rows``).
    SKIPS_CROSS_DECODER = True
    AUX_NAMES = AUX_NAMES  # rows the runner appends to a step's packed tokens

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------

    def leaf_shapes(self) -> Dict[str, Dict[str, tuple]]:
        """Per group of layers, each leaf's per-layer shape. The groups:
        ``self_mamba`` / ``self_attn`` (the pairs of the self-decoder,
        scanned), ``mid_mamba`` / ``mid_attn`` (layers ``n/2`` and ``n/2 +
        1``), ``gmu`` / ``cross`` (the pairs of the cross-decoder, scanned).
        Every group carries its block's norms and MLP."""
        c = self.cfg
        D, F, Di, N = c.hidden_size, c.intermediate_size, c.d_inner, c.d_state
        hd2 = 2 * c.head_dim
        block = {
            "ln1_w": (D,), "ln1_b": (D,), "ln2_w": (D,), "ln2_b": (D,),
            "fc1": (D, 2 * F), "fc2": (F, D),
        }
        mamba = {
            "in_proj": (D, 2 * Di), "conv_w": (c.d_conv, Di), "conv_b": (Di,),
            "x_proj": (Di, c.dt_rank + 2 * N), "dt_proj": (c.dt_rank, Di),
            "dt_bias": (Di,), "A_log": (N, Di), "D": (Di,),
            "out_proj": (Di, D),
        }
        query = {
            "wq": (D, c.q_size), "bq": (c.q_size,), "wo": (c.q_size, D),
            "bo": (D,), "lambda_q1": (c.head_dim,), "lambda_k1": (c.head_dim,),
            "lambda_q2": (c.head_dim,), "lambda_k2": (c.head_dim,),
            "subln": (hd2,),
        }
        attn = {**query, "wkv": (D, 2 * c.kv_size), "bkv": (2 * c.kv_size,)}
        gmu = {"gmu_in": (D, Di), "gmu_out": (Di, D)}
        return {
            "self_mamba": {**block, **mamba},
            "self_attn": {**block, **attn},
            "mid_mamba": {**block, **mamba},
            "mid_attn": {**block, **attn},
            "gmu": {**block, **gmu},
            "cross": {**block, **query},
        }

    def _stack(self, group: str) -> int:
        """Layers stacked in a group (0: one layer, its leaves unstacked)."""
        c = self.cfg
        return {"self_mamba": c.self_pairs, "self_attn": c.self_pairs,
                "gmu": c.cross_pairs, "cross": c.cross_pairs}.get(group, 0)

    def init_params(self, rng: jax.Array) -> Params:
        """Random initialisation, a leaf and a layer at a time (each its own
        key, so that no temporary is larger than one layer's leaf)."""
        c = self.cfg
        d = c.jdtype

        def key_of(name: str) -> jax.Array:
            return jax.random.fold_in(
                rng, xxhash.xxh32(name.encode()).intdigest() & 0x7FFF_FFFF)

        layers: Params = {}
        for group, leaves in self.leaf_shapes().items():
            n = self._stack(group)
            layers[group] = {}
            for leaf, shape in leaves.items():
                dtype = jnp.float32 if leaf in _F32 else d
                key = key_of(f"{group}.{leaf}")
                per = [init_leaf(leaf, shape, dtype, jax.random.fold_in(key, i))
                       for i in range(max(n, 1))]
                layers[group][leaf] = jnp.stack(per) if n else per[0]
        V, D = c.vocab_size, c.hidden_size
        params: Params = {
            "embed": init_leaf("embed", (V, D), d, key_of("embed")),
            "layers": layers,
            "final_norm": jnp.ones((D,), d),
            "final_norm_b": init_leaf("final_norm_b", (D,), d,
                                      key_of("final_norm_b")),
        }
        if not c.tie_word_embeddings:
            params["lm_head"] = init_leaf("embed", (V, D), d, key_of("lm_head"))
        return params

    # ------------------------------------------------------------------
    # Per-request memory: two page groups and the state slots
    # ------------------------------------------------------------------

    def make_kv_cache(
        self, num_blocks: int, block_size: int, dtype: Optional[str] = None,
        state_slots: int = 1, window_blocks: int = 1,
    ) -> Dict[str, jax.Array]:
        """``kv``: pages of the full-attention layer, ``[k1 | k2]`` heads on
        the lanes, in ``Llama``'s page layout. ``wkv``: the window layers'
        pages, a group of its own. ``ssm`` / ``conv``: one slot a sequence
        and one more, the last, that padding rows write to. ``aux``: what
        the last step reported (:meth:`step_aux`)."""
        c = self.cfg
        d = jnp.dtype(dtype) if dtype else c.jdtype
        n_m = c.num_mamba_layers
        return {
            "kv": jnp.zeros((1, num_blocks, 2, block_size, c.kv_size), d),
            "wkv": jnp.zeros(
                (c.num_window_layers, window_blocks, 2, block_size, c.kv_size),
                d),
            "ssm": jnp.zeros(
                (n_m, state_slots + 1, c.d_state, c.d_inner), jnp.float32),
            # a slot's tail as one row: a [K - 1, Di] tail would be tiled
            # with its three rows padded, and copied to another layout and
            # back around every gather
            "conv": jnp.zeros(
                (n_m, state_slots + 1, (c.d_conv - 1) * c.d_inner), c.jdtype),
            "aux": jnp.zeros((len(AUX_NAMES),), jnp.float32),
        }

    # ------------------------------------------------------------------
    # Forward
    # ------------------------------------------------------------------

    def forward(
        self,
        params: Params,
        tokens: jax.Array,  # [B, T]
        positions: jax.Array,  # [B, T]
        write_idx: jax.Array,  # [B, T] flat slot of the global group
        block_tables: jax.Array,  # [B, W] global group
        kv_lens: jax.Array,  # [B] valid kv length after this step's writes
        last_idx: jax.Array,  # [B] index in T of each row's last real token
        cache: Dict[str, jax.Array],
        *,
        state_slots: jax.Array,  # [B] each row's slot (padding: any)
        window_tables: jax.Array,  # [B, W] window group, same indexing
        sample_rows: Optional[jax.Array] = None,  # [B] a token is sampled
        attn_impl: str = "auto",
        all_logits: bool = False,
        **_unused,  # token_budget, lora_*, moe_impl, pp_size, mesh
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """One engine step; ``Llama.forward``'s contract plus the slots and
        the window group's tables."""
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

        # Where a token lands in the window group: its page by the group's
        # own table, dropped where the global write is.
        _, nb, _, bs, _ = cache["kv"].shape
        nbw = cache["wkv"].shape[1]
        wblk = jnp.take_along_axis(
            window_tables, jnp.minimum(positions // bs,
                                       window_tables.shape[1] - 1), axis=1)
        flat = write_idx.reshape(-1)
        w_flat = jnp.where(
            flat >= nb * bs, nbw * bs,
            wblk.reshape(-1) * bs + positions.reshape(-1) % bs)

        x = base._embed_lookup(params, tokens, cfg)
        layers = params["layers"]
        kv, wkv, pool, tails = (cache["kv"], cache["wkv"], cache["ssm"],
                                cache["conv"])

        def self_pair(carry, xs):
            x, wkv, pool, tails = carry
            mp, ap, i = xs
            with jax.named_scope("ssm_mixer"):
                out, _, pool, tails = self._mamba(mp, x, pool, tails, i, rows)
            x = self._mlp(mp, x + out.astype(x.dtype))
            with jax.named_scope("window_attn"):
                h = _layer_norm(x, ap["ln1_w"], ap["ln1_b"], cfg.layer_norm_eps)
                wkv = self._write_pages(ap, h, wkv, i, w_flat)
                out = self._diff_attention(
                    ap, h, wkv, i, window_tables, kv_lens, positions,
                    2 * i + 1, attn_impl, window=cfg.sliding_window)
            x = self._mlp(ap, x + out.astype(x.dtype))
            return (x, wkv, pool, tails), None

        n_pairs = cfg.self_pairs
        (x, wkv, pool, tails), _ = jax.lax.scan(
            self_pair, (x, wkv, pool, tails),
            (layers["self_mamba"], layers["self_attn"],
             jnp.arange(n_pairs, dtype=jnp.int32)))

        # Layer n/2: the Mamba layer that hands on its scan output.
        mp = layers["mid_mamba"]
        with jax.named_scope("ssm_mixer"):
            out, m, pool, tails = self._mamba(
                mp, x, pool, tails, jnp.int32(n_pairs), rows)
        x = self._mlp(mp, x + out.astype(x.dtype))

        # Layer n/2 + 1: keys and values of every token; everything after on
        # the sampled positions alone.
        ap = layers["mid_attn"]
        full_layer = cfg.num_layers // 2 + 1
        h = _layer_norm(x, ap["ln1_w"], ap["ln1_b"], cfg.layer_norm_eps)
        kv = self._write_pages(ap, h, kv, 0, flat)
        q_pos = positions
        skipping = T > 1 and not all_logits  # all_logits: every token
        if skipping:
            take = lambda a: jnp.take_along_axis(  # noqa: E731
                a, last_idx[:, None, None], axis=1)
            x, h, m = take(x), take(h), take(m)
            q_pos = jnp.take_along_axis(positions, last_idx[:, None], axis=1)
            last_idx = jnp.zeros_like(last_idx)

        def rest(x, h, m, kv):
            """-> (x after the last layer, the positions it was run on: as
            many as the batch handed over holds, padding rows included)."""
            out = self._diff_attention(
                ap, h, kv, 0, block_tables, kv_lens, q_pos, full_layer,
                attn_impl)
            x = self._mlp(ap, x + out.astype(x.dtype))
            x = self._cross_decoder(
                layers, x, m, kv, block_tables, kv_lens, q_pos, attn_impl)
            return x, jnp.float32(x.shape[0] * x.shape[1])

        if skipping and sample_rows is not None:
            # A step none of whose rows ends its prompt samples nothing
            # anyone reads: the cross-decoder's weights are not even read.
            x, ran = jax.lax.cond(
                jnp.any(sample_rows), rest,
                lambda x, h, m, kv: (x, jnp.float32(0)), x, h, m, kv)
        else:
            x, ran = rest(x, h, m, kv)
        # cross_decoder_positions_total counts prefill steps: a decode step
        # runs every layer on its one position a row by definition (and a
        # prefill chunk of one token looks like one here: not counted)
        aux = (ran if T > 1 else jnp.float32(0)).reshape(1)

        x = _layer_norm(x, params["final_norm"], params["final_norm_b"],
                        cfg.layer_norm_eps)
        head = params["lm_head" if "lm_head" in params else "embed"]
        if all_logits:
            logits = jnp.einsum(
                "btd,vd->btv", x, head, preferred_element_type=jnp.float32)
        else:
            last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
            logits = jnp.einsum(
                "bd,vd->bv", last, head, preferred_element_type=jnp.float32)
        return logits, {"kv": kv, "wkv": wkv, "ssm": pool, "conv": tails,
                        "aux": aux}

    def _cross_decoder(self, layers, x, m, kv, block_tables, kv_lens, q_pos,
                       attn_impl):
        """Layers ``n/2 + 2`` on: pairs of a gated memory unit and a
        cross-attention layer over the full-attention layer's pages."""
        cfg = self.cfg
        full_layer = cfg.num_layers // 2 + 1

        def cross_pair(x, xs):
            gp, cp, i = xs
            with jax.named_scope("gmu"):
                h = _layer_norm(x, gp["ln1_w"], gp["ln1_b"], cfg.layer_norm_eps)
                g = _mm(h, gp["gmu_in"])
                out = _mm((m.astype(jnp.float32) * jax.nn.silu(g)).astype(
                    x.dtype), gp["gmu_out"])
            x = self._mlp(gp, x + out.astype(x.dtype))
            with jax.named_scope("cross_attn"):
                h = _layer_norm(x, cp["ln1_w"], cp["ln1_b"], cfg.layer_norm_eps)
                out = self._diff_attention(
                    cp, h, kv, 0, block_tables, kv_lens, q_pos,
                    full_layer + 2 + 2 * i, attn_impl)
            x = self._mlp(cp, x + out.astype(x.dtype))
            return x, None

        x, _ = jax.lax.scan(
            cross_pair, x,
            (layers["gmu"], layers["cross"],
             jnp.arange(cfg.cross_pairs, dtype=jnp.int32)))
        return x

    # -- the block's MLP ---------------------------------------------------

    def _mlp(self, lp, x):
        cfg = self.cfg
        h = _layer_norm(x, lp["ln2_w"], lp["ln2_b"], cfg.layer_norm_eps)
        g, u = jnp.split(_mm(h, lp["fc1"]), 2, axis=-1)
        out = _mm((jax.nn.silu(g) * u).astype(x.dtype), lp["fc2"])
        return x + out.astype(x.dtype)

    # -- Mamba-1 -------------------------------------------------------------

    def _mamba(self, lp, x, pool, tails, li, rows):
        """-> (the mixer's output [B, T, D] float32, the scan's ``y`` before
        the gate [B, T, Di] in the model dtype, pool, tails)."""
        cfg = self.cfg
        slots, true_len, valid, keep = rows
        B, T, _ = x.shape
        Di, N, K, R = cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank
        f32 = jnp.float32
        h = _layer_norm(x, lp["ln1_w"], lp["ln1_b"], cfg.layer_norm_eps)
        u, z = jnp.split(_mm(h, lp["in_proj"]).astype(h.dtype), 2, axis=-1)

        # Causal depthwise convolution over [tail | this step's rows].
        tail = tails[li, slots].reshape(B, K - 1, Di)
        tail = jnp.where(keep[:, None, None], tail, jnp.zeros_like(tail))
        window = jnp.concatenate([tail, u], axis=1)  # [B, K-1+T, Di]
        conv = lp["conv_b"].astype(f32)
        for k in range(K):
            conv = conv + window[:, k:k + T].astype(f32) * lp["conv_w"][k].astype(f32)
        u = jax.nn.silu(conv).astype(h.dtype)
        # The tail at the row's true length: rows [len, len + K - 1) of the
        # window are positions len - (K - 1) .. len - 1.
        new_tail = jax.vmap(
            lambda w, n: jax.lax.dynamic_slice_in_dim(w, n, K - 1, axis=0)
        )(window, true_len)
        tails = tails.at[li, slots].set(new_tail.reshape(B, (K - 1) * Di))

        dbc = _mm(u, lp["x_proj"])  # [B, T, R + 2N] float32
        dt_r, bm, cm = jnp.split(dbc, [R, R + N], axis=-1)
        dt = jax.nn.softplus(
            _mm(dt_r.astype(h.dtype), lp["dt_proj"]) + lp["dt_bias"])
        dt = jnp.where(valid[..., None], dt, 0.0)  # padded: state untouched
        a_t = -jnp.exp(lp["A_log"])  # [N, Di]

        if not scan.use_kernels():
            s0 = jnp.where(keep[:, None, None], pool[li, slots], 0.0)
            y, s = scan.scan_reference(s0, u, dt, a_t, bm, cm, lp["D"])
            pool = pool.at[li, slots].set(s)
        elif T == 1:
            with jax.named_scope("ssm_decode"):
                y, pool = scan.selective_scan_decode(
                    pool, li, slots, keep, u[:, 0], dt[:, 0], a_t, bm[:, 0],
                    cm[:, 0], lp["D"])
            y = y[:, None]
        else:
            with jax.named_scope("ssm_prefill"):
                y, pool = scan.selective_scan_prefill(
                    pool, li, slots, keep, true_len, u, dt, a_t, bm, cm,
                    lp["D"])
        out = _mm((y * jax.nn.silu(z.astype(f32))).astype(h.dtype),
                  lp["out_proj"])
        return out, y.astype(h.dtype), pool, tails

    # -- differential attention over pages ---------------------------------

    def _write_pages(self, lp, h, pages, li, flat_write):
        """This step's keys and values into ``pages`` at layer ``li``: one
        scatter over the flattened row view, as ``Llama.forward`` does (the
        drop sentinel ``nb * bs`` maps out of the whole array). A token's
        ``kv_size`` keys are its pairs' ``[k1 | k2]`` as they come."""
        cfg = self.cfg
        B, T, _ = h.shape
        kvp = (_mm(h, lp["wkv"]) + lp["bkv"].astype(jnp.float32)).astype(
            pages.dtype)
        k, v = jnp.split(kvp.reshape(B * T, 2 * cfg.kv_size), 2, axis=-1)
        n_l, nb, _, bs, _ = pages.shape
        idx_k = jnp.where(
            flat_write >= nb * bs, n_l * nb * 2 * bs,
            (li * nb + flat_write // bs) * (2 * bs) + flat_write % bs)
        return (
            pages.reshape(n_l * nb * 2 * bs, cfg.kv_size)
            .at[jnp.concatenate([idx_k, idx_k + bs])]
            .set(jnp.concatenate([k, v]), mode="drop")
            .reshape(pages.shape)
        )

    def paired_queries(self, q: jax.Array) -> jax.Array:
        """``q [B, T, q_size]`` -> ``[B, T, num_heads, 2 head]``: pair ``a``'s
        ``[q1 | 0]`` then ``[0 | q2]``, so that heads ``4p .. 4p + 3`` read
        key-value pair ``p``."""
        cfg = self.cfg
        B, T, _ = q.shape
        hd = cfg.head_dim
        q = q.reshape(B, T, cfg.num_heads // 2, 2, hd)
        zero = jnp.zeros_like(q[..., 0, :])
        return jnp.stack(
            [jnp.concatenate([q[..., 0, :], zero], -1),
             jnp.concatenate([zero, q[..., 1, :]], -1)], axis=3,
        ).reshape(B, T, cfg.num_heads, 2 * hd)

    def combine(self, lp, attn: jax.Array, layer) -> jax.Array:
        """The kernels' ``[B, T, num_heads, 2 head]`` (``o1`` then ``o2`` a
        pair) -> ``RMSNorm(o1 - lam * o2) * (1 - lambda_init)`` ``[B, T,
        q_size]`` float32."""
        cfg = self.cfg
        B, T = attn.shape[:2]
        f32 = jnp.float32
        init = cfg.lambda_init(layer)
        lam = (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
               - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + init)
        o = attn.astype(f32).reshape(B, T, cfg.num_heads // 2, 2, -1)
        d = o[..., 0, :] - lam * o[..., 1, :]
        d = d * jax.lax.rsqrt(
            jnp.mean(d * d, -1, keepdims=True) + cfg.layer_norm_eps)
        d = d * lp["subln"].astype(f32) * (1.0 - init)
        return d.reshape(B, T, cfg.q_size)

    def _diff_attention(self, lp, h, pages, li, tables, kv_lens, positions,
                        layer, attn_impl, window=0):
        cfg = self.cfg
        q = (_mm(h, lp["wq"]) + lp["bq"].astype(jnp.float32)).astype(h.dtype)
        attn = paged_attention(
            self.paired_queries(q), pages, tables, kv_lens, positions, li,
            scale=1.0 / math.sqrt(cfg.head_dim), impl=attn_impl, window=window)
        o = self.combine(lp, attn, layer).astype(h.dtype)
        return _mm(o, lp["wo"]) + lp["bo"].astype(jnp.float32)
