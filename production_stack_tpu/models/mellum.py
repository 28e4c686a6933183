"""Window and full attention mixed, every layer followed by a
mixture-of-experts block (``model_type: mellum``): ``layer_types`` is a
period of ``sliding_attention`` layers closed by one ``full_attention``
layer, repeated.

``h = x + Attn_t(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))`` with ``t`` the
layer's type; a final RMSNorm before the untied head.

- **Attention**: ``q``, ``k``, ``v`` without bias; queries and keys
  RMS-normalised a head (plain weight); rotate-half rotary embedding over
  every lane with the inverse frequencies of ``t``: a ``sliding_attention``
  layer's are the default ``theta^(-2i / head)``, a ``full_attention``
  layer's YaRN's (``base.yarn_inv_freq``) with ``cos`` and ``sin`` scaled by
  ``attention_factor``; grouped causal attention at ``head^-1/2``; a
  ``sliding_attention`` query at ``p`` sees keys ``p - window + 1 .. p``.
- **Expert block**: softmax over **all** ``router_experts`` in float32, the
  top ``num_experts_per_tok``, renormalised over the chosen
  (``norm_topk_prob``); gated SwiGLU experts, no shared expert. **The layer
  holds a share**: ``n_routed_experts`` of the ``router_experts`` the router
  scores, from ``expert_first`` on (``models/moe_dispatch.py``). Pairs routed
  elsewhere are dropped before the grouped products; nothing stands in for
  the other ranks.

Two page groups (``make_kv_cache``), each layer's keys and values in the
group of its type: ``kv``, the global group, one layer a period, a page kept
for the whole context; ``wkv``, the window group, whose pages the cache
manager releases once a sequence has moved a window past them
(``engine/kv_manager.py``) and matches again by their hashes when the same
prefix comes back. ``forward`` hands each layer its group's table; the paged
kernels neither fetch nor fold a window layer's pages below the window.

The seven periods are one scanned body (the window layers an inner scan), so
a step's program holds one period's layers, not 28.

The draft head of the published checkpoint (``described_as``: "MTP head") is
beside the model and not built for this class. A class that brings its
module serves it (``--speculative-mtp``: ``models/exaone_moe.py``, a
verify-and-draft step on the device); ``engine/spec.py`` drafts n-grams.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import xxhash

from ..ops.attention import paged_attention
from . import base, moe_dispatch
from .moe_dispatch import AUX_NAMES, AUX_WIDTH

Params = Dict[str, Any]

SLIDING, FULL = "sliding_attention", "full_attention"
_BANKS = ("w1", "w2")


@dataclasses.dataclass(frozen=True)
class MellumConfig(base.ModelConfig):
    vocab_size: int = 98304
    hidden_size: int = 2304
    num_layers: int = 28
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 7
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    # rotary embedding by layer type: the window layers' default, the full
    # layers' YaRN (``yarn_factor`` 0: the default there too)
    rope_theta: float = 500000.0
    full_rope_theta: float = 500000.0
    yarn_factor: float = 16.0
    yarn_original_max_position: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782
    # expert block: ``n_routed_experts`` held of ``router_experts`` scored
    n_routed_experts: int = 64
    router_experts: int = 64
    expert_first: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    name: str = "mellum"
    eos_token_ids: Tuple[int, ...] = (2,)
    bos_token_id: Optional[int] = None

    # What the engine asks of any model config.
    window_pages = True  # the window layers' page group, released below it

    def __post_init__(self):
        types = tuple(self.layer_types)
        n = types.index(FULL) + 1 if FULL in types else 0
        if (n < 2 or len(types) != self.num_layers
                or types != ((SLIDING,) * (n - 1) + (FULL,)) * (len(types) // n)):
            raise ValueError(
                f"layer_types {types} is not whole periods of "
                "sliding_attention layers closed by one full_attention layer "
                f"over num_hidden_layers {self.num_layers}")
        if self.sliding_window <= 0:
            raise ValueError("mellum needs a sliding_window")

    @property
    def period(self) -> int:
        """Layers a period: its window layers and the full layer."""
        return self.layer_types.index(FULL) + 1

    @property
    def periods(self) -> int:
        return self.num_layers // self.period

    @property
    def num_kv_layers(self) -> int:
        """Layers of the global group: the KV pool is sized from these."""
        return self.periods

    @property
    def num_window_layers(self) -> int:
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

    def window_page_bytes(self, block_size: int, itemsize: int) -> int:
        """A page of the window group, over every window layer."""
        return self.num_window_layers * 2 * block_size * self.kv_size * itemsize

    def inv_freq(self, layer_type: str) -> Tuple[np.ndarray, float]:
        """(inverse frequencies ``[head / 2]``, the scale of ``cos`` and
        ``sin``) of a layer type's rotary embedding."""
        if layer_type == FULL and self.yarn_factor:
            return base.yarn_inv_freq(
                self.head_dim, self.full_rope_theta, self.yarn_factor,
                self.yarn_original_max_position, self.yarn_beta_fast,
                self.yarn_beta_slow), self.yarn_attention_factor
        theta = self.full_rope_theta if layer_type == FULL else self.rope_theta
        half = self.head_dim // 2
        return theta ** (-np.arange(half, dtype=np.float64) / half), 1.0


def config_from_hf(hf: dict, name: str = "") -> MellumConfig:
    """The ``mellum`` keys of an HF ``config.json``. Beside them, an
    expert-parallel share: ``num_experts`` is what this engine holds,
    ``ep_share`` = ``{"first": i, "of": n}`` says of how many the router is
    and where the held ones start (absent: it holds them all)."""
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {hf['hidden_act']!r}: silu only")
    if hf.get("attention_bias"):
        raise ValueError("attention_bias is not built for mellum")
    n_layers = hf["num_hidden_layers"]
    if any(t != "sparse" for t in hf.get("mlp_layer_types") or []):
        raise ValueError(
            "mlp_layer_types: a dense MLP layer is not built for mellum")
    if not hf.get("use_sliding_window", True) or not hf.get("sliding_window"):
        raise ValueError("mellum needs use_sliding_window and a sliding_window")
    ropes = hf.get("rope_parameters") or {}
    win, full = ropes.get(SLIDING) or {}, ropes.get(FULL) or {}
    if win.get("rope_type", "default") != "default":
        raise ValueError(
            f"rope_parameters.{SLIDING}.rope_type {win['rope_type']!r}: "
            "default only")
    kind = full.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise ValueError(
            f"rope_parameters.{FULL}.rope_type {kind!r}: default or yarn")
    if kind == "yarn" and not full.get("truncate", True):
        raise ValueError("rope_parameters: yarn without truncate is not built")
    factor = float(full.get("factor", 1.0)) if kind == "yarn" else 0.0
    held = hf["num_experts"]
    share = hf.get("ep_share") or {"first": 0, "of": held}
    first, of = int(share["first"]), int(share["of"])
    if not 0 <= first <= of - held:
        raise ValueError(
            f"ep_share {share}: {held} experts from {first} do not lie "
            f"within {of}")
    heads = hf["num_attention_heads"]
    eos = hf.get("eos_token_id", 2)
    return MellumConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        num_layers=n_layers,
        layer_types=tuple(hf["layer_types"]),
        num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        sliding_window=int(hf["sliding_window"]),
        rope_theta=float(win.get("rope_theta", hf.get("rope_theta", 10000.0))),
        full_rope_theta=float(
            full.get("rope_theta", hf.get("rope_theta", 10000.0))),
        yarn_factor=factor,
        yarn_original_max_position=int(full.get(
            "original_max_position_embeddings",
            hf.get("max_position_embeddings", 4096))),
        yarn_beta_fast=float(full.get("beta_fast") or 32.0),
        yarn_beta_slow=float(full.get("beta_slow") or 1.0),
        yarn_attention_factor=float(
            full.get("attention_factor")
            or (0.1 * math.log(factor) + 1.0 if factor > 1.0 else 1.0)),
        n_routed_experts=held,
        router_experts=of,
        expert_first=first,
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
        max_position_embeddings=hf.get("max_position_embeddings", 4096),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        dtype=hf.get("torch_dtype") or "bfloat16",
        name=name or hf.get("_name_or_path", "mellum"),
        eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
        bos_token_id=hf.get("bos_token_id"),
    )


def init_leaf(name: str, shape, dtype, key: jax.Array) -> jax.Array:
    """One leaf's random init by its name. Norm weights ``1 + normal(0,
    0.1)`` (not all ones: a weight that is skipped then shows); matrices
    normal with std ``fan_in^-1/2``."""
    if "norm" in name:
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(
            dtype)
    # [.., in, out]; the rows of [V, D] contract their last axis
    fan_in = shape[-1] if name in ("embed", "lm_head") else shape[-2]
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def _mm(x, w):
    return jnp.einsum("...d,de->...e", x, w, preferred_element_type=jnp.float32)


class Mellum(base.Model):
    """Stateless model functions bound to a config (the runner's model
    object)."""

    AUX_NAMES = AUX_NAMES  # rows the runner appends to a step's packed tokens
    TOKEN_BUDGET = True  # the expert dispatch packs a padded step's tokens

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------

    def leaf_shapes(self) -> Dict[str, Dict[str, tuple]]:
        """Per kind, each leaf's per-layer shape; both kinds hold every
        layer, in layer order."""
        c = self.cfg
        D, Fe = c.hidden_size, c.moe_intermediate_size
        return {
            "attn": {
                "norm": (D,),
                "wq": (D, c.q_size),
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
            },
        }

    def init_params(self, rng: jax.Array) -> Params:
        """Random initialisation, a leaf and a layer at a time under a
        ``lax.map`` (each layer its own key): no temporary is larger than
        one layer's leaf in float32."""
        c = self.cfg
        d = c.jdtype

        def key_of(name: str) -> jax.Array:
            return jax.random.fold_in(
                rng, xxhash.xxh32(name.encode()).intdigest() & 0x7FFF_FFFF)

        layers: Params = {
            kind: {
                leaf: jax.lax.map(
                    lambda i, leaf=leaf, shape=shape,
                    dtype=jnp.float32 if leaf == "w_router" else d,
                    key=key_of(f"{kind}.{leaf}"): init_leaf(
                        leaf, shape, dtype, jax.random.fold_in(key, i)),
                    jnp.arange(c.num_layers))
                for leaf, shape in leaves.items()}
            for kind, leaves in self.leaf_shapes().items()}
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
    # Per-request memory: a page group a layer type
    # ------------------------------------------------------------------

    def make_kv_cache(
        self, num_blocks: int, block_size: int, dtype: Optional[str] = None,
        window_blocks: int = 1,
    ) -> Dict[str, jax.Array]:
        """``kv``: pages of the full-attention layers, one a period, in
        ``Llama``'s page layout. ``wkv``: the window layers' pages, a group
        of its own. ``aux``: what the last step reported
        (:meth:`step_aux`)."""
        c = self.cfg
        d = jnp.dtype(dtype) if dtype else c.jdtype
        return {
            "kv": jnp.zeros(
                (c.num_kv_layers, num_blocks, 2, block_size, c.kv_size), d),
            "wkv": jnp.zeros(
                (c.num_window_layers, window_blocks, 2, block_size, c.kv_size),
                d),
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
        write_idx: jax.Array,  # [B, T] flat slot of the global group
        block_tables: jax.Array,  # [B, W] global group
        kv_lens: jax.Array,  # [B] valid kv length after this step's writes
        last_idx: jax.Array,  # [B] index in T of each row's last real token
        cache: Dict[str, jax.Array],
        *,
        window_tables: jax.Array,  # [B, W] window group, same indexing
        token_budget: Optional[int] = None,  # most real tokens a step holds
        attn_impl: str = "auto",
        all_logits: bool = False,
        **_unused,  # lora_idx, lora_scale, moe_impl, pp_size, mesh: refused
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """One engine step; ``Llama.forward``'s contract plus the window
        group's tables."""
        cfg = self.cfg
        B, T = tokens.shape
        valid = (jnp.arange(T, dtype=jnp.int32)[None, :]
                 < jnp.where(kv_lens > 0, last_idx + 1, 0)[:, None])

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

        def rope(layer_type):
            inv, scale = cfg.inv_freq(layer_type)
            angles = (positions.astype(jnp.float32)[..., None]
                      * jnp.asarray(inv, jnp.float32))
            return jnp.cos(angles) * scale, jnp.sin(angles) * scale

        common = dict(kv_lens=kv_lens, positions=positions, attn_impl=attn_impl)
        win_step = dict(common, flat_write=w_flat, tables=window_tables,
                        rope=rope(SLIDING), window=cfg.sliding_window)
        full_step = dict(common, flat_write=flat, tables=block_tables,
                         rope=rope(FULL), window=0)

        layers = params["layers"]
        attn, moe = layers["attn"], layers["moe"]
        held, n_win = cfg.n_routed_experts, cfg.period - 1
        # The banks stay whole and closed over: a layer reads its experts in
        # place, as groups of one big bank. So do the other stacks: a layer's
        # leaves are indexed where they are used (a period's slice handed to
        # the inner scan would be copied first).
        banks = {w: moe[w].reshape((cfg.num_layers * held,) + moe[w].shape[2:])
                 for w in _BANKS}
        at = lambda stack, i: {  # noqa: E731
            k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
            for k, v in stack.items() if k not in _BANKS}
        flat_valid = valid.reshape(-1)

        def layer(li, x, pages, page_layer, step, aux):
            """One decoder layer ``li`` on page layer ``page_layer`` of its
            group's ``pages``."""
            out, pages = self._attention(at(attn, li), x, pages, page_layer, step)
            x = x + out.astype(x.dtype)
            mp = at(moe, li)
            u = base._rms_norm(x, mp["norm"], cfg.rms_norm_eps)
            out, stats = self.routed(
                mp, banks, li * held, u.reshape(B * T, -1), flat_valid,
                token_budget)
            return x + out.reshape(B, T, -1).astype(x.dtype), pages, aux + stats

        def period(carry, p):
            x, kv, wkv, aux = carry

            def window_layer(carry, j):
                x, wkv, aux = carry
                with jax.named_scope("window_attn"):
                    return layer(p * cfg.period + j, x, wkv, p * n_win + j,
                                 win_step, aux), None

            (x, wkv, aux), _ = jax.lax.scan(
                window_layer, (x, wkv, aux), jnp.arange(n_win, dtype=jnp.int32))
            with jax.named_scope("full_attn"):
                x, kv, aux = layer(
                    p * cfg.period + n_win, x, kv, p, full_step, aux)
            return (x, kv, wkv, aux), None

        x = base._embed_lookup(params, tokens, cfg)
        (x, kv, wkv, aux), _ = jax.lax.scan(
            period,
            (x, cache["kv"], cache["wkv"], jnp.zeros((AUX_WIDTH,), jnp.float32)),
            jnp.arange(cfg.periods, dtype=jnp.int32))

        x = base._rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        head = params["lm_head" if "lm_head" in params else "embed"]
        if all_logits:
            logits = jnp.einsum(
                "btd,vd->btv", x, head, preferred_element_type=jnp.float32)
        else:
            last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
            logits = jnp.einsum(
                "bd,vd->bv", last, head, preferred_element_type=jnp.float32)
        return logits, {"kv": kv, "wkv": wkv, "aux": aux}

    # -- attention ------------------------------------------------------------

    def _attention(self, lp, x, pages, li, step):
        """-> (the mixer's output [B, T, D] float32, the group's pages with
        this step's rows). ``step``: the group's table and flat write slots,
        the layer type's rotary tables and window."""
        cfg = self.cfg
        B, T, _ = x.shape
        eps = cfg.rms_norm_eps
        cos, sin = step["rope"]
        h = base._rms_norm(x, lp["norm"], eps)
        # The barrier keeps the projections' rows as the products leave them:
        # left to XLA, the reshape to heads below turns the whole ``wq`` and
        # ``wk`` stacks instead, once a step (a copy of 528 + 66 MB).
        q, k, v = jax.lax.optimization_barrier(tuple(
            _mm(h, lp[w]).astype(h.dtype) for w in ("wq", "wk", "wv")))
        q = base._rms_norm(
            q.reshape(B, T, cfg.num_heads, cfg.head_dim), lp["q_norm"], eps)
        k = base._rms_norm(
            k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim), lp["k_norm"], eps)
        q, k = base._apply_rope(q, cos, sin), base._apply_rope(k, cos, sin)
        # One scatter over the flattened row view, as Llama.forward does: the
        # drop sentinel (nb*bs) maps out of the whole array.
        flat_write = step["flat_write"]
        n_l, nb, _, bs, _ = pages.shape
        idx_k = jnp.where(
            flat_write >= nb * bs, n_l * nb * 2 * bs,
            (li * nb + flat_write // bs) * (2 * bs) + flat_write % bs)
        kvd = jnp.concatenate(
            [k.reshape(B * T, cfg.kv_size), v.reshape(B * T, cfg.kv_size)]
        ).astype(pages.dtype)
        pages = (
            pages.reshape(n_l * nb * 2 * bs, cfg.kv_size)
            .at[jnp.concatenate([idx_k, idx_k + bs])].set(kvd, mode="drop")
            .reshape(pages.shape)
        )
        out = paged_attention(
            q, pages, step["tables"], step["kv_lens"], step["positions"], li,
            scale=1.0 / math.sqrt(cfg.head_dim), impl=step["attn_impl"],
            window=step["window"],
        ).reshape(B, T, cfg.q_size)
        return _mm(out.astype(h.dtype), lp["wo"]), pages

    # -- expert block --------------------------------------------------------

    def routed(self, mp, banks, bank_first, u: jax.Array, valid: jax.Array,
               token_budget: Optional[int] = None):
        """This share's part of the routed sum ``[N, D]`` float32 and the
        dispatch's counts: softmax scores over all ``router_experts``, the
        top k, renormalised over the chosen. ``banks``: ``w1``, ``w2`` as
        ``[groups, k, n]`` with this layer's experts from group
        ``bank_first`` on."""
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
