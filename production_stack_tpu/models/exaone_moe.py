"""Window and full attention mixed, a leading dense layer, then expert
layers with one shared expert, and a multi-token-prediction module that
drafts the token after next (``model_type: exaone_moe``).

Main layer ``l``, attention type ``t = layer_types[l]``, block kind ``m =
mlp_layer_types[l]``: ``h = x + Attn_t(RMSNorm(x))``, ``y = h +
MLP_m(RMSNorm(h))``; a final RMSNorm ``h^`` before the untied head.

- **Attention**: ``q``, ``k``, ``v`` without bias; queries and keys
  RMS-normalised a head; grouped causal attention at ``head^-1/2``. A
  ``sliding_attention`` layer: a query at ``p`` sees keys ``p - window + 1
  .. p``, rotate-half rotary embedding over every lane. A
  ``full_attention`` layer: every earlier key, **no positional embedding**.
- **Dense block** (``mlp_layer_types[l] == "dense"``): SwiGLU.
- **Sparse block**: ``s = sigmoid(W_r x)`` in float32 over all
  ``router_experts``; the top ``num_experts_per_tok`` of ``s + b`` (``b`` the
  selection bias); weights ``s`` of the chosen, renormalised
  (``norm_topk_prob``), times ``routed_scaling_factor``; ``sum_e w_e
  Expert_e(x) + Shared(x)``. **The layer holds a share** of the routed
  experts (``n_routed_experts`` from ``expert_first`` on,
  ``models/moe_dispatch.py``); the shared expert is whole.
- **MTP module** (``num_nextn_predict_layers`` 1, DeepSeek-V3's form): for
  position ``i`` and the token after it, ``u_i = W_eh [RMSNorm_e(Emb(t_{i+1}))
  ; RMSNorm_h(h^_i)]``, ``z_i = Layer_mtp(u)_i`` (one more full-attention
  sparse layer over ``u_0 .. u_i``), ``Head(RMSNorm(z_i))`` predicts
  ``t_{i+2}``. ``Emb`` and ``Head`` are the main model's.

Two page groups as ``models/mellum.py`` has them: ``kv``, the global group
(the full-attention layers, then **the MTP layer as its last layer**), and
``wkv``, the window group. **The slot rule**: the MTP layer's keys and values
for position ``i`` are made from token ``i + 1``, so they are stored at slot
``i + 1`` and slot 0 stays empty and masked (``key_floor``): a page's content
is then a function of the tokens it is hashed by, and a prefix-cache hit
hands on nothing made from another request's token. The caller hands
``mtp_forward`` the write slots and lengths of that shift
(``engine/runner.py``).

The layers are written out one by one (a cut of the stack need not be whole
periods), each with leaves of its own: an expert layer's bank is read in
place as it is.
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

Params = Dict[str, Any]

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"
# What the verify-and-draft step counts on the device beside the expert
# dispatch's counts (``engine/runner.py`` fills them; a prefill step's are 0).
MTP_AUX_NAMES = (
    "spec_decode_num_draft_tokens_total",
    "spec_decode_num_accepted_tokens_total",
    "mtp_steps_total", "mtp_row_steps_total", "mtp_tokens_emitted_total")
AUX_NAMES = moe_dispatch.AUX_NAMES + MTP_AUX_NAMES
AUX_WIDTH = len(AUX_NAMES)
MOE_WIDTH = moe_dispatch.AUX_WIDTH


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig(base.ModelConfig):
    vocab_size: int = 153600
    hidden_size: int = 6144
    intermediate_size: int = 18432
    num_layers: int = 48
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 12
    mlp_layer_types: Tuple[str, ...] = (DENSE,) + (SPARSE,) * 47
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 128
    rope_theta: float = 1000000.0
    # expert block: ``n_routed_experts`` held of ``router_experts`` scored
    n_routed_experts: int = 128
    router_experts: int = 128
    expert_first: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 2048
    num_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    num_mtp_layers: int = 1  # the draft module (0: the checkpoint has none)
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    name: str = "exaone_moe"
    eos_token_ids: Tuple[int, ...] = (2,)
    bos_token_id: Optional[int] = None

    # What the engine asks of any model config.
    window_pages = True  # the window layers' page group, released below it

    def __post_init__(self):
        types, kinds = tuple(self.layer_types), tuple(self.mlp_layer_types)
        if (len(types) != self.num_layers or len(kinds) != self.num_layers
                or set(types) - {SLIDING, FULL} or set(kinds) - {DENSE, SPARSE}):
            raise ValueError(
                f"layer_types {types} / mlp_layer_types {kinds}: one of "
                f"{SLIDING} | {FULL} and one of {DENSE} | {SPARSE} for each "
                f"of num_hidden_layers {self.num_layers}")
        if SLIDING not in types or FULL not in types:
            raise ValueError("exaone_moe serves a stack with both layer types")
        if self.sliding_window <= 0:
            raise ValueError("exaone_moe needs a sliding_window")
        if self.num_mtp_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers: 0 or 1 is built")

    @property
    def mtp_layers(self) -> int:
        """Draft layers the engine may serve (``--speculative-mtp``)."""
        return self.num_mtp_layers

    @property
    def num_full_layers(self) -> int:
        return sum(t == FULL for t in self.layer_types)

    @property
    def num_kv_layers(self) -> int:
        """Layers of the global group: the full-attention layers and the MTP
        layer. The KV pool is sized from these."""
        return self.num_full_layers + self.num_mtp_layers

    @property
    def num_window_layers(self) -> int:
        return self.num_layers - self.num_full_layers

    @property
    def num_sparse_layers(self) -> int:
        """Expert layers a step evaluates, the MTP layer's among them."""
        return (sum(k == SPARSE for k in self.mlp_layer_types)
                + self.num_mtp_layers)

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

    def inv_freq(self) -> np.ndarray:
        """The window layers' inverse frequencies ``[head / 2]``."""
        half = self.head_dim // 2
        return self.rope_theta ** (-np.arange(half, dtype=np.float64) / half)


def config_from_hf(hf: dict, name: str = "") -> ExaoneMoeConfig:
    """The ``exaone_moe`` keys of an HF ``config.json``. Beside them, an
    expert-parallel share: ``num_experts`` is what this engine holds,
    ``ep_share`` = ``{"first": i, "of": n}`` says of how many the router is
    and where the held ones start (absent: it holds them all)."""
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {hf['hidden_act']!r}: silu only")
    if hf.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(f"scoring_func {hf['scoring_func']!r}: sigmoid only")
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("n_group / topk_group: a grouped router is not built")
    n_layers = hf["num_hidden_layers"]
    rope = hf.get("rope_parameters") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}: default only")
    n_mtp = int(hf.get("num_nextn_predict_layers", 0))
    if n_mtp not in (0, 1):
        raise ValueError("num_nextn_predict_layers: 0 or 1 is built")
    if n_mtp and (list(hf.get("mtp_layer_types") or [FULL]) != [FULL] * n_mtp
                  or any(hf.get("mtp_sliding_windows") or [0])):
        raise ValueError(
            "mtp_layer_types / mtp_sliding_windows: a full-attention draft "
            "layer is what is built")
    kinds = tuple(hf.get("mlp_layer_types") or (
        (DENSE,) * int(hf.get("first_k_dense_replace", 0))
        + (SPARSE,) * n_layers)[:n_layers])
    held = hf["num_experts"]
    share = hf.get("ep_share") or {"first": 0, "of": held}
    first, of = int(share["first"]), int(share["of"])
    if not 0 <= first <= of - held:
        raise ValueError(
            f"ep_share {share}: {held} experts from {first} do not lie "
            f"within {of}")
    heads = hf["num_attention_heads"]
    eos = hf.get("eos_token_id", 2)
    return ExaoneMoeConfig(
        vocab_size=hf["vocab_size"],
        hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=n_layers,
        layer_types=tuple(hf["layer_types"]),
        mlp_layer_types=kinds,
        num_heads=heads,
        num_kv_heads=hf.get("num_key_value_heads", heads),
        head_dim=hf.get("head_dim") or hf["hidden_size"] // heads,
        sliding_window=int(hf["sliding_window"]),
        rope_theta=float(rope.get("rope_theta", hf.get("rope_theta", 10000.0))),
        n_routed_experts=held,
        router_experts=of,
        expert_first=first,
        num_experts_per_tok=hf["num_experts_per_tok"],
        moe_intermediate_size=hf["moe_intermediate_size"],
        num_shared_experts=int(hf.get("num_shared_experts", 1)),
        norm_topk_prob=bool(hf.get("norm_topk_prob", True)),
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        num_mtp_layers=n_mtp,
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        max_position_embeddings=hf.get("max_position_embeddings", 4096),
        tie_word_embeddings=bool(hf.get("tie_word_embeddings", False)),
        dtype=hf.get("torch_dtype") or "bfloat16",
        name=name or hf.get("_name_or_path", "exaone_moe"),
        eos_token_ids=tuple(eos) if isinstance(eos, list) else (eos,),
        bos_token_id=hf.get("bos_token_id"),
    )


def init_leaf(name: str, shape, dtype, key: jax.Array) -> jax.Array:
    """One leaf's random init by its name. Norm weights ``1 + normal(0,
    0.1)`` (not all ones: a weight that is skipped then shows); the selection
    bias ``normal(0, 0.1)`` (so that selecting by ``score + bias`` and
    weighing by ``score`` differ); matrices normal with std ``fan_in^-1/2``."""
    if "norm" in name:
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(
            dtype)
    if name == "router_bias":
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    # [.., in, out]; the rows of [V, D] contract their last axis
    fan_in = shape[-1] if name in ("embed", "lm_head") else shape[-2]
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(fan_in)).astype(dtype)


def _mm(x, w):
    return jnp.einsum("...d,de->...e", x, w, preferred_element_type=jnp.float32)


def _swiglu(u, w_gate, w_up, w_down):
    a = (jax.nn.silu(_mm(u, w_gate)) * _mm(u, w_up)).astype(u.dtype)
    return _mm(a, w_down)


class ExaoneMoe(base.Model):
    """Stateless model functions bound to a config (the runner's model
    object)."""

    AUX_NAMES = AUX_NAMES  # rows the runner appends to a step's packed tokens
    TOKEN_BUDGET = True  # the expert dispatch packs a padded step's tokens

    # ------------------------------------------------------------------
    # Parameters
    # ------------------------------------------------------------------

    def layer_shapes(self) -> Dict[str, Dict[str, Dict[str, tuple]]]:
        """Layer name -> block -> leaf -> shape: ``l<i>`` for the main stack,
        ``mtp`` for the draft module (its layer, the two input norms, the
        projection and its own last norm)."""
        c = self.cfg
        D, F, Fe = c.hidden_size, c.intermediate_size, c.moe_intermediate_size
        Fs = Fe * c.num_shared_experts
        attn = {
            "norm": (D,), "wq": (D, c.q_size), "wk": (D, c.kv_size),
            "wv": (D, c.kv_size), "q_norm": (c.head_dim,),
            "k_norm": (c.head_dim,), "wo": (c.q_size, D)}
        dense = {"norm": (D,), "w_gate": (D, F), "w_up": (D, F),
                 "w_down": (F, D)}
        moe = {
            "norm": (D,), "w_router": (D, c.router_experts),
            "router_bias": (c.router_experts,),
            # gate | up of every held expert, one bank
            "w1": (c.n_routed_experts, D, 2 * Fe),
            "w2": (c.n_routed_experts, Fe, D),
            "w_shared_gate": (D, Fs), "w_shared_up": (D, Fs),
            "w_shared_down": (Fs, D)}
        out = {f"l{i}": {"attn": attn, DENSE if k == DENSE else "moe":
                         dense if k == DENSE else moe}
               for i, k in enumerate(c.mlp_layer_types)}
        if c.num_mtp_layers:
            out["mtp"] = {
                "attn": attn, "moe": moe,
                "io": {"enorm": (D,), "hnorm": (D,), "w_eh": (2 * D, D),
                       "final_norm": (D,)}}
        return out

    def init_params(self, rng: jax.Array) -> Params:
        """Random initialisation, a leaf at a time and an expert bank an
        expert at a time (each its own key): no temporary is larger than one
        expert's matrix in float32."""
        c = self.cfg
        d = c.jdtype

        def key_of(name: str) -> jax.Array:
            return jax.random.fold_in(
                rng, xxhash.xxh32(name.encode()).intdigest() & 0x7FFF_FFFF)

        def leaf(path: str, name: str, shape):
            dtype = jnp.float32 if name in ("w_router", "router_bias") else d
            key = key_of(f"{path}.{name}")
            if name in ("w1", "w2"):
                return jax.lax.map(
                    lambda e: init_leaf(name, shape[1:], dtype,
                                        jax.random.fold_in(key, e)),
                    jnp.arange(shape[0]))
            return init_leaf(name, shape, dtype, key)

        V, D = c.vocab_size, c.hidden_size
        params: Params = {
            "embed": init_leaf("embed", (V, D), d, key_of("embed")),
            "layers": {
                layer: {block: {name: leaf(f"{layer}.{block}", name, shape)
                                for name, shape in leaves.items()}
                        for block, leaves in blocks.items()}
                for layer, blocks in self.layer_shapes().items()},
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
        """``kv``: pages of the full-attention layers and, last, of the MTP
        layer, in ``Llama``'s page layout. ``wkv``: the window layers' pages,
        a group of its own. ``aux``: what the last step reported
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
        return_hidden: bool = False,
        **_unused,  # lora_idx, lora_scale, moe_impl, pp_size, mesh: refused
    ):
        """One engine step; ``Llama.forward``'s contract plus the window
        group's tables. ``return_hidden``: also ``h^`` ``[B, T, D]``, every
        position's state after the final norm (what ``mtp_forward`` takes)."""
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

        angles = (positions.astype(jnp.float32)[..., None]
                  * jnp.asarray(cfg.inv_freq(), jnp.float32))
        common = dict(kv_lens=kv_lens, positions=positions, attn_impl=attn_impl)
        win_step = dict(common, flat_write=w_flat, tables=window_tables,
                        rope=(jnp.cos(angles), jnp.sin(angles)),
                        window=cfg.sliding_window)
        full_step = dict(common, flat_write=flat, tables=block_tables,
                         rope=None, window=0)

        x = base._embed_lookup(params, tokens, cfg)
        kv, wkv = cache["kv"], cache["wkv"]
        aux = jnp.zeros((MOE_WIDTH,), jnp.float32)
        flat_valid = valid.reshape(-1)
        n_full = n_win = 0
        for li, kind in enumerate(cfg.layer_types):
            lp = params["layers"][f"l{li}"]
            if kind == SLIDING:
                with jax.named_scope("window_attn"):
                    out, wkv = self._attention(lp["attn"], x, wkv, n_win, win_step)
                n_win += 1
            else:
                with jax.named_scope("full_attn"):
                    out, kv = self._attention(lp["attn"], x, kv, n_full, full_step)
                n_full += 1
            x = x + out.astype(x.dtype)
            x, aux = self._mlp(lp, x, flat_valid, token_budget, aux)

        x = base._rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        logits = self._head(params, x, last_idx, all_logits)
        cache = {"kv": kv, "wkv": wkv,
                 "aux": jnp.pad(aux, (0, AUX_WIDTH - MOE_WIDTH))}
        if return_hidden:
            return logits, x, cache
        return logits, cache

    def mtp_forward(
        self,
        params: Params,
        hidden: jax.Array,  # [B, T, D] ``h^`` of the same positions
        next_tokens: jax.Array,  # [B, T] the token after each position
        positions: jax.Array,  # [B, T]
        write_idx: jax.Array,  # [B, T] flat slot of position + 1 (or drop)
        block_tables: jax.Array,  # [B, W] global group
        kv_lens: jax.Array,  # [B] valid length of the shifted slots
        last_idx: jax.Array,  # [B]
        cache: Dict[str, jax.Array],
        *,
        token_budget: Optional[int] = None,
        attn_impl: str = "auto",
        all_logits: bool = False,
        shifted: bool = True,
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """The draft module on positions the main stack has just run: logits
        that predict the token two after each position (``[B, V]`` at
        ``last_idx``, or every position's) and the cache with the module's
        keys and values **one slot ahead** (the slot rule) and its expert
        counts added to ``aux``. ``shifted`` False stores and reads them at
        the position's own slot: the control that shows what the rule is for
        (``scripts/tpu_mtp_check.py``), served nowhere."""
        cfg = self.cfg
        B, T = next_tokens.shape
        eps = cfg.rms_norm_eps
        mp = params["layers"]["mtp"]
        valid = (jnp.arange(T, dtype=jnp.int32)[None, :]
                 < jnp.where(kv_lens > 0, last_idx + 1, 0)[:, None])
        e = base._rms_norm(
            base._embed_lookup(params, next_tokens, cfg), mp["io"]["enorm"], eps)
        h = base._rms_norm(hidden, mp["io"]["hnorm"], eps)
        x = _mm(jnp.concatenate([e, h], axis=-1), mp["io"]["w_eh"]).astype(
            hidden.dtype)
        ahead = 1 if shifted else 0
        step = dict(kv_lens=kv_lens, positions=positions + ahead,
                    attn_impl=attn_impl, flat_write=write_idx.reshape(-1),
                    tables=block_tables, rope=None, window=0, key_floor=ahead)
        with jax.named_scope("mtp_attn"):
            out, kv = self._attention(
                mp["attn"], x, cache["kv"], cfg.num_full_layers, step)
        x = x + out.astype(x.dtype)
        x, aux = self._mlp(mp, x, valid.reshape(-1), token_budget,
                           cache["aux"][:MOE_WIDTH])
        x = base._rms_norm(x, mp["io"]["final_norm"], eps)
        logits = self._head(params, x, last_idx, all_logits)
        return logits, dict(
            cache, kv=kv,
            aux=jnp.concatenate([aux, cache["aux"][MOE_WIDTH:]]))

    @staticmethod
    def _head(params, x, last_idx, all_logits):
        head = params["lm_head" if "lm_head" in params else "embed"]
        if all_logits:
            return jnp.einsum(
                "btd,vd->btv", x, head, preferred_element_type=jnp.float32)
        last = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
        return jnp.einsum(
            "bd,vd->bv", last, head, preferred_element_type=jnp.float32)

    # -- attention ------------------------------------------------------------

    def _attention(self, lp, x, pages, li, step):
        """-> (the mixer's output [B, T, D] float32, the group's pages with
        this step's rows). ``step``: the group's table and flat write slots,
        the layer type's rotary tables (None: no positional embedding) and
        window."""
        cfg = self.cfg
        B, T, _ = x.shape
        eps = cfg.rms_norm_eps
        h = base._rms_norm(x, lp["norm"], eps)
        # The barrier keeps the projections' rows as the products leave them
        # (``models/mellum.py``: left to XLA, the reshape to heads turns the
        # weights instead).
        q, k, v = jax.lax.optimization_barrier(tuple(
            _mm(h, lp[w]).astype(h.dtype) for w in ("wq", "wk", "wv")))
        q = base._rms_norm(
            q.reshape(B, T, cfg.num_heads, cfg.head_dim), lp["q_norm"], eps)
        k = base._rms_norm(
            k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim), lp["k_norm"], eps)
        if step["rope"] is not None:
            cos, sin = step["rope"]
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
            window=step["window"], key_floor=step.get("key_floor", 0),
        ).reshape(B, T, cfg.q_size)
        return _mm(out.astype(h.dtype), lp["wo"]), pages

    # -- the block after attention ------------------------------------------

    def _mlp(self, lp, x, flat_valid, token_budget, aux):
        """-> (``x`` plus the layer's dense or expert block, ``aux`` plus the
        dispatch's counts)."""
        cfg = self.cfg
        B, T, _ = x.shape
        if DENSE in lp:
            dp = lp[DENSE]
            u = base._rms_norm(x, dp["norm"], cfg.rms_norm_eps)
            out = _swiglu(u, dp["w_gate"], dp["w_up"], dp["w_down"])
            return x + out.astype(x.dtype), aux
        mp = lp["moe"]
        u = base._rms_norm(x, mp["norm"], cfg.rms_norm_eps).reshape(B * T, -1)
        out, stats = self.routed(mp, u, flat_valid, token_budget)
        with jax.named_scope("moe_shared"):
            out = out + _swiglu(u, mp["w_shared_gate"], mp["w_shared_up"],
                                mp["w_shared_down"])
        return x + out.reshape(B, T, -1).astype(x.dtype), aux + stats

    def routed(self, mp, u: jax.Array, valid: jax.Array,
               token_budget: Optional[int] = None):
        """This share's part of the routed sum ``[N, D]`` float32 and the
        dispatch's counts: sigmoid scores over all ``router_experts``, the
        top k of ``score + bias``, the chosen scores renormalised and
        scaled."""
        cfg = self.cfg
        Fe = cfg.moe_intermediate_size

        def body(xs, gmm):
            a = gmm(xs, mp["w1"])
            a = (jax.nn.silu(a[:, :Fe]) * a[:, Fe:]).astype(u.dtype)
            return gmm(a, mp["w2"])

        return moe_dispatch.routed_experts(
            u, u, valid, mp["w_router"], mp["router_bias"], body,
            top_k=cfg.num_experts_per_tok, norm_topk_prob=cfg.norm_topk_prob,
            scale=cfg.routed_scaling_factor, scoring="sigmoid",
            held=cfg.n_routed_experts, expert_first=cfg.expert_first,
            token_budget=token_budget)
