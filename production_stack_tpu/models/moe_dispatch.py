"""The expert dispatch the mixture-of-experts classes call
(``models/nemotron_h.py``: ungated ``relu^2`` experts in a latent space;
``models/glm4_moe_lite.py``: gated SwiGLU experts at full width;
``models/qwen3_next.py``: the same experts behind a softmax router).

One router (scores over *all* the experts the router knows, sigmoid
(``noaux_tc``) or softmax as the model says, the top-k of ``score + bias``
chosen, weighted by ``score`` renormalised and scaled), one sort-by-expert
dispatch over the experts **this engine holds** (``held`` of them from
``expert_first`` on: an expert-parallel share; pairs routed elsewhere are
dropped before the grouped products and nothing stands in for the other
ranks), the grouped products
(``megablox.gmm``), the way back (each token gathers its pairs' rows by the
inverse of the sort and sums them: no scatter anywhere), and the five counts
a step reports. The expert's body is the caller's: a function of the sorted
rows and a grouped product bound to this step's group sizes.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..device import pallas_interpret

# What a step reports beside its tokens (``step_aux``), summed over its
# expert layers, under the names the engine's stats carry them by: routed
# pairs, pairs this share holds, the pairs at its busiest expert, the held
# experts that got a pair (whose weights the grouped products read), and the
# layers counted (the denominator of a mean a layer and step).
AUX_NAMES = (
    "moe_pairs_routed_total", "moe_pairs_held_total",
    "moe_busiest_expert_pairs_total", "moe_experts_touched_total",
    "moe_layer_steps_total")
AUX_WIDTH = len(AUX_NAMES)
# Row tile of the grouped expert products: pair rows are padded to it.
GROUP_ROWS = 128


def grouped_matmul(xs: jax.Array, bank: jax.Array, sizes: jax.Array) -> jax.Array:
    """``xs [rows, k]`` sorted by expert, ``bank [experts, k, n]``, ``sizes
    [experts]`` rows each -> float32 ``[rows, n]``; rows past the last group
    are undefined. The grouped-matmul Pallas kernel that ships with JAX
    (``megablox.gmm``) at tiles of up to 1,024: it streams each touched
    expert's weights once (83 % of the HBM bound at 704 rows over
    128 experts on a v5e, where ``lax.ragged_dot`` reaches 22-30 %; PERF.md
    §6, PR 31). ``rows`` is a multiple of ``GROUP_ROWS``."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def tile(d: int) -> int:  # the widest multiple of 128 up to 1,024 in d
        return next((t for t in range(1024, 0, -128) if d % t == 0), d)

    return gmm(
        xs, bank, sizes, preferred_element_type=jnp.float32,
        tiling=(GROUP_ROWS, tile(bank.shape[1]), tile(bank.shape[2])),
        interpret=pallas_interpret(),
    )


def route(u: jax.Array, w_router: jax.Array,
          router_bias: Optional[jax.Array], *, top_k: int,
          norm_topk_prob: bool, scale: float, scoring: str = "sigmoid"):
    """Router over all the experts ``w_router`` scores: ``(ids [N, K],
    weights [N, K])``, in float32. ``scoring`` is the model's: ``sigmoid``
    (``noaux_tc``) or ``softmax`` over all the experts. Selection is by
    ``score + bias`` (no bias: by score); the weights are the scores alone,
    renormalised and scaled."""
    logits = jnp.einsum(
        "nd,de->ne", u.astype(jnp.float32), w_router,
        precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"router scoring {scoring!r}: sigmoid or softmax")
    _, ids = jax.lax.top_k(s if router_bias is None else s + router_bias, top_k)
    w = jnp.take_along_axis(s, ids, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return ids, w * scale


def dispatch(
    ids: jax.Array,  # [N, K] expert of each pair, among all the router's
    w: jax.Array,  # [N, K] its weight
    valid: jax.Array,  # [N] the token is real
    *,
    held: int,
    expert_first: int = 0,
    bank_experts: Optional[int] = None,
    bank_first=0,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort the held pairs by expert: ``(tok [rows], pos [N, K], wheld
    [N, K], sizes, stats [AUX_WIDTH])``, ``rows`` the ``N x K`` pairs padded
    to the kernel's row tile. Row ``r`` of the grouped products is token
    ``tok[r]``; pair ``(n, k)`` sits at row ``pos[n, k]`` (the inverse of
    the sort) with weight ``wheld[n, k]``: 0 where the pair is held
    elsewhere or its token is padding, and its row is then past every group.

    ``sizes`` has one entry an expert of the bank the products are given:
    ``held`` by default; with ``bank_experts`` the bank is a stack of layers'
    banks seen as one (``[layers x held, k, n]``, read in place) and this
    layer's groups start at ``bank_first`` (traced), every other group
    empty: the kernel visits no empty group. The counts are comparisons and
    a sum, not ``bincount``: that is a scatter-add of ones (89.8 us at
    10,240 pairs in PR 41's trace, 14 us as a sum at 22,528 in PR 42's)."""
    N, K = ids.shape
    f32 = jnp.float32
    local = ids - expert_first
    mine = (local >= 0) & (local < held) & valid[:, None]
    # Pairs of experts held elsewhere (and of padding tokens) sort behind
    # every group and belong to none: the grouped products do not reach
    # them. Rows are padded to the kernel's row tile.
    rows = -(-N * K // GROUP_ROWS) * GROUP_ROWS
    key = jnp.pad(jnp.where(mine, local, held).reshape(-1),
                  (0, rows - N * K), constant_values=held)
    order = jnp.argsort(key)
    pos = jnp.argsort(order)[:N * K].reshape(N, K)
    tok = jnp.minimum(order // K, N - 1)
    sizes = jnp.sum(
        key[:, None] == jnp.arange(held, dtype=key.dtype), axis=0,
        dtype=jnp.int32)
    stats = jnp.stack([  # in the order of AUX_NAMES
        jnp.sum(valid).astype(f32) * K, jnp.sum(sizes).astype(f32),
        jnp.max(sizes).astype(f32), jnp.sum(sizes > 0).astype(f32),
        jnp.ones((), f32)])
    if bank_experts is not None:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((bank_experts,), jnp.int32), sizes,
            (jnp.asarray(bank_first, jnp.int32),))
    return tok, pos, jnp.where(mine, w, 0.0), sizes, stats


def combine(y: jax.Array, pos: jax.Array, wheld: jax.Array) -> jax.Array:
    """The weighted sum of the experts' outputs ``y [rows, n]`` back at
    their tokens, float32 ``[N, n]``: token ``n`` gathers the rows of its
    ``K`` pairs and sums them in the router's order. A pair of weight 0
    (held elsewhere, or of a padding token) adds exactly 0 whatever its row
    holds: rows past the last group are whatever the kernel left there, NaN
    included, hence the ``where`` and not a bare product.

    No scatter-add: XLA runs one as a serial loop over rows, 71 ns a row
    of 8 KB (115 GB/s on a v5e: 730 us for a 1,024-token step's 10,240
    pairs, PR 41's trace), where the gather of the same rows takes 132 us
    and their sum 114 (PERF.md §6, PR 42). The gather is choice-major,
    ``[K, N, n]``: that is the gathered ``[K x N, n]`` seen again, where
    ``[N, K, n]`` pads ``K`` to the tile's eight rows and XLA lays the 84
    MB out a second time (343 us)."""
    wk = wheld.T[..., None]
    return jnp.sum(jnp.where(wk != 0.0, y[pos.T] * wk, 0.0), axis=0)


def routed_experts(
    u: jax.Array,  # [N, D] what the router scores
    x: jax.Array,  # [N, k] what the experts take (u, or its latent)
    valid: jax.Array,
    w_router: jax.Array,
    router_bias: Optional[jax.Array],
    body: Callable[[jax.Array, Callable], jax.Array],
    *,
    top_k: int,
    norm_topk_prob: bool,
    scale: float,
    scoring: str = "sigmoid",
    held: int,
    expert_first: int = 0,
    token_budget: Optional[int] = None,
    bank_experts: Optional[int] = None,
    bank_first=0,
):
    """This share's part of the routed sum, float32 ``[N, n]``, and the
    step's ``[AUX_WIDTH]`` counts. ``body(xs, gmm)`` is one expert's
    mathematics over the sorted rows ``xs [rows, k]``, with ``gmm(a, bank)``
    the grouped product over this step's groups. Rows go out by a gather
    along the sort (``x[tok]``) and come back by a gather along its inverse
    (``combine``): no scatter between the router and the residual.

    ``token_budget`` bounds the real tokens among the ``N`` (a prefill step
    is padded to rows x longest chunk, up to eight times its budget): the
    real tokens are then drawn to the front and the layer, router included,
    runs over ``budget`` tokens, so a step costs what its budget costs
    however far it is padded; each token reads its sum back from its place
    among the real ones."""
    N = u.shape[0]
    packed = token_budget is not None and token_budget < N
    if packed:
        front = jnp.argsort(~valid)[:token_budget]  # stable: in their order
        place = jnp.cumsum(valid) - 1  # of a real token among the real
        real, u, x, valid = valid, u[front], x[front], valid[front]
    with jax.named_scope("moe_router"):
        ids, w = route(u, w_router, router_bias, top_k=top_k,
                       norm_topk_prob=norm_topk_prob, scale=scale,
                       scoring=scoring)
        tok, pos, wheld, sizes, stats = dispatch(
            ids, w, valid, held=held, expert_first=expert_first,
            bank_experts=bank_experts, bank_first=bank_first)
    xs = x[tok]
    with jax.named_scope("moe_experts"):
        y = body(xs, lambda a, bank: grouped_matmul(a, bank, sizes))
    out = combine(y, pos, wheld)
    if packed:
        kept = real & (place < token_budget)
        out = jnp.where(kept[:, None], out[jnp.maximum(place, 0)], 0.0)
    return out, stats
