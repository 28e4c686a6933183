"""The expert dispatch the mixture-of-experts classes call
(``models/nemotron_h.py``: ungated ``relu^2`` experts in a latent space;
``models/glm4_moe_lite.py``: gated SwiGLU experts at full width;
``models/qwen3_next.py``, ``models/mellum.py``: the same experts behind a
softmax router).

One router (scores over *all* the experts the router knows, sigmoid
(``noaux_tc``) or softmax as the model says, the top-k of ``score + bias``
chosen, weighted by ``score`` renormalised and scaled), one sort-by-expert
dispatch over the experts **this engine holds** (``held`` of them from
``expert_first`` on: an expert-parallel share; pairs routed elsewhere are
dropped before the grouped products and nothing stands in for the other
ranks), the grouped products (``megablox.gmm``), the way back, and the six
counts a step reports. The expert's body is the caller's: a function of the
sorted rows and a grouped product bound to this step's group sizes.

**Between the router and the residual the layer works on the pairs this
share holds, not on every pair the router made** (PR 52). The sort puts the
held pairs first, in expert order; a share that holds ``held`` of ``scored``
experts expects ``N x K x held / scored`` of them, and ``capacity`` gives
that, with half as much again for headroom, in whole row tiles: the rows
``x[tok]`` gathers, the body computes on and the way back reads. The held
count is data, so a step that holds more than one capacity of pairs runs
further rounds over the same sorted order, one capacity of rows each, and
every held pair is computed whatever the routing
(``moe_dispatch_overflow_total`` counts such layer-steps). A share that
holds every expert the router scores, and a step so small that one row tile
is all its pairs, has capacity = all its rows, and its program is the plain
one: no loop of rounds is traced.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..device import pallas_interpret

# What a step reports beside its tokens (``step_aux``), summed over its
# expert layers, under the names the engine's stats carry them by: routed
# pairs, pairs this share holds, the pairs at its busiest expert, the held
# experts that got a pair (whose weights the grouped products read), the
# layers counted (the denominator of a mean a layer and step), and of those
# the layers whose held pairs passed the row capacity (further rounds ran).
AUX_NAMES = (
    "moe_pairs_routed_total", "moe_pairs_held_total",
    "moe_busiest_expert_pairs_total", "moe_experts_touched_total",
    "moe_layer_steps_total", "moe_dispatch_overflow_total")
AUX_WIDTH = len(AUX_NAMES)
# Row tile of the grouped expert products: pair rows are padded to it.
GROUP_ROWS = 128
# The most rows a round under a capacity works on: its way back keeps their
# tokens and weights in scalar memory, 256 KiB of a v5e's 1 MiB at this many
# (the hybrid's 1,024-token step has 8,448; 135,168 are refused by the
# chip's compiler). A larger step's share takes further rounds.
ROUND_ROWS_MAX = 32768


def _row_tiles(pairs: int) -> int:
    return -(-pairs // GROUP_ROWS) * GROUP_ROWS


def capacity(pairs: int, held: int, scored: int) -> int:
    """The rows a round of the expert layer works on, for a step of
    ``pairs`` token-expert pairs at a share of ``held`` of the ``scored``
    experts: one and a half times the pairs such a share expects, in whole
    row tiles, never above the pairs' own rows. A function of shapes alone
    (nobody sets it): uniform routing puts 1,024 tokens x 10 of 512 at 64
    held within 1,280 +- 34 pairs (1,920 rows), a 64-row decode step within
    80 +- 8 (128 rows); what a routing skewed towards this share adds is
    computed in further rounds, not dropped."""
    rows = _row_tiles(pairs)
    if held >= scored:
        return rows
    return min(rows, ROUND_ROWS_MAX,
               _row_tiles(-(-3 * pairs * held // (2 * scored))))


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
    renormalised and scaled.

    The chosen scores are not picked a second time out of ``s``
    (``take_along_axis``: 10,240 scalars one by one, 104 us a 1,024-token
    layer; PERF.md §6, PR 42): with no bias they are ``top_k``'s values, and
    with one each is the one nonzero term of a select against the expert's
    index, summed. The same floats either way."""
    logits = jnp.einsum(
        "nd,de->ne", u.astype(jnp.float32), w_router,
        precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"router scoring {scoring!r}: sigmoid or softmax")
    if router_bias is None:
        w, ids = jax.lax.top_k(s, top_k)
    else:
        _, ids = jax.lax.top_k(s + router_bias, top_k)
        experts = jnp.arange(s.shape[-1], dtype=ids.dtype)
        w = jnp.sum(
            jnp.where(ids[..., None] == experts, s[:, None, :], 0.0), axis=-1)
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
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sort the held pairs by expert: ``(tok [rows], wrow [rows], pos [N, K],
    wheld [N, K], sizes [held], counts [5])``, ``rows`` the ``N x K`` pairs
    padded to the kernel's row tile. Row ``r`` of the sorted order is token
    ``tok[r]`` with weight ``wrow[r]``; pair ``(n, k)`` sits at row
    ``pos[n, k]`` (the inverse of the sort) with weight ``wheld[n, k]``.
    Both weights are 0 where the pair is held elsewhere or its token is
    padding, and its row is then past every group: the held pairs are the
    first ``sum(sizes)`` rows, in expert order. The weights ride the sort
    as a second operand (picking them afterwards is a gather of scalars);
    of ``pos`` and ``wrow`` a program keeps the one its way back reads.

    ``sizes`` are the held experts' pairs and ``counts`` the first five of
    ``AUX_NAMES``: comparisons and a sum, not ``bincount``, which is a
    scatter-add of ones (89.8 us at 10,240 pairs in PR 41's trace, 14 us as
    a sum at 22,528 in PR 42's)."""
    N, K = ids.shape
    f32 = jnp.float32
    local = ids - expert_first
    mine = (local >= 0) & (local < held) & valid[:, None]
    wheld = jnp.where(mine, w, 0.0)
    # Pairs of experts held elsewhere (and of padding tokens) sort behind
    # every group and belong to none: the grouped products do not reach
    # them. Rows are padded to the kernel's row tile.
    rows = _row_tiles(N * K)
    pad = (0, rows - N * K)
    key = jnp.pad(jnp.where(mine, local, held).reshape(-1), pad,
                  constant_values=held)
    _, order, wrow = jax.lax.sort(
        (key, jnp.arange(rows, dtype=jnp.int32),
         jnp.pad(wheld.reshape(-1), pad)), num_keys=1)
    pos = jnp.argsort(order)[:N * K].reshape(N, K)
    tok = jnp.minimum(order // K, N - 1)
    sizes = jnp.sum(
        key[:, None] == jnp.arange(held, dtype=key.dtype), axis=0,
        dtype=jnp.int32)
    counts = jnp.stack([  # in the order of AUX_NAMES
        jnp.sum(valid).astype(f32) * K, jnp.sum(sizes).astype(f32),
        jnp.max(sizes).astype(f32), jnp.sum(sizes > 0).astype(f32),
        jnp.ones((), f32)])
    return tok, wrow, pos, wheld, sizes, counts


def bank_sizes(sizes: jax.Array, bank_experts: Optional[int],
               bank_first) -> jax.Array:
    """One entry an expert of the bank the grouped products are given: the
    held experts' ``sizes`` as they are, or, where the bank is a stack of
    layers' banks seen as one (``[layers x held, k, n]``, read in place),
    this layer's groups from ``bank_first`` (traced) on and every other
    group empty: the kernel visits no empty group."""
    if bank_experts is None:
        return sizes
    return jax.lax.dynamic_update_slice(
        jnp.zeros((bank_experts,), jnp.int32), sizes,
        (jnp.asarray(bank_first, jnp.int32),))


def combine(y: jax.Array, pos: jax.Array, wheld: jax.Array) -> jax.Array:
    """The way back where the rows are all the pairs: the weighted sum of
    the experts' outputs ``y [rows, n]`` at their tokens, float32 ``[N,
    n]``. Token ``n`` gathers the rows of its ``K`` pairs and sums them in
    the router's order. A pair of weight 0 (held elsewhere, or of a padding
    token) adds exactly 0 whatever its row holds: rows past the last group
    are whatever the kernel left there, NaN included, hence the ``where``
    and not a bare product.

    No scatter-add over all the pairs: XLA runs one as a serial loop over
    rows, 71 ns a row of 8 KB (115 GB/s on a v5e: 730 us for a 1,024-token
    step's 10,240 pairs, PR 41's trace), where the gather of the same rows
    takes 132 us and their sum 114 (PERF.md §6, PR 42). The gather is
    choice-major, ``[K, N, n]``: that is the gathered ``[K x N, n]`` seen
    again, where ``[N, K, n]`` pads ``K`` to the tile's eight rows and XLA
    lays the 84 MB out a second time (343 us)."""
    wk = wheld.T[..., None]
    return jnp.sum(jnp.where(wk != 0.0, y[pos.T] * wk, 0.0), axis=0)


# The held rows' way back keeps its sums in fast memory: a block of tokens'
# float32 sums of at most this many bytes (8 MiB is 1,024 tokens of 2,048).
SUMS_BLOCK_BYTES = 16 << 20
SUMS_ROW_TILE = 256  # rows of ``y`` a grid step brings in


def sums_blocks(n_tokens: int, n: int) -> Tuple[int, int]:
    """``(blocks, tokens a block)`` of the sums ``sum_rows`` keeps for
    ``n_tokens`` tokens of ``n`` floats: one block where they fit
    ``SUMS_BLOCK_BYTES``, else blocks of whole eights of tokens."""
    if n_tokens * n * 4 <= SUMS_BLOCK_BYTES:
        return 1, n_tokens
    block = max(8, SUMS_BLOCK_BYTES // (n * 4) // 8 * 8)
    return -(-n_tokens // block), block


def _sum_rows_kernel(meta_ref, tok_ref, w_ref, y_ref, sums_ref, out_ref):
    j, i = pl.program_id(0), pl.program_id(1)
    tokens, tile = out_ref.shape[0], y_ref.shape[0]
    count, onto = meta_ref[0], meta_ref[1]

    @pl.when((i == 0) & (onto == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when((i == 0) & (onto != 0))
    def _():
        pltpu.sync_copy(sums_ref.at[pl.ds(j * tokens, tokens)], out_ref)

    def add(r, carry):
        t = tok_ref[i * tile + r] - j * tokens

        @pl.when((t >= 0) & (t < tokens))
        def _():
            out_ref[pl.ds(t, 1), :] += (
                w_ref[i * tile + r] * y_ref[pl.ds(r, 1), :])
        return carry

    jax.lax.fori_loop(0, jnp.clip(count - i * tile, 0, tile), add, 0)


def sum_rows(y: jax.Array, tok: jax.Array, wrow: jax.Array, count,
             sums: jax.Array, onto) -> jax.Array:
    """The way back where the rows are the held pairs: ``y [C, n]`` float32,
    row ``r`` of token ``tok[r]`` with weight ``wrow[r]``, the first
    ``count`` (traced) of them live -> the weighted sums at their tokens,
    float32, in ``sums``'s place (``[blocks x block, n]`` as ``sums_blocks``
    says: donated to the call): added onto what it holds where ``onto``
    (traced) is set, else over it, unread. A Pallas kernel: a block of
    tokens' sums stays in fast memory, ``y`` streams through it a row tile
    at a time (tiles past ``count`` are not fetched) and each live row is
    added at its token, so it costs the held pairs and not ``K`` rows a
    token. A token's pairs add up in expert order, in float32; a row past
    ``count`` is never read, so whatever the grouped product left there
    (NaN included) adds nothing."""
    C, n = y.shape
    tile = next(t for t in (SUMS_ROW_TILE, GROUP_ROWS) if C % t == 0)
    block = sums_blocks(sums.shape[0], n)[1]
    meta = jnp.stack([jnp.asarray(count, jnp.int32),
                      jnp.asarray(onto, jnp.int32)])
    return pl.pallas_call(
        _sum_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(sums.shape[0] // block, C // tile),
            in_specs=[
                pl.BlockSpec((tile, n), lambda j, i, meta, tok, w: (
                    jnp.minimum(i, jnp.maximum(meta[0] - 1, 0) // tile), 0)),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, n), lambda j, i, *_: (j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(sums.shape, jnp.float32),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=2 * 4 * n * (block + tile) + (8 << 20)),
        interpret=pallas_interpret(),
        name="moe_sum_rows",
    )(meta, tok, wrow, y, sums)


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
    mathematics over sorted rows ``xs [C, k]``, with ``gmm(a, bank)`` the
    grouped product over those rows' groups; ``C`` is ``capacity`` of the
    step's pairs at this share of the router's experts (``w_router``'s
    width). Rows go out by a gather along the sort (``x[tok]``).

    Where ``C`` is all the pairs' rows (a share that holds every expert, a
    step of one row tile) they come back by a gather along the sort's
    inverse (``combine``) and that is the whole program. Where it is less,
    the first ``C`` sorted rows are a round: its groups are the experts'
    pairs that fall inside it, its rows come back added at their tokens
    (``sum_rows``), and as long as held pairs are left a further round takes
    the next ``C`` rows and adds onto the same sums: one round on nearly
    every step, any number when the router sends this share more.

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
        tok, wrow, pos, wheld, sizes, counts = dispatch(
            ids, w, valid, held=held, expert_first=expert_first)
    rows = tok.shape[0]
    C = capacity(ids.size, held, w_router.shape[1])

    def experts(xs, sizes):
        with jax.named_scope("moe_experts"):
            in_bank = bank_sizes(sizes, bank_experts, bank_first)
            return body(xs, lambda a, bank: grouped_matmul(a, bank, in_bank))

    if C == rows:
        out = combine(experts(x[tok], sizes), pos, wheld)
        overflow = jnp.zeros((), jnp.float32)
    else:
        n_tokens = valid.shape[0]
        ends = jnp.cumsum(sizes)
        total = ends[-1]
        # whole rounds: a slice of the last one stays inside
        spare = -rows % C
        tok, wrow = jnp.pad(tok, (0, spare)), jnp.pad(wrow, (0, spare))

        def one_round(i, sums):  # the sorted rows i C .. (i + 1) C
            lo = i * C
            inside = (jnp.clip(ends, lo, lo + C)
                      - jnp.clip(ends - sizes, lo, lo + C))
            t = jax.lax.dynamic_slice(tok, (lo,), (C,))
            return sum_rows(
                experts(x[t], inside), t,
                jax.lax.dynamic_slice(wrow, (lo,), (C,)),
                jnp.clip(total - lo, 0, C), sums, i > 0)

        n = jax.eval_shape(
            lambda xs: experts(xs, sizes),
            jax.ShapeDtypeStruct((C, x.shape[1]), x.dtype)).shape[1]
        blocks, block = sums_blocks(n_tokens, n)
        # one body for every round, the first included: a step program
        # holds each kernel once (a second copy of them, for the rounds
        # after a first written out of the loop, cost every program 0.2 s
        # more to load: PERF.md §6, PR 52)
        out = jax.lax.fori_loop(
            0, jnp.maximum(1, -(-total // C)), one_round,
            jnp.zeros((blocks * block, n), jnp.float32))[:n_tokens]
        overflow = (total > C).astype(jnp.float32)
    if packed:
        kept = real & (place < token_budget)
        out = jnp.where(kept[:, None], out[jnp.maximum(place, 0)], 0.0)
    return out, jnp.append(counts, overflow)
