"""``models/moe_dispatch.py`` alone: the sort-by-expert dispatch and the way
back against a per-token loop over every expert, at the shapes the step
programs hand it (an expert-parallel share, a padded prefill step under its
token budget, a decode step under the row tile, a stack of layers' banks),
with the rows no group owns poisoned; the counts against ``numpy``; the
lowered program, which holds no scatter; and, since PR 52, the shapes at
which the layer works on one row capacity of held pairs a round (held pairs
under it, exactly it, over it by part of a round and by whole rounds)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import moe_dispatch

D, F = 16, 8  # the experts' width and their inner width

# name -> tokens, real tokens (None: all), scored experts, top_k, held,
# expert_first, token_budget, layers in the bank and this layer's place
# (None: the layer's own bank), rows past the last group set to NaN, every
# pair drawn to this share (a bias on its experts: ``draw``)
CASES = {
    "all_held": dict(N=16, real=None, E=8, K=3, held=8, first=0),
    # half the scored experts' pairs land elsewhere: some tokens hold none
    "ep_share": dict(N=24, real=None, E=16, K=2, held=4, first=8),
    # a padded prefill step: 256 x 4 pairs, 896 of them padding, 128 rows
    "budget": dict(N=256, real=32, E=8, K=4, held=8, first=0, budget=32),
    "budget_ep_share": dict(N=256, real=20, E=16, K=4, held=4, first=4,
                            budget=32),
    # a decode step of 3 rows: 6 pairs in a row tile of 128
    "rows_past_pairs": dict(N=3, real=None, E=8, K=2, held=8, first=0),
    "bank": dict(N=16, real=None, E=8, K=3, held=4, first=2, bank=(3, 1)),
    "poisoned": dict(N=16, real=None, E=8, K=3, held=8, first=0, poison=True),
    "poisoned_budget_ep_share": dict(N=256, real=20, E=16, K=4, held=4,
                                     first=4, budget=32, poison=True),
    "poisoned_rows_past_pairs": dict(N=3, real=2, E=8, K=2, held=3, first=1,
                                     poison=True),
    # a quarter share of 768 pairs: a capacity of 384 rows, about 190 held
    "capacity_under": dict(N=192, real=None, E=16, K=4, held=4, first=8),
    # every pair here: 96 x 4 = the capacity to the row, one round
    "capacity_exact": dict(N=192, real=96, E=16, K=4, held=4, first=8,
                           draw=True),
    # 150 x 4 = 600: a second round of 216 rows
    "capacity_over": dict(N=192, real=150, E=16, K=4, held=4, first=8,
                          draw=True),
    # 800 pairs in 896 rows: three rounds, the last slice past the rows
    "capacity_rounds": dict(N=200, real=None, E=16, K=4, held=4, first=4,
                            draw=True),
    "capacity_budget": dict(N=1024, real=150, E=16, K=4, held=4, first=4,
                            budget=192),
    "capacity_budget_over": dict(N=1024, real=150, E=16, K=4, held=4,
                                 first=4, budget=192, draw=True),
    "capacity_bank": dict(N=192, real=None, E=16, K=4, held=4, first=8,
                          bank=(3, 2)),
    "capacity_bank_over": dict(N=192, real=None, E=16, K=4, held=4, first=8,
                               bank=(3, 1), draw=True),
    "poisoned_capacity_under": dict(N=192, real=170, E=16, K=4, held=4,
                                    first=8, poison=True),
    "poisoned_capacity_over": dict(N=192, real=150, E=16, K=4, held=4,
                                   first=8, draw=True, poison=True),
}
# rows a round of the case works on, where that is less than its pairs' rows
ROUND_ROWS = {name: 384 for name in CASES if "capacity" in name}


def _plain_grouped(poison):
    """The grouped product in plain ``jax.numpy`` (``lax.ragged_dot``), the
    rows past the last group NaN where asked: what the kernel may leave."""

    def grouped(xs, bank, sizes):
        y = jax.lax.ragged_dot(
            xs.astype(jnp.float32), bank.astype(jnp.float32), sizes,
            precision=jax.lax.Precision.HIGHEST)
        if poison:
            dead = jnp.arange(xs.shape[0]) >= jnp.sum(sizes)
            y = jnp.where(dead[:, None], jnp.nan, y)
        return y

    return grouped


def _inputs(case, seed=0):
    c = dict(real=None, budget=None, bank=None, poison=False,
             draw=False) | CASES[case]
    rng = np.random.default_rng(seed)
    N, E, held = c["N"], c["E"], c["held"]
    layers, at = c["bank"] or (1, 0)
    valid = np.ones(N, bool)
    if c["real"] is not None:  # real tokens anywhere among the padding
        valid[:] = False
        valid[rng.choice(N, c["real"], replace=False)] = True
    return c, dict(
        u=rng.standard_normal((N, D)).astype(np.float32),
        valid=valid,
        w_router=rng.standard_normal((D, E)).astype(np.float32),
        bias=(0.1 * rng.standard_normal(E) + 8.0 * c["draw"] * (
            (np.arange(E) >= c["first"])
            & (np.arange(E) < c["first"] + held))).astype(np.float32),
        w1=rng.standard_normal((layers * held, D, F)).astype(np.float32),
        w2=rng.standard_normal((layers * held, F, D)).astype(np.float32),
        bank_first=at * held,
    )


def _routed(c, a, scoring, bias=True):
    """``routed_experts`` over the case, a function of what a step traces."""
    def body(xs, gmm):
        return gmm(jnp.square(jax.nn.relu(gmm(xs, a["w1"]))), a["w2"])

    def run(u, valid, bank_first):
        bank = dict(bank_experts=a["w1"].shape[0],
                    bank_first=bank_first) if c["bank"] else {}
        return moe_dispatch.routed_experts(
            u, u, valid, a["w_router"], a["bias"] if bias else None, body,
            top_k=c["K"], norm_topk_prob=True, scale=2.5, scoring=scoring,
            held=c["held"], expert_first=c["first"],
            token_budget=c["budget"], **bank)

    return run


def _per_token(c, a, ids, w):
    """Every token's sum over its pairs held here, one pair at a time."""
    out = np.zeros((c["N"], D), np.float64)
    u = a["u"].astype(np.float64)
    for n in np.flatnonzero(a["valid"]):
        for e, wk in zip(ids[n], w[n]):
            if c["first"] <= e < c["first"] + c["held"]:
                g = a["bank_first"] + e - c["first"]
                h = np.maximum(u[n] @ a["w1"][g], 0.0) ** 2
                out[n] += wk * (h @ a["w2"][g])
    return out


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
@pytest.mark.parametrize("case", list(CASES))
def test_routed_experts_matches_per_token_loop(case, scoring, monkeypatch):
    c, a = _inputs(case)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul",
                        _plain_grouped(c["poison"]))
    run = _routed(c, a, scoring)
    got, stats = jax.jit(run)(a["u"], a["valid"], a["bank_first"])
    ids, w = moe_dispatch.route(
        a["u"], a["w_router"], a["bias"], top_k=c["K"], norm_topk_prob=True,
        scale=2.5, scoring=scoring)
    ids, w = np.asarray(ids), np.asarray(w)
    want = _per_token(c, a, ids, w)
    got = np.asarray(got)
    assert got.dtype == np.float32 and got.shape == (c["N"], D)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5 * np.abs(want).max())
    # what holds no pair here (a padding token, a token routed elsewhere)
    # gets exactly nothing
    mine = ((ids >= c["first"]) & (ids < c["first"] + c["held"])
            & a["valid"][:, None])
    assert (got[~mine.any(axis=1)] == 0.0).all()
    if case in ("ep_share", "budget_ep_share"):
        assert (~mine.any(axis=1) & a["valid"]).any()  # the case is one
    assert float(stats[1]) == mine.sum()
    # the rows a round works on, and whether this step needed a second
    pairs = (c["budget"] or c["N"]) * c["K"]
    cap = moe_dispatch.capacity(pairs, c["held"], c["E"])
    assert cap == ROUND_ROWS.get(case, moe_dispatch._row_tiles(pairs))
    assert float(stats[5]) == (mine.sum() > cap)
    if "capacity" in case:
        assert (mine.sum() > cap) == ("over" in case or "rounds" in case)
        assert (mine.sum() == cap) == ("exact" in case)


@pytest.mark.parametrize("case", ["bank", "capacity_bank", "capacity_over"])
def test_routed_experts_through_the_kernel(case):
    """The same through ``megablox.gmm`` (interpreted here), whose rows past
    the last group are its own leavings, at a bank of several layers; and a
    share's rounds, whose groups are the parts of the experts' pairs that
    fall inside a round."""
    c, a = _inputs(case)
    u = jnp.asarray(a["u"], jnp.bfloat16)
    got, _ = jax.jit(_routed(c, a, "sigmoid"))(u, a["valid"], a["bank_first"])
    ids, w = moe_dispatch.route(
        u, a["w_router"], a["bias"], top_k=c["K"], norm_topk_prob=True,
        scale=2.5, scoring="sigmoid")
    a["u"] = np.asarray(u.astype(jnp.float32))
    want = _per_token(c, a, np.asarray(ids), np.asarray(w))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-2,
                               atol=2e-2 * np.abs(want).max())


@pytest.mark.parametrize("case", ["ep_share", "budget_ep_share",
                                  "rows_past_pairs", "bank"])
def test_dispatch_counts_are_numpy_bincount(case):
    c, a = _inputs(case, seed=3)
    ids, w = moe_dispatch.route(
        a["u"], a["w_router"], a["bias"], top_k=c["K"], norm_topk_prob=True,
        scale=1.0)
    tok, wrow, pos, wheld, sizes, stats = moe_dispatch.dispatch(
        ids, w, jnp.asarray(a["valid"]), held=c["held"],
        expert_first=c["first"])
    if c["bank"]:
        sizes = moe_dispatch.bank_sizes(
            sizes, a["w1"].shape[0], a["bank_first"])
    tok, wrow, pos, wheld, sizes, stats = map(
        np.asarray, (tok, wrow, pos, wheld, sizes, stats))
    ids, N, K = np.asarray(ids), c["N"], c["K"]
    local = ids - c["first"]
    mine = (local >= 0) & (local < c["held"]) & a["valid"][:, None]
    counts = np.bincount(local[mine], minlength=c["held"])
    want = np.zeros(a["w1"].shape[0] if c["bank"] else c["held"], np.int64)
    want[a["bank_first"] * bool(c["bank"]):][:c["held"]] = counts
    assert sizes.dtype == np.int32 and (sizes == want).all()
    assert stats.tolist() == [
        a["valid"].sum() * K, counts.sum(), counts.max(), (counts > 0).sum(), 1.0]
    # the way out and the way back are one permutation: a held pair's row
    # is its token's, inside its expert's group
    rows = -(-N * K // moe_dispatch.GROUP_ROWS) * moe_dispatch.GROUP_ROWS
    assert tok.shape == (rows,) and pos.shape == (N, K)
    assert (pos[mine] < counts.sum()).all() and (pos[~mine] >= counts.sum()).all()
    assert (tok[pos[mine]] == np.nonzero(mine)[0]).all()
    starts = np.concatenate([[0], np.cumsum(counts)])
    assert (starts[local[mine]] <= pos[mine]).all()
    assert (pos[mine] < starts[local[mine] + 1]).all()
    assert (wheld[~mine] == 0).all() and (wheld[mine] == np.asarray(w)[mine]).all()
    # the weights rode the sort: a row's is its pair's
    assert (wrow[pos] == wheld).all() and (wrow[counts.sum():] == 0).all()


def _lowered(case, monkeypatch):
    c, a = _inputs(case)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul", _plain_grouped(False))
    return jax.jit(_routed(c, a, "softmax", bias=False)).lower(
        jnp.asarray(a["u"], jnp.bfloat16), a["valid"],
        a["bank_first"]).as_text()


@pytest.mark.parametrize("case", ["budget_ep_share", "rows_past_pairs",
                                  "capacity_under", "capacity_budget"])
def test_lowered_program_holds_no_scatter(case, monkeypatch):
    """A prefill shape and a decode shape, and a share's rounds: nothing
    between the router and the residual lowers to a scatter (XLA runs one
    row after row on the chip; a CPU run would not show it come back). The
    grouped product is the plain one: ``megablox`` builds its group
    metadata with small scatters of its own, which are the kernel's and not
    the dispatch's."""
    text = _lowered(case, monkeypatch)
    assert "gather" in text and "sort" in text  # the text is the program's
    assert "scatter" not in text


@pytest.mark.parametrize("case,rounds", [
    ("all_held", False), ("budget", False), ("ep_share", False),
    ("rows_past_pairs", False), ("capacity_under", True),
    ("capacity_bank", True)])
def test_rounds_are_traced_only_under_a_capacity(case, rounds, monkeypatch):
    """A share that holds every expert the router scores, and a step whose
    pairs are one row tile, get the plain program: no loop stands in its
    text (of rounds, or the interpreted sum over held rows), and a capacity
    that was all the rows would give the same text. A share of a larger
    step gets the rounds."""
    text = _lowered(case, monkeypatch)
    assert ("stablehlo.while" in text) == rounds
    monkeypatch.setattr(
        moe_dispatch, "capacity",
        lambda pairs, held, scored: moe_dispatch._row_tiles(pairs))
    assert (_lowered(case, monkeypatch) == text) == (not rounds)


@pytest.mark.parametrize("pairs,held,scored,rows", [
    (1024 * 10, 64, 512, 1920), (64 * 10, 64, 512, 128),  # the qwen cell
    (1024 * 22, 128, 512, 8448), (32 * 22, 128, 512, 384),  # the hybrid
    (1024 * 8, 16, 64, 3072), (32 * 8, 16, 64, 128),  # the window mix
    (1024 * 4, 64, 64, 4096), (16 * 4, 64, 64, 128),  # the latent: all
    (3 * 2, 3, 8, 128), (24 * 2, 4, 16, 128), (1, 1, 1000, 128),
    (8192 * 22, 128, 512, 32768), (8192 * 22, 512, 512, 8192 * 22)])
def test_capacity_is_a_function_of_shapes(pairs, held, scored, rows):
    got = moe_dispatch.capacity(pairs, held, scored)
    assert got == rows and got % moe_dispatch.GROUP_ROWS == 0
    # all the rows, the cap, or room for the pairs a share expects
    assert (got in (moe_dispatch._row_tiles(pairs), moe_dispatch.ROUND_ROWS_MAX)
            or got * scored >= pairs * held)


@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_route_weights_are_the_chosen_scores_bit_for_bit(scoring, bias):
    """The weights without a second pick out of the scores: what
    ``take_along_axis`` picked, to the bit, ties and all."""
    rng = np.random.default_rng(5)
    u = rng.standard_normal((40, D)).astype(np.float32)
    u[7] = u[3]  # two tokens alike
    w_r = rng.standard_normal((D, 32)).astype(np.float32)
    w_r[:, 9] = w_r[:, 4]  # two experts tied in every token
    b = (0.5 * rng.standard_normal(32)).astype(np.float32) if bias else None
    ids, w = moe_dispatch.route(u, w_r, b, top_k=6, norm_topk_prob=False,
                                scale=1.0, scoring=scoring)
    logits = jnp.einsum("nd,de->ne", u, w_r,
                        precision=jax.lax.Precision.HIGHEST)
    s = (jax.nn.softmax(logits, -1) if scoring == "softmax"
         else jax.nn.sigmoid(logits))
    _, want_ids = jax.lax.top_k(s if b is None else s + b, 6)
    assert (np.asarray(ids) == np.asarray(want_ids)).all()
    want = np.asarray(jnp.take_along_axis(s, want_ids, axis=-1))
    assert np.asarray(w).tobytes() == want.tobytes()


@pytest.mark.parametrize("count", [0, 1, 200, 256, 300])
@pytest.mark.parametrize("block_bytes", [16 << 20, 24 * 128 * 4])
def test_sum_rows_adds_the_live_rows_at_their_tokens(count, block_bytes,
                                                     monkeypatch):
    """The held rows' way back alone: rows past the count are NaN and are
    not read; the tokens' sums in one block and in blocks of 24 tokens of
    which the last is part empty; over what the sums held (a first round:
    unread) and onto it (a further round)."""
    monkeypatch.setattr(moe_dispatch, "SUMS_BLOCK_BYTES", block_bytes)
    rng = np.random.default_rng(count)
    C, n, N = 384, 128, 50
    y = rng.standard_normal((C, n)).astype(np.float32)
    tok = rng.integers(0, N, C).astype(np.int32)
    w = rng.standard_normal(C).astype(np.float32)
    want = np.zeros((N, n), np.float64)
    np.add.at(want, tok[:count], w[:count, None].astype(np.float64) * y[:count])
    y[count:] = np.nan
    blocks, block = moe_dispatch.sums_blocks(N, n)
    assert (blocks, block) == ((1, N) if block_bytes > N * n * 4 else (3, 24))
    before = rng.standard_normal((blocks * block, n)).astype(np.float32)
    over, onto = (np.asarray(jax.jit(moe_dispatch.sum_rows)(
        y, tok, w, jnp.int32(count), before, flag))[:N] for flag in (0, 1))
    assert over.shape == (N, n) and over.dtype == np.float32
    np.testing.assert_allclose(over, want, rtol=1e-5, atol=1e-5)
    assert (over[np.setdiff1d(np.arange(N), tok[:count])] == 0.0).all()
    # a further round adds onto what the sums hold
    np.testing.assert_allclose(onto, before[:N] + want, rtol=1e-5, atol=1e-5)
