"""``models/moe_dispatch.py`` alone: the sort-by-expert dispatch and the way
back against a per-token loop over every expert, at the shapes the step
programs hand it (an expert-parallel share, a padded prefill step under its
token budget, a decode step under the row tile, a stack of layers' banks),
with the rows no group owns poisoned; the counts against ``numpy``; and the
lowered program, which holds no scatter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.models import moe_dispatch

D, F = 16, 8  # the experts' width and their inner width

# name -> tokens, real tokens (None: all), scored experts, top_k, held,
# expert_first, token_budget, layers in the bank and this layer's place
# (None: the layer's own bank), rows past the last group set to NaN
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
}


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
    c = dict(real=None, budget=None, bank=None, poison=False) | CASES[case]
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
        bias=(0.1 * rng.standard_normal(E)).astype(np.float32),
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


def test_routed_experts_through_the_kernel():
    """The same through ``megablox.gmm`` (interpreted here), whose rows past
    the last group are its own leavings, at a bank of several layers."""
    c, a = _inputs("bank")
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
    kw = dict(held=c["held"], expert_first=c["first"])
    if c["bank"]:
        kw.update(bank_experts=a["w1"].shape[0], bank_first=a["bank_first"])
    tok, pos, wheld, sizes, stats = map(np.asarray, moe_dispatch.dispatch(
        ids, w, jnp.asarray(a["valid"]), **kw))
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


@pytest.mark.parametrize("case", ["budget_ep_share", "rows_past_pairs"])
def test_lowered_program_holds_no_scatter(case, monkeypatch):
    """A prefill shape and a decode shape: nothing between the router and
    the residual lowers to a scatter (XLA runs one row after row on the
    chip; a CPU run would not show it come back). The grouped product is
    the plain one: ``megablox`` builds its group metadata with small
    scatters of its own, which are the kernel's and not the dispatch's."""
    c, a = _inputs(case)
    monkeypatch.setattr(moe_dispatch, "grouped_matmul", _plain_grouped(False))
    text = jax.jit(_routed(c, a, "softmax", bias=False)).lower(
        jnp.asarray(a["u"], jnp.bfloat16), a["valid"],
        a["bank_first"]).as_text()
    assert "gather" in text and "sort" in text  # the text is the program's
    assert "scatter" not in text
