"""A run with the timed path broken underneath reports ``correct``
false: one case for each fault a cell can have. (A single-chip cell has
no exchange between chips to leave out.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.serve.scorer import BatchScorer

from test_bench_cells import small_cell

REAL_FIT = repro.fit


def _checks(ctx):
    return {n: (v, lim) for n, v, lim in ctx.checks}


def _failed(ctx):
    return sorted(n for n, (v, lim) in _checks(ctx).items() if not v <= lim)


def _kernel(X, kg):
    n = jnp.sum(X * X, axis=1)
    sq = n[:, None] + n[None, :] - 2.0 * jnp.dot(X, X.T,
                                                 precision="highest")
    return jnp.exp(-kg * jnp.maximum(sq, 0.0))


def _with(res, gamma, f):
    return res._replace(model=res.model._replace(gamma=gamma), f=f)


def fit_state_unchanged(X, spec, **kw):
    """The solver returns its starting point: feasible, never moved."""
    res = REAL_FIT(X, spec, **kw)
    m = X.shape[0]
    g0 = jnp.full((m,), spec.total() / m, jnp.float32)
    return _with(res, g0, _kernel(X, spec.kernel.gamma) @ g0)


def fit_answer_altered(X, spec, **kw):
    """One pair of coefficients moved after the solve: still feasible,
    but no longer the answer the returned f belongs to."""
    res = REAL_FIT(X, spec, **kw)
    g = res.model.gamma
    step = 0.25 * spec.upper(X.shape[0])
    i, j = int(jnp.argmax(g)), int(jnp.argmin(g))
    return _with(res, g.at[i].add(-step).at[j].add(step), res.f)


def fit_half_the_rows(X, spec, **kw):
    """Half the rows left out of the solve, the answer spread over all."""
    m = X.shape[0]
    half = REAL_FIT(X[: m // 2], spec, **kw)
    g = jnp.concatenate([half.model.gamma, jnp.zeros((m - m // 2,))])
    return _with(half._replace(model=half.model._replace(X=X)), g,
                 jnp.concatenate([half.f, jnp.zeros((m - m // 2,))]))


def fit_compiles_in_the_window(X, spec, **kw):
    """A fresh program traced and compiled on every call."""
    jax.jit(lambda x: x * 2.0 + 1.0)(X).block_until_ready()
    return REAL_FIT(X, spec, **kw)


@pytest.mark.parametrize("name", ["fraud.fit", "embed.fit"])
@pytest.mark.parametrize("fault,expect", [
    (fit_state_unchanged, "kkt_max"),
    (fit_answer_altered, "f_rel"),
    (fit_half_the_rows, "kkt_max"),
    (fit_compiles_in_the_window, "window_compiles"),
])
def test_fit_fault_is_not_correct(fault, expect, name, monkeypatch):
    monkeypatch.setattr(repro, "fit", fault)
    _, _, ctx = small_cell(name, seconds=0.5)
    assert not ctx.correct
    assert expect in _failed(ctx), _checks(ctx)


REAL_SCORE = BatchScorer._score_once


def score_altered(self, q):
    """Every score shifted where it is produced."""
    return np.asarray(REAL_SCORE(self, q)) + np.float32(1e-4)


def score_half_the_rows(self, q):
    """Half the rows scored, the rest given the mean of that half."""
    out = np.asarray(REAL_SCORE(self, q)).copy()
    n = out.shape[0]
    if n > 1:
        out[(n + 1) // 2:] = out[:(n + 1) // 2].mean()
    return out


@pytest.mark.parametrize("name", ["fraud.events", "embed.batch"])
@pytest.mark.parametrize("fault", [score_altered, score_half_the_rows])
def test_serving_fault_is_not_correct(name, fault, monkeypatch):
    monkeypatch.setattr(BatchScorer, "_score_once", fault)
    _, _, ctx = small_cell(name)
    assert not ctx.correct
    assert "kernel_sum_rel" in _failed(ctx), _checks(ctx)
