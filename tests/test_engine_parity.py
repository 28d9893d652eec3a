"""Engine parity matrix: every (GramProvider x Selector) composition must
reach the QP-baseline objective on the toy set — including the Pallas
provider in interpret mode (CPU), shrinking-through-engine, and the
``repro.fit`` strategy router. Also asserts the blocked solver's f-cache
update really goes through the Pallas ``fupdate`` kernel when
``gram_mode="pallas"``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import (SlabSpec, dual_objective, linear, rbf, solve_blocked,
                        solve_qp, solve_smo)
from repro.core.shrinking import solve_blocked_shrinking
from repro.data import make_toy
# the same scale-aware per-dtype tolerances the kernel parity matrix in
# tests/test_kernels.py asserts with
from repro.kernels.precision import truth_tolerance

SPEC = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
M = 96

PROVIDERS = ["precomputed", "on_the_fly", "pallas"]
SELECTORS = ["paper", "mvp", "block"]


@pytest.fixture(scope="module")
def toy():
    X, y = make_toy(jax.random.PRNGKey(5), M)
    K = SPEC.kernel.gram(X.astype(jnp.float32))
    qp = solve_qp(X, SPEC, max_iters=60_000, tol=1e-10)
    return X, K, float(dual_objective(qp.gamma, K))


def _objective(res, K):
    return float(dual_objective(res.model.gamma, K))


@pytest.mark.parametrize("gram_mode", PROVIDERS)
@pytest.mark.parametrize("selection", SELECTORS)
def test_provider_selector_matrix_reaches_qp(toy, gram_mode, selection):
    X, K, o_qp = toy
    if selection == "block":
        res = solve_blocked(X, SPEC, P=4, gram_mode=gram_mode, tol=1e-4)
    else:
        res = solve_smo(X, SPEC, selection=selection, gram_mode=gram_mode,
                        tol=1e-4)
    assert _objective(res, K) == pytest.approx(o_qp, abs=2e-3)
    # feasibility of the returned gamma
    g = res.model.gamma
    assert float(jnp.sum(g)) == pytest.approx(SPEC.total(), abs=1e-4)
    assert float(jnp.max(g)) <= SPEC.upper(M) + 1e-6
    assert float(jnp.min(g)) >= SPEC.lower(M) - 1e-6


def test_shrinking_through_engine_pallas(toy):
    """The shrinking repack driver drives the engine's pallas provider."""
    X, K, o_qp = toy
    res = solve_blocked_shrinking(X, SPEC, P=4, gram_mode="pallas",
                                  tol=1e-4, warm_iters=30)
    assert _objective(res, K) == pytest.approx(o_qp, abs=2e-3)


def test_pallas_gram_invokes_fupdate_kernel(toy, monkeypatch):
    """gram_mode='pallas' must route the f-cache update through the Pallas
    fupdate kernel (interpret mode on CPU)."""
    from repro.core.engine import gram as engine_gram
    from repro.kernels.fupdate.ops import fupdate as real_fupdate

    calls = {"n": 0}

    def counting_fupdate(*args, **kwargs):
        calls["n"] += 1
        return real_fupdate(*args, **kwargs)

    monkeypatch.setattr(engine_gram, "fupdate", counting_fupdate)
    X, K, o_qp = toy
    # P=3 is used nowhere else in the suite, so jit must retrace and the
    # trace goes through the patched symbol.
    res = solve_blocked(X, SPEC, P=3, gram_mode="pallas", tol=1e-3)
    assert calls["n"] > 0
    assert _objective(res, K) == pytest.approx(o_qp, abs=2e-3)


@pytest.mark.parametrize("strategy", ["auto", "paper", "mvp", "blocked"])
def test_fit_strategies_reach_qp(toy, strategy):
    X, K, o_qp = toy
    res = repro.fit(X, SPEC, strategy=strategy, tol=1e-4)
    assert _objective(res, K) == pytest.approx(o_qp, abs=2e-3)


def test_fit_rejects_unknown_strategy(toy):
    X, _, _ = toy
    with pytest.raises(ValueError):
        repro.fit(X, SPEC, strategy="nope")
    with pytest.raises(ValueError):
        repro.fit(X, SPEC, strategy="distributed")   # no mesh given


def test_block_selector_p1_matches_mvp(toy):
    """Block top-P with P=1 is the classic maximal-violating pair — the
    paper's single-pair analytic update — and lands on the same optimum."""
    X, K, _ = toy
    r_blk = solve_blocked(X, SPEC, P=1, gram_mode="precomputed", tol=1e-4)
    r_mvp = solve_smo(X, SPEC, selection="mvp", gram_mode="precomputed",
                      tol=1e-4)
    assert _objective(r_blk, K) == pytest.approx(_objective(r_mvp, K),
                                                 abs=1e-4)


def test_engine_state_is_single_source():
    """No duplicated solver state types remain: all facades carry the
    engine's SolverState and return its SMOResult."""
    from repro.core import batched_smo, distributed_smo, smo
    from repro.core.engine.types import SMOResult

    assert smo.SMOResult is SMOResult
    for mod in (smo, batched_smo, distributed_smo):
        assert not hasattr(mod, "SMOState")
        assert not hasattr(mod, "BlockedState")
        assert not hasattr(mod, "_DistState")


def test_spec_roundtrip_from_fitted_model(toy):
    """A spec recovered from a fitted model (its kernel params come back
    as 0-d jax arrays through the jit boundary) must be reusable."""
    X, K, o_qp = toy
    res = repro.fit(X, SPEC, strategy="blocked", tol=1e-3)
    spec_rt = res.model.spec
    assert not isinstance(spec_rt.kernel.gamma, float)   # array round-trip
    res2 = repro.fit(X, spec_rt, strategy="blocked", tol=1e-3)
    assert _objective(res2, K) == pytest.approx(o_qp, abs=2e-3)


def test_fit_kwargs_flow_across_strategies(toy):
    """The iteration-cap kwarg reaches whichever solver 'auto' picks —
    max_iters and max_outer are accepted interchangeably."""
    X, _, _ = toy
    r1 = repro.fit(X, SPEC, strategy="shrinking", max_outer=500, tol=1e-3)
    r2 = repro.fit(X, SPEC, strategy="paper", max_outer=50, tol=1e-3)
    r3 = repro.fit(X, SPEC, strategy="blocked", max_iters=50, tol=1e-3)
    assert int(r2.iters) <= 50
    assert int(r3.iters) <= 50
    assert np.isfinite(float(r1.gap))


@pytest.mark.parametrize("precision", ["bf16", "f16"])
@pytest.mark.parametrize("gram_mode", PROVIDERS)
def test_low_precision_providers_reach_qp(toy, gram_mode, precision):
    """16-bit Gram tile inputs must not move the optimum beyond the
    documented tolerance: the solve still reaches the f32 QP objective
    (f32 accumulation keeps the dual well-conditioned; only the inputs
    are rounded) and returns a feasible gamma."""
    X, K, o_qp = toy
    res = solve_blocked(X, SPEC, P=4, gram_mode=gram_mode,
                        precision=precision, tol=1e-4)
    assert _objective(res, K) == pytest.approx(o_qp, abs=5e-3)
    g = res.model.gamma
    assert float(jnp.sum(g)) == pytest.approx(SPEC.total(), abs=1e-4)
    assert float(jnp.max(g)) <= SPEC.upper(M) + 1e-6
    assert float(jnp.min(g)) >= SPEC.lower(M) - 1e-6


def test_precision_f32_solve_bit_identical(toy):
    """precision="f32" must leave the solver bit-for-bit unchanged."""
    X, _, _ = toy
    r0 = solve_blocked(X, SPEC, P=4, gram_mode="precomputed", tol=1e-4)
    r1 = solve_blocked(X, SPEC, P=4, gram_mode="precomputed",
                       precision="f32", tol=1e-4)
    assert bool(jnp.all(r0.model.gamma == r1.model.gamma))
    assert int(r0.iters) == int(r1.iters)


def test_fit_threads_precision_to_provider(toy, monkeypatch):
    """repro.fit(..., precision=...) must reach the provider layer for
    every local strategy."""
    from repro.core.engine import gram as engine_gram

    seen = []
    real = engine_gram.make_provider

    def spying(gram_mode, X, kernel, interpret=None, precision="f32"):
        seen.append(precision)
        return real(gram_mode, X, kernel, interpret=interpret,
                    precision=precision)

    monkeypatch.setattr(engine_gram, "make_provider", spying)
    # the facades bind engine.make_provider through the package namespace
    import repro.core.engine as engine_pkg
    monkeypatch.setattr(engine_pkg, "make_provider", spying)
    X, _, _ = toy
    for strategy in ("blocked", "mvp", "shrinking"):
        seen.clear()
        repro.fit(X, SPEC, strategy=strategy, precision="bf16", tol=1e-2,
                  max_outer=40, **({"warm_iters": 20}
                                   if strategy == "shrinking" else {}))
        assert seen and all(p == "bf16" for p in seen), strategy


SHRINK_KERNELS = {"rbf": lambda: rbf(gamma=0.5), "linear": linear}

# Two independently converged solves of the same dual agree only to the
# KKT tolerance, not to machine precision: this floor (calibrated on the
# toy set at tol=1e-4) is added on top of the per-dtype kernel
# tolerances, which only cover the Gram-tile rounding.
SOLVER_ATOL_FLOOR = 5e-3


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("kernel_name", ["rbf", "linear"])
def test_shrinking_matches_blocked(kernel_name, precision):
    """The shrinking repack driver must land on the same slab as the
    plain blocked solver — objective AND both offsets — for every
    (kernel, precision) cell, within the scale-aware per-dtype
    tolerances plus the solver-convergence floor."""
    spec = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5,
                    kernel=SHRINK_KERNELS[kernel_name]())
    X, _ = make_toy(jax.random.PRNGKey(5), M)
    K = spec.kernel.gram(X.astype(jnp.float32))   # f32 scoreboard
    r_blk = solve_blocked(X, spec, P=4, gram_mode="precomputed",
                          precision=precision, tol=1e-4)
    r_shr = solve_blocked_shrinking(X, spec, P=4, gram_mode="precomputed",
                                    precision=precision, tol=1e-4,
                                    warm_iters=30)
    o_blk = float(dual_objective(r_blk.model.gamma, K))
    o_shr = float(dual_objective(r_shr.model.gamma, K))
    tol_obj = truth_tolerance(precision, np.asarray([o_blk]))
    np.testing.assert_allclose(
        o_shr, o_blk, rtol=tol_obj["rtol"],
        atol=max(tol_obj["atol"], SOLVER_ATOL_FLOOR))

    rho_blk = np.asarray([float(r_blk.model.rho1), float(r_blk.model.rho2)])
    rho_shr = np.asarray([float(r_shr.model.rho1), float(r_shr.model.rho2)])
    tol_rho = truth_tolerance(precision, rho_blk)
    np.testing.assert_allclose(
        rho_shr, rho_blk, rtol=tol_rho["rtol"],
        atol=max(tol_rho["atol"], SOLVER_ATOL_FLOOR))


def test_provider_rejects_unknown_precision(toy):
    X, _, _ = toy
    with pytest.raises(ValueError):
        solve_blocked(X, SPEC, P=4, gram_mode="precomputed",
                      precision="fp8", tol=1e-2)


# -- sharded engine cells ---------------------------------------------------
# The sharded provider/selector need >1 device, and jax pins the device
# count at first import, so each cell runs in a forced-device subprocess
# (the shared harness in conftest.py). One subprocess per precision keeps
# the jax start-up cost at one import per cell while still giving CI a
# distinct pass/fail signal per dtype.

from conftest import run_forced_devices  # noqa: E402


@pytest.mark.parametrize("precision", ["f32", "bf16", "f16"])
def test_sharded_engine_parity_matches_blocked(precision):
    """repro.fit(strategy="sharded") on an 8-forced-device launch-layer
    mesh must reach the single-device blocked optimum at every supported
    Gram tile precision — objective AND both slab offsets — and the hot
    loop must actually run the per-shard Pallas fupdate kernel (counted
    via the engine module's symbol, which ShardedGram.apply_update
    resolves at trace time) on the shard's X_local as the provider
    prepared it: 12 local rows (96 over 8 shards, not a lane multiple)
    padded to 128, 128 lanes, the stream dtype, lane-dense norms."""
    res = run_forced_devices(f"""
        import json
        import jax, jax.numpy as jnp
        import repro
        import repro.core.engine.gram as eg
        from repro.core import SlabSpec, rbf, solve_blocked, dual_objective
        from repro.data import make_toy
        from repro.kernels.fupdate.ops import PreparedX

        calls = {{"n": 0, "prepared": set()}}
        real_fupdate = eg.fupdate
        def counting(*a, **k):
            calls["n"] += 1
            x = a[0]
            calls["prepared"].add(
                (type(x).__name__, x.m, *x.x.shape, str(x.x.dtype),
                 *x.xn.shape) if isinstance(x, PreparedX)
                else (type(x).__name__,))
            return real_fupdate(*a, **k)
        eg.fupdate = counting

        precision = {precision!r}
        X, _ = make_toy(jax.random.PRNGKey(5), 96)
        spec = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
        K = spec.kernel.gram(X.astype(jnp.float32))
        rs = repro.fit(X, spec, strategy="sharded", P=4, tol=1e-4,
                       precision=precision)
        rb = solve_blocked(X, spec, P=4, tol=1e-4, precision=precision)
        print(json.dumps({{
            "obj_sharded": float(dual_objective(rs.model.gamma, K)),
            "obj_blocked": float(dual_objective(rb.model.gamma, K)),
            "rho_sharded": [float(rs.model.rho1), float(rs.model.rho2)],
            "rho_blocked": [float(rb.model.rho1), float(rb.model.rho2)],
            "sum_gamma": float(rs.model.gamma.sum()),
            "expected_sum": spec.total(),
            "converged": bool(rs.converged),
            "fupdate_calls": calls["n"],
            "prepared": sorted(calls["prepared"]),
            "n_devices": jax.device_count(),
        }}))
    """, devices=8)
    assert res["n_devices"] == 8
    assert res["converged"]
    assert res["fupdate_calls"] > 0, "sharded hot loop bypassed Pallas"
    stream = {"f32": "float32", "bf16": "bfloat16", "f16": "float16"}
    assert res["prepared"] == [
        ["PreparedX", 12, 128, 128, stream[precision], 1, 128]]
    assert res["sum_gamma"] == pytest.approx(res["expected_sum"], abs=1e-4)
    tol_obj = truth_tolerance(precision, np.asarray([res["obj_blocked"]]))
    np.testing.assert_allclose(
        res["obj_sharded"], res["obj_blocked"], rtol=tol_obj["rtol"],
        atol=max(tol_obj["atol"], SOLVER_ATOL_FLOOR))
    tol_rho = truth_tolerance(precision, np.asarray(res["rho_blocked"]))
    np.testing.assert_allclose(
        np.asarray(res["rho_sharded"]), np.asarray(res["rho_blocked"]),
        rtol=tol_rho["rtol"], atol=max(tol_rho["atol"], SOLVER_ATOL_FLOOR))
