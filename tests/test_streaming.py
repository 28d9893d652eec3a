"""Streaming warm-start layer: artifact, fit_update, drift-gated refresh.

Covers the incremental-fit path end to end:

* warm-vs-cold parity matrix — {rbf, linear} x {f32, bf16} x {blocked,
  pallas, sharded}: a warm-started re-fit must land on the cold fit's
  optimum within the documented precision tolerance (the sharded cells
  run under forced host devices in a subprocess);
* the ISSUE acceptance bound — ``fit_update`` on a 5% appended-rows
  delta converges in <= 25% of the cold iteration count, read from the
  engine's own ``SMOResult.iters``;
* drift gating — an in-distribution append refreshes warm, a shifted
  append demonstrably forces the cold refit (``refresh_modes``);
* ``ExtendableFingerprint`` parity with the full re-hash, and its
  refusal to extend when only a full re-hash can be exact;
* registry refresh preserving per-model quota and the admission layer's
  window/latency state, on a manual clock;
* ``SolverArtifact`` checkpoint round-trip feeding ``fit_update``;
* provider-level ``append_rows`` / ``expire_rows`` parity against a
  from-scratch provider.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro
from conftest import run_forced_devices
from repro.core import SlabSpec, engine, linear, rbf
from repro.core.ocssvm import dual_objective_matfree
from repro.data import make_toy
from repro.kernels.precision import truth_tolerance
from repro.serve import (AdmissionController, BucketStats,
                         ExtendableFingerprint, ModelRegistry,
                         fingerprint_array, score_drift)

KERNELS = {"rbf": rbf(gamma=0.5), "linear": linear()}
M_PREV, N_APP, N_EXP = 96, 12, 6


def _spec(kernel_name):
    return SlabSpec(nu1=0.5, nu2=0.05, eps=0.5,
                    kernel=KERNELS[kernel_name])


def _stream(seed=5, m=M_PREV, n_app=N_APP, n_exp=N_EXP):
    """(X_prev, X_new): drop the first n_exp rows, append n_app fresh."""
    X = np.asarray(make_toy(jax.random.PRNGKey(seed), m + n_app)[0],
                   np.float32)
    X_prev = X[:m]
    X_new = np.concatenate([X_prev[n_exp:], X[m:]])
    return X_prev, X_new


def _objective(res, X, spec):
    return float(dual_objective_matfree(
        res.model.gamma, jnp.asarray(X, jnp.float32), spec.kernel))


# -- warm vs cold parity matrix ---------------------------------------------

@pytest.mark.parametrize("strategy", ["blocked", "pallas"])
@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("kernel_name", ["rbf", "linear"])
def test_warm_cold_parity_matrix(kernel_name, precision, strategy):
    spec = _spec(kernel_name)
    X_prev, X_new = _stream()
    prev = repro.fit(X_prev, spec, strategy=strategy, precision=precision,
                     tol=1e-4)
    art = engine.artifact_from_result(prev, precision=precision)

    cold = repro.fit(X_new, spec, strategy=strategy, precision=precision,
                     tol=1e-4)
    stats = {}
    warm = repro.fit_update(art, X_new, strategy=strategy, tol=1e-4,
                            stats_out=stats)
    assert stats["mode"] == "warm"
    assert stats["n_fresh"] == N_APP and stats["n_expired"] == N_EXP
    assert stats["n_overlap"] == M_PREV - N_EXP

    obj_cold = _objective(cold, X_new, spec)
    obj_warm = _objective(warm, X_new, spec)
    np.testing.assert_allclose(obj_warm, obj_cold,
                               **truth_tolerance(precision, obj_cold))
    # the slab the two fits carve must agree on fresh queries
    q = np.asarray(make_toy(jax.random.PRNGKey(9), 32)[0], np.float32)
    sc = np.asarray(cold.model.decision_function(q))
    sw = np.asarray(warm.model.decision_function(q))
    np.testing.assert_allclose(sw, sc, **truth_tolerance(precision, sc))


def test_warm_cold_parity_sharded():
    """The sharded cells of the matrix: {rbf, linear} x {f32, bf16}
    under 4 forced host devices, warm seeded from a local blocked fit."""
    out = run_forced_devices("""
        import json
        import numpy as np, jax, jax.numpy as jnp
        import repro
        from repro.core import SlabSpec, engine, linear, rbf
        from repro.core.ocssvm import dual_objective_matfree
        from repro.data import make_toy

        M, APP, EXP = 64, 8, 4
        X = np.asarray(make_toy(jax.random.PRNGKey(5), M + APP)[0],
                       np.float32)
        X_prev, X_new = X[:M], np.concatenate([X[EXP:M], X[M:]])
        cells = {}
        for kname, kern in (("rbf", rbf(gamma=0.5)), ("linear", linear())):
            spec = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=kern)
            for prec in ("f32", "bf16"):
                prev = repro.fit(X_prev, spec, strategy="blocked",
                                 precision=prec, tol=1e-4)
                art = engine.artifact_from_result(prev, precision=prec)
                cold = repro.fit(X_new, spec, strategy="sharded",
                                 precision=prec, tol=1e-4)
                stats = {}
                warm = repro.fit_update(art, X_new, strategy="sharded",
                                        tol=1e-4, stats_out=stats)
                obj = lambda r: float(dual_objective_matfree(
                    r.model.gamma, jnp.asarray(X_new), kern))
                cells[f"{kname}-{prec}"] = {
                    "cold": obj(cold), "warm": obj(warm),
                    "mode": stats["mode"]}
        print(json.dumps({"devices": jax.device_count(), "cells": cells}))
    """, devices=4)
    assert out["devices"] == 4
    for name, cell in out["cells"].items():
        assert cell["mode"] == "warm", name
        prec = name.split("-")[1]
        np.testing.assert_allclose(
            cell["warm"], cell["cold"],
            err_msg=name, **truth_tolerance(prec, cell["cold"]))


# -- the acceptance bound: 5% delta in <= 25% of cold iterations ------------

def test_fit_update_5pct_delta_quarter_iters():
    M, APP = 1000, 50                        # 5% appended-rows delta
    spec = _spec("rbf")
    X = np.asarray(make_toy(jax.random.PRNGKey(5), M + APP)[0], np.float32)
    X_prev, X_new = X[:M], X                 # pure append, no expiry

    prev = repro.fit(X_prev, spec, strategy="blocked", tol=1e-4)
    art = engine.artifact_from_result(prev)
    cold = repro.fit(X_new, spec, strategy="blocked", tol=1e-4)

    stats = {}
    warm = repro.fit_update(art, X_new, strategy="blocked", tol=1e-4,
                            stats_out=stats)
    assert stats["mode"] == "warm"
    assert warm.converged and cold.converged
    ratio = int(warm.iters) / int(cold.iters)
    assert ratio <= 0.25, (
        f"warm {int(warm.iters)} vs cold {int(cold.iters)} iters "
        f"(ratio {ratio:.2f} > 0.25)")
    obj_cold = _objective(cold, X_new, spec)
    np.testing.assert_allclose(_objective(warm, X_new, spec), obj_cold,
                               **truth_tolerance("f32", obj_cold))


def test_fit_update_low_overlap_falls_back_cold():
    spec = _spec("rbf")
    X_prev, _ = _stream(seed=5)
    X_other = np.asarray(make_toy(jax.random.PRNGKey(77), M_PREV)[0],
                         np.float32)
    prev = repro.fit(X_prev, spec, strategy="blocked", tol=1e-3)
    stats = {}
    res = repro.fit_update(engine.artifact_from_result(prev), X_other,
                           strategy="blocked", tol=1e-3, stats_out=stats)
    assert stats["mode"] == "cold" and stats["n_overlap"] == 0
    assert res.converged


# -- drift gating through the registry --------------------------------------

SPEC = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
FIT_KW = dict(tol=1e-3, strategy="blocked")


def _inband_append(X_prev, n):
    """Fresh rows guaranteed in-distribution: jittered training rows
    (the jitter keeps their content hashes fresh, the distribution not —
    the toy generator's tail can run anomaly-heavy, which at n=12 is a
    legitimate drift signal, not a flake to paper over)."""
    rng = np.random.default_rng(0)
    return np.asarray(
        X_prev[:n] + rng.normal(0, 1e-3, (n, X_prev.shape[1])), np.float32)


def test_refresh_routes_warm_by_default_and_drift_forces_refit():
    X_prev, _ = _stream(seed=5)
    app = _inband_append(X_prev, N_APP)
    reg = ModelRegistry()
    reg.register("a", X_prev, SPEC, **FIT_KW)
    sm1 = reg.get("a")
    assert sm1.artifact is not None and sm1.artifact.m == M_PREV

    # in-distribution append: warm delta-solve through the cache
    sm2 = reg.refresh("a", append=app)
    st = reg.refresh_stats("a")
    assert sm2 is not sm1
    assert st["modes"] == {"warm": 1, "cold": 0}
    assert st["last_drift"] is not None and not st["last_drift"].drifted
    assert st["last_warm"]["mode"] == "warm"
    assert st["last_warm"]["n_fresh"] == N_APP

    # adversarial cell: a shifted append must trip the detector and
    # refit cold — warm-starting from the wrong distribution is the
    # failure mode the gate exists for
    shifted = np.asarray(app + 5.0, np.float32)
    sm3 = reg.refresh("a", append=shifted)
    st = reg.refresh_stats("a")
    assert sm3 is not sm2
    assert st["modes"]["cold"] == 1
    assert st["last_drift"].drifted
    assert st["last_drift"].statistic > st["last_drift"].threshold

    # the detector's raw verdicts, straight from the artifact
    assert not score_drift(sm1.artifact, X_prev).drifted
    assert score_drift(sm1.artifact, X_prev + 5.0).drifted


def test_refresh_mode_forced_and_validated():
    X_prev, X_new = _stream(seed=5)
    reg = ModelRegistry()
    reg.register("a", X_prev, SPEC, **FIT_KW)
    reg.get("a")
    reg.refresh("a", mode="cold")
    reg.refresh("a", mode="warm")
    assert reg.refresh_stats("a")["modes"] == {"warm": 1, "cold": 1}
    with pytest.raises(ValueError):
        reg.refresh("a", mode="tepid")
    with pytest.raises(ValueError):
        reg.refresh("a", append=X_new[:2], X=X_new)


# -- extendable fingerprint --------------------------------------------------

def test_extendable_fingerprint_matches_full_rehash():
    X = np.asarray(make_toy(jax.random.PRNGKey(3), 64)[0], np.float32)
    fp = ExtendableFingerprint(X[:48])
    assert fp.key == fingerprint_array(X[:48])
    ext = fp.extend(X[48:])
    assert ext is not None
    assert ext.key == fingerprint_array(X)          # O(dm) == full O(m)
    # chaining keeps parity
    more = np.asarray(make_toy(jax.random.PRNGKey(4), 8)[0], np.float32)
    assert ext.extend(more).key == fingerprint_array(
        np.concatenate([X, more]))


def test_extendable_fingerprint_refuses_when_rehash_required(monkeypatch):
    from repro.serve import model_cache
    X = np.asarray(make_toy(jax.random.PRNGKey(3), 64)[0], np.float32)
    fp = ExtendableFingerprint(X)
    # dtype / width changes break the byte-prefix property
    assert fp.extend(X[:4].astype(np.float64)) is None
    assert fp.extend(np.zeros((2, X.shape[1] + 1), np.float32)) is None
    # above the sample budget fingerprint_array strides — no prefix
    monkeypatch.setattr(model_cache, "_HASH_SAMPLE_BYTES", X.nbytes - 1)
    sampled = ExtendableFingerprint(X)
    assert sampled.key == model_cache.fingerprint_array(X)
    assert sampled.extend(X[:4]) is None
    # an extension that would cross the budget refuses too
    monkeypatch.setattr(model_cache, "_HASH_SAMPLE_BYTES", X.nbytes + 1)
    small = ExtendableFingerprint(X)
    assert small.extend(X[:4]) is None


def test_refresh_append_rekeys_in_delta_only(monkeypatch):
    """After the first append the registry re-keys through the cached
    fingerprint: fingerprint_array (the full re-hash) must not run."""
    from repro.serve import registry as registry_mod
    X_prev, X_new = _stream(seed=5)
    app = X_new[M_PREV - N_EXP:]
    reg = ModelRegistry()
    reg.register("a", X_prev[:M_PREV - N_EXP], SPEC, **FIT_KW)
    reg.get("a")
    reg.refresh("a", append=app[:6])        # first append: builds the fp

    def boom(X):
        raise AssertionError("full re-hash on the delta path")

    monkeypatch.setattr(registry_mod.ExtendableFingerprint, "__init__",
                        lambda self, X: boom(X))
    sm = reg.refresh("a", append=app[6:])   # O(dm) keying only
    expect = np.concatenate(
        [X_prev[:M_PREV - N_EXP], app[:6], app[6:]])
    assert reg.recipe("a").X.shape == expect.shape
    assert reg.get("a") is sm


# -- refresh preserves quota + admission state (fake clock) ------------------

class ManualClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def test_refresh_preserves_quota_and_admission_state():
    X_prev, X_new = _stream(seed=5)
    app = X_new[M_PREV - N_EXP:]
    reg = ModelRegistry()
    reg.register("a", X_prev, SPEC, quota=100, **FIT_KW)
    clock = ManualClock()
    ctrl = AdmissionController(reg, clock=clock, max_batch=128)
    svc1 = ctrl.service("a")
    # a deterministic latency observation the deadline policy relies on
    svc1.stats.setdefault(64, BucketStats()).record(64, 1, 0.25)
    est_before = ctrl.estimate_latency_s("a", 30)
    assert est_before == pytest.approx(0.25)
    # an open window mid-refresh
    h = ctrl.submit("a", np.asarray(X_prev[4:12]))
    ver_before = reg.version("a")

    reg.refresh("a", append=app)

    assert reg.quota("a") == 100                 # quota survives
    assert reg.version("a") == ver_before + 1    # consumers re-resolve
    assert ctrl.queued_rows("a") == 8            # window survives
    svc2 = ctrl.service("a")
    assert svc2 is not svc1                      # fresh model behind it
    # ...but the observed bucket latencies carried over: the deadline
    # policy keeps estimating instead of resetting to fallback
    assert ctrl.estimate_latency_s("a", 30) == pytest.approx(est_before)
    assert ctrl.flush_model("a") >= 1 and h.done
    scores = np.asarray(h.result())
    direct = np.asarray(reg.get("a").scorer().score(
        np.asarray(X_prev[4:12])))
    np.testing.assert_allclose(scores, direct, rtol=0, atol=0)


def test_refresh_window_deadline_state_survives_on_fake_clock():
    """A deadline set before a refresh still flushes at the right tick
    after it — refresh must not reset the window's deadline pressure."""
    X_prev, X_new = _stream(seed=5)
    reg = ModelRegistry()
    reg.register("a", X_prev, SPEC, **FIT_KW)
    clock = ManualClock()
    ctrl = AdmissionController(reg, clock=clock, max_batch=128)
    svc = ctrl.service("a")
    svc.stats.setdefault(64, BucketStats()).record(64, 1, 0.25)
    h = ctrl.submit("a", np.asarray(X_prev[:4]), deadline=1.0)
    assert not ctrl.due("a")
    reg.refresh("a", append=X_new[M_PREV - N_EXP:])
    assert not ctrl.due("a")            # not due merely because refreshed
    clock.advance(0.8)                  # 0.8 + 0.25 >= 1.0: due now
    assert ctrl.due("a")
    assert ctrl.poll() == 1 and h.done


# -- artifact checkpoint round-trip -----------------------------------------

def test_artifact_roundtrip_feeds_fit_update(tmp_path):
    spec = _spec("rbf")
    X_prev, X_new = _stream(seed=5)
    prev = repro.fit(X_prev, spec, strategy="blocked", tol=1e-4)
    art = engine.artifact_from_result(prev)
    path = str(tmp_path / "model.npz")
    art.save(path)
    loaded = engine.SolverArtifact.load(path)
    assert loaded.m == art.m and loaded.precision == art.precision
    np.testing.assert_array_equal(loaded.hashes, art.hashes)
    np.testing.assert_allclose(np.asarray(loaded.f), np.asarray(art.f),
                               rtol=0, atol=0)
    assert (float(loaded.spec.nu1) == pytest.approx(float(spec.nu1))
            and loaded.spec.kernel.name == "rbf")

    stats = {}
    warm = repro.fit_update(loaded, X_new, strategy="blocked", tol=1e-4,
                            stats_out=stats)
    assert stats["mode"] == "warm"
    cold = repro.fit(X_new, spec, strategy="blocked", tol=1e-4)
    obj_cold = _objective(cold, X_new, spec)
    np.testing.assert_allclose(_objective(warm, X_new, spec), obj_cold,
                               **truth_tolerance("f32", obj_cold))


# -- provider-level append / expire parity -----------------------------------

@pytest.mark.parametrize("gram_mode", ["precomputed", "on_the_fly",
                                       "pallas"])
def test_provider_append_expire_matches_rebuild(gram_mode):
    spec = _spec("rbf")
    X_prev, _ = _stream(seed=5)
    X = jnp.asarray(X_prev[:40])
    X_app = jnp.asarray(X_prev[40:52])
    kern = spec.kernel
    prov = engine.make_provider(gram_mode, X, kern, interpret=True)
    gamma = jnp.linspace(0.001, 0.02, X.shape[0], dtype=jnp.float32)
    f = prov.init_scores(gamma)

    p2, g2, f2 = prov.append_rows(X_app, gamma, f)
    ref = engine.make_provider(
        gram_mode, jnp.concatenate([X, X_app]), kern, interpret=True)
    f_ref = ref.init_scores(g2)
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f_ref),
                               rtol=0, atol=5e-6)
    assert float(jnp.abs(g2[X.shape[0]:]).max()) == 0.0

    idx = np.asarray([0, 3, 17, 41])
    p3, g3, f3 = p2.expire_rows(idx, g2, f2)
    keep = np.setdiff1d(np.arange(int(g2.shape[0])), idx)
    ref3 = engine.make_provider(
        gram_mode, jnp.concatenate([X, X_app])[keep], kern, interpret=True)
    np.testing.assert_allclose(np.asarray(f3),
                               np.asarray(ref3.init_scores(g3)),
                               rtol=0, atol=5e-6)
    np.testing.assert_allclose(np.asarray(g3),
                               np.asarray(g2)[keep], rtol=0, atol=0)


@pytest.mark.parametrize("nq,n", [(5000, 4000), (40, 600), (4100, 4100)],
                         ids=["blocked", "one-pass", "square"])
def test_raw_scores_blocked_against_other_rows(nq, n):
    """k(Xq, Y) @ g in row blocks (the warm start's appended-row and
    correction passes) equals the dense product, whether or not the
    block would have fitted in one pass."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    Xq = jax.random.normal(k1, (nq, 3), jnp.float32)
    Y = Xq if nq == n else jax.random.normal(k2, (n, 3), jnp.float32)
    g = jax.random.uniform(k3, (n,), jnp.float32)
    kern = rbf(gamma=0.5)
    got = engine.raw_scores_blocked(Xq, g, kern, Y=Y)
    want = kern.cross(Xq, Y) @ g
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
