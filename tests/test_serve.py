"""Serving-subsystem tests: bucket-edge parity against the model's jnp
reference, warm-cache semantics, micro-batching scatter, the sharded
scorer (subprocess, forced host devices), and the fit -> PallasGram
``interpret`` plumbing."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import OCSSVMModel, SlabSpec, compact_support, rbf
from repro.data import make_toy
from repro.serve import (BUCKETS, ModelCache, ScoringService, bucket_for,
                         pack_model)

SPEC = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
M = 96


class TickClock:
    """Fake service clock: every call advances a fixed step, so each
    timed launch reads exactly ``step`` seconds — latency assertions
    become equalities instead of wall-clock-dependent inequalities."""

    def __init__(self, step=1e-3):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t

# every bucket boundary (63/64/65, ...), non-multiples of the query tile,
# single row, and a beyond-top-bucket size that exercises chunking
PARITY_SIZES = [1, 63, 64, 65, 200, 255, 256, 257, 1000]


@pytest.fixture(scope="module")
def served():
    X, _ = make_toy(jax.random.PRNGKey(5), M)
    return repro.serve(X, SPEC, cache=ModelCache(), tol=1e-3)


def _ref(sm, q):
    return np.asarray(sm.model.decision_function(jnp.asarray(q, jnp.float32)))


@pytest.mark.parametrize("n", PARITY_SIZES)
def test_scorer_parity_bucket_edges(served, n):
    q, _ = make_toy(jax.random.PRNGKey(n), n)
    out = served.score(np.asarray(q))
    assert out.shape == (n,)
    np.testing.assert_allclose(np.asarray(out), _ref(served, q),
                               rtol=2e-4, atol=2e-4)


def test_scorer_chunks_beyond_top_bucket(served):
    n = BUCKETS[-1] + 70    # one full top-bucket chunk + a remainder chunk
    q, _ = make_toy(jax.random.PRNGKey(77), n)
    out = served.score(np.asarray(q))
    assert out.shape == (n,)
    np.testing.assert_allclose(np.asarray(out), _ref(served, q),
                               rtol=2e-4, atol=2e-4)


def test_cache_distinguishes_array_kwargs():
    """Array-valued fit kwargs (warm starts) are content-fingerprinted:
    reprs truncate with '...' and would collide."""
    from repro.serve.model_cache import _kwarg_key
    a = np.zeros((2000,), np.float32)
    b = a.copy()
    b[1000] = 1.0
    assert repr(a) == repr(b)                      # the trap
    assert _kwarg_key(a) != _kwarg_key(b)
    assert _kwarg_key(a) == _kwarg_key(a.copy())


def test_fit_interpret_forces_pallas_mode_small_m():
    """An explicit interpret override must reach the Pallas provider even
    below the precomputed-Gram threshold."""
    from repro.api import _auto_gram_mode
    assert _auto_gram_mode(100) == "precomputed"
    assert _auto_gram_mode(100, interpret=True) == "pallas"
    assert _auto_gram_mode(100, interpret=False) == "pallas"


def test_service_counts_chunked_launches(served):
    """A single oversized request is several kernel launches, and each
    launch is filed under the bucket that actually served it: the full
    chunk under the top bucket, the 70-row remainder under ITS bucket
    (256), not lumped under the top one. The injected tick clock makes
    the latency counters exact (one step per launch) instead of
    wall-clock-dependent."""
    clock = TickClock(step=1e-3)
    svc = ScoringService(served.scorer(), clock=clock)
    n = BUCKETS[-1] + 70
    q = np.asarray(make_toy(jax.random.PRNGKey(88), n)[0])
    svc.submit(q)
    assert svc.flush() == 2
    top = svc.stats[BUCKETS[-1]]
    rem = svc.stats[bucket_for(70)]
    assert (top.batches, top.queries, top.requests) == (1, BUCKETS[-1], 1)
    assert (rem.batches, rem.queries, rem.requests) == (1, 70, 0)
    assert top.total_s == pytest.approx(clock.step)
    assert rem.total_s == pytest.approx(clock.step)
    assert top.mean_latency_s == pytest.approx(clock.step)
    assert rem.last_s == pytest.approx(clock.step)


def test_service_default_clock_is_monotonic():
    """No direct time.* calls in the hot loop: all BucketStats timing
    goes through the injectable clock, defaulting to time.monotonic."""
    import time as _time

    svc = ScoringService(_FakeScorer())
    assert svc.clock is _time.monotonic


def test_service_chunked_scatter_parity(served):
    """Chunk-by-chunk scoring inside flush must still hand every handle
    exactly its own rows."""
    svc = ScoringService(served.scorer())
    n = BUCKETS[-1] + 70
    q = np.asarray(make_toy(jax.random.PRNGKey(89), n)[0])
    h = svc.submit(q)
    svc.flush()
    np.testing.assert_allclose(np.asarray(h.result()), _ref(served, q),
                               rtol=2e-4, atol=2e-4)


def test_service_queue_is_deque():
    """The queue must not be a list: list.pop(0) makes a deep drain
    O(n^2)."""
    from collections import deque
    svc = ScoringService.__new__(ScoringService)
    ScoringService.__init__(svc, scorer=_FakeScorer())
    assert isinstance(svc._queue, deque)


class _FakeScorer:
    """Minimal stand-in so queue-structure tests need no fitted model."""

    def _check(self, q):
        pass

    def chunk_rows(self):
        return BUCKETS[-1]

    def bucket_used(self, n):
        return bucket_for(n)

    def launch_plan(self, n):
        cap = self.chunk_rows()
        sizes = [cap] * (n // cap) + ([n % cap] if n % cap else [])
        return [(r, bucket_for(r)) for r in sizes]

    def score(self, q):
        return jnp.zeros((q.shape[0],), jnp.float32)


def test_scorer_device_array_input(served):
    q, _ = make_toy(jax.random.PRNGKey(9), 33)
    np.testing.assert_allclose(np.asarray(served.score(q)), _ref(served, q),
                               rtol=2e-4, atol=2e-4)


def test_zero_support_vector_model():
    """All-zero gamma packs to an all-padding tile; every query scores the
    constant (0 - rho1) * (rho2 - 0)."""
    X = jnp.asarray(np.random.default_rng(0).normal(size=(40, 3)),
                    jnp.float32)
    model = OCSSVMModel(gamma=jnp.zeros((40,)), rho1=jnp.float32(0.2),
                        rho2=jnp.float32(0.8), X=X, spec=SPEC)
    sm = pack_model(model)
    assert sm.n_sv == 0
    q = np.random.default_rng(1).normal(size=(65, 3)).astype(np.float32)
    out = np.asarray(sm.score(q))
    np.testing.assert_allclose(out, np.full((65,), -0.2 * 0.8),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out, _ref(sm, q), rtol=1e-6, atol=1e-6)


def test_compact_support_drops_only_tiny_gammas():
    X, _ = make_toy(jax.random.PRNGKey(3), 32)
    gamma = jnp.zeros((32,)).at[jnp.asarray([3, 7, 20])].set(
        jnp.asarray([0.4, -0.2, 0.3]))
    model = OCSSVMModel(gamma=gamma, rho1=jnp.float32(0.0),
                        rho2=jnp.float32(1.0), X=X, spec=SPEC)
    small = compact_support(model)
    assert small.X.shape == (3, X.shape[1])
    np.testing.assert_allclose(np.asarray(small.gamma), [0.4, -0.2, 0.3])
    q, _ = make_toy(jax.random.PRNGKey(4), 10)
    np.testing.assert_allclose(np.asarray(small.decision_function(q)),
                               np.asarray(model.decision_function(q)),
                               rtol=1e-5, atol=1e-6)


def test_bucket_for_policy():
    assert [bucket_for(n) for n in (1, 63, 64, 65, 256, 257, 4096, 9999)] \
        == [64, 64, 64, 256, 256, 1024, 4096, 4096]
    with pytest.raises(ValueError):
        bucket_for(0)


def test_scorer_rejects_bad_shapes(served):
    with pytest.raises(ValueError):
        served.scorer().score(np.zeros((4, 7), np.float32))  # wrong d
    with pytest.raises(ValueError):
        served.scorer().score(np.zeros((4,), np.float32))    # not 2-D


def test_cache_hits_skip_fit(monkeypatch):
    from repro import api
    calls = {"n": 0}
    real_fit = api.fit

    def counting_fit(*args, **kwargs):
        calls["n"] += 1
        return real_fit(*args, **kwargs)

    monkeypatch.setattr(api, "fit", counting_fit)
    cache = ModelCache()
    X, _ = make_toy(jax.random.PRNGKey(5), M)
    sm1 = cache.get_or_fit(X, SPEC, tol=1e-3)
    sm2 = cache.get_or_fit(X, SPEC, tol=1e-3)
    assert sm2 is sm1 and calls["n"] == 1
    assert (cache.hits, cache.misses) == (1, 1)
    # a different spec, data, or fit kwarg is a different model
    cache.get_or_fit(X, SPEC, tol=1e-4)
    spec2 = SlabSpec(nu1=0.4, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
    cache.get_or_fit(X, spec2, tol=1e-3)
    X2, _ = make_toy(jax.random.PRNGKey(6), M)
    cache.get_or_fit(X2, SPEC, tol=1e-3)
    assert calls["n"] == 4 and cache.misses == 4


def test_cache_lru_eviction():
    cache = ModelCache(maxsize=2)
    X, _ = make_toy(jax.random.PRNGKey(5), 48)
    for nu1 in (0.3, 0.4, 0.5):
        cache.get_or_fit(
            X, SlabSpec(nu1=nu1, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5)),
            tol=1e-2, max_outer=50)
    assert len(cache) == 2
    # the oldest entry (nu1=0.3) was evicted -> a re-request misses
    cache.get_or_fit(
        X, SlabSpec(nu1=0.3, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5)),
        tol=1e-2, max_outer=50)
    assert cache.misses == 4


def test_service_microbatch_scatter_parity(served):
    """Queued requests coalesce into one launch and every handle gets
    exactly its own rows back."""
    clock = TickClock(step=2e-3)
    svc = ScoringService(served.scorer(), clock=clock)
    sizes = (5, 48, 63, 100)
    reqs = [np.asarray(make_toy(jax.random.PRNGKey(40 + i), n)[0])
            for i, n in enumerate(sizes)]
    handles = [svc.submit(q) for q in reqs]
    assert svc.queued_rows == sum(sizes)
    launches = svc.flush()
    assert launches == 1          # 216 rows coalesce under the top bucket
    for q, h in zip(reqs, handles):
        assert h.done
        np.testing.assert_allclose(np.asarray(h.result()), _ref(served, q),
                                   rtol=2e-4, atol=2e-4)
    b = bucket_for(sum(sizes))
    assert svc.stats[b].batches == 1
    assert svc.stats[b].requests == len(sizes)
    assert svc.stats[b].queries == sum(sizes)
    # one launch, one clock step — exact under the fake clock
    assert svc.stats[b].total_s == pytest.approx(clock.step)


def test_service_groups_respect_max_batch(served):
    svc = ScoringService(served.scorer(), max_batch=128)
    for i in range(4):
        svc.submit(np.asarray(make_toy(jax.random.PRNGKey(50 + i), 40)[0]))
    # 40+40 fits under 128, a third 40 would not: two groups of two
    assert svc.flush() == 2
    assert sum(s.requests for s in svc.stats.values()) == 4
    assert sum(s.batches for s in svc.stats.values()) == 2


def test_service_result_triggers_flush(served):
    svc = ScoringService(served.scorer())
    q = np.asarray(make_toy(jax.random.PRNGKey(60), 10)[0])
    h = svc.submit(q)
    assert not h.done
    np.testing.assert_allclose(np.asarray(h.result()), _ref(served, q),
                               rtol=2e-4, atol=2e-4)
    assert h.done and not svc._queue


def test_fit_threads_interpret_to_pallas_provider(monkeypatch):
    """repro.fit(..., interpret=True) must reach the PallasGram provider —
    the deterministic CPU-CI hook for the pallas path."""
    from repro.core.engine import gram as engine_gram
    seen = {}
    real = engine_gram.PallasGram.__init__

    def spying_init(self, X, kernel, interpret=None, precision="f32"):
        seen["interpret"] = interpret
        real(self, X, kernel, interpret=interpret, precision=precision)

    monkeypatch.setattr(engine_gram.PallasGram, "__init__", spying_init)
    X, _ = make_toy(jax.random.PRNGKey(5), M)
    res = repro.fit(X, SPEC, strategy="blocked", gram_mode="pallas",
                    interpret=True, tol=1e-2, max_outer=64)
    assert seen["interpret"] is True
    assert np.isfinite(float(res.gap))


def test_fit_sharded_rejects_gram_mode():
    """The sharded strategies own Gram access (per-shard Pallas fupdate);
    gram_mode must be rejected before any mesh work happens. interpret is
    NOT rejected anymore — it now reaches the per-shard kernel."""
    X, _ = make_toy(jax.random.PRNGKey(5), 32)
    with pytest.raises(ValueError):
        repro.fit(X, SPEC, strategy="distributed", mesh=object(),
                  gram_mode="pallas")
    with pytest.raises(ValueError):
        repro.fit(X, SPEC, strategy="sharded", gram_mode="precomputed")


def test_example_has_no_direct_kernel_imports():
    """Acceptance: the example runs through the serve subsystem only."""
    path = os.path.join(os.path.dirname(__file__), "..", "examples",
                        "serve_ocssvm.py")
    with open(path) as fh:
        src = fh.read()
    assert "repro.kernels" not in src
    assert "repro.serve" in src or "repro.serve(" in src


def test_sharded_scorer_matches_local():
    """shard_map'd scoring over a forced 4-device host mesh must agree
    with the local bucketed path (subprocess: the main pytest process
    stays 1-device)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        import repro
        from repro.core import SlabSpec, rbf
        from repro.data import make_toy
        X, _ = make_toy(jax.random.PRNGKey(5), 96)
        spec = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
        sm = repro.serve(X, spec, tol=1e-3)
        mesh = jax.make_mesh((4,), ("data",))
        q, _ = make_toy(jax.random.PRNGKey(7), 130)   # not a shard multiple
        local = np.asarray(sm.score(np.asarray(q)))
        sharded = np.asarray(sm.score(np.asarray(q), mesh=mesh))
        # beyond one sharded launch's capacity (4 * top bucket): must chunk
        scorer = sm.scorer(mesh=mesh)
        nbig = scorer.chunk_rows() + 60
        qb, _ = make_toy(jax.random.PRNGKey(8), nbig)
        big = np.asarray(scorer.score(np.asarray(qb)))
        ref = np.asarray(sm.model.decision_function(
            jnp.asarray(qb, jnp.float32)))
        print(json.dumps({
            "max_abs_diff": float(np.max(np.abs(local - sharded))),
            "n": int(sharded.shape[0]),
            "big_n": int(big.shape[0]),
            "big_max_abs_diff": float(np.max(np.abs(big - ref)))}))
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["n"] == 130
    assert res["max_abs_diff"] < 1e-5
    assert res["big_n"] == 4 * 4096 + 60
    assert res["big_max_abs_diff"] < 1e-4


# -- satellite regressions: herd / warmup / fingerprint / precision ---------

def test_cache_thundering_herd_single_fit(monkeypatch):
    """Two threads missing on the same key must run ONE fit: the loser
    blocks on the winner's in-flight entry instead of fitting again."""
    import threading
    import time as _time

    from repro import api

    calls = {"n": 0}
    real_fit = api.fit

    def slow_fit(*args, **kwargs):
        calls["n"] += 1
        _time.sleep(0.5)        # long enough for both threads to race
        return real_fit(*args, **kwargs)

    monkeypatch.setattr(api, "fit", slow_fit)
    cache = ModelCache()
    X, _ = make_toy(jax.random.PRNGKey(5), 48)
    results = {}
    barrier = threading.Barrier(2)

    def worker(name):
        barrier.wait()
        results[name] = cache.get_or_fit(X, SPEC, tol=1e-2, max_outer=50)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert calls["n"] == 1, "both threads ran the expensive fit"
    assert results[0] is results[1]
    assert cache.misses == 1 and cache.hits == 1


def test_cache_failed_fit_not_cached(monkeypatch):
    """A raising fit must not poison the key: the next caller re-fits."""
    from repro import api

    calls = {"n": 0}
    real_fit = api.fit

    def flaky_fit(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom")
        return real_fit(*args, **kwargs)

    monkeypatch.setattr(api, "fit", flaky_fit)
    cache = ModelCache()
    X, _ = make_toy(jax.random.PRNGKey(5), 48)
    with pytest.raises(RuntimeError):
        cache.get_or_fit(X, SPEC, tol=1e-2, max_outer=50)
    sm = cache.get_or_fit(X, SPEC, tol=1e-2, max_outer=50)
    assert calls["n"] == 2 and sm is not None
    assert not cache._inflight


def test_warmup_compiles_the_served_path(served, monkeypatch):
    """warmup() must pre-compile the path score() will take: the sharded
    (shard_map) executables when a mesh is set — NOT the local bucket
    programs."""
    from repro.serve.scorer import BatchScorer

    mesh = jax.make_mesh((1,), ("data",))
    calls = {"sharded": [], "local": 0}
    real_sharded = BatchScorer._score_sharded

    def spy_sharded(self, q, n):
        calls["sharded"].append(n)
        return real_sharded(self, q, n)

    def spy_bucket(self, q_pad):
        calls["local"] += 1
        raise AssertionError("warmup with mesh hit the local bucket path")

    monkeypatch.setattr(BatchScorer, "_score_sharded", spy_sharded)
    monkeypatch.setattr(BatchScorer, "_score_bucket", spy_bucket)
    scorer = served.scorer(mesh=mesh)
    scorer.warmup()
    # one warm request per bucket, each landing on that per-shard bucket
    assert calls["sharded"] == list(BUCKETS)
    assert calls["local"] == 0


def test_sharded_scorer_warm_bucket_never_recompiles(served):
    """After warmup, sharded scoring of a warm bucket lowers no program:
    the shard_map'd launch is built once per bucket, not per call (each
    call used to re-trace and re-compile it)."""
    mesh = jax.make_mesh((1,), ("data",))
    scorer = served.scorer(mesh=mesh)
    scorer.warmup()
    q = np.asarray(make_toy(jax.random.PRNGKey(12), 100)[0])
    lowered = []

    def listen(name, _secs, **_kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            lowered.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for _ in range(3):
            out = scorer.score(q)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert out.shape == (100,)
    assert lowered == []


def test_warmup_local_matches_serving_buckets(served):
    """Local warmup still covers every bucket and a post-warmup score
    agrees with the reference."""
    scorer = served.scorer()
    scorer.warmup()
    q, _ = make_toy(jax.random.PRNGKey(91), 65)
    np.testing.assert_allclose(np.asarray(scorer.score(np.asarray(q))),
                               _ref(served, q), rtol=2e-4, atol=2e-4)


def test_fingerprint_array_edge_cases():
    from repro.serve import fingerprint_array

    # 0-d and 1-D inputs must fingerprint without tripping on a[0]
    f0 = fingerprint_array(np.float32(3.0))
    assert f0[0] == ()
    f1 = fingerprint_array(np.arange(7, dtype=np.float32))
    assert f1[0] == (7,)
    assert f0 != f1

    # same content, different layout -> equal fingerprints
    a = np.arange(24, dtype=np.float32).reshape(4, 6)
    fa = fingerprint_array(a)
    assert fingerprint_array(np.asfortranarray(a)) == fa
    wide = np.zeros((4, 12), np.float32)
    wide[:, ::2] = a
    b = wide[:, ::2]            # strided view, same logical content as a
    assert not b.flags.c_contiguous
    assert fingerprint_array(b) == fa
    assert fingerprint_array(np.ascontiguousarray(b)) == fa

    # different content / dtype / shape -> different fingerprints
    assert fingerprint_array(a + 1) != fa
    assert fingerprint_array(a.astype(np.float64)) != fa
    assert fingerprint_array(a.reshape(6, 4)) != fa


def test_fingerprint_array_sampling_above_budget(monkeypatch):
    """Above the byte budget an evenly strided row sample is hashed; the
    sample must still see content differences in sampled rows and be
    layout-invariant."""
    from repro.serve import model_cache

    monkeypatch.setattr(model_cache, "_HASH_SAMPLE_BYTES", 1 << 10)
    a = np.arange(4096, dtype=np.float32).reshape(256, 16)
    fa = model_cache.fingerprint_array(a)
    assert model_cache.fingerprint_array(np.asfortranarray(a)) == fa
    b = a.copy()
    b[0, 0] += 1.0          # row 0 is always in the sample
    assert model_cache.fingerprint_array(b) != fa
    # big 1-D inputs sample instead of hashing everything
    v = np.arange(1 << 12, dtype=np.float32)
    fv = model_cache.fingerprint_array(v)
    assert fv[0] == ((1 << 12),)
    assert model_cache.fingerprint_array(v * 0) != fv


@pytest.mark.parametrize("precision", ["bf16", "f16"])
def test_serving_precision_parity(precision):
    """A model served at 16-bit tile precision must match the f32
    reference within the documented per-dtype tolerance, and its packed
    support block must actually be stored in the 16-bit dtype."""
    from repro.kernels.precision import tile_dtype, truth_tolerance

    X, _ = make_toy(jax.random.PRNGKey(5), M)
    sm = repro.serve(X, SPEC, cache=ModelCache(), tol=1e-3,
                     precision=precision)
    assert sm.precision == precision
    assert sm.t_pad.dtype == tile_dtype(precision)
    q, _ = make_toy(jax.random.PRNGKey(11), 130)
    out = np.asarray(sm.score(np.asarray(q)))
    ref = _ref(sm, q)
    np.testing.assert_allclose(out, ref, **truth_tolerance(precision, ref))


def test_serving_precision_is_part_of_cache_key():
    cache = ModelCache()
    X, _ = make_toy(jax.random.PRNGKey(5), 48)
    sm32 = cache.get_or_fit(X, SPEC, tol=1e-2, max_outer=50)
    smbf = cache.get_or_fit(X, SPEC, tol=1e-2, max_outer=50,
                            precision="bf16")
    assert sm32 is not smbf
    assert cache.misses == 2
    assert sm32.t_pad.dtype == jnp.float32
    assert smbf.t_pad.dtype == jnp.bfloat16
    # same precision again -> hit
    assert cache.get_or_fit(X, SPEC, tol=1e-2, max_outer=50,
                            precision="bf16") is smbf
    assert cache.hits == 1


def test_serve_rejects_unknown_precision():
    X, _ = make_toy(jax.random.PRNGKey(5), 32)
    with pytest.raises(ValueError):
        repro.serve(X, SPEC, cache=ModelCache(), precision="int8")


def test_cache_clear_during_inflight_fit(monkeypatch):
    """clear() while a fit is in flight: the fit's waiter still gets a
    model, but nothing re-appears in the cleared cache."""
    import threading
    import time as _time

    from repro import api

    real_fit = api.fit
    started = threading.Event()

    def slow_fit(*args, **kwargs):
        started.set()
        _time.sleep(0.4)
        return real_fit(*args, **kwargs)

    monkeypatch.setattr(api, "fit", slow_fit)
    cache = ModelCache()
    X, _ = make_toy(jax.random.PRNGKey(5), 48)
    out = {}

    t = threading.Thread(
        target=lambda: out.update(
            sm=cache.get_or_fit(X, SPEC, tol=1e-2, max_outer=50)))
    t.start()
    started.wait(timeout=60)
    cache.clear()
    t.join(timeout=120)
    assert out["sm"] is not None        # the in-flight caller got a model
    assert len(cache) == 0              # ...but the cleared cache stayed empty
    assert not cache._inflight


def test_service_rejects_empty_request(served):
    """A zero-row request must fail fast at submit time, not crash a
    later flush with an unrelated concatenate error."""
    svc = ScoringService(served.scorer())
    with pytest.raises(ValueError):
        svc.submit(np.zeros((0, served.d), np.float32))
    assert not svc._queue
    assert svc.flush() == 0
