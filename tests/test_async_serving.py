"""Async serving front-end tests: driver, awaitables, shm fleet, and
the serving-layer bugfix sweep.

Three regression groups that FAIL on the pre-async admission layer:

* dead-deadline inline flush — a submit onto a window whose deadline
  already passed must flush at submit time, not queue behind a poll()
  that may never come;
* ``fit_update`` with a ``gamma0``-carrying recipe / an engine whose
  incremental structures raise ``NotImplementedError`` mid-update must
  take the documented cold-refit fallback (counted in refresh_modes),
  not surface a traceback;
* cold (compile-laden) launches must not skew ``BucketStats`` deadline
  estimates;
* the per-shape compile trap: numpy requests must pad AND unpad
  host-side (no per-request-shape device programs), and the deadline
  estimate must charge the observed additive per-window flush overhead.

Plus the tentpole: driver lifecycle (start → storm → stop drains all),
driver-crash propagation to awaiting callers, asyncio awaitables, and
the shared-memory fleet (bitwise attach parity, refcounting, leader
death). Policy tests run on the manual fake clock; driver-thread tests
use the real clock with generous timeouts (the driver is event-driven,
so they wait on completion, never on a fixed sleep).
"""
import asyncio
import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

import repro
from repro import api
from repro.core import SlabSpec, rbf
from repro.data import make_toy
from repro.serve import (AdmissionController, AsyncDriver, BatchScorer,
                         BucketStats, DriverCrashed, ModelRegistry,
                         ScoringService, ShmKeyError, shm_registry)
from repro.serve.async_driver import serve_async

SPEC = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
M = 48
FIT_KW = dict(tol=1e-2, max_outer=60)


class ManualClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _wait(pred, timeout=20.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.01)
    return False


@pytest.fixture()
def X():
    return make_toy(jax.random.PRNGKey(5), M)[0]


@pytest.fixture()
def registry(X):
    reg = ModelRegistry()
    reg.register("a", X, SPEC, **FIT_KW)
    return reg


def _q(X, n=3, seed=0):
    rng = np.random.default_rng(seed)
    base = np.asarray(X[:n], np.float32)
    return base + rng.normal(scale=0.01, size=base.shape).astype(np.float32)


# -- satellite 1: dead-deadline inline flush ---------------------------------

def test_submit_onto_dead_deadline_flushes_inline(registry, X):
    """REGRESSION: pre-PR, a window whose deadline passed while nobody
    polled kept queueing new arrivals — the miss grew unbounded."""
    clock = ManualClock()
    ctrl = AdmissionController(registry, clock=clock, max_batch=128)
    ctrl.service("a")                       # pay the fit up front
    h1 = ctrl.submit("a", _q(X), deadline=5.0)
    assert not h1.flushed                   # future deadline: coalesce
    clock.advance(10.0)                     # deadline passes; NOBODY polls
    h2 = ctrl.submit("a", _q(X, seed=1), deadline=clock.t + 100.0)
    assert h1.done and h2.done              # inline flush served BOTH
    stats = ctrl.stats_dict()["a"]["windows"]
    assert stats["inline_flushes"] == 1
    assert stats["flushed_requests"] == 2


def test_submit_own_deadline_already_passed_flushes_inline(registry, X):
    """The degenerate case: the request is born dead (e.g. its deadline
    passed during a long fit-on-first-use) — it must be served NOW."""
    clock = ManualClock(t=50.0)
    ctrl = AdmissionController(registry, clock=clock, max_batch=128)
    h = ctrl.submit("a", _q(X), deadline=10.0)      # already in the past
    assert h.done
    assert h.result().shape == (3,)


def test_future_deadline_still_coalesces(registry, X):
    """The inline flush is for DEAD deadlines only — deadline pressure
    with a live deadline stays poll()'s job (due() policy)."""
    clock = ManualClock()
    ctrl = AdmissionController(registry, clock=clock, max_batch=128)
    ctrl.service("a")
    h = ctrl.submit("a", _q(X), deadline=1.0)
    assert not h.flushed
    assert ctrl.queued_rows("a") == 3


# -- satellite 2: fit_update cold-refit fallback -----------------------------

def test_refresh_with_gamma0_recipe_does_not_traceback(X):
    """REGRESSION: pre-PR, a recipe registered with a gamma0 fit kwarg
    cold-fitted fine but any warm refresh died on the solvers' "pass
    warm= or gamma0=, not both" ValueError."""
    reg = ModelRegistry()
    m0 = repro.fit(np.asarray(X), SPEC, **FIT_KW)
    g0 = np.asarray(m0.model.gamma)
    reg.register("a", X, SPEC, gamma0=g0, **FIT_KW)
    reg.get("a")
    app = _q(X, n=4, seed=7)
    reg.refresh("a", append=app, mode="warm")       # pre-PR: ValueError
    counts = reg.refresh_modes["a"]
    assert counts["warm"] + counts["cold"] == 1


def test_fit_update_same_size_gamma0_routes_cold(X):
    Xh = np.asarray(X)
    m0 = repro.fit(Xh, SPEC, **FIT_KW)
    g0 = np.asarray(m0.model.gamma)
    stats = {}
    repro.fit_update(m0, Xh, stats_out=stats, gamma0=g0, **FIT_KW)
    assert stats["mode"] == "cold"
    assert stats["fallback"] == "gamma0_conflict"


def test_fit_update_stale_gamma0_dropped_keeps_warm_route(X):
    Xh = np.asarray(X)
    m0 = repro.fit(Xh, SPEC, **FIT_KW)
    g0 = np.asarray(m0.model.gamma)                 # sized for OLD data
    X2 = np.concatenate([Xh, _q(X, n=4, seed=8)])
    stats = {}
    repro.fit_update(m0, X2, stats_out=stats, gamma0=g0, **FIT_KW)
    assert stats["mode"] == "warm"
    assert stats["fallback"] == "gamma0_stale_dropped"


def test_fit_update_warm_notimplemented_falls_back_cold(X, monkeypatch):
    """An engine whose incremental structures cannot mutate mid-update
    (ShardedGram.append_rows raises NotImplementedError) must degrade to
    the documented cold refit, recorded in stats_out."""
    Xh = np.asarray(X)
    m0 = repro.fit(Xh, SPEC, **FIT_KW)
    real_fit = api.fit

    def no_warm_fit(Xa, spec=None, **kw):
        if kw.get("warm_start") is not None:
            raise NotImplementedError(
                "append_rows is not supported on ShardedGram")
        return real_fit(Xa, spec, **kw)

    monkeypatch.setattr(api, "fit", no_warm_fit)
    X2 = np.concatenate([Xh, _q(X, n=2, seed=9)])
    stats = {}
    res = api.fit_update(m0, X2, stats_out=stats, **FIT_KW)
    assert stats["mode"] == "cold"
    assert stats["fallback"].startswith("warm_unsupported")
    assert res.model.X.shape[0] == X2.shape[0]


# -- satellite 3: cold launches excluded from estimates ----------------------

def test_bucket_stats_cold_excluded_from_mean():
    """REGRESSION: pre-PR the first compile-laden launch entered the
    mean the admission deadline policy reads — one 5 s compile made
    every post-refresh window flush pathologically early."""
    s = BucketStats()
    s.record(64, 1, 5.0, cold=True)         # trace+compile launch
    s.record(64, 1, 0.010)
    s.record(64, 1, 0.030)
    assert s.batches == 3 and s.cold_batches == 1
    assert s.mean_latency_s == pytest.approx(0.020)   # warm-only
    assert s.total_s == pytest.approx(5.040)          # throughput keeps all


def test_bucket_stats_cold_only_falls_back_to_cold_mean():
    s = BucketStats()
    s.record(64, 1, 2.0, cold=True)
    assert s.mean_latency_s == pytest.approx(2.0)     # over-estimate =
    #                                                   flush early, safe


def test_service_marks_first_unwarmed_launch_cold(registry, X):
    clock = ManualClock()
    sm = registry.get("a")
    svc = ScoringService(BatchScorer(sm), clock=clock)
    svc.submit(_q(X))
    svc.flush()
    svc.submit(_q(X, seed=1))
    svc.flush()
    (stats,) = svc.stats.values()
    assert stats.batches == 2 and stats.cold_batches == 1


def test_warmup_suppresses_cold_marking(registry, X):
    clock = ManualClock()
    sm = registry.get("a")
    svc = ScoringService(BatchScorer(sm), clock=clock)
    svc.warmup()
    svc.submit(_q(X))
    svc.flush()
    (stats,) = svc.stats.values()
    assert stats.batches == 1 and stats.cold_batches == 0


# -- continuous windows ------------------------------------------------------

def test_window_reopens_after_flush(registry, X):
    clock = ManualClock()
    ctrl = AdmissionController(registry, clock=clock, max_batch=128)
    ctrl.submit("a", _q(X))
    ctrl.flush_model("a")
    ctrl.submit("a", _q(X, seed=1))         # lands in a FRESH window
    assert ctrl.queued_rows("a") == 3
    w = ctrl.stats_dict()["a"]["windows"]
    assert w["opened"] == 2 and w["flushed"] == 1
    assert w["flushed_rows"] == 3 and w["max_rows"] == 3


def test_window_stats_hold_each_requests_queue_wait(registry, X):
    """``wait_s`` sums each request's time from its admission to the pop
    of its window, on the controller's clock; ``wait_max_s`` is the
    longest. Both ride ``stats_dict`` and ``stats_lines``."""
    clock = ManualClock()
    ctrl = AdmissionController(registry, clock=clock, max_batch=128)
    ctrl.service("a")
    ctrl.submit("a", _q(X))                 # t=0: opens the window
    clock.advance(0.25)
    ctrl.submit("a", _q(X, seed=1))         # t=0.25
    clock.advance(0.5)
    ctrl.flush_model("a")                   # popped at 0.75: 0.75 + 0.5
    clock.advance(1.0)
    ctrl.submit("a", _q(X, seed=2))         # t=1.75, a fresh window
    clock.advance(0.125)
    ctrl.flush_model("a")                   # 0.125
    w = ctrl.stats_dict()["a"]["windows"]
    assert w["flushed_requests"] == 3
    assert w["wait_s"] == 1.375
    assert w["wait_max_s"] == 0.75
    line = next(ln for ln in ctrl.stats_lines() if ln.startswith("model=a"))
    assert "mean_wait_ms=458.333" in line and "max_wait_ms=750.000" in line


def test_aborted_requests_add_no_queue_wait(registry, X):
    """A request failed by ``abort_pending`` never reaches a pop: it is
    counted as aborted and adds no wait."""
    clock = ManualClock()
    ctrl = AdmissionController(registry, clock=clock, max_batch=128)
    ctrl.service("a")
    ctrl.submit("a", _q(X))
    clock.advance(2.0)
    assert ctrl.abort_pending(RuntimeError("driver down")) == 1
    w = ctrl.stats_dict()["a"]["windows"]
    assert w["aborted"] == 1
    assert w["wait_s"] == 0.0 and w["wait_max_s"] == 0.0


def test_submit_during_inflight_flush_lands_in_next_window(registry, X):
    """Late arrivals join the next launch instead of blocking on the
    in-flight flush-and-wait cycle."""
    clock = ManualClock()
    ctrl = AdmissionController(registry, clock=clock, max_batch=128)
    svc = ctrl.service("a")
    entered = threading.Event()
    release = threading.Event()
    real_flush = svc.flush

    def slow_flush():
        entered.set()
        release.wait(10.0)
        return real_flush()

    svc.flush = slow_flush
    ctrl.submit("a", _q(X))
    t = threading.Thread(target=ctrl.flush_model, args=("a",))
    t.start()
    assert entered.wait(10.0)
    # flush is mid-launch under the model lock; admission must not block
    h2 = ctrl.submit("a", _q(X, seed=1))
    assert ctrl.queued_rows("a") == 3 and not h2.flushed
    release.set()
    t.join(10.0)
    assert ctrl.queued_rows("a") == 3       # window 2 untouched by flush 1
    ctrl.flush_model("a")
    assert h2.done


def test_next_due_time_tracks_earliest_window(registry, X):
    clock = ManualClock()
    ctrl = AdmissionController(registry, clock=clock, max_batch=128,
                               max_wait_s=50.0)
    assert ctrl.next_due_time() is None
    ctrl.service("a")
    ctrl.submit("a", _q(X), deadline=30.0)
    assert ctrl.next_due_time() == pytest.approx(30.0)  # no latency obs
    ctrl.submit("a", _q(X, seed=1), deadline=12.0)
    assert ctrl.next_due_time() == pytest.approx(12.0)


# -- driver lifecycle --------------------------------------------------------

def test_driver_start_storm_stop_drains_everything(registry, X):
    ctrl = AdmissionController(registry, max_batch=4096)
    ctrl.service("a")
    far = time.monotonic() + 3600.0         # never due on its own
    handles = []
    with AsyncDriver(ctrl) as driver:
        assert driver.alive
        for i in range(24):
            handles.append(ctrl.submit("a", _q(X, seed=i), deadline=far))
    # context exit = stop(drain=True): nothing silently dropped
    assert all(h.done for h in handles)
    assert sum(h.result().shape[0] for h in handles) == 24 * 3


def test_driver_flushes_on_deadline_without_any_polling(registry, X):
    ctrl = AdmissionController(registry, max_batch=4096)
    ctrl.service("a")                       # keep the fit out of the window
    driver = AsyncDriver(ctrl).start()
    try:
        h = ctrl.submit("a", _q(X), deadline=time.monotonic() + 0.2)
        assert not h.done                   # really queued, nobody polls
        assert _wait(lambda: h.done)        # the DRIVER flushed it
        assert h.result().shape == (3,)
    finally:
        driver.stop()
    assert not driver.alive


def test_driver_exception_aborts_pending_and_surfaces(registry, X,
                                                      monkeypatch):
    ctrl = AdmissionController(registry, max_batch=4096)
    ctrl.service("a")

    def boom():
        raise RuntimeError("poll exploded")

    monkeypatch.setattr(ctrl, "poll", boom)
    driver = AsyncDriver(ctrl).start()
    h = ctrl.submit("a", _q(X), deadline=time.monotonic() + 0.1)
    assert _wait(lambda: driver.crashed is not None)
    assert _wait(lambda: h.done)
    with pytest.raises(DriverCrashed) as ei:
        h.result()
    assert isinstance(ei.value.cause, RuntimeError)
    with pytest.raises(DriverCrashed):
        driver.stop()
    with pytest.raises(DriverCrashed):
        driver.start()                      # no silent restart of a corpse


def test_driver_crash_does_not_mask_body_exception(registry, X,
                                                   monkeypatch):
    """REGRESSION: __exit__ promised to prefer the body's exception,
    but stop() unconditionally re-raised the crash — DriverCrashed
    replaced the in-flight body exception (demoted to __context__)."""
    ctrl = AdmissionController(registry, max_batch=4096)
    ctrl.service("a")

    def boom():
        raise RuntimeError("poll exploded")

    monkeypatch.setattr(ctrl, "poll", boom)
    with pytest.raises(ValueError, match="body failed first"):
        with AsyncDriver(ctrl) as driver:
            ctrl.submit("a", _q(X), deadline=time.monotonic() + 0.05)
            assert _wait(lambda: driver.crashed is not None)
            raise ValueError("body failed first")
    assert driver.crashed is not None       # still diagnosable after


def test_driver_crash_still_raises_on_clean_body_exit(registry, X,
                                                      monkeypatch):
    ctrl = AdmissionController(registry, max_batch=4096)
    ctrl.service("a")

    def boom():
        raise RuntimeError("poll exploded")

    monkeypatch.setattr(ctrl, "poll", boom)
    with pytest.raises(DriverCrashed):
        with AsyncDriver(ctrl) as driver:
            ctrl.submit("a", _q(X), deadline=time.monotonic() + 0.05)
            assert _wait(lambda: driver.crashed is not None)


def test_driver_rearms_on_earlier_deadline(registry, X):
    """A new submit with an EARLIER deadline must wake the parked driver
    — event-driven, not a fixed poll interval."""
    ctrl = AdmissionController(registry, max_batch=4096)
    ctrl.service("a")
    driver = AsyncDriver(ctrl).start()
    try:
        h_far = ctrl.submit("a", _q(X), deadline=time.monotonic() + 3600)
        h_near = ctrl.submit("a", _q(X, seed=1),
                             deadline=time.monotonic() + 0.2)
        assert _wait(lambda: h_near.done)
        assert h_far.done                   # same window, same flush
    finally:
        driver.stop()


# -- awaitables --------------------------------------------------------------

def test_submit_async_resolves_via_driver(registry, X):
    ctrl = AdmissionController(registry, max_batch=4096)
    sm = registry.get("a")
    qs = [_q(X, seed=i) for i in range(4)]
    expected = [np.asarray(sm.score(q)) for q in qs]

    async def main():
        futs = [ctrl.submit_async("a", q,
                                  deadline=time.monotonic() + 0.2)
                for q in qs]
        return await asyncio.gather(*futs)

    with AsyncDriver(ctrl):
        got = asyncio.run(main())
    for g, e in zip(got, expected):
        np.testing.assert_array_equal(np.asarray(g), e)


def test_serve_async_coroutine_front_door(registry, X):
    ctrl = AdmissionController(registry, max_batch=4096)

    async def main():
        return await serve_async("a", _q(X), controller=ctrl,
                                 deadline=time.monotonic() + 0.2)

    with AsyncDriver(ctrl):
        out = asyncio.run(main())
    assert np.asarray(out).shape == (3,)


def test_submit_async_propagates_flush_error(registry, X):
    """A request that becomes unservable at flush time must reject the
    future, not hang it."""
    ctrl = AdmissionController(registry, max_batch=4096)
    svc = ctrl.service("a")

    def bad_submit(q):
        raise ValueError("feature dim moved under the request")

    async def main():
        fut = ctrl.submit_async("a", _q(X))
        svc.submit = bad_submit
        ctrl.flush_model("a")
        with pytest.raises(ValueError):
            await fut

    asyncio.run(main())


# -- shm fleet ---------------------------------------------------------------

def test_shm_attach_scores_bitwise_identical(registry, X, tmp_path):
    sm = registry.get("a")
    q = _q(X, n=7, seed=3)
    ref = np.asarray(sm.score(q))
    lease = shm_registry.publish(sm, "fleet-key", dir=str(tmp_path))
    try:
        sm2, lease2 = shm_registry.attach("fleet-key", dir=str(tmp_path))
        with lease2:
            got = np.asarray(sm2.score(q))
        assert got.tobytes() == ref.tobytes()       # bitwise, not approx
    finally:
        lease.close()


def test_shm_refcount_attach_detach_unlinks_at_zero(registry, X, tmp_path):
    sm = registry.get("a")
    d = str(tmp_path)
    lease = shm_registry.publish(sm, "k", dir=d)
    _, lease2 = shm_registry.attach("k", dir=d)
    assert shm_registry.live_refs("k", dir=d) == 2
    lease2.close()
    lease2.close()                          # double close is a no-op
    assert shm_registry.live_refs("k", dir=d) == 1
    lease.close()
    assert shm_registry.live_refs("k", dir=d) == 0
    with pytest.raises(ShmKeyError):        # segment + manifest gone
        shm_registry.attach("k", dir=d)


def test_shm_leader_death_is_pruned(registry, X, tmp_path):
    """A publisher that dies WITHOUT detaching must not strand the
    refcount: its pid entry is liveness-pruned, and the last live
    holder still unlinks."""
    sm = registry.get("a")
    d = str(tmp_path)
    lease = shm_registry.publish(sm, "k", dir=d)
    # forge the leader's death: replace our pid with one that is gone
    # (a finished subprocess's pid is as dead as a crashed leader's)
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()                             # reaped: the pid is dead
    dead_pid = proc.pid
    if shm_registry._pid_alive(dead_pid):
        pytest.skip("could not obtain a dead pid")
    refs = tmp_path / f"{shm_registry._digest('k')}.refs"
    refs.write_text('{"pids": [%d]}' % dead_pid)
    assert shm_registry.live_refs("k", dir=d) == 0
    sm2, lease2 = shm_registry.attach("k", dir=d)   # revives the fleet
    assert shm_registry.live_refs("k", dir=d) == 1
    lease2.close()                                  # last LIVE holder out
    with pytest.raises(ShmKeyError):
        shm_registry.attach("k", dir=d)
    lease._shm.close()                              # our stale mapping
    lease.closed = True


def test_attach_untracks_from_resource_tracker(registry, X, tmp_path,
                                               monkeypatch):
    """REGRESSION: on POSIX CPython 3.8-3.12, ``SharedMemory.__init__``
    registers with the resource_tracker unconditionally — for ATTACH
    too, not just create. Pre-fix only the create path untracked, so an
    attached worker's tracker unlinked the live segment when that
    worker's process tree exited, out from under surviving leaseholders
    (masked in forked tests, which share one tracker). Every open must
    leave the tracker balanced for this segment, and unregisters must
    never outrun registers (tracker-daemon KeyError tracebacks)."""
    from multiprocessing import resource_tracker
    events = []
    real_reg = resource_tracker.register
    real_unreg = resource_tracker.unregister
    monkeypatch.setattr(
        resource_tracker, "register",
        lambda name, rtype: (events.append((+1, name, rtype)),
                             real_reg(name, rtype)))
    monkeypatch.setattr(
        resource_tracker, "unregister",
        lambda name, rtype: (events.append((-1, name, rtype)),
                             real_unreg(name, rtype)))
    sm = registry.get("a")
    d = str(tmp_path)
    lease = shm_registry.publish(sm, "tracker-k", dir=d)
    seg = lease._shm.name

    def balance():
        total = 0
        for s, name, rtype in events:
            if rtype == "shared_memory" and name.lstrip("/") == seg:
                total += s
                assert total >= 0           # no unmatched UNREGISTER
        return total

    assert balance() == 0                   # create path untracks
    _, lease2 = shm_registry.attach("tracker-k", dir=d)
    assert balance() == 0                   # THE regression: attach too
    lease2.close()
    lease.close()                           # last out: unlink path
    assert balance() == 0                   # re-register/unlink balanced


def test_attached_worker_exit_does_not_unlink_segment(registry, X,
                                                      tmp_path):
    """End-to-end cross-process version of the tracker regression: a
    worker in a SEPARATE process tree (its own resource_tracker —
    forked test children share the parent's, which masked the bug)
    attaches, detaches cleanly, and exits. Pre-fix, the worker's
    tracker unlinked the segment at exit, out from under the
    publisher's live lease."""
    import repro as repro_pkg
    sm = registry.get("a")
    d = str(tmp_path)
    lease = shm_registry.publish(sm, "worker-k", dir=d)
    code = (
        "from repro.serve import shm_registry\n"
        f"sm, lease = shm_registry.attach('worker-k', dir={d!r})\n"
        "lease.close()\n"
    )
    src_dir = os.path.dirname(os.path.dirname(repro_pkg.__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [src_dir, os.environ.get("PYTHONPATH", "")]))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    try:
        # the worker's exit (and its tracker's cleanup) must not have
        # taken the fleet down with it
        sm2, lease2 = shm_registry.attach("worker-k", dir=d)
        lease2.close()
    finally:
        lease.close()


def test_flock_retries_on_unlinked_lock_inode(tmp_path):
    """REGRESSION: last-lease cleanup unlinks the .lock file; a
    contender that had already opened (and then flocked) the dying
    inode held a lock no fresh opener contends on — two processes in
    the refcount critical section at once. ``_flock`` must detect that
    the locked fd no longer IS the path and retry on the new file."""
    import fcntl
    lock = tmp_path / "x.lock"
    f = open(lock, "a+")
    fcntl.flock(f, fcntl.LOCK_EX)
    f.write("doomed inode")     # marker: only the OLD inode carries it
    f.flush()                   # (inode NUMBERS get recycled; bytes don't)
    seen = {}

    def contender():
        with shm_registry._flock(lock):
            seen["content"] = lock.read_text()

    t = threading.Thread(target=contender)
    t.start()
    time.sleep(0.3)             # contender opened the doomed inode and
    #                             is parked in flock()
    lock.unlink()               # cleanup retires the inode UNDER the lock
    fcntl.flock(f, fcntl.LOCK_UN)
    f.close()
    t.join(10.0)
    assert not t.is_alive()
    assert seen["content"] == ""            # body ran on the fresh inode


def test_attach_or_publish_builds_once(registry, X, tmp_path):
    sm = registry.get("a")
    d = str(tmp_path)
    builds = []

    def build():
        builds.append(1)
        return sm

    sm1, l1 = shm_registry.attach_or_publish("k", build, dir=d)
    sm2, l2 = shm_registry.attach_or_publish("k", build, dir=d)
    assert len(builds) == 1
    q = _q(X, seed=4)
    assert (np.asarray(sm2.score(q)).tobytes()
            == np.asarray(sm.score(q)).tobytes())
    l1.close()
    l2.close()


# -- CLI ---------------------------------------------------------------------

def test_cli_quota_shed_does_not_crash(tmp_path):
    """REGRESSION: ``submit_stream`` rebound ``rejected`` without
    ``nonlocal``, so the first QuotaExceededError raised
    UnboundLocalError — the CLI crashed in exactly the load-shedding
    scenario its own usage examples document."""
    from repro.launch import serve_slab
    out_json = tmp_path / "stats.json"
    serve_slab.main(["--m", "48", "--requests", "8", "--min-batch", "8",
                     "--max-batch", "64", "--models", "a=rbf:0.5",
                     "--quota", "6", "--tol", "1e-2",
                     "--json", str(out_json)])
    stats = json.loads(out_json.read_text())
    assert stats["rejected"] >= 1           # quota actually bound
    assert stats["admitted"] + stats["rejected"] == 8


# -- per-shape compile trap + flush-overhead estimates -----------------------

def test_numpy_requests_score_to_numpy_host_side(registry, X):
    """REGRESSION: pre-PR the scorer unpadded with a DEVICE slice
    ``out[:n]`` — one fresh trace+compile per distinct (n, bucket) pair,
    ~10-30ms on every continuously-varying admission window. The fix
    keeps numpy requests (the service boundary) on the host for the
    unpad, so numpy in must mean numpy out; jax callers keep a device
    result."""
    scorer = BatchScorer(registry.get("a"))
    q = _q(X, n=5, seed=6)
    out_np = scorer.score(q)
    assert isinstance(out_np, np.ndarray)
    out_dev = scorer.score(jax.numpy.asarray(q))
    assert isinstance(out_dev, jax.Array)
    np.testing.assert_allclose(out_np, np.asarray(out_dev), rtol=1e-6)


def test_estimate_charges_observed_flush_overhead(registry, X):
    """REGRESSION: the deadline estimate summed per-launch bucket means
    only — the per-window non-launch cost (drain/pad/scatter) is
    ADDITIVE, so for a fast model no multiplicative safety factor could
    cover it and windows flushed too late. The estimate must charge the
    service's observed mean flush overhead once per window."""
    clock = ManualClock()
    ctrl = AdmissionController(registry, clock=clock,
                               fallback_latency_s=0.010, safety_factor=1.0)
    svc = ctrl.service("a")
    base = ctrl.estimate_latency_s("a", rows=3)
    assert svc.mean_flush_overhead_s == 0.0      # nothing observed yet
    svc.flush_groups, svc.flush_overhead_s = 4, 4 * 0.025
    assert svc.mean_flush_overhead_s == pytest.approx(0.025)
    assert ctrl.estimate_latency_s("a", rows=3) == pytest.approx(base + 0.025)


def test_flush_overhead_recorded_under_real_clock(registry, X):
    """A real flush must move the overhead counters (the manual-clock
    test above pins the math; this pins the recording seam)."""
    ctrl = AdmissionController(registry)
    svc = ctrl.service("a")
    ctrl.submit("a", _q(X, seed=8))
    ctrl.flush_model("a")
    assert svc.flush_groups == 1
    assert svc.flush_overhead_s >= 0.0
    assert svc.mean_flush_overhead_s == svc.flush_overhead_s
