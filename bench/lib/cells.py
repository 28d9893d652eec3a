"""The three kinds of cell, one runner each, chosen by a traffic mix's
``kind``: ``fit`` (cold fits back to back), ``open_loop`` (events on a
Poisson schedule) and ``closed_loop`` (clients that each wait for their
previous request).

A runner does the cell's set-up, calls ``ctx.begin_window()``, drives
the program for ``ctx.seconds``, calls ``ctx.end_window()``, and only
then compares what the program produced with the reference. It fills
``ctx.e2e`` (end-to-end metrics), ``ctx.counters`` (what the per-layer
readers read) and ``ctx.checks``.
"""
from __future__ import annotations

import inspect
import threading
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench.lib import data, reference


# -- shared --------------------------------------------------------------------
def slab_spec(cfg: dict):
    from repro.core import SlabSpec, rbf
    return SlabSpec(nu1=cfg["nu1"], nu2=cfg["nu2"], eps=cfg["eps"],
                    kernel=rbf(gamma=cfg["kernel_gamma"]))


def box(cfg: dict, m: int):
    return (1.0 / (cfg["nu1"] * m), -cfg["eps"] / (cfg["nu2"] * m),
            1.0 - cfg["eps"])


def solution_numbers(cfg: dict, X, res) -> dict:
    """What a fit must satisfy, recomputed with the reference f = K @ gamma.

    ``f_rel`` compares the scores f that the fit returns. The shrinking
    driver that ``repro.fit`` takes at the cell's size recomputes them
    with XLA after its last round; the Pallas ``fupdate`` kernel's own
    f-cache is not returned, so its precision shows here only through
    the coefficients it leads the solve to (``kkt_max``)."""
    g = np.asarray(res.model.gamma, np.float64)
    m = g.shape[0]
    hi, lo, total = box(cfg, m)
    f_ref = reference.raw_scores(X, X, g, cfg["kernel_gamma"], "highest")
    f_got = np.asarray(res.f, np.float64)
    v = reference.kkt_violation(g, f_ref, float(res.model.rho1),
                                float(res.model.rho2), hi=hi, lo=lo)
    scale = float(np.max(np.abs(f_ref)))
    return {
        "not_converged": 0.0 if bool(res.converged) else 1.0,
        "sum_err": abs(g.sum() - total),
        "box_excess": max(0.0, g.max() - hi, lo - g.min()) / (hi - lo),
        "kkt_max": float(v.max()),
        "f_rel": float(np.max(np.abs(f_got - f_ref))) / scale,
    }


def check_worst(ctx, numbers: list) -> None:
    """Each number's worst reading over the window's answers, against the
    configuration's limit for it."""
    lim = ctx.config["limits"]
    for k in numbers[0]:
        ctx.check(k, max(n[k] for n in numbers), lim[k])


# -- fit: cold fits back to back -----------------------------------------------
FIT_SPAN = "bench.fit"


def run_fit(ctx) -> None:
    import repro
    cfg = ctx.config
    m, d, rate, tol = cfg.get("fit_rows", cfg["rows"]), cfg["features"], \
        cfg["anomaly_rate"], cfg["tol"]
    spec = slab_spec(cfg)

    def rows(k):
        return data.slab_rows(data.key_for(ctx.seed, 1, k), m, d, rate)

    with TraceAnnotation("bench.setup_fit"):
        jax.block_until_ready(repro.fit(rows(0), spec, tol=tol).f)
        margin = cfg.get("repack_warmup_margin")
        if margin is not None:
            # The same problem again, with the shrinking driver's freezing
            # margin set so that its active set lands in the range that
            # the driver repacks: the programs of that path compile here,
            # and not in the window's first fit that takes it.
            jax.block_until_ready(
                repro.fit(rows(0), spec, tol=tol, margin=margin).f)

    fits = []
    t0 = ctx.begin_window()
    k = 1
    while time.perf_counter() - t0 < ctx.seconds:
        with TraceAnnotation("bench.gen"):
            X = jax.block_until_ready(rows(k))
        with TraceAnnotation(FIT_SPAN):
            ts = time.perf_counter()
            res = repro.fit(X, spec, tol=tol)
            jax.block_until_ready(res.f)
            dt = time.perf_counter() - ts
        fits.append((X, res, dt))
        k += 1
    ctx.end_window()

    secs = [dt for _, _, dt in fits]
    iters = [int(r.iters) for _, r, _ in fits]
    ctx.e2e["fit_s"] = sum(secs) / len(secs)
    # the pairs an iteration updates: repro.fit's own P, which the
    # window's calls leave at its default
    from repro import api
    pairs = inspect.signature(api.fit).parameters["P"].default
    ctx.counters.update(fits=len(fits), fit_seconds=sum(secs),
                        fit_iters=sum(iters), m=m, d=d, pairs=pairs)
    ctx.attempted = len(fits)
    ctx.reduce_trace(span=FIT_SPAN)
    ctx.kept["fits"] = [(X, res) for X, res, _ in fits]
    numbers = [solution_numbers(cfg, X, res) for X, res, _ in fits]
    ctx.failed = int(sum(n["not_converged"] for n in numbers))
    check_worst(ctx, numbers)


# -- serving --------------------------------------------------------------------
class _Registry:
    """The admission controller's registry contract (``get``, ``quota``)
    over models the benchmark packed itself."""

    def __init__(self, models: dict):
        self._models = models

    def get(self, name):
        return self._models[name]

    def quota(self, name):
        if name not in self._models:
            raise KeyError(name)
        return None


class ServingModel:
    """The served slab: rows, dual coefficients and offsets made from the
    seed (they stand in for a fit of the source's rows, which would cost
    minutes of set-up), packed by the program and put behind its
    admission stack."""

    NAME = "slab"

    def __init__(self, ctx):
        from repro.core.ocssvm import OCSSVMModel
        from repro.serve import AdmissionController, AsyncDriver, pack_model
        cfg, tr = ctx.config, ctx.traffic
        n, d = cfg["rows"], cfg["features"]
        self.kg = cfg["kernel_gamma"]
        self.T = data.slab_rows(data.key_for(ctx.seed, 3, 0), n, d,
                                cfg["anomaly_rate"])
        hi, lo, total = box(cfg, n)
        self.gamma = data.feasible_gamma(data.key_for(ctx.seed, 3, 1), n,
                                         total=total, lo=lo, hi=hi)
        # Offsets: the quartiles of the reference scores of a seeded
        # sample of the rows. Quartiles lie among the target rows for any
        # anomaly rate under 25%, so the slab, and the scale of the
        # decision values, is the same from seed to seed.
        idx = np.asarray(jax.random.choice(data.key_for(ctx.seed, 3, 2), n,
                                           (min(n, 4096),), replace=False))
        s = reference.raw_scores(np.asarray(self.T)[idx], self.T,
                                 self.gamma, self.kg)
        self.rho1, self.rho2 = (float(np.quantile(s, 0.25)),
                                float(np.quantile(s, 0.75)))
        model = OCSSVMModel(gamma=self.gamma, rho1=np.float32(self.rho1),
                            rho2=np.float32(self.rho2), X=self.T,
                            spec=slab_spec(cfg))
        self.packed = pack_model(model, precision=tr["precision"])
        self.packed.scorer().warmup()
        self.ctrl = AdmissionController(
            _Registry({self.NAME: self.packed}),
            max_wait_s=tr["max_wait_s"])
        self.driver = AsyncDriver(self.ctrl).start()

    def service(self):
        return self.ctrl.service(self.NAME)

    def bucket_totals(self):
        st = self.service().stats
        return (sum(s.batches for s in st.values()),
                sum(s.queries for s in st.values()),
                {b: (s.batches, s.queries) for b, s in st.items()})

    def stop(self):
        self.driver.stop()

    def reference(self, queries: list, passes="highest") -> np.ndarray:
        """Kernel sums s(q) = k(q, T) @ gamma of the reference."""
        return reference.raw_scores(np.concatenate(queries), self.T,
                                    self.gamma, self.kg, passes)

    def decision(self, s) -> np.ndarray:
        s = np.asarray(s, np.float64)
        return (s - self.rho1) * (self.rho2 - s)

    def compare(self, queries: list, scores: list) -> float:
        """Worst error of the kernel sums behind the served decision
        values, relative to the largest reference sum.

        A decision value d = (s - rho1)(rho2 - s) damps an error of its
        kernel sum s by its slope rho1 + rho2 - 2s, which is small inside
        a narrow slab; so each row's decision error is divided by that
        slope at the reference sum, which gives the error of the sum the
        program computed. Rows within 5% of the slab's width of its
        middle, where the slope vanishes, are left out."""
        s_ref = self.reference(queries).astype(np.float64)
        d_got = np.concatenate(scores).astype(np.float64)
        slope = np.abs(self.rho1 + self.rho2 - 2.0 * s_ref)
        use = slope >= 0.1 * (self.rho2 - self.rho1)
        err = np.abs(d_got - self.decision(s_ref))[use] / slope[use]
        return float(np.max(err) / np.max(np.abs(s_ref)))


def _query_pool(ctx, rows: int):
    cfg = ctx.config
    return np.asarray(data.slab_rows(data.key_for(ctx.seed, 4), rows,
                                     cfg["features"], cfg["anomaly_rate"]))


def _window_launches(ctx, sm, before):
    b1, q1, per1 = sm.bucket_totals()
    per0 = before[2]
    ctx.counters.update(launches=b1 - before[0], rows_scored=q1 - before[1])
    ctx.counters["buckets"] = {
        b: (n - per0.get(b, (0, 0))[0], r - per0.get(b, (0, 0))[1])
        for b, (n, r) in per1.items()}
    ctx.counters.update(n_sv=sm.packed.n_sv, d=ctx.config["features"])


def _sample(ctx, n: int, k: int) -> np.ndarray:
    rng = np.random.default_rng([ctx.seed, 5])
    return np.sort(rng.choice(n, size=min(n, k), replace=False))


def run_open_loop(ctx) -> None:
    """Requests of ``sizes`` rows on a Poisson schedule at ``rate`` per
    second. Every seed sends the same set of gaps and sizes in an order
    of its own; latency runs from a request's due time to its scores on
    the host."""
    tr = ctx.traffic
    rate = float(tr["rate"])
    sizes_set = list(tr["sizes"])
    n_req = int(rate * ctx.seconds)
    rng = np.random.default_rng([ctx.seed, 6])
    gaps = rng.permutation(data.poisson_gaps(n_req, rate))
    sizes = rng.permutation(np.resize(np.asarray(sizes_set), n_req))
    due = np.cumsum(gaps) - gaps[0]
    pool = _query_pool(ctx, tr["pool_rows"])
    offs = np.concatenate([[0], np.cumsum(sizes)[:-1]]) % (
        pool.shape[0] - max(sizes_set))

    sm = ServingModel(ctx)
    for s in sizes_set:             # the admission path, warm
        sm.ctrl.submit(sm.NAME, pool[:s]).result()
    # The requests compared afterwards are drawn before the window; only
    # their handles are kept, so the harness holds no per-request
    # objects beyond those in flight.
    pick = _sample(ctx, n_req, tr["compare_requests"])
    picked = set(pick.tolist())
    kept = {}
    submitted = np.full(n_req, np.nan)
    finished = np.full(n_req, np.nan)
    remaining = threading.Semaphore(0)

    def on_done(h):
        finished[h.bench_index] = time.perf_counter()
        remaining.release()

    before = sm.bucket_totals()
    svc = sm.service()
    g0, o0 = svc.flush_groups, svc.flush_overhead_s
    sent = 0
    t0 = ctx.begin_window()
    for i in range(n_req):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        with TraceAnnotation("bench.submit"):
            submitted[i] = time.perf_counter()
            try:
                h = sm.ctrl.submit(sm.NAME,
                                   pool[offs[i]:offs[i] + sizes[i]])
            except Exception as e:      # noqa: BLE001 - refused: missing
                ctx.say(f"request {i} refused: {e!r}")
                continue
            h.bench_index = i
            h.add_done_callback(on_done)
            sent += 1
            if i in picked:
                kept[i] = h
    end = time.perf_counter() + 60.0
    with TraceAnnotation("bench.drain"):
        for _ in range(sent):
            if not remaining.acquire(timeout=max(0.0,
                                                 end - time.perf_counter())):
                break
    ctx.end_window()
    sm.stop()

    lat = (finished - (t0 + due)) * 1e3
    missing = ~np.isfinite(lat)
    # a request that never came back waited until the harness gave up
    lat[missing] = (time.perf_counter() - (t0 + due[missing])) * 1e3
    ctx.kept["latency_ms"] = lat
    ctx.e2e["score_p95_ms"] = float(np.percentile(lat, 95))
    ctx.counters.update(
        requests=n_req, rate=rate, p50_ms=float(np.percentile(lat, 50)),
        p99_ms=float(np.percentile(lat, 99)),
        gen_lag_p99_ms=float(np.percentile(
            (submitted - (t0 + due))[np.isfinite(submitted)] * 1e3, 99)),
        flush_groups=svc.flush_groups - g0,
        flush_overhead_s=svc.flush_overhead_s - o0)
    _window_launches(ctx, sm, before)
    ctx.attempted, ctx.failed = n_req, int(missing.sum())
    ctx.reduce_trace()
    ctx.say(f"requests {n_req}, p50 {ctx.counters['p50_ms']:.4f} ms, "
            f"p95 {ctx.e2e['score_p95_ms']:.4f} ms, p99 "
            f"{ctx.counters['p99_ms']:.4f} ms, generator lag p99 "
            f"{ctx.counters['gen_lag_p99_ms']:.4f} ms")

    done = [i for i in pick if np.isfinite(finished[i])]
    queries = [pool[offs[i]:offs[i] + sizes[i]] for i in done]
    scores = [kept[i].result() for i in done]
    ctx.kept.update(model=sm, queries=queries, scores=scores)
    ctx.check("missing", float(missing.sum()), 0)
    ctx.check("kernel_sum_rel", sm.compare(queries, scores),
              ctx.config["limits"]["kernel_sum_rel"])


def run_closed_loop(ctx) -> None:
    """``clients`` threads, each sending ``rows``-row requests and waiting
    for each before the next. Throughput counts the rows whose scores
    came back inside the window."""
    tr = ctx.traffic
    rows, clients = int(tr["rows"]), int(tr["clients"])
    pool = _query_pool(ctx, rows * tr["pool_requests"])
    sm = ServingModel(ctx)
    for _ in range(2):
        sm.ctrl.submit(sm.NAME, pool[:rows]).result()

    # Scores are kept for a seeded share of the requests (and each
    # client's last), not for all: the harness holds few objects.
    keep = np.random.default_rng([ctx.seed, 5]).random(1 << 20) \
        < tr["compare_share"]
    log = []                            # (end time, offset, scores or None)
    last = {}
    errors = []
    before = sm.bucket_totals()
    svc = sm.service()
    g0, o0 = svc.flush_groups, svc.flush_overhead_s
    t0 = ctx.begin_window()
    t_end = t0 + ctx.seconds

    def client(c):
        k = c
        while time.perf_counter() < t_end:
            off = (k % tr["pool_requests"]) * rows
            try:
                with TraceAnnotation("bench.request"):
                    out = sm.ctrl.submit(sm.NAME, pool[off:off + rows]) \
                        .result()
            except Exception as e:      # noqa: BLE001 - counted, reported
                errors.append(repr(e))
                return
            log.append((time.perf_counter(), off,
                        out if keep[k % keep.size] else None))
            last[c] = (off, out)
            k += clients

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(ctx.seconds + 120.0)
    ctx.end_window()
    sm.stop()
    if any(t.is_alive() for t in threads):
        errors.append("a client did not finish")

    inside = sum(1 for e in log if e[0] <= t_end)
    ctx.e2e["score_rows_per_s"] = inside * rows / ctx.seconds
    ctx.counters.update(
        requests=len(log), flush_groups=svc.flush_groups - g0,
        flush_overhead_s=svc.flush_overhead_s - o0)
    _window_launches(ctx, sm, before)
    ctx.attempted = len(log) + len(errors)
    ctx.failed = len(errors)
    ctx.reduce_trace()
    for e in errors:
        ctx.say(f"client error: {e}")

    pairs = [(off, out) for _, off, out in log if out is not None] \
        + list(last.values())
    queries = [pool[off:off + rows] for off, _ in pairs]
    scores = [out for _, out in pairs]
    ctx.kept.update(model=sm, queries=queries, scores=scores)
    ctx.check("missing", float(len(errors)), 0)
    ctx.check("kernel_sum_rel", sm.compare(queries, scores) if pairs
              else float("inf"), ctx.config["limits"]["kernel_sum_rel"])


RUNNERS = {"fit": run_fit, "open_loop": run_open_loop,
           "closed_loop": run_closed_loop}
