"""Async-serving soak: many writers x many models through the driver.

The serving benchmark measures one-thread throughput; this one measures
the async front-end under contention — ``--writers`` threads (default 8)
submit deadline-carrying requests for ``--models`` registered models
(default 2) while a single background ``AsyncDriver`` flushes on
deadline pressure. Nothing here polls: if the driver's wake-on-earliest-
deadline loop is wrong, requests miss their deadlines and the gate
below fails.

Per-request latency is measured submit -> done-callback (the callback
fires when the request's flush lands), against the absolute deadline on
the controller's clock. The BENCH JSON carries the tail:

* ``p50_s`` / ``p95_s`` / ``p99_s`` — gated ratio-wise like every
  timing. The tail is deadline-bound (a window flushes when waiting
  longer would miss its earliest deadline), so p99 tracks the
  configured ``--deadline-s``, stable enough to gate.
* ``deadline_miss_rate`` — gated by ``check_regression.py`` as an
  ABSOLUTE ceiling (``*_rate`` rule): the committed baseline is 0, so
  the first CI miss fails the job. Means alone don't gate tails.
* ``shm`` — the cross-process registry parity row: the lead model is
  published to shared memory, re-attached, and scored; ``parity`` is
  True only for bitwise-equal scores.

Estimates are seeded before the clock starts (one warm scoring pass per
bucket per model, after ``warmup()`` so nothing is recorded cold) —
deadline policy needs observed latencies, and a soak that guessed them
would measure the fallback constant, not the driver.

    PYTHONPATH=src python benchmarks/serving_soak.py [--reduced]
        [--writers 8] [--models 2] [--deadline-s 0.75] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import threading
import time

import jax
import numpy as np

from repro.core import SlabSpec, linear, rbf
from repro.data import make_toy
from repro.serve import (AdmissionController, AsyncDriver, ModelRegistry,
                         attach, publish)

SEED_BUCKETS = (64, 256, 1024)


def _build_registry(n_models: int, m: int, tol: float) -> ModelRegistry:
    X, _ = make_toy(jax.random.PRNGKey(0), m)
    specs = [SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5)),
             SlabSpec(nu1=0.3, nu2=0.05, eps=0.5, kernel=linear())]
    reg = ModelRegistry()
    for i in range(n_models):
        spec = specs[i % len(specs)]
        if i >= len(specs):
            spec = SlabSpec(nu1=spec.nu1, nu2=spec.nu2,
                            eps=spec.eps + 0.05 * (i // len(specs)),
                            kernel=spec.kernel)
        reg.register(f"soak-{i}", X, spec, tol=tol, P=16)
    return reg


def _prewarm(ctrl: AdmissionController, names, max_batch: int,
             pool: dict) -> None:
    """Fit + compile + recorded warm observations per bucket per model.

    Two rounds: single scores seed every bucket the traffic can touch,
    then two traffic-shaped windows (many coalesced requests through
    ``flush_model``) refresh the big-bucket means with launches recorded
    in real flush context — the deadline estimate reads those means, and
    seeding them from single-request launches alone would understate
    what a soak window costs to serve."""
    sizes = sorted(pool)
    for name in names:
        svc = ctrl.service(name)
        svc.warmup()
        for b in SEED_BUCKETS:
            if b > max_batch:
                break
            q = np.asarray(make_toy(jax.random.PRNGKey(1000 + b), b)[0])
            jax.block_until_ready(svc.score(q))
        for _ in range(2):
            for i in range(32):
                ctrl.submit(name, pool[sizes[i % len(sizes)]])
            ctrl.flush_model(name)


def _percentiles(latencies) -> dict:
    lat = np.asarray(latencies, dtype=np.float64)
    return {"p50_s": float(np.percentile(lat, 50)),
            "p95_s": float(np.percentile(lat, 95)),
            "p99_s": float(np.percentile(lat, 99))}


def _shm_parity(ctrl: AdmissionController, name: str) -> dict:
    """Publish the model, attach it back, compare scores bitwise."""
    sm = ctrl.registry.get(name)
    key = f"soak-parity-{os.getpid()}"
    q = np.asarray(make_toy(jax.random.PRNGKey(7), 96)[0])
    want = np.asarray(sm.scorer().score(q))
    with publish(sm, key):
        sm2, lease = attach(key)
        with lease:
            got = np.asarray(sm2.scorer().score(q))
    identical = bool(np.array_equal(want, got))
    return {"parity": identical, "n_sv": sm.n_sv,
            "max_abs_err": float(np.max(np.abs(want - got)))}


def run(n_models: int = 2, writers: int = 8, requests_per_writer: int = 24,
        m: int = 500, deadline_s: float = 0.75, rows_lo: int = 8,
        rows_hi: int = 32, tol: float = 1e-3,
        max_batch: int = 1024) -> dict:
    registry = _build_registry(n_models, m, tol)
    names = registry.names()
    # safety_factor 6: the earliest-deadline request is served last-
    # minute by construction (the whole point of deadline-pressure
    # coalescing), so the factor is its only slack — it must cover
    # scheduler jitter AND one other model's flush, which the single
    # driver thread may run first when deadlines collide.
    ctrl = AdmissionController(registry, max_batch=max_batch,
                               fallback_latency_s=0.05, safety_factor=6.0)

    # Queries are pre-generated: make_toy inside the writer loop would
    # trace/compile one executable per distinct row count while the
    # clock runs, and that GIL-heavy burst starves the driver thread —
    # the soak would measure jax compilation, not the serving path.
    pool = {n: np.asarray(make_toy(jax.random.PRNGKey(10_000 + n), n)[0])
            for n in range(rows_lo, rows_hi + 1)}
    _prewarm(ctrl, names, max_batch, pool)

    records = []                 # (model, latency_s, missed)
    errors = []
    rec_lock = threading.Lock()
    total = writers * requests_per_writer

    def writer(wid: int) -> None:
        rng = np.random.default_rng(wid)
        for i in range(requests_per_writer):
            name = names[int(rng.integers(len(names)))]
            n = int(rng.integers(rows_lo, rows_hi + 1))
            q = pool[n]
            t0 = ctrl.clock()
            deadline = t0 + deadline_s

            def _done(h, t0=t0, deadline=deadline, name=name):
                t1 = ctrl.clock()
                with rec_lock:
                    if h._error is not None:
                        errors.append(repr(h._error))
                    records.append((name, t1 - t0, t1 > deadline))

            ctrl.submit(name, q, deadline=deadline).add_done_callback(_done)
            time.sleep(float(rng.uniform(0.0005, 0.003)))

    t_start = time.perf_counter()
    with AsyncDriver(ctrl):
        threads = [threading.Thread(target=writer, args=(w,), daemon=True)
                   for w in range(writers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        deadline_wall = time.monotonic() + max(60.0, 4 * deadline_s)
        while time.monotonic() < deadline_wall:
            with rec_lock:
                if len(records) >= total:
                    break
            time.sleep(0.01)
        # context exit drains (nothing should be left: the driver owns
        # every pending deadline) and stops the driver
    soak_s = time.perf_counter() - t_start

    if len(records) != total:
        raise RuntimeError(f"soak lost requests: {len(records)}/{total} "
                           f"resolved (driver stalled?)")
    if errors:
        raise RuntimeError(f"soak flush errors: {errors[:3]}")

    stats = ctrl.stats_dict()
    per_model = {}
    for name in names:
        rows = [(lat, miss) for mdl, lat, miss in records if mdl == name]
        ws = stats[name]["windows"]
        per_model[name] = {
            "requests": len(rows),
            **_percentiles([lat for lat, _ in rows]),
            "deadline_miss_rate": (sum(miss for _, miss in rows)
                                   / max(1, len(rows))),
            "windows": ws,
            "mean_fill_rows": (ws["flushed_rows"] / ws["flushed"]
                               if ws["flushed"] else 0.0),
        }
    misses = sum(miss for _, _, miss in records)
    return {
        "models": list(names), "writers": writers,
        "requests": total, "deadline_s": deadline_s, "soak_s": soak_s,
        **_percentiles([lat for _, lat, _ in records]),
        "deadline_misses": misses,
        "deadline_miss_rate": misses / total,
        "per_model": per_model,
        "shm": _shm_parity(ctrl, names[0]),
    }


def _print_rows(res: dict) -> None:
    print(f"soak,models={len(res['models'])},writers={res['writers']},"
          f"requests={res['requests']},deadline={res['deadline_s']*1e3:.0f}ms,"
          f"p50={res['p50_s']*1e3:.1f}ms,p99={res['p99_s']*1e3:.1f}ms,"
          f"miss_rate={res['deadline_miss_rate']:.4f}")
    for name, row in res["per_model"].items():
        print(f"soak_model,model={name},requests={row['requests']},"
              f"p99={row['p99_s']*1e3:.1f}ms,"
              f"miss_rate={row['deadline_miss_rate']:.4f},"
              f"windows={row['windows']['flushed']}/"
              f"{row['windows']['opened']},"
              f"mean_fill={row['mean_fill_rows']:.1f}")
    shm = res["shm"]
    print(f"soak_shm,parity={shm['parity']},n_sv={shm['n_sv']},"
          f"max_abs_err={shm['max_abs_err']:.2e}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true",
                    help="small problem for CI smoke (fewer requests, "
                         "smaller fit; writer/model counts keep the "
                         "contention shape)")
    ap.add_argument("--writers", type=int, default=8)
    ap.add_argument("--models", type=int, default=2)
    ap.add_argument("--deadline-s", type=float, default=0.75)
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args(argv)

    kwargs = dict(n_models=args.models, writers=args.writers,
                  deadline_s=args.deadline_s)
    if args.reduced:
        kwargs.update(m=300, requests_per_writer=8, rows_hi=16)
    res = run(**kwargs)
    _print_rows(res)
    if res["deadline_misses"]:
        print(f"WARNING: {res['deadline_misses']} deadline misses "
              f"({res['deadline_miss_rate']:.2%}) — the regression gate "
              f"fails on any miss against a zero baseline")
    if not res["shm"]["parity"]:
        print("WARNING: shm re-attach was NOT bitwise identical")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(res, fh, indent=2)
        print(f"wrote {args.json}")
    return res


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
