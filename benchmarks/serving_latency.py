"""Serving benchmark: cold path vs warm cache + cached bucket executables.

Cold = first ``repro.serve`` on a fresh cache (fit + SV compaction + tile
packing) plus the first score per bucket (compiles the executable).
Warm = the same request stream again: cache hit + cached executables.
Acceptance (ISSUE 2): warm beats cold by >= 5x on the 2000-row toy.

``--precisions`` repeats the whole cold/warm protocol once per Gram tile
precision (each is its own cache entry + packed model) and nests the
per-precision rows under ``per_precision`` in the BENCH JSON — the trend
line for the 16-bit support-stream win (meaningful on TPU; the
interpret-mode CPU numbers only track that the path stays wired).

A two-model fleet (registry + deadline-aware admission controller) runs
once at the lead precision and lands under ``multi_model`` in the JSON:
per-model cold fit, deadline-driven stream throughput, per-model
per-bucket stats, and a routing-parity spot check.

    PYTHONPATH=src python benchmarks/serving_latency.py [--reduced]
        [--precisions f32,bf16] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

import repro
from repro.core import SlabSpec, linear, rbf
from repro.data import make_toy
from repro.kernels.precision import parse_precisions
from repro.serve import (AdmissionController, ModelCache, ModelRegistry,
                         ScoringService)

BATCHES = (64, 256, 1024)


def _stream(sm, batches):
    """One scoring pass per batch size; returns per-bucket seconds."""
    svc = ScoringService(sm.scorer())
    out = {}
    for i, n in enumerate(batches):
        q = np.asarray(make_toy(jax.random.PRNGKey(100 + i), n)[0])
        t0 = time.perf_counter()
        jax.block_until_ready(svc.score(q))
        out[n] = time.perf_counter() - t0
    return out


def run(m: int = 2000, batches=BATCHES, tol: float = 1e-3,
        precision: str = "f32") -> dict:
    spec = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
    X, _ = make_toy(jax.random.PRNGKey(0), m)
    cache = ModelCache()

    t0 = time.perf_counter()
    sm = repro.serve(X, spec, cache=cache, tol=tol, P=16,
                     precision=precision)
    cold_first = _stream(sm, batches)
    cold_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    sm2 = repro.serve(X, spec, cache=cache, tol=tol, P=16,
                      precision=precision)
    warm_first = _stream(sm2, batches)
    warm_s = time.perf_counter() - t0

    assert sm2 is sm and cache.hits == 1, "warm pass must hit the cache"
    return {
        "m": m, "n_sv": sm.n_sv, "tol": tol, "precision": precision,
        "cold_s": cold_s, "warm_s": warm_s,
        "speedup": cold_s / warm_s,
        "cold_per_bucket_s": {str(k): v for k, v in cold_first.items()},
        "warm_per_bucket_s": {str(k): v for k, v in warm_first.items()},
    }


def run_multi_model(m: int = 500, requests: int = 16,
                    deadline_ms: float = 20.0, tol: float = 1e-3,
                    precision: str = "f32") -> dict:
    """Two-model fleet through the registry + admission controller.

    Measures the multi-model serving front-end end to end: per-model
    cold fit (fit-on-first-use via the registry), then a deadline-driven
    interleaved stream — every submit is followed by a ``poll()`` so
    flushes happen exactly when deadline pressure (observed per-bucket
    latency vs earliest deadline) says they must. Routing correctness is
    spot-checked against each model's direct scorer.
    """
    X, _ = make_toy(jax.random.PRNGKey(0), m)
    registry = ModelRegistry()
    registry.register(
        "slab-rbf", X,
        SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5)),
        tol=tol, P=16, precision=precision)
    registry.register(
        "slab-linear", X,
        SlabSpec(nu1=0.3, nu2=0.05, eps=0.5, kernel=linear()),
        tol=tol, P=16, precision=precision)
    names = registry.names()

    ctrl = AdmissionController(registry, max_wait_s=0.05)
    cold = {}
    for name in names:
        t0 = time.perf_counter()
        ctrl.service(name).scorer.warmup()      # fit + compile, once
        cold[name] = time.perf_counter() - t0

    handles = []
    t0 = time.perf_counter()
    for i in range(requests):
        name = names[i % len(names)]
        q = np.asarray(make_toy(jax.random.PRNGKey(200 + i),
                                32 + 16 * (i % 5))[0])
        handles.append((name, q, ctrl.submit(
            name, q, deadline=ctrl.clock() + deadline_ms / 1e3)))
        ctrl.poll()
    ctrl.drain()
    stream_s = time.perf_counter() - t0

    max_err = 0.0
    for name, q, h in handles[:4]:      # routing spot check, kept cheap
        direct = np.asarray(registry.get(name).scorer().score(q))
        max_err = max(max_err, float(np.max(np.abs(
            np.asarray(h.result()) - direct))))
    assert max_err < 1e-5, f"routing parity broke: {max_err}"

    queries = sum(h.n for _, _, h in handles)
    return {
        "m": m, "precision": precision, "models": list(names),
        "requests": requests, "queries": queries,
        "deadline_ms": deadline_ms, "stream_s": stream_s,
        "routing_max_abs_err": max_err,
        "cold_s": cold,
        "per_model": ctrl.stats_dict(),
    }


def _print_rows(res):
    print(f"serving,m={res['m']},n_sv={res['n_sv']},"
          f"precision={res['precision']},"
          f"cold={res['cold_s']*1e3:.0f}ms,warm={res['warm_s']*1e3:.1f}ms,"
          f"speedup={res['speedup']:.0f}x")
    for b in res["cold_per_bucket_s"]:
        print(f"serving_bucket,b={b},precision={res['precision']},"
              f"cold={res['cold_per_bucket_s'][b]*1e3:.1f}ms,"
              f"warm={res['warm_per_bucket_s'][b]*1e3:.1f}ms")


def _print_multi_rows(res):
    for name in res["models"]:
        stats = res["per_model"][name]
        served = sum(b["queries"] for b in stats["buckets"].values())
        print(f"serving_multimodel,model={name},"
              f"precision={res['precision']},"
              f"cold={res['cold_s'][name]*1e3:.0f}ms,"
              f"queries={served},rejected={stats['rejected']},"
              f"routing_max_abs_err={res['routing_max_abs_err']:.2e}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true",
                    help="small problem for CI smoke (m=500, 2 buckets)")
    ap.add_argument("--precisions", type=str, default="f32",
                    help="comma list of Gram tile precisions to benchmark "
                         "(each runs the full cold/warm protocol)")
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args(argv)

    precisions = parse_precisions(args.precisions)
    kwargs = dict(m=500, batches=(64, 256)) if args.reduced else {}
    per_precision = {}
    for p in precisions:
        per_precision[p] = run(precision=p, **kwargs)
        _print_rows(per_precision[p])
        if per_precision[p]["speedup"] < 5:
            print(f"WARNING: warm speedup "
                  f"{per_precision[p]['speedup']:.1f}x below the 5x "
                  f"acceptance bar at precision={p}")

    # top level keeps the first (f32 by convention) run's schema so older
    # trend consumers of BENCH_serving.json keep working
    res = dict(per_precision[precisions[0]])
    res["per_precision"] = per_precision

    # multi-model registry + admission rows (once, at the lead precision)
    multi_kwargs = (dict(m=300, requests=8) if args.reduced
                    else dict(m=500, requests=16))
    res["multi_model"] = run_multi_model(precision=precisions[0],
                                         **multi_kwargs)
    _print_multi_rows(res["multi_model"])
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(res, fh, indent=2)
        print(f"wrote {args.json}")
    return res


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
