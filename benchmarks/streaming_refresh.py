"""Streaming refresh benchmark: warm ``fit_update`` vs cold re-fit.

The ISSUE-8 acceptance story in numbers: append a 5% row delta to an
already-fitted set and re-solve three ways —

* ``cold``  — ``repro.fit`` from scratch on the extended set;
* ``warm``  — ``repro.fit_update`` seeded from the prior fit's
  ``SolverArtifact`` (row matching + f-cache reconcile + delta-scaled
  working set);
* ``registry`` — the serving-facing path: ``ModelRegistry.refresh``
  with ``append=``, which adds the drift gate, the O(Δm) re-key and the
  pack on top of the warm solve (plus one forced-cold refresh so the
  routed-vs-forced costs sit side by side in the JSON).

Iteration counts are the portable signal (interpret-mode CPU timings
only track that the path stays wired); ``iters_ratio`` is the <= 0.25
acceptance bound asserted by ``tests/test_streaming.py``.

    PYTHONPATH=src python benchmarks/streaming_refresh.py [--reduced]
        [--precisions f32,bf16] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

import repro
from repro.core import SlabSpec, engine, rbf
from repro.data import make_toy
from repro.kernels.precision import parse_precisions
from repro.serve import ModelRegistry


def _spec():
    return SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))


def _data(m: int, n_app: int):
    X = np.asarray(make_toy(jax.random.PRNGKey(0), m + n_app)[0],
                   np.float32)
    return X[:m], X


def _inband(X, n):
    """In-distribution fresh rows: jittered training rows (fresh content
    hashes, same distribution — keeps the drift gate on the warm route,
    which is the path this benchmark is pricing)."""
    rng = np.random.default_rng(1)
    return np.asarray(X[:n] + rng.normal(0, 1e-3, (n, X.shape[1])),
                      np.float32)


def run(m: int = 2000, delta_frac: float = 0.05, tol: float = 1e-4,
        precision: str = "f32") -> dict:
    spec = _spec()
    n_app = max(1, int(m * delta_frac))
    X_prev, X_new = _data(m, n_app)

    t0 = time.perf_counter()
    prev = repro.fit(X_prev, spec, strategy="blocked", tol=tol,
                     precision=precision)
    prev_fit_s = time.perf_counter() - t0
    art = engine.artifact_from_result(prev, precision=precision)

    t0 = time.perf_counter()
    cold = repro.fit(X_new, spec, strategy="blocked", tol=tol,
                     precision=precision)
    cold_s = time.perf_counter() - t0

    stats: dict = {}
    t0 = time.perf_counter()
    warm = repro.fit_update(art, X_new, strategy="blocked", tol=tol,
                            precision=precision, stats_out=stats)
    warm_s = time.perf_counter() - t0
    assert stats["mode"] == "warm" and warm.converged, stats

    return {
        "m": m, "precision": precision, "n_app": n_app, "tol": tol,
        "prev_fit_s": prev_fit_s, "cold_s": cold_s, "warm_s": warm_s,
        "cold_iters": int(cold.iters), "warm_iters": int(warm.iters),
        "iters_ratio": int(warm.iters) / int(cold.iters),
        "speedup": cold_s / warm_s,
        "overlap_frac": stats["overlap_frac"], "warm_P": stats["P"],
    }


def run_registry(m: int = 500, delta_frac: float = 0.05,
                 tol: float = 1e-3, precision: str = "f32") -> dict:
    """The serving-facing refresh: drift gate + O(Δm) re-key + pack."""
    spec = _spec()
    n_app = max(1, int(m * delta_frac))
    X_prev, _ = _data(m, n_app)
    reg = ModelRegistry()
    reg.register("stream", X_prev, spec, strategy="blocked", tol=tol,
                 precision=precision)

    t0 = time.perf_counter()
    reg.get("stream")
    first_fit_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    reg.refresh("stream", append=_inband(X_prev, n_app))
    refresh_warm_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    reg.refresh("stream", mode="cold")
    refresh_cold_s = time.perf_counter() - t0

    st = reg.refresh_stats("stream")
    assert st["modes"]["warm"] >= 1, st
    return {
        "m": m, "precision": precision, "n_app": n_app,
        "first_fit_s": first_fit_s,
        "refresh_warm_s": refresh_warm_s,
        "refresh_cold_s": refresh_cold_s,
        "refresh_modes": dict(st["modes"]),
        "drift_statistic": (st["last_drift"].statistic
                            if st["last_drift"] is not None else None),
    }


def _print_rows(res):
    print(f"streaming,m={res['m']},precision={res['precision']},"
          f"n_app={res['n_app']},cold_iters={res['cold_iters']},"
          f"warm_iters={res['warm_iters']},"
          f"iters_ratio={res['iters_ratio']:.3f},"
          f"cold={res['cold_s']*1e3:.0f}ms,warm={res['warm_s']*1e3:.0f}ms")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true",
                    help="small problem for CI smoke (m=400)")
    ap.add_argument("--precisions", type=str, default="f32",
                    help="comma list of Gram tile precisions (each runs "
                         "the full cold/warm protocol)")
    ap.add_argument("--json", type=str, default=None)
    args = ap.parse_args(argv)

    precisions = parse_precisions(args.precisions)
    kwargs = dict(m=400, tol=1e-3) if args.reduced else {}
    per_precision = {}
    for p in precisions:
        per_precision[p] = run(precision=p, **kwargs)
        _print_rows(per_precision[p])
        if per_precision[p]["iters_ratio"] > 0.25:
            print(f"WARNING: warm/cold iteration ratio "
                  f"{per_precision[p]['iters_ratio']:.2f} above the "
                  f"0.25 acceptance bound at precision={p}")

    res = dict(per_precision[precisions[0]])
    res["per_precision"] = per_precision
    reg_kwargs = dict(m=200) if args.reduced else {}
    res["registry"] = run_registry(precision=precisions[0], **reg_kwargs)
    print(f"streaming_registry,m={res['registry']['m']},"
          f"warm={res['registry']['refresh_warm_s']*1e3:.0f}ms,"
          f"cold={res['registry']['refresh_cold_s']*1e3:.0f}ms,"
          f"modes={res['registry']['refresh_modes']}")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(res, fh, indent=2)
        print(f"wrote {args.json}")
    return res


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
