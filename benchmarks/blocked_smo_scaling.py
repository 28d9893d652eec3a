"""Beyond-paper: blocked-SMO scaling with pair-block size P.

The paper's claim is SMO scales better than generic QP with m; the
TPU-native blocked solver additionally turns the per-iteration work into
rank-2P matmuls. This benchmark sweeps P at fixed m and m at fixed P
(RBF kernel — the non-degenerate regime).
"""
from __future__ import annotations

import time

import jax

import repro
from repro.core import SlabSpec, rbf
from repro.data import make_toy


def _timed(fn):
    out = fn()
    jax.block_until_ready(out.model.gamma)
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out.model.gamma)
    return out, time.perf_counter() - t0


def run():
    spec = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
    rows = []
    m = 2048
    X, _ = make_toy(jax.random.PRNGKey(0), m)
    # gram_mode pinned so the sweep stays apples-to-apples (fit's auto
    # heuristic would switch provider with m).
    for P in (1, 4, 16, 64):
        res, t = _timed(lambda: repro.fit(X, spec, strategy="blocked", P=P,
                                          gram_mode="on_the_fly", tol=1e-3,
                                          max_outer=50_000))
        rows.append({"sweep": "P", "m": m, "P": P, "time_s": t,
                     "iters": int(res.iters),
                     "converged": bool(res.converged)})
    for m2 in (512, 1024, 2048, 4096):
        X2, _ = make_toy(jax.random.PRNGKey(0), m2)
        res, t = _timed(lambda: repro.fit(X2, spec, strategy="blocked", P=16,
                                          gram_mode="on_the_fly", tol=1e-3,
                                          max_outer=50_000))
        rows.append({"sweep": "m", "m": m2, "P": 16, "time_s": t,
                     "iters": int(res.iters),
                     "converged": bool(res.converged)})
    return rows


def main():
    for r in run():
        print(f"blocked_scaling,{r['sweep']},m={r['m']},P={r['P']},"
              f"time={r['time_s']*1e6:.0f}us,iters={r['iters']},"
              f"converged={r['converged']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
