"""Pallas-kernel microbenchmarks (interpret mode on CPU; the BlockSpec
tiling is the TPU contract — wall numbers here are CPU-emulation only and
serve as regression canaries, not TPU projections)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import rbf
from repro.kernels import decision, fupdate, gram
from repro.kernels.gram.ref import gram_ref


def _timed(fn, n=3):
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def run():
    kern = rbf(gamma=0.5)
    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (1024, 128), jnp.float32)
    rows = []
    t = _timed(lambda: gram(X, X, kern, interpret=True))
    rows.append(("gram_1024x1024x128_pallas", t))
    t = _timed(lambda: gram_ref(X, X, kind="rbf", gamma=0.5))
    rows.append(("gram_1024x1024x128_jnp_ref", t))
    f = jnp.zeros((1024,))
    dl = jnp.ones((16,)) * 0.01
    t = _timed(lambda: fupdate(X, X[:16], dl, f, kern, interpret=True))
    rows.append(("fupdate_1024x128_P16_pallas", t))
    gv = jnp.ones((1024,)) * 0.001
    t = _timed(lambda: decision(X[:256], X, gv, 0.1, 0.9, kern,
                                interpret=True))
    rows.append(("decision_256q_1024sv_pallas", t))
    return rows


def main():
    for name, t in run():
        print(f"{name},{t*1e6:.0f}us,interpret=True")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
