"""Paper Figs 1-2: the recovered slab on the 2-D toy set, as data.

Fig 1: m=1000, nu1=0.5, nu2=0.01, eps=2/3.
Fig 2: m=2000, nu1=0.2, nu2=0.08, eps=1/2.
For the linear kernel the primal normal is w = sum_i gamma_i x_i; the two
hyperplanes are {w.x = rho1} and {w.x = rho2}.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.ocssvm_paper import FIG2_SPEC, PAPER_SPEC
from repro.core import mcc, solve_smo
from repro.data import make_toy


def run():
    out = []
    for name, m, spec in (("fig1", 1000, PAPER_SPEC),
                          ("fig2", 2000, FIG2_SPEC)):
        X, y = make_toy(jax.random.PRNGKey(0), m)
        res = solve_smo(X, spec, selection="paper", tol=1e-3,
                        max_iters=200_000)
        w = res.model.gamma @ res.model.X          # (d,) primal normal
        out.append({
            "name": name, "m": m,
            "w": [float(v) for v in w],
            "rho1": float(res.model.rho1), "rho2": float(res.model.rho2),
            "slab_width": float(res.model.rho2 - res.model.rho1),
            "iters": int(res.iters),
            "converged": bool(res.converged),
            "mcc": float(mcc(y, res.model.predict(X))),
            "n_sv": int(jnp.sum(jnp.abs(res.model.gamma) > 1e-7)),
        })
    return out


def main():
    for r in run():
        print(f"{r['name']},m={r['m']},w=({r['w'][0]:.4f},{r['w'][1]:.4f}),"
              f"rho1={r['rho1']:.4f},rho2={r['rho2']:.4f},"
              f"width={r['slab_width']:.4f},mcc={r['mcc']:.3f},"
              f"sv={r['n_sv']},iters={r['iters']}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
