"""Paper Table 1: training time and MCC vs training-set size.

Protocol (Section 4): linear kernel, nu1=0.5, nu2=0.01, eps=2/3,
m in {500, 1000, 2000, 5000}. We time the paper-faithful SMO, the MVP
variant, the blocked TPU-native solver, and the generic-QP baseline the
paper compares against. Paper's reported times (their hardware):
0.35 / 0.67 / 2.1 / 5.91 s; MCC 0.07 / 0.13 / 0.26 / 0.33.

``--precisions`` additionally times the blocked Pallas solver per Gram
tile precision (f32 vs bf16/f16 streams) and emits
``pallas_<precision>_s`` rows into the BENCH JSON — the trend line for
the bytes-bound MXU win (meaningful on TPU; interpret-mode CPU numbers
only track that the path stays wired).
"""
from __future__ import annotations

import argparse
import json
import time

import jax

import repro
from repro.configs.ocssvm_paper import PAPER_SPEC, TABLE1_SIZES
from repro.core import mcc, solve_qp
from repro.data import make_toy
from repro.kernels.precision import parse_precisions


def _timed(fn):
    # compile (excluded, as the paper times the solve)
    out = fn()
    jax.block_until_ready(jax.tree.leaves(out)[0])
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(jax.tree.leaves(out)[0])
    return out, time.perf_counter() - t0


def run(sizes=TABLE1_SIZES, precisions=()):
    rows = []
    for m in sizes:
        X, y = make_toy(jax.random.PRNGKey(0), m)
        # gram_mode pinned per solver (the historical defaults) so timings
        # stay comparable across m and with previously recorded numbers.
        res_p, t_p = _timed(lambda: repro.fit(
            X, PAPER_SPEC, strategy="paper", gram_mode="precomputed",
            tol=1e-3, max_iters=100_000))
        res_m, t_m = _timed(lambda: repro.fit(
            X, PAPER_SPEC, strategy="mvp", gram_mode="precomputed",
            tol=1e-3, max_iters=100_000))
        res_b, t_b = _timed(lambda: repro.fit(
            X, PAPER_SPEC, strategy="blocked", gram_mode="on_the_fly",
            P=16, tol=1e-3, max_outer=50_000))
        res_q, t_q = _timed(lambda: solve_qp(
            X, PAPER_SPEC, max_iters=20_000, tol=1e-9))
        row = {
            "m": m,
            "paper_smo_s": t_p, "paper_smo_iters": int(res_p.iters),
            "paper_smo_mcc": float(mcc(y, res_p.model.predict(X))),
            "mvp_smo_s": t_m, "mvp_iters": int(res_m.iters),
            "blocked_s": t_b, "blocked_iters": int(res_b.iters),
            "qp_fista_s": t_q, "qp_iters": int(res_q.iters),
        }
        for p in precisions:
            res_x, t_x = _timed(lambda: repro.fit(
                X, PAPER_SPEC, strategy="blocked", gram_mode="pallas",
                precision=p, P=16, tol=1e-3, max_outer=50_000))
            row[f"pallas_{p}_s"] = t_x
            row[f"pallas_{p}_iters"] = int(res_x.iters)
        rows.append(row)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reduced", action="store_true",
                    help="CI smoke: only the two smallest sizes")
    ap.add_argument("--precisions", type=str, default="",
                    help="comma list (e.g. f32,bf16): also time the "
                         "blocked Pallas solver per Gram tile precision")
    ap.add_argument("--json", type=str, default=None,
                    help="also write the rows to this path as JSON")
    args = ap.parse_args(argv)

    precisions = parse_precisions(args.precisions) if args.precisions \
        else ()
    rows = run(sizes=(500, 1000) if args.reduced else TABLE1_SIZES,
               precisions=precisions)
    for r in rows:
        print(f"table1,m={r['m']},paper_smo={r['paper_smo_s']*1e6:.0f}us"
              f"(iters={r['paper_smo_iters']}),mcc={r['paper_smo_mcc']:.3f},"
              f"mvp={r['mvp_smo_s']*1e6:.0f}us,"
              f"blocked={r['blocked_s']*1e6:.0f}us,"
              f"qp={r['qp_fista_s']*1e6:.0f}us")
        for p in precisions:
            print(f"table1_precision,m={r['m']},precision={p},"
                  f"pallas={r[f'pallas_{p}_s']*1e6:.0f}us"
                  f"(iters={r[f'pallas_{p}_iters']})")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(rows, fh, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
