#!/usr/bin/env python3
"""The controls of ``correct``, read on the chip at a cell's own size:

    python3 bench/control.py --workload fraud.fit --seconds 1 \\
        --seeds 11,12,13

For each seed it runs the cell (a short window at the cell's own load)
and reads each compared number again on the same inputs with the
program's own lower-precision path switched on: ``precision="bf16"``,
for a fit (``repro.fit``) and for a served model (``pack_model``). That
is the control; it must come out above a limit. Read beside it, and not
required to fail:

* a fit whose ``fupdate`` kernel contracts f32 in Mosaic's default
  (one bf16 pass, the fault the program once had), planted by taking
  away the kernel's ``Precision.HIGHEST``;
* for a served model, the plain reference in the program's place at
  bf16x3 (``Precision.HIGH``) and at one bf16 pass.

Not part of a benchmark run.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import numpy as np  # noqa: E402

from bench import run  # noqa: E402
from bench.lib.cells import slab_spec, solution_numbers  # noqa: E402

LOWER = {"high_bf16x3": 3, "bf16_one_pass": 1}


def _worst(cfg: dict, fits: list) -> dict:
    numbers = [solution_numbers(cfg, X, res) for X, res in fits]
    return {k: max(n[k] for n in numbers) for k in numbers[0]}


def _refit(ctx, **kw) -> dict:
    import jax
    import repro
    cfg = ctx.config
    spec = slab_spec(cfg)
    fits = []
    for X, _ in ctx.kept["fits"]:
        res = repro.fit(X, spec, tol=cfg["tol"], **kw)
        jax.block_until_ready(res.f)
        fits.append((X, res))
    return _worst(cfg, fits)


def fupdate_one_pass(ctx) -> dict:
    """The window's fits again, with the ``fupdate`` kernel's dots at
    Mosaic's default precision."""
    import jax
    from repro.kernels.fupdate import kernel
    real = kernel.mxu_precision
    kernel.mxu_precision = lambda dtype: None
    jax.clear_caches()
    try:
        return _refit(ctx)
    finally:
        kernel.mxu_precision = real
        jax.clear_caches()


def fit_controls(ctx, planted: bool = True) -> dict:
    """Every compared number of the window's fits, under each control."""
    out = {"program_bf16": _refit(ctx, precision="bf16")}
    if planted:
        out["fupdate_one_pass"] = fupdate_one_pass(ctx)
    return out


def serving_controls(ctx) -> dict:
    from repro.core.ocssvm import OCSSVMModel
    from repro.serve import pack_model
    sm, queries = ctx.kept["model"], ctx.kept["queries"]
    out = {label: sm.compare(queries, [sm.decision(sm.reference(
        queries, passes))]) for label, passes in LOWER.items()}
    bf16 = pack_model(OCSSVMModel(
        gamma=sm.gamma, rho1=np.float32(sm.rho1), rho2=np.float32(sm.rho2),
        X=sm.T, spec=slab_spec(ctx.config)), precision="bf16")
    out["program_bf16"] = sm.compare(
        queries, [np.asarray(bf16.scorer().score(q)) for q in queries])
    return {label: {"kernel_sum_rel": v} for label, v in out.items()}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench, wl, devices = run.prepare(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = run.run_cell(bench, wl, seed=seed, seconds=args.seconds,
                           trace=False, devices=devices,
                           t_start=time.perf_counter())
        ctl = fit_controls(ctx) if "fits" in ctx.kept \
            else serving_controls(ctx)
        print(json.dumps({
            "seed": seed, "correct": ctx.correct,
            "program": {n: v for n, v, _ in ctx.checks},
            "limits": {n: lim for n, _, lim in ctx.checks},
            "control": ctl}), flush=True)


if __name__ == "__main__":
    main()
