#!/usr/bin/env python3
"""Bring-up smoke run of the one-class slab SVM on a TPU: fit -> warm
refresh -> serve, once, through the entry points a user calls.

    python chip_smoke.py                 # one chip, m = 65,536 rows
    python chip_smoke.py --m 284807      # the full-size deployment
    python chip_smoke.py --four-chips    # sharded fit + scorer, 4 chips

Deployment: event-level fraud scoring at the width of the ULB
credit-card fraud set (284,807 rows x 30 features, 0.17% positives).
Data is ``make_toy(PRNGKey(seed), m, d=30)``, made from ``--seed``.
Cut: m = 65,536 training rows by default (``--m``), 23% of the source's
284,807; the width (30) is the source's own. That m is above the
shrinking threshold and the single-pass Gram size, so on a TPU
``strategy="auto"`` runs the shrinking driver over the Pallas
``fupdate`` hot loop.

Phases, one process, each fatal on failure:

1. device gate: a TPU, no ``JAX_PLATFORMS=cpu``, no ``REPRO_INTERPRET``;
2. train: ``repro.fit`` converges; feasibility; KKT violation recomputed
   from f = K @ gamma in blocks of ``kernels/gram/ref.py``; an m=2048
   fit at the same width against ``solve_qp``'s objective;
3. warm refresh: 5% appended rows through ``repro.fit_update``;
4. serve: the refreshed slab registered in a ``ModelRegistry`` and
   scored through ``AdmissionController`` at f32 and bf16 against
   ``kernels/decision/ref.py``, with zero compiles in the request loop;
5. kernel check: the ``fupdate`` and ``decision_packed`` launches at the
   run's shapes lower to a compiled Mosaic kernel (``tpu_custom_call``).

``--four-chips`` runs only the sharded path and what it is compared
with: ``repro.fit(strategy="sharded")`` on a (4, 1) mesh against the
one-chip ``strategy="shrinking"`` fit, the shard placement, the
per-iteration collective bytes, and sharded against local scoring.

Times are one run each, compilation included, and not a benchmark. The
last line of stdout is one JSON object naming the device. The compile
cache lives in ``$JAX_COMPILATION_CACHE_DIR`` when set, else in
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import repro  # noqa: E402
from repro.core import SlabSpec, dual_objective, rbf, solve_qp  # noqa: E402
from repro.core.engine import (CollectiveLedger,  # noqa: E402
                               artifact_from_result)
from repro.core.engine.stats import violation  # noqa: E402
from repro.core.ocssvm import (dual_objective_matfree,  # noqa: E402
                               recover_rhos)
from repro.data import make_toy  # noqa: E402
from repro.kernels.decision.ops import decision_packed  # noqa: E402
from repro.kernels.decision.ref import decision_ref  # noqa: E402
from repro.kernels.fupdate.ops import fupdate  # noqa: E402
from repro.kernels.gram.ref import gram_ref  # noqa: E402
from repro.kernels.precision import truth_tolerance  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_solver_mesh  # noqa: E402
from repro.serve import (BUCKETS, AdmissionController,  # noqa: E402
                         ModelRegistry, pack_model)

# Bound on the max KKT violation recomputed from an independent
# f = K @ gamma, in score units: 2x the solve tolerance covers the
# solver's own <=1-coordinate convergence slack plus f32 drift between
# its f-cache and the fresh pass. CPU rehearsal at m=65,536, d=30:
# 5.3e-4, and 6.0e-4 with the solver's f32 matmuls rounded to one bf16
# pass as XLA runs them on a TPU.
KKT_BOUND_FACTOR = 2.0
# Documented objective agreement of the engine with the QP baseline
# (docs: ~2e-3 abs on toy sets). At this width the objective itself is
# ~4e-3, so the fit must also agree to 2% relative (the m=2048 fit stops
# at TOL; CPU rehearsal: 0.77%).
QP_OBJ_ATOL = 2e-3
QP_OBJ_RTOL = 0.02
# Feasibility: |sum(gamma) - (1 - eps)| in f32 over m coordinates.
SUM_ATOL = 1e-4
TOL = 1e-3
# References run at full f32: on a TPU, XLA's default precision for an
# f32 matmul is a single bf16 pass, which a reference must not inherit.
EXACT = "highest"
D = 30
SOURCE_ROWS = 284_807
REQUEST_ROWS = (1, 8, 64, 300, 4096)
REF_ROWS = 256
PRECISIONS = ("f32", "bf16")


class SmokeFailure(SystemExit):
    """A failed phase: exits non-zero with the message, prints no result."""

    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAIL: {msg}")


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def device_gate(need: int):
    env = os.environ.get("JAX_PLATFORMS", "")
    platforms = {p.strip() for p in env.split(",") if p.strip()}
    check(platforms != {"cpu"},
          f"JAX_PLATFORMS={env!r} names only the CPU; this run needs a TPU")
    check(os.environ.get("REPRO_INTERPRET", "").strip().lower()
          not in ("1", "true", "on"),
          "REPRO_INTERPRET forces Pallas interpret mode; unset it")
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: jax.devices()[0] is {devs[0].platform}")
    check(len(devs) >= need, f"need {need} TPU devices, found {len(devs)}")
    return devs


class Run:
    """Shared state of one smoke run: device label, timers, spec."""

    def __init__(self, args, devs):
        self.args = args
        self.devs = devs
        self.kind = devs[0].device_kind
        self.label = f"[{self.kind} x{len(devs)}]"
        self.spec = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5,
                             kernel=rbf(gamma=1.0 / D))
        self.key = jax.random.PRNGKey(args.seed)

    def say(self, msg: str) -> None:
        print(f"{self.label} {msg}", flush=True)

    def timed(self, fn, *a, **kw):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*a, **kw))
        return out, time.perf_counter() - t0


def ref_scores(X, gamma, kernel, block: int = 1024):
    """f = K @ gamma from ``gram_ref`` row blocks (no solver code)."""
    m = X.shape[0]
    nblk = -(-m // block)
    Xp = jnp.pad(X, ((0, nblk * block - m), (0, 0)))
    g = jnp.asarray(gamma, jnp.float32)
    rows = jax.jit(lambda xb, X, g: gram_ref(
        xb, X, kind=kernel.name, gamma=kernel.gamma, coef0=kernel.coef0,
        degree=kernel.degree) @ g)
    with jax.default_matmul_precision(EXACT):
        return jnp.concatenate([rows(Xp[i * block:(i + 1) * block], X, g)
                                for i in range(nblk)])[:m]


def check_solution(run: Run, X, res, what: str) -> float:
    """Convergence, feasibility and the recomputed KKT violation."""
    spec = run.spec
    m = X.shape[0]
    check(bool(res.converged), f"{what}: not converged "
          f"(n_viol={int(res.n_viol)}, max_viol={float(res.max_viol):.3g})")
    gamma = res.model.gamma
    g = np.asarray(gamma, np.float64)
    hi, lo = spec.upper(m), spec.lower(m)
    check(abs(g.sum() - spec.total()) <= SUM_ATOL,
          f"{what}: sum(gamma)={g.sum():.7f} != {spec.total()}")
    box = 1e-6 * (hi - lo)
    check(g.max() <= hi + box and g.min() >= lo - box,
          f"{what}: gamma outside [{lo:.3g}, {hi:.3g}]: "
          f"[{g.min():.3g}, {g.max():.3g}]")
    f = ref_scores(jnp.asarray(X, jnp.float32), gamma, spec.kernel)
    rho1, rho2 = recover_rhos(gamma, f, spec)
    v = violation(gamma, f, rho1, rho2, hi=hi, lo=lo, m=m)
    kkt = float(jnp.max(v))
    bound = KKT_BOUND_FACTOR * TOL
    check(kkt <= bound, f"{what}: recomputed KKT violation {kkt:.3g} > "
          f"bound {bound:.3g}")
    return kkt


def phase_train(run: Run):
    m = run.args.m
    X, _ = make_toy(run.key, m, d=D)
    res, dt = run.timed(repro.fit, X, run.spec, tol=TOL)
    kkt = check_solution(run, X, res, "fit")
    run.say(f"train: repro.fit m={m} d={D} (cut from {SOURCE_ROWS} rows) "
            f"converged iters={int(res.iters)} kkt_max={kkt:.3g} "
            f"wall_s={dt:.3f} (one run, compile included)")

    Xs, _ = make_toy(jax.random.fold_in(run.key, 1), 2048, d=D)
    small, _ = run.timed(repro.fit, Xs, run.spec, tol=TOL)
    check_solution(run, Xs, small, "fit m=2048")
    with jax.default_matmul_precision(EXACT):
        K = run.spec.kernel.gram(Xs.astype(jnp.float32))
        qp = solve_qp(Xs, run.spec, max_iters=60_000, tol=1e-10)
        obj, obj_qp = (float(dual_objective(small.model.gamma, K)),
                       float(qp.objective))
    diff = abs(obj - obj_qp)
    check(diff <= QP_OBJ_ATOL and diff <= QP_OBJ_RTOL * abs(obj_qp),
          f"m=2048 objective {obj:.6f} vs solve_qp {obj_qp:.6f}")
    run.say(f"train: m=2048 objective {obj:.6f} vs solve_qp {obj_qp:.6f} "
            f"(|diff|={diff:.2e}, {diff / abs(obj_qp):.2%})")
    return X, res


def phase_refresh(run: Run, X, res):
    m_new = max(1, run.args.m // 20)
    X_app, _ = make_toy(jax.random.fold_in(run.key, 2), m_new, d=D)
    X2 = jnp.concatenate([X, X_app])
    stats = {}
    res2, dt = run.timed(repro.fit_update, res, X2, tol=TOL,
                         stats_out=stats)
    check(stats.get("mode") == "warm", f"refresh ran {stats.get('mode')}")
    kkt = check_solution(run, X2, res2, "fit_update")
    run.say(f"refresh: +{m_new} rows -> m={X2.shape[0]} warm "
            f"iters={int(res2.iters)} vs cold iters={int(res.iters)} "
            f"kkt_max={kkt:.3g} wall_s={dt:.3f} (one run, compile "
            "included)")
    return X2, res2


class CompileCounter:
    """Counts programs lowered for compilation while active (a
    persistent-cache hit still lowers, so cache state does not hide a
    retrace)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.n = 0
        self.on = False

    def __call__(self, name, _secs, **_kw):
        if self.on and name == self.EVENT:
            self.n += 1


def phase_serve(run: Run, X2, res2):
    registry = ModelRegistry()
    art = artifact_from_result(res2)
    for p in PRECISIONS:
        # The registry fits on first use; warm-started from the refreshed
        # fit's artifact over the same rows, that costs one reconcile.
        registry.register(f"fraud-{p}", X2, run.spec, tol=TOL,
                          precision=p, warm_start=art)
    ctrl = AdmissionController(registry)
    t0 = time.perf_counter()
    for name in registry.names():
        ctrl.service(name).scorer.warmup()
    run.say(f"serve: {len(registry.names())} models fitted, packed and "
            f"warmed in {time.perf_counter() - t0:.3f} s (one run)")

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    reqs = [np.asarray(make_toy(jax.random.fold_in(run.key, 100 + i), n,
                                d=D)[0]) for i, n in enumerate(REQUEST_ROWS)]
    counter.on = True
    t0 = time.perf_counter()
    out = {}
    for name in registry.names():
        one = [ctrl.submit(name, q) for q in reqs[:1]]
        ctrl.drain()
        for q in reqs[1:]:           # one flush per request: each bucket
            one.append(ctrl.submit(name, q))
            ctrl.drain()
        burst = [ctrl.submit(name, q) for q in reqs]   # one coalesced window
        ctrl.drain()
        out[name] = ([h.result() for h in one], [h.result() for h in burst])
    loop_s = time.perf_counter() - t0
    counter.on = False
    check(counter.n == 0, f"{counter.n} compiles inside the request loop")

    for name in registry.names():
        sm = registry.get(name)
        kern = sm.spec.kernel
        worst = ref_max = 0.0
        for i, q in enumerate(reqs):
            # In query blocks: at the source's 299k support rows a
            # 4,096-row kernel block alone would take 4.9 GB.
            with jax.default_matmul_precision(EXACT):
                ref = np.concatenate([np.asarray(decision_ref(
                    jnp.asarray(q[j:j + REF_ROWS]), sm.model.X,
                    sm.model.gamma, sm.rho1, sm.rho2, kind=kern.name,
                    gamma=kern.gamma, coef0=kern.coef0,
                    degree=kern.degree))
                    for j in range(0, q.shape[0], REF_ROWS)])
            tol = truth_tolerance(sm.precision, ref)
            for got in (out[name][0][i], out[name][1][i]):
                got = np.asarray(got)
                check(got.shape == ref.shape and np.all(np.isfinite(got)),
                      f"{name}: {q.shape[0]}-row scores malformed")
                err = np.abs(got - ref)
                worst = max(worst, float(err.max()))
                ref_max = max(ref_max, float(np.abs(ref).max()))
                check(np.all(err <= tol["atol"] + tol["rtol"] * np.abs(ref)),
                      f"{name}: {q.shape[0]}-row scores off the reference "
                      f"by up to {err.max():.3g}")
        run.say(f"serve: {name} n_sv={sm.n_sv} requests {REQUEST_ROWS} "
                f"(single + one coalesced window) match decision_ref "
                f"within truth_tolerance({sm.precision!r}): max |diff| "
                f"{worst:.3g}, max |ref| {ref_max:.3g}")
    run.say(f"serve: {2 * len(REQUEST_ROWS) * len(PRECISIONS)} requests in "
            f"{loop_s:.3f} s (one run, not a benchmark), compiles in the "
            f"request loop: {counter.n}")
    return registry


def phase_kernels(run: Run, X2, registry):
    m = X2.shape[0]
    kern = run.spec.kernel
    S = jax.ShapeDtypeStruct
    lowered = {
        "fupdate": jax.jit(partial(fupdate, kernel=kern)).lower(
            S((m, D), jnp.float32), S((16, D), jnp.float32),
            S((16,), jnp.float32), S((m,), jnp.float32)),
    }
    for name in registry.names():
        sm = registry.get(name)
        lowered[f"decision_packed[{sm.precision}]"] = jax.jit(partial(
            decision_packed, kernel=sm.spec.kernel, tm=256, tn=sm.tn,
            precision=sm.precision)).lower(
            S((BUCKETS[-1], sm.t_pad.shape[1]), jnp.float32), sm.t_pad,
            sm.gamma_pad, sm.t_norms, sm.rho1, sm.rho2)
    for name, low in lowered.items():
        check("tpu_custom_call" in low.as_text(),
              f"{name}: no compiled Mosaic kernel (tpu_custom_call) in "
              "its lowering")
    run.say(f"kernels: {', '.join(lowered)} lower to tpu_custom_call "
            f"(compiled, not interpreted) at m={m}, d={D}")


def phase_four_chips(run: Run):
    m = run.args.m
    devs = run.devs[:4]
    mesh, axes = make_solver_mesh(devices=devs)
    check(mesh.devices.size == 4, f"solver mesh {dict(mesh.shape)}")
    X, _ = make_toy(run.key, m, d=D)
    Xs = jax.device_put(X, NamedSharding(mesh, P(axes, None)))
    placed = {s.device for s in Xs.addressable_shards}
    rows = {s.data.shape[0] for s in Xs.addressable_shards}
    check(len(placed) == 4 and rows == {m // 4},
          f"X shards on {len(placed)} devices, rows per shard {rows}")
    ledger = CollectiveLedger()
    sh, dt_sh = run.timed(repro.fit, Xs, run.spec, strategy="sharded",
                          mesh=mesh, data_axes=axes, tol=TOL, ledger=ledger)
    f_devs = {s.device for s in sh.f.addressable_shards}
    check(len(f_devs) == 4, f"sharded f-cache on {len(f_devs)} devices")
    X0 = jax.device_put(X, devs[0])     # the one-chip reference
    loc, dt_loc = run.timed(repro.fit, X0, run.spec, strategy="shrinking",
                            tol=TOL)
    for what, r in (("sharded", sh), ("one-chip", loc)):
        check(bool(r.converged), f"{what} fit not converged")
    kern = run.spec.kernel
    g_sh = jax.device_put(sh.model.gamma, devs[0])
    with jax.default_matmul_precision(EXACT):
        obj_sh = float(dual_objective_matfree(g_sh, X0, kern))
        obj_loc = float(dual_objective_matfree(loc.model.gamma, X0, kern))
    # bands of tests/test_distributed.py: per-dtype truth tolerance with
    # the solver-convergence floor of 5e-3
    tol_o = truth_tolerance("f32", np.asarray([obj_loc]))
    check(abs(obj_sh - obj_loc) <= max(tol_o["atol"], 5e-3)
          + tol_o["rtol"] * abs(obj_loc),
          f"objective sharded {obj_sh:.6f} vs one-chip {obj_loc:.6f}")
    rho_sh = np.asarray([float(sh.model.rho1), float(sh.model.rho2)])
    rho_loc = np.asarray([float(loc.model.rho1), float(loc.model.rho2)])
    tol_r = truth_tolerance("f32", rho_loc)
    check(np.all(np.abs(rho_sh - rho_loc) <= max(tol_r["atol"], 5e-3)
                 + tol_r["rtol"] * np.abs(rho_loc)),
          f"rho sharded {rho_sh} vs one-chip {rho_loc}")
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    P_pairs = 8
    budget = 4 * n_shards * P_pairs * (D + 4) * 4 + 256
    it_bytes = ledger.iteration_bytes
    check(0 < it_bytes <= budget,
          f"collective bytes per iteration {it_bytes} vs budget {budget}")
    run.say(f"four-chips: mesh {dict(mesh.shape)} X rows/shard {m // 4} on "
            f"{len(placed)} devices; sharded iters={int(sh.iters)} "
            f"wall_s={dt_sh:.3f}, one-chip iters={int(loc.iters)} "
            f"wall_s={dt_loc:.3f} (one run each, compile included)")
    run.say(f"four-chips: objective {obj_sh:.6f} vs {obj_loc:.6f} "
            f"(|diff|={abs(obj_sh - obj_loc):.2e}), rho {rho_sh.tolist()} vs "
            f"{rho_loc.tolist()}, collective bytes/iter {it_bytes} <= "
            f"{budget}")

    sm = pack_model(loc.model)
    q = np.asarray(make_toy(jax.random.fold_in(run.key, 7), 4096, d=D)[0])
    local = np.asarray(sm.scorer().score(q))
    scorer = sm.scorer(mesh=mesh)
    scorer.warmup()
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    counter.on = True
    sharded = np.asarray(scorer.score(q))
    counter.on = False
    check(counter.n == 0, f"{counter.n} compiles in warm sharded scoring")
    tol_s = truth_tolerance("f32", local)
    err = np.abs(sharded - local)
    check(np.all(err <= tol_s["atol"] + tol_s["rtol"] * np.abs(local)),
          f"sharded scoring off local by up to {err.max():.3g}")
    run.say(f"four-chips: sharded scoring of {q.shape[0]} rows over "
            f"{n_shards} devices matches local (max |diff|={err.max():.2e}),"
            f" compiles after warmup: {counter.n}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--m", type=int, default=65_536,
                    help="training rows (source: 284,807)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fit + scorer on 4 chips")
    args = ap.parse_args(argv)
    devs = device_gate(4 if args.four_chips else 1)

    cache_dir = use_compile_cache()
    run = Run(args, devs)
    run.say(f"device: platform={devs[0].platform} kind={run.kind} "
            f"count={len(devs)} compile_cache={cache_dir}")
    if args.four_chips:
        phase_four_chips(run)
    else:
        X, res = phase_train(run)
        X2, res2 = phase_refresh(run, X, res)
        registry = phase_serve(run, X2, res2)
        phase_kernels(run, X2, registry)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": run.kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
