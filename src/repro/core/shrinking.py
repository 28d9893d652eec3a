"""LIBSVM-style shrinking, adapted to fixed-shape JAX: a repack driver.

Classic shrinking skips bound-pinned coordinates inside the solver loop.
Under jit every vector op is full-m regardless of masks, so masking saves
nothing — instead this driver PHYSICALLY repacks the active set:

1. run the engine-backed blocked solver a bounded number of iterations on
   the full set,
2. freeze coordinates at a bound whose score keeps them there with margin
   (they cannot be part of any violating pair),
3. gather the active coordinates (size rounded up to a bucket to bound
   recompilation), fold the frozen coordinates' kernel contribution into a
   per-row ``f_offset``, and solve the small problem exactly
   (box bounds rescaled: nu' = nu * m_total / m_active keeps
   1/(nu1' m_active) == 1/(nu1 m_total)),
4. scatter back, verify KKT on the FULL set, repeat if anything at a
   bound woke up (the classic unshrink pass).

Per-iteration work in step 3 is O(m_active * d) instead of O(m * d) —
near convergence m_active is the support-vector count, typically a small
fraction of m. The reached optimum is the full-problem optimum (the final
full-set KKT check gates termination); tests assert objective parity.

Every inner solve routes through the shared engine (``solve_blocked`` is
an engine facade), so ``gram_mode="pallas"`` drives the fused Pallas
f-update inside the shrinking rounds too.

``solve_sharded_shrinking`` is the row-sharded composition of the same
idea: bounded *distributed* warm rounds (``solve_blocked_distributed``,
per-shard Pallas fupdate on the hot loop), per-shard freeze masks (one
fused pmax gives every shard the global movable-score extrema), and —
once the global active set fits under ``SINGLE_PASS_MAX`` — a gather of
the active rows to one shard followed by the LOCAL blocked solver on the
repacked problem, with the frozen shards' kernel contribution riding
along as ``f_offset``. Full-set KKT verification between rounds runs
sharded (``sharded_raw_scores``), so no step ever needs the O(m^2) Gram
or an unsharded O(m d) pass on one device.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.batched_smo import solve_blocked
from repro.core.engine import CollectiveLedger, MeshComm
from repro.core.engine.gram import SINGLE_PASS_MAX, raw_scores_blocked
from repro.core.engine.stats import violation as _violation
from repro.core.engine.types import SMOResult
from repro.core.ocssvm import (OCSSVMModel, SlabSpec, concrete_spec,
                               recover_rhos)
from repro.kernels.precision import round_to_tile

Array = jax.Array

__all__ = ["solve_blocked_shrinking", "solve_sharded_shrinking"]


def _bucket(n: int, m: int) -> int:
    """Round n up to a power-of-two-ish bucket (bounds recompiles)."""
    if n >= m:
        return m
    b = 1 << max(6, math.ceil(math.log2(max(n, 1))))
    return min(b, m)


def solve_blocked_shrinking(
    X: Array,
    spec: SlabSpec,
    *,
    P: int = 8,
    gram_mode: str = "on_the_fly",
    interpret: Optional[bool] = None,
    precision: str = "f32",
    tol: float = 1e-4,
    warm_iters: int = 200,
    max_rounds: int = 8,
    round_iters: int = 50_000,
    margin: float = 2.0,
    max_outer: Optional[int] = None,
    patience: int = 20,
    gamma0: Optional[Array] = None,
    warm=None,
) -> SMOResult:
    """max_outer caps the per-round iteration budget (alias of
    round_iters, so the blocked solvers' signature works here too);
    gamma0 warm-starts the phase-1 full-set solve. ``warm`` (an
    ``engine.WarmStart``) goes one further: the phase-1 solve seeds
    gamma AND reconciles its f-cache from the prior fit's scores with
    one fused rank-s sweep (``solve_blocked(warm=)``); later rounds
    proceed from wherever phase 1 lands, exactly as with gamma0."""
    if max_outer is not None:
        round_iters = min(round_iters, max_outer)
    m, d = X.shape
    X32 = jnp.asarray(X, jnp.float32)
    # Tile-round once up front: the repack driver's own KKT sweeps and
    # f_offset folds then see exactly the rows the inner low-precision
    # solves see (for "f32" this is the plain f32 cast). The RETURNED
    # model still carries the unrounded X32 — precision is an execution
    # detail of the solve, and every facade returns the same model data.
    Xf = round_to_tile(X32, precision)
    kernel = spec.kernel
    hi, lo = spec.upper(m), spec.lower(m)
    bnd = 1e-8 * (hi - lo)

    def _solve(Xs, sp, **kw):
        return solve_blocked(Xs, sp, P=P, gram_mode=gram_mode,
                             interpret=interpret, precision=precision,
                             tol=tol, patience=patience, **kw)

    # Host spans on the profiler's clock. fit.solve and fit.kkt_sweep end
    # at a host read the driver already makes, so they cover the device
    # work they dispatched; fit.repack and fit.rescore cover a dispatch.
    # Phase 1: bounded full-set warm solve.
    with TraceAnnotation("fit.solve"):
        res = _solve(Xf, spec, max_outer=warm_iters, gamma0=gamma0,
                     warm=warm)
        gamma = res.model.gamma
        converged = bool(res.converged)
    if converged:
        return res

    total_iters = int(res.iters)
    for _ in range(max_rounds):
        with TraceAnnotation("fit.kkt_sweep"):
            f = raw_scores_blocked(Xf, gamma, kernel)
            rho1, rho2 = recover_rhos(gamma, f, spec)
            v = _violation(gamma, f, rho1, rho2, hi=hi, lo=lo, m=m)
            n_viol = int(jnp.sum(v > tol))
        if n_viol <= 1:
            break

        with TraceAnnotation("fit.repack"):
            # Freeze coordinates pinned at a bound with margin: at hi the
            # KKT wants f <= lambda; it can never pair as the "down" end
            # of a violating pair if f is below every movable-up score by
            # margin.
            up_ok = gamma < hi - bnd
            dn_ok = gamma > lo + bnd
            m_up = jnp.min(jnp.where(up_ok, f, jnp.inf))
            m_dn = jnp.max(jnp.where(dn_ok, f, -jnp.inf))
            frozen_hi = (~up_ok) & (f < m_up - margin * tol)
            frozen_lo = (~dn_ok) & (f > m_dn + margin * tol)
            frozen_zero = (jnp.abs(gamma) < bnd) & (v <= tol * 0.5)
            frozen = (frozen_hi | frozen_lo | frozen_zero) & (v <= tol)

            active = np.asarray(~frozen)
            n_active = int(active.sum())
            shrink = 4 * P <= n_active < int(0.9 * m)
            if shrink:
                # Bucket the active size by waking the least-frozen
                # coordinates.
                n_b = _bucket(n_active, m)
                order = np.argsort(~active, kind="stable")  # active first
                idx = np.sort(order[:n_b])
                idx_j = jnp.asarray(idx)

                X_act = Xf[idx_j]
                g_act = gamma[idx_j]
                # Frozen contribution to the active rows' scores:
                f_act_full = f[idx_j]
                k_act = (kernel.cross(X_act, X_act) @ g_act
                         if n_b <= SINGLE_PASS_MAX
                         else raw_scores_blocked(X_act, g_act, kernel))
                f_offset = f_act_full - k_act
        if not shrink:
            # shrinking not profitable: finish on the full set
            with TraceAnnotation("fit.solve"):
                res = _solve(Xf, spec, max_outer=round_iters, gamma0=gamma)
                gamma = res.model.gamma
                total_iters += int(res.iters)
            break

        sub_spec = dataclasses.replace(
            spec, nu1=spec.nu1 * m / n_b, nu2=spec.nu2 * m / n_b)
        with TraceAnnotation("fit.solve"):
            sub = _solve(X_act, sub_spec, max_outer=round_iters,
                         gamma0=g_act, f_offset=f_offset)
            gamma = gamma.at[idx_j].set(sub.model.gamma)
            total_iters += int(sub.iters)

    with TraceAnnotation("fit.rescore"):
        f = raw_scores_blocked(Xf, gamma, kernel)
        rho1, rho2 = recover_rhos(gamma, f, spec)
        v = _violation(gamma, f, rho1, rho2, hi=hi, lo=lo, m=m)
        up_ok = gamma < hi - bnd
        dn_ok = gamma > lo + bnd
        gap = (jnp.max(jnp.where(dn_ok, f, -jnp.inf))
               - jnp.min(jnp.where(up_ok, f, jnp.inf)))
    model = OCSSVMModel(gamma=gamma, rho1=rho1, rho2=rho2, X=X32, spec=spec)
    return SMOResult(model=model, iters=jnp.asarray(total_iters),
                     n_viol=jnp.sum(v > tol).astype(jnp.int32),
                     max_viol=jnp.max(v), gap=gap,
                     converged=jnp.sum(v > tol) <= 1, f=f)


def _sharded_freeze_mask(gamma: Array, f: Array, v: Array, mesh: Mesh,
                         data_axes: Tuple[str, ...], *, hi: float,
                         lo: float, tol: float, margin: float, m: int,
                         ledger: Optional[CollectiveLedger] = None
                         ) -> Array:
    """The freeze decision of ``solve_blocked_shrinking``, tracked per
    shard: each shard classifies ITS rows from its local gamma/f/v slices;
    the only cross-shard facts needed are the two global movable-score
    extrema, which cost one fused pmax (billed to the ledger's "sweep"
    phase). Returns the global frozen mask (padded tail rows report
    frozen — they are never part of the active set). The compiled
    shard function is cached like the solve/sweep entry points, so
    repeated repack rounds of the same geometry trace once."""
    from repro.core.distributed_smo import _cached_shard_fn

    bnd = 1e-8 * (hi - lo)
    sizes = tuple(int(mesh.shape[ax]) for ax in data_axes)
    n_shards = 1
    for s_ in sizes:
        n_shards *= s_
    m_pad = ((m + n_shards - 1) // n_shards) * n_shards
    gp = jnp.pad(gamma.astype(jnp.float32), (0, m_pad - m))
    fp = jnp.pad(f.astype(jnp.float32), (0, m_pad - m))
    vp = jnp.pad(v.astype(jnp.float32), (0, m_pad - m))
    validp = jnp.arange(m_pad) < m
    if ledger is not None:
        ledger.set_phase("sweep")

    def build():
        comm = MeshComm(data_axes, sizes=sizes, ledger=ledger)

        def local_freeze(g_l, f_l, v_l, valid_l):
            up_ok = valid_l & (g_l < hi - bnd)
            dn_ok = valid_l & (g_l > lo + bnd)
            # One pmax of [-(min movable-up f), max movable-down f]: the
            # mins ride negated, exactly like the fused solver stats.
            pm = comm.pmax(jnp.stack([
                -jnp.min(jnp.where(up_ok, f_l, jnp.inf)),
                jnp.max(jnp.where(dn_ok, f_l, -jnp.inf)),
            ]))
            m_up, m_dn = -pm[0], pm[1]
            frozen_hi = (~up_ok) & (f_l < m_up - margin * tol)
            frozen_lo = (~dn_ok) & (f_l > m_dn + margin * tol)
            frozen_zero = (jnp.abs(g_l) < bnd) & (v_l <= tol * 0.5)
            frozen = (frozen_hi | frozen_lo | frozen_zero) & (v_l <= tol)
            return frozen | ~valid_l

        dspec = P(data_axes)
        return jax.jit(shard_map(local_freeze, mesh=mesh,
                                 in_specs=(dspec, dspec, dspec, dspec),
                                 out_specs=dspec, check_vma=False))

    shard_fn = _cached_shard_fn(
        ("freeze", mesh, tuple(data_axes), m, hi, lo, tol, margin,
         None if ledger is None else id(ledger)), build)
    return shard_fn(gp, fp, vp, validp)[:m]


def solve_sharded_shrinking(
    X: Array,
    spec: SlabSpec,
    mesh: Mesh,
    *,
    data_axes: Tuple[str, ...] = ("data",),
    P_pairs: int = 8,
    gram_mode: str = "on_the_fly",
    interpret: Optional[bool] = None,
    precision: str = "f32",
    tol: float = 1e-4,
    warm_iters: int = 200,
    max_rounds: int = 8,
    round_iters: int = 50_000,
    margin: float = 2.0,
    max_outer: Optional[int] = None,
    patience: int = 20,
    gamma0: Optional[Array] = None,
    warm=None,
    gather_max: Optional[int] = None,
    rho_every: int = 1,
    ledger: Optional[CollectiveLedger] = None,
) -> SMOResult:
    """Shrinking repack driver for a ROW-SHARDED problem.

    Rounds alternate between bounded distributed solves on the mesh and —
    as soon as the global active set fits under ``gather_max`` (default
    ``SINGLE_PASS_MAX``) — a gather of the active rows to one shard and a
    LOCAL blocked repack solve (``gram_mode`` picks its provider; the
    distributed rounds always run the per-shard Pallas fupdate). The
    full-set KKT sweep between rounds is sharded, so per-device memory
    stays O(m d / n_shards) throughout.

    ``ledger`` threads through to every distributed solve and sharded
    score sweep for collective-bytes accounting.
    """
    # Imported here, not at module top: distributed_smo imports this
    # module's sibling facades' dependency chain (engine -> gram) and the
    # shrinking driver is the only piece that needs the reverse edge.
    from repro.core.distributed_smo import (sharded_raw_scores,
                                            solve_blocked_distributed)

    if max_outer is not None:
        round_iters = min(round_iters, max_outer)
    if gather_max is None:
        gather_max = SINGLE_PASS_MAX
    # Concrete (hashable) spec up front: the distributed rounds and the
    # sweeps key their compiled shard functions on it, and the per-shard
    # Pallas fupdate specializes on the kernel parameters anyway.
    spec = concrete_spec(spec)
    m, d = X.shape
    X32 = jnp.asarray(X, jnp.float32)
    # Same invariant as the local driver: the repack sweeps and f_offset
    # folds see exactly the tile-rounded rows the solves see.
    Xf = round_to_tile(X32, precision)
    kernel = spec.kernel
    hi, lo = spec.upper(m), spec.lower(m)
    bnd = 1e-8 * (hi - lo)

    def _dist(g0, iters, w=None):
        return solve_blocked_distributed(
            X32, spec, mesh, data_axes=data_axes, P_pairs=P_pairs, tol=tol,
            max_outer=iters, patience=patience, precision=precision,
            interpret=interpret, gamma0=g0, rho_every=rho_every,
            ledger=ledger, warm=w)

    def _scores(g):
        return sharded_raw_scores(Xf, g, kernel, mesh, data_axes=data_axes,
                                  precision=precision, ledger=ledger)

    # Host spans as in the local driver.
    # Phase 1: bounded full-set distributed warm solve.
    with TraceAnnotation("fit.solve"):
        res = _dist(gamma0, warm_iters, warm)
        gamma = res.model.gamma
        converged = bool(res.converged)
    if converged:
        return res

    total_iters = int(res.iters)
    for _ in range(max_rounds):
        with TraceAnnotation("fit.kkt_sweep"):
            f = _scores(gamma)
            rho1, rho2 = recover_rhos(gamma, f, spec)
            v = _violation(gamma, f, rho1, rho2, hi=hi, lo=lo, m=m)
            n_viol = int(jnp.sum(v > tol))
        if n_viol <= 1:
            break

        with TraceAnnotation("fit.repack"):
            frozen = _sharded_freeze_mask(gamma, f, v, mesh, data_axes,
                                          hi=hi, lo=lo, tol=tol,
                                          margin=margin, m=m, ledger=ledger)
            active = np.asarray(~frozen)
            n_active = int(active.sum())
            shrink = 4 * P_pairs <= n_active < int(0.9 * m)
            gather = shrink and n_active <= gather_max
            if gather:
                # The global active set fits on one shard: gather it and
                # repack (bucketed to bound recompiles, waking the
                # least-frozen rows to fill the bucket).
                n_b = _bucket(n_active, m)
                order = np.argsort(~active, kind="stable")  # active first
                idx = np.sort(order[:n_b])
                idx_j = jnp.asarray(idx)

                X_act = Xf[idx_j]
                g_act = gamma[idx_j]
                k_act = (kernel.cross(X_act, X_act) @ g_act
                         if n_b <= SINGLE_PASS_MAX
                         else raw_scores_blocked(X_act, g_act, kernel))
                f_offset = f[idx_j] - k_act
        if not gather:
            # Shrinking not profitable: finish distributed on the full
            # set. Or the active set is still at sharded scale: another
            # bounded distributed round, warm-started, then re-sweep.
            with TraceAnnotation("fit.solve"):
                res = _dist(gamma, round_iters)
                gamma = res.model.gamma
                total_iters += int(res.iters)
            if shrink:
                continue
            break

        # Continue with the LOCAL blocked solver on the repacked problem.
        sub_spec = dataclasses.replace(
            spec, nu1=spec.nu1 * m / n_b, nu2=spec.nu2 * m / n_b)
        with TraceAnnotation("fit.solve"):
            sub = solve_blocked(X_act, sub_spec, P=P_pairs,
                                gram_mode=gram_mode, interpret=interpret,
                                precision=precision, tol=tol,
                                max_outer=round_iters, gamma0=g_act,
                                f_offset=f_offset, patience=patience)
            gamma = gamma.at[idx_j].set(sub.model.gamma)
            total_iters += int(sub.iters)

    # Final full-set verification, sharded.
    with TraceAnnotation("fit.rescore"):
        f = _scores(gamma)
        rho1, rho2 = recover_rhos(gamma, f, spec)
        v = _violation(gamma, f, rho1, rho2, hi=hi, lo=lo, m=m)
        up_ok = gamma < hi - bnd
        dn_ok = gamma > lo + bnd
        gap = (jnp.max(jnp.where(dn_ok, f, -jnp.inf))
               - jnp.min(jnp.where(up_ok, f, jnp.inf)))
    model = OCSSVMModel(gamma=gamma, rho1=rho1, rho2=rho2, X=X32, spec=spec)
    return SMOResult(model=model, iters=jnp.asarray(total_iters),
                     n_viol=jnp.sum(v > tol).astype(jnp.int32),
                     max_viol=jnp.max(v), gap=gap,
                     converged=jnp.sum(v > tol) <= 1, f=f)
