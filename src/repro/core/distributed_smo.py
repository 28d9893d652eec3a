"""Data-parallel blocked SMO over the production mesh (engine facade).

The training set X, the dual vector gamma, and the f-cache are sharded by
rows across the mesh's data axes (("data",) single-pod, ("pod","data")
multi-pod — ``repro.launch.mesh.make_solver_mesh`` builds both from the
launch layer). The whole solve is the SAME engine driver as the
single-device solvers, run inside ``shard_map`` with the sharded
provider/selector:

1. ``ShardedBlockSelector``: every shard proposes its local top-P grow /
   top-P shrink candidates; one ``all_gather`` of the tiny packed
   candidate set (O(P) scalars + P*d floats per shard — independent of m)
   makes selection *globally identical* on every device,
2. the Gauss-Seidel pair solve runs replicated (2P x 2P block),
3. ``ShardedGram`` applies the rank-2P f update to the local rows only —
   no communication — through the SAME fused Pallas ``fupdate`` kernel as
   the single-device pallas provider (interpret mode on CPU), and
   scatters delta-gamma into the local slice,
4. rho recovery / convergence tests are the fused-stats reductions
   (``engine.stats.solver_stats_prev``): ONE psum of a stacked vector plus
   ONE pmax per iteration instead of 12 small collectives. At production
   scale each small all-reduce is latency-bound (~10 us on multi-hop ICI),
   so this drops the per-iteration critical path ~6x (hillclimb 3,
   EXPERIMENTS.md).

Per-iteration communication is O(P d) — independent of m — which is what
makes the paper's "scales to large training sets" claim hold at pod scale:
compute per shard is O(m_local d), halving with every doubling of shards.
Pass a ``CollectiveLedger`` to get that bill itemized at trace time
(``ledger.iteration_bytes`` — see docs/distributed.md).

The un-sharded reference (`solve_blocked`) produces identical selections
on one device; tests assert distributed == single-device optima.
"""
from __future__ import annotations

from collections import OrderedDict
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import engine
from repro.core.engine.types import SMOResult
from repro.core.ocssvm import (OCSSVMModel, SlabSpec, concrete_spec,
                               feasible_init)
from repro.kernels.precision import round_to_tile

Array = jax.Array

__all__ = ["solve_blocked_distributed", "sharded_raw_scores"]


def _axis_rank(data_axes: Sequence[str], sizes: Sequence[int]) -> Array:
    rank = jnp.zeros((), jnp.int32)
    for ax, size in zip(data_axes, sizes):
        rank = rank * size + jax.lax.axis_index(ax)
    return rank


def _shard_geometry(m: int, mesh: Mesh, data_axes: Tuple[str, ...]):
    """(sizes, n_shards, m_pad, m_local) for row-sharding m over the
    mesh's data axes."""
    sizes = tuple(int(mesh.shape[ax]) for ax in data_axes)
    n_shards = 1
    for s_ in sizes:
        n_shards *= s_
    m_pad = ((m + n_shards - 1) // n_shards) * n_shards
    return sizes, n_shards, m_pad, m_pad // n_shards


# Compiled sharded entry points, keyed on everything that shapes the
# trace: mesh, axes, problem shape, spec, solver knobs, precision,
# interpret, and the ledger identity (a cache hit re-runs the compiled
# collectives WITHOUT re-recording — the ledger is a trace-time hook).
# Without this cache every shrinking round would re-trace and recompile
# the whole distributed while-loop solver (the local driver pays one
# compile per bucket shape via the module-level jit in batched_smo; this
# is the sharded counterpart). Bounded LRU: each entry pins a compiled
# executable (and, through the MeshComm closure, its ledger), so a
# workload handing a fresh ledger per fit call must not grow this
# forever — old entries are evicted, and with them the pinned ledgers.
_SHARD_FN_CACHE = OrderedDict()
_SHARD_FN_CACHE_MAX = 32


def _cached_shard_fn(key, build):
    try:
        hash(key)
    except TypeError:       # e.g. a kernel carrying traced/array params
        return build()
    fn = _SHARD_FN_CACHE.get(key)
    if fn is None:
        fn = _SHARD_FN_CACHE[key] = build()
    else:
        _SHARD_FN_CACHE.move_to_end(key)
    while len(_SHARD_FN_CACHE) > _SHARD_FN_CACHE_MAX:
        _SHARD_FN_CACHE.popitem(last=False)
    return fn


def _place(mesh: Mesh, spec: P, *arrays):
    """Explicit input shardings: lay each operand out row-sharded BEFORE
    the shard_map call, so entering the solve never implies a resharding
    transfer (the launch layer hands fit already-placed global arrays).
    Under an outer jit (the pod-scale benchmark lowers the whole facade)
    the placement becomes a sharding constraint on the traced value."""
    sharding = NamedSharding(mesh, spec)
    return tuple(
        jax.lax.with_sharding_constraint(a, sharding)
        if isinstance(a, jax.core.Tracer) else jax.device_put(a, sharding)
        for a in arrays)


def solve_blocked_distributed(
    X: Array,
    spec: SlabSpec,
    mesh: Mesh,
    *,
    data_axes: Tuple[str, ...] = ("data",),
    P_pairs: int = 8,
    tol: float = 1e-4,
    max_outer: int = 50_000,
    patience: int = 20,
    fused_stats: bool = True,
    rho_every: int = 1,
    precision: str = "f32",
    interpret: Optional[bool] = None,
    gamma0: Optional[Array] = None,
    warm=None,
    ledger: Optional[engine.CollectiveLedger] = None,
) -> SMOResult:
    """Solve the OCSSVM dual with X row-sharded over ``data_axes``.

    fused_stats: retained for signature compatibility. The engine's
    sharded statistics path (``solver_stats_prev``) IS the fused
    implementation — 2 collectives per iteration — and is always used;
    there is no slower unfused path to fall back to anymore.
    rho_every=k recomputes rho1/rho2 every k iterations (the margin-SV
    averages drift slowly near convergence; the paper recomputes each
    step). precision: Gram tile-input dtype — the sharded provider
    applies the same tile rounding as the local providers, so a
    distributed solve matches its single-device counterpart at any
    precision. interpret: force the per-shard Pallas fupdate kernel into
    interpret mode (None auto-detects: interpret on CPU, compiled on
    TPU). gamma0 warm-starts the solve (the sharded shrinking driver
    re-enters here between repack rounds). warm: an
    ``engine.WarmStart`` — gamma0/f_seed enter row-sharded like every
    data vector, the (small) correction set rides REPLICATED, and each
    shard reconciles its own f slice with one local fused fupdate
    sweep: the warm init costs ZERO collectives (the cold init
    all-gathers X and gamma). Mutually exclusive with gamma0. ledger: a
    ``CollectiveLedger`` populated at trace time with every collective's
    per-device payload, split into "init" (once) and "iter"
    (per-iteration) phases.
    """
    del fused_stats
    if warm is not None and gamma0 is not None:
        raise ValueError("pass warm= or gamma0=, not both")
    # The per-shard Pallas fupdate kernel specializes on concrete kernel
    # parameters (same rule as the local pallas provider).
    spec = concrete_spec(spec)
    m, d = X.shape
    kernel = spec.kernel
    sizes, n_shards, m_pad, m_local = _shard_geometry(m, mesh, data_axes)

    Xf = jnp.pad(X.astype(jnp.float32), ((0, m_pad - m), (0, 0)))
    valid = jnp.arange(m_pad) < m
    if warm is not None:
        g0 = jnp.pad(warm.gamma0.astype(jnp.float32), (0, m_pad - m))
        # f_seed shards exactly like gamma; the pad rows' seed value is
        # irrelevant (valid masks them everywhere, same as cold init).
        f_seed = jnp.pad(warm.f_seed.astype(jnp.float32), (0, m_pad - m))
        x_corr, d_corr = warm.x_corr, warm.delta
    else:
        g0 = (feasible_init(m, spec, jnp.float32) if gamma0 is None
              else gamma0.astype(jnp.float32))
        g0 = jnp.pad(g0, (0, m_pad - m))

    hi, lo = spec.upper(m), spec.lower(m)
    data_spec = P(data_axes)
    row_spec = P(data_axes, None)

    def build():
        comm = engine.MeshComm(data_axes, sizes=sizes, ledger=ledger)

        def local_solve(X_l, gamma_l, valid_l, *warm_ops):
            # Tile-round once, before provider AND selector: both then
            # see identical rows (ShardedGram's precision invariant) and
            # no per-iteration re-round is needed anywhere.
            X_l = round_to_tile(X_l, precision)
            rank = _axis_rank(data_axes, sizes)
            gids = rank * m_local + jnp.arange(m_local, dtype=jnp.int32)

            provider = engine.ShardedGram(X_l, kernel, gids=gids,
                                          rank=rank, m_local=m_local,
                                          m_pad=m_pad, comm=comm,
                                          interpret=interpret,
                                          precision=precision)
            selector = engine.ShardedBlockSelector(X_l, P=P_pairs, hi=hi,
                                                   lo=lo, gids=gids,
                                                   valid=valid_l,
                                                   comm=comm)
            stats_fn = partial(engine.solver_stats_prev, hi=hi, lo=lo,
                               m=m, tol=tol, comm=comm, valid=valid_l)

            w_l = None
            if warm_ops:
                # Local f_seed slice + replicated correction set: the
                # reconcile sweep is purely shard-local.
                f_l, x_c, d_c = warm_ops
                w_l = engine.WarmStart(gamma0=gamma_l, f_seed=f_l,
                                       x_corr=x_c, delta=d_c)
            state0 = engine.init_state(provider, stats_fn, gamma_l,
                                       ledger=ledger, warm=w_l)
            s = engine.run(provider, selector, stats_fn, state0, hi=hi,
                           lo=lo, tol=tol, max_iters=max_outer,
                           patience=patience, rho_every=rho_every,
                           ledger=ledger)
            return (s.gamma, s.f, s.rho1, s.rho2, s.it, s.n_viol,
                    s.max_viol, s.gap)

        in_specs = (row_spec, data_spec, data_spec)
        if warm is not None:
            in_specs = in_specs + (data_spec, P(None, None), P(None))
        return jax.jit(shard_map(
            local_solve, mesh=mesh,
            in_specs=in_specs,
            out_specs=(data_spec, data_spec, P(), P(), P(), P(), P(), P()),
            check_vma=False,
        ))

    warm_key = None if warm is None else tuple(warm.x_corr.shape)
    shard_fn = _cached_shard_fn(
        ("solve", mesh, data_axes, m, d, spec, P_pairs, tol, max_outer,
         patience, rho_every, precision, interpret, warm_key,
         None if ledger is None else id(ledger)), build)
    Xf, = _place(mesh, row_spec, Xf)
    g0, valid = _place(mesh, data_spec, g0, valid)
    if warm is not None:
        f_seed, = _place(mesh, data_spec, f_seed)
        x_corr, = _place(mesh, P(None, None), x_corr)
        d_corr, = _place(mesh, P(None), d_corr)
        gamma, f, rho1, rho2, it, n_viol, max_viol, gap = shard_fn(
            Xf, g0, valid, f_seed, x_corr, d_corr)
    else:
        gamma, f, rho1, rho2, it, n_viol, max_viol, gap = shard_fn(
            Xf, g0, valid)
    model = OCSSVMModel(gamma=gamma[:m], rho1=rho1, rho2=rho2, X=Xf[:m],
                        spec=spec)
    return SMOResult(model=model, iters=it, n_viol=n_viol,
                     max_viol=max_viol, gap=gap, converged=gap <= tol,
                     f=f[:m])


def sharded_raw_scores(
    X: Array,
    gamma: Array,
    kernel,
    mesh: Mesh,
    *,
    data_axes: Tuple[str, ...] = ("data",),
    precision: str = "f32",
    ledger: Optional[engine.CollectiveLedger] = None,
) -> Array:
    """f = K @ gamma with X row-sharded over the mesh's data axes.

    Each shard gathers X and gamma once and accumulates its local rows'
    scores over column blocks (``ShardedGram.init_scores``) — the sharded
    counterpart of ``raw_scores_blocked``, used by the sharded shrinking
    driver's full-set KKT sweeps. O(m d / n_shards) compute per device,
    one gather of X + gamma total. The ledger bills this O(m d) gather
    under its own "sweep" phase — it is once-per-repack-round work, not
    part of the per-iteration O(P d) bill.
    """
    m, d = X.shape
    sizes, n_shards, m_pad, m_local = _shard_geometry(m, mesh, data_axes)
    Xf = jnp.pad(X.astype(jnp.float32), ((0, m_pad - m), (0, 0)))
    gp = jnp.pad(gamma.astype(jnp.float32), (0, m_pad - m))
    data_spec = P(data_axes)
    row_spec = P(data_axes, None)
    if ledger is not None:
        ledger.set_phase("sweep")

    def build():
        comm = engine.MeshComm(data_axes, sizes=sizes, ledger=ledger)

        def local_scores(X_l, g_l):
            X_l = round_to_tile(X_l, precision)
            rank = _axis_rank(data_axes, sizes)
            gids = rank * m_local + jnp.arange(m_local, dtype=jnp.int32)
            provider = engine.ShardedGram(X_l, kernel, gids=gids,
                                          rank=rank, m_local=m_local,
                                          m_pad=m_pad, comm=comm,
                                          precision=precision)
            return provider.init_scores(g_l)

        return jax.jit(shard_map(
            local_scores, mesh=mesh, in_specs=(row_spec, data_spec),
            out_specs=data_spec, check_vma=False))

    shard_fn = _cached_shard_fn(
        ("scores", mesh, data_axes, m, d, kernel, precision,
         None if ledger is None else id(ledger)), build)
    Xf, = _place(mesh, row_spec, Xf)
    gp, = _place(mesh, data_spec, gp)
    return shard_fn(Xf, gp)[:m]
