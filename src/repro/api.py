"""Top-level training entry point: pick an engine composition by problem.

``repro.fit(X, spec)`` routes to the right (GramProvider x Selector)
composition of the solver engine for the problem size and hardware:

* small m            -> blocked solver, precomputed Gram (O(m^2) is cheap)
* medium m           -> blocked solver, on-the-fly rows (no m^2 memory);
                        the fused Pallas f-update on TPU
* large m            -> shrinking repack driver around the blocked solver
* mesh given / "sharded" -> row-sharded solver over the mesh's data axes
                        (per-shard Pallas fupdate on the hot loop); large
                        m additionally gets the sharded shrinking repack
                        driver. With no mesh given, "sharded" builds one
                        from the launch layer
                        (``repro.launch.mesh.make_solver_mesh``).

Every strategy returns the same ``SMOResult``; explicit strategies are
available for benchmarks and tests that compare compositions.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.batched_smo import solve_blocked
from repro.core.distributed_smo import solve_blocked_distributed
from repro.core.engine.gram import SINGLE_PASS_MAX
from repro.core.engine.state import (SolverArtifact, WarmStart,
                                     artifact_from_result,
                                     prepare_warm_start)
from repro.core.engine.types import SMOResult
from repro.core.ocssvm import SlabSpec
from repro.core.shrinking import (solve_blocked_shrinking,
                                  solve_sharded_shrinking)
from repro.core.smo import solve as solve_smo

Array = jax.Array

# Above this row count the shrinking repack driver wins: per-iteration
# work drops to the active (support-vector) set.
_SHRINKING_MIN_M = 8192

STRATEGIES = ("auto", "paper", "mvp", "blocked", "pallas", "shrinking",
              "distributed", "sharded")


def _auto_gram_mode(m: int, interpret: Optional[bool] = None) -> str:
    if interpret is not None:
        # An explicit interpret override is a request to exercise the
        # Pallas provider deterministically (CPU CI forces interpret=True;
        # TPU perf runs force interpret=False) — don't second-guess it
        # from the problem size or whatever backend jax resolved.
        return "pallas"
    if m <= SINGLE_PASS_MAX // 2:
        return "precomputed"
    if jax.default_backend() == "tpu":
        return "pallas"            # fused fupdate kernel on the MXU
    return "on_the_fly"


def fit(
    X: Array,
    spec: Optional[SlabSpec] = None,
    *,
    strategy: str = "auto",
    gram_mode: Optional[str] = None,
    interpret: Optional[bool] = None,
    precision: str = "f32",
    P: int = 8,
    tol: float = 1e-4,
    mesh=None,
    data_axes: Tuple[str, ...] = ("data",),
    multi_pod: bool = False,
    ledger=None,
    warm_start=None,
    warm_info_out: Optional[dict] = None,
    **kwargs,
) -> SMOResult:
    """Train a One-Class Slab SVM; returns an ``SMOResult``.

    strategy: "auto" (size/hardware heuristic), "paper" / "mvp" (the
    sequential Algorithm 1 selectors), "blocked", "pallas" (the blocked
    solver pinned to the Pallas Gram/fupdate provider — tile sizes come
    from the committed autotune table, ``kernels/tuned_configs.json``,
    unless ``REPRO_NO_AUTOTUNE=1``; see docs/kernels.md), "shrinking",
    "sharded" (row-sharded engine over a mesh — built from the launch
    layer via ``make_solver_mesh(multi_pod=...)`` when ``mesh`` is not
    given; large m composes with the sharded shrinking repack driver),
    or "distributed" (the plain row-sharded solver; requires ``mesh``).
    interpret: force Pallas interpret mode on (True; CPU CI) or off
    (False; TPU) instead of auto-detecting the backend — this reaches
    the per-shard fupdate kernel for the sharded strategies too.
    precision: Gram tile-input dtype ("f32" default, "bf16", "f16") —
    halves kernel HBM traffic; dot products still accumulate f32
    (``repro.kernels.precision``; every strategy honors it, including
    the sharded ones). ledger: a
    ``repro.core.engine.CollectiveLedger`` the sharded strategies fill
    with per-device collective-bytes accounting (ignored by the local
    strategies). warm_start: a prior fit to seed from — a
    ``SolverArtifact`` (or an ``SMOResult``, converted; or an
    already-prepared ``engine.WarmStart``): gamma seeds from the
    overlapping rows and the f-cache is reconciled with one fused rank-s
    sweep instead of the O(m^2) init (``docs/streaming.md``; the
    paper/mvp strategies seed gamma only). warm_info_out: a dict the
    warm-start accounting (overlap/fresh/expired/correction counts) is
    written into. Extra kwargs flow to the chosen solver
    (max_iters/max_outer, patience, gamma0, ...).
    """
    with TraceAnnotation("fit"):        # the whole call (profiler clock)
        if spec is None:
            spec = SlabSpec()
        if strategy not in STRATEGIES:
            raise ValueError(f"unknown strategy {strategy!r}; "
                             f"expected one of {STRATEGIES}")
        m = X.shape[0]

        warm = None
        if warm_start is not None:
            if isinstance(warm_start, WarmStart):
                warm = warm_start      # prepared by the caller (fit_update)
            else:
                art = _as_artifact(warm_start, precision=precision)
                warm, winfo = prepare_warm_start(art, X, spec,
                                                 precision=precision)
                if warm_info_out is not None:
                    warm_info_out.update(dataclasses.asdict(winfo))

        if strategy == "auto":
            if mesh is not None:
                strategy = "sharded"
            elif m > _SHRINKING_MIN_M:
                strategy = "shrinking"
            else:
                strategy = "blocked"

        # The sequential solvers call their iteration cap max_iters, the
        # blocked family max_outer; accept either so "auto" can reroute a call
        # without the caller caring which solver won.
        if strategy in ("paper", "mvp"):
            if "max_outer" in kwargs:
                kwargs["max_iters"] = kwargs.pop("max_outer")
        elif "max_iters" in kwargs:
            kwargs["max_outer"] = kwargs.pop("max_iters")

        if strategy in ("distributed", "sharded"):
            if gram_mode is not None:
                raise ValueError(
                    "gram_mode is not configurable for the sharded/"
                    "distributed strategies: the sharded provider owns Gram "
                    "access (its hot loop is the per-shard Pallas fupdate; "
                    "the local repack solves of the sharded shrinking driver "
                    "pick their own provider)")
            if strategy == "distributed" and mesh is None:
                raise ValueError("strategy='distributed' needs a mesh; "
                                 "use strategy='sharded' to build one from "
                                 "the launch layer")
            if mesh is None:
                from repro.launch.mesh import make_solver_mesh
                mesh, data_axes = make_solver_mesh(multi_pod=multi_pod)
            if strategy == "sharded" and m > _SHRINKING_MIN_M:
                return solve_sharded_shrinking(X, spec, mesh,
                                               data_axes=data_axes,
                                               P_pairs=P, tol=tol,
                                               precision=precision,
                                               interpret=interpret,
                                               ledger=ledger, warm=warm,
                                               **kwargs)
            # Below the shrinking threshold the plain sharded solve runs;
            # surface a clear error for shrinking-only knobs instead of an
            # opaque TypeError (the accepted kwargs must not silently change
            # when a growing dataset crosses the threshold).
            shrink_only = [k for k in ("warm_iters", "max_rounds",
                                       "round_iters", "margin", "gather_max")
                           if k in kwargs]
            if shrink_only:
                raise ValueError(
                    f"kwargs {shrink_only} configure the sharded shrinking "
                    f"driver, which only runs for m > {_SHRINKING_MIN_M} "
                    f"(got m={m}); drop them or call "
                    "repro.core.solve_sharded_shrinking directly")
            return solve_blocked_distributed(X, spec, mesh,
                                             data_axes=data_axes, P_pairs=P,
                                             tol=tol, precision=precision,
                                             interpret=interpret,
                                             ledger=ledger, warm=warm,
                                             **kwargs)

        if strategy == "pallas":
            if gram_mode is not None and gram_mode != "pallas":
                raise ValueError(
                    f"strategy='pallas' pins gram_mode='pallas'; got "
                    f"gram_mode={gram_mode!r} — drop it or use "
                    f"strategy='blocked'")
            return solve_blocked(X, spec, P=P, gram_mode="pallas",
                                 interpret=interpret, precision=precision,
                                 tol=tol, warm=warm, **kwargs)

        gm = (gram_mode if gram_mode is not None
              else _auto_gram_mode(m, interpret))
        if strategy in ("paper", "mvp"):
            # The sequential facades predate the warm f-cache path: seed
            # gamma only (the init pass still scores it from scratch).
            if warm is not None:
                kwargs["gamma0"] = warm.gamma0
            return solve_smo(X, spec, selection=strategy, gram_mode=gm,
                             interpret=interpret, precision=precision, tol=tol,
                             **kwargs)
        if strategy == "shrinking":
            return solve_blocked_shrinking(X, spec, P=P, gram_mode=gm,
                                           interpret=interpret,
                                           precision=precision, tol=tol,
                                           warm=warm, **kwargs)
        return solve_blocked(X, spec, P=P, gram_mode=gm, interpret=interpret,
                             precision=precision, tol=tol, warm=warm, **kwargs)


def _as_artifact(prev, *, precision: str = "f32") -> SolverArtifact:
    if isinstance(prev, SolverArtifact):
        return prev
    if isinstance(prev, SMOResult):
        return artifact_from_result(prev, precision=precision)
    raise TypeError(
        f"expected a SolverArtifact or SMOResult, got {type(prev).__name__}")


def fit_update(
    prev,
    X_new: Array,
    spec: Optional[SlabSpec] = None,
    *,
    min_overlap: float = 0.5,
    stats_out: Optional[dict] = None,
    **kwargs,
) -> SMOResult:
    """Delta-solve: re-fit on ``X_new`` warm-started from a prior fit.

    ``prev`` is a ``SolverArtifact`` (or an ``SMOResult``, converted).
    Rows are matched by content hash — appended rows enter with zero
    coefficient, expired rows' contribution is subtracted from the
    f-cache with the same fused rank-s sweep the hot loop runs — so the
    solve starts next to the prior optimum: on small deltas it converges
    in a small fraction of the cold iteration count (the streaming
    acceptance test asserts <= 25% on a 5% append).

    When the overlap fraction falls below ``min_overlap`` the warm seed
    is more misdirection than head start (most of the f-cache would be
    corrections), so the call falls back to a cold ``fit`` — the routing
    is recorded in ``stats_out`` (``mode``: "warm" | "cold", plus the
    overlap/fresh/expired/correction counts). The same cold route — with
    ``stats_out["fallback"]`` recording why — is taken when the warm
    path cannot run at all: an explicit ``gamma0`` seed among the kwargs
    (the solvers take ``warm=`` or ``gamma0=``, not both), or an engine
    raising ``NotImplementedError`` from incremental structures
    mid-update (the sharded Gram facade's ``append_rows``). A streaming
    refresh degrades to a cold refit; it never surfaces a traceback.

    ``spec`` defaults to the artifact's; kwargs flow to ``fit``
    (strategy, precision, tol, ...). ``precision`` defaults to the
    artifact's so the warm correction rows are rounded to the same Gram
    tiles the prior solve streamed.
    """
    precision = kwargs.pop("precision", None)
    art = _as_artifact(prev, precision=precision or "f32")
    if precision is None:
        precision = art.precision
    if spec is None:
        spec = art.spec
    warm, info = prepare_warm_start(art, X_new, spec, precision=precision)
    mode = "warm" if info.overlap_frac >= min_overlap else "cold"
    fallback = None
    g0 = kwargs.get("gamma0")
    if g0 is not None:
        if int(np.shape(g0)[0]) == int(X_new.shape[0]):
            # An explicit dual seed and a warm-start seed are mutually
            # exclusive down in the solvers ("pass warm= or gamma0=,
            # not both") — detect it HERE and take the documented cold
            # route (where gamma0 IS the seed) instead of surfacing the
            # solver's ValueError after warm state was prepared.
            mode = "cold"
            fallback = "gamma0_conflict"
        else:
            # A seed pinned to a previous data shape (e.g. a registry
            # recipe carrying gamma0 in its fit kwargs, refreshed with
            # appended rows) cannot seed ANY fit on X_new — drop it so
            # the warm/cold routing above stands, rather than crash
            # whichever route it reaches.
            kwargs.pop("gamma0")
            fallback = "gamma0_stale_dropped"
    p_injected = False
    if mode == "warm" and "P" not in kwargs:
        # A delta-solve's violators concentrate on the delta: the fresh
        # rows must acquire mass and the corrected rows re-equilibrate,
        # while the rest of the active set barely moves. Scaling the
        # working-set size with the delta lets one rank-2P sweep touch
        # most of the moving set — fewer full HBM passes over X, which
        # is the blocked solver's per-iteration cost — instead of
        # drip-feeding 8 pairs at a time through a cold-sized block.
        # Capped at m/16 so the per-shard top_k of the sharded engine
        # (local rows ~ m/devices) never asks for more pairs than a
        # shard holds.
        moving = info.n_fresh + info.n_corr
        kwargs["P"] = max(8, min(64, info.m // 16,
                                 1 << max(moving // 2, 1).bit_length()))
        p_injected = True
    if stats_out is not None:
        stats_out.update(dataclasses.asdict(info))
        stats_out["mode"] = mode
        stats_out["P"] = kwargs.get("P")
        if fallback is not None:
            stats_out["fallback"] = fallback
    if mode == "cold":
        return fit(X_new, spec, precision=precision, **kwargs)
    try:
        return fit(X_new, spec, precision=precision, warm_start=warm,
                   **kwargs)
    except NotImplementedError as e:
        # The documented cold-refit fallback for engines whose
        # incremental structures cannot mutate mid-update — e.g. the
        # sharded Gram facade raising from append_rows/expire_rows. A
        # streaming refresh must degrade to a cold refit (counted in the
        # registry's refresh_modes), never surface a traceback after the
        # warm state was prepared.
        if stats_out is not None:
            stats_out["mode"] = "cold"
            stats_out["fallback"] = f"warm_unsupported: {e}"
        if p_injected:
            kwargs.pop("P", None)   # the delta-scaled working set was
            #                         sized for the warm route only
        return fit(X_new, spec, precision=precision, **kwargs)


def serve(X: Optional[Array] = None, spec: Optional[SlabSpec] = None, *,
          model: Optional[str] = None, registry=None,
          quota: Optional[int] = None, **kwargs):
    """Train-then-serve: a warm ``ServingModel`` ready to ``score(q)``.

    The serving-side counterpart of ``fit``: hits the process-wide
    warm-model cache (fit + SV compaction + tile packing happen once per
    (spec, data) key) and returns a ``repro.serve.ServingModel`` whose
    ``score`` runs batched through the Pallas decision kernel. kwargs
    flow to ``repro.serve.ModelCache.get_or_fit`` (cache=, offsets=,
    sv_threshold=, tn=, precision=) and on to ``fit`` (strategy,
    interpret, tol, ...); ``precision="bf16"`` trains AND serves with
    16-bit Gram tile streams (f32 accumulate/epilogue).

    ``model=`` switches on multi-model routing: with ``X`` the recipe is
    registered under that name in ``registry`` (default: the
    process-wide ``repro.serve.default_registry()``; idempotent — a
    *different* recipe under the same name raises
    ``DuplicateModelError``) and the registry's warm model comes back;
    without ``X`` it is a pure name lookup (``UnknownModelError`` if
    absent). ``quota=`` records the per-model admission budget the
    ``AdmissionController`` enforces.
    """
    from repro.serve.registry import serve as _serve
    return _serve(X, spec, model=model, registry=registry, quota=quota,
                  **kwargs)
