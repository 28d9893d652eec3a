"""GramProvider — the pluggable Gram-access axis of the solver engine.

A provider owns the training rows and answers the four kernel-matrix
queries the SMO hot loop needs, each against a ``Selection`` of 2P rows:

* ``init_scores(gamma)``          — f = K @ gamma (once, at solve start)
* ``block(sel)``                  — the (2P, 2P) Gram block of the pairs
* ``apply_update(f, sel, delta)`` — f + K[:, sel] @ delta (rank-2P update,
                                    the per-iteration hot path)
* ``scatter(gamma, sel, delta)``  — fold the pair steps back into gamma

Implementations:

* ``precomputed`` — materialize K once (O(m^2) memory; small m / tests).
* ``on_the_fly``  — recompute the needed kernel rows from X per iteration
                    (O(m d) per step, no m^2 memory).
* ``pallas``      — ``on_the_fly`` with the f-cache update fused into the
                    Pallas ``kernels/fupdate`` kernel (one HBM pass over X
                    per iteration; interpret mode on non-TPU backends), and
                    the init pass fused the same way when m is small enough
                    for the selected block to sit in VMEM.
* ``sharded``     — device-local rows under ``shard_map``: updates touch
                    only the local f/gamma slices, selections arrive as
                    gathered (2P, d) row blocks so no global indexing is
                    ever needed.

Every provider takes a ``precision`` ("f32" default, "bf16", "f16"): the
training rows are round-tripped through the tile dtype ONCE at
construction, so the pure-jnp providers see exactly the rounded values
the Pallas provider streams in 16-bit tiles — a given (selector,
precision) pair converges to the same gamma whichever provider runs it.
Norms, the f-cache, gamma and all epilogues stay f32
(``repro.kernels.precision``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernel_fn import KernelFn
from repro.core.engine.types import Selection
from repro.kernels.fupdate.ops import fupdate
from repro.kernels.precision import check_precision, round_to_tile

Array = jax.Array

# Largest m for a single unblocked cross-kernel pass; above this,
# row-blocked accumulation (raw_scores_blocked / _blocked pieces) keeps the
# working set at O(BLOCK * m) instead of O(m^2). Shared by every caller
# that decides "one pass vs blocked" (scores, objectives, shrinking).
SINGLE_PASS_MAX = 4096
BLOCK = 2048


def raw_scores_blocked(X: Array, gamma: Array, kernel: KernelFn,
                       block: int = BLOCK, *, Y: Optional[Array] = None
                       ) -> Array:
    """k(X, Y) @ gamma (Y defaults to X: K @ gamma) without materializing
    the kernel block: row-blocked over X once it would exceed
    SINGLE_PASS_MAX^2 entries."""
    Y = X if Y is None else Y
    m = X.shape[0]
    if m * Y.shape[0] <= SINGLE_PASS_MAX ** 2:
        return kernel.cross(X, Y) @ gamma
    nblk = (m + block - 1) // block
    pad = nblk * block - m
    Xp = jnp.pad(X, ((0, pad), (0, 0)))

    def body(i, acc):
        xb = jax.lax.dynamic_slice_in_dim(Xp, i * block, block)
        return jax.lax.dynamic_update_slice_in_dim(
            acc, kernel.cross(xb, Y) @ gamma, i * block, 0)

    out = jax.lax.fori_loop(0, nblk, body,
                            jnp.zeros((nblk * block,), gamma.dtype))
    return out[:m]


class _ScoreDeltas:
    """Shared O(s * m) score-delta algebra — the warm-start substrate.

    Every provider mixes this in: ``delta_scores`` folds a rank-s kernel
    contribution into an f-cache with ONE pass over the owned rows (the
    fused Pallas sweep under the pallas/sharded providers), and
    ``append_rows``/``expire_rows`` compose it with a cache rebuild so a
    data delta costs O(dm * m) instead of the O(m^2) cold init.
    ``reconcile_scores`` is the driver-facing entry: it turns a
    ``engine.state.WarmStart``'s assumed-configuration f_seed into the
    new problem's exact K @ gamma0.
    """

    def delta_scores(self, f: Array, X_delta: Array,
                     g_delta: Array) -> Array:
        """f + k(X_own, X_delta) @ g_delta — one pass, no m^2 anything."""
        if X_delta.shape[0] == 0:
            return f
        return f + raw_scores_blocked(self.X, g_delta, self.kernel,
                                      Y=X_delta)

    def reconcile_scores(self, warm) -> Array:
        """Fold a WarmStart's correction set into its seeded f-cache.

        ``prepare_warm_start`` guarantees the result equals K @ gamma0
        over the owned rows (the local slice when sharded — zero
        collectives: corrections ride replicated, f_seed rides sharded).
        """
        return self.delta_scores(warm.f_seed, warm.x_corr, warm.delta)

    def append_rows(self, X_app, gamma: Array, f: Array, g_app=None):
        """(provider', gamma', f') for the extended problem [X; X_app].

        Appended rows default to gamma = 0 (fresh data), so surviving
        scores are untouched; their own scores cost one O(dm * m) pass.
        A nonzero ``g_app`` first folds the same-rank delta into the
        surviving f. Host-side API (between solves, concrete shapes).
        """
        Xa = round_to_tile(
            jnp.asarray(X_app, jnp.float32).reshape(-1, self.X.shape[1]),
            self.precision)
        if g_app is None:
            g_app = jnp.zeros((Xa.shape[0],), jnp.float32)
            f_old = f
        else:
            g_app = jnp.asarray(g_app, jnp.float32)
            f_old = self.delta_scores(f, Xa, g_app)
        p2 = self._rebuilt_extended(Xa)
        gamma2 = jnp.concatenate([jnp.asarray(gamma, jnp.float32), g_app])
        # The appended rows' own scores against the full extended set.
        f_app = raw_scores_blocked(Xa, gamma2, self.kernel, Y=p2.X)
        return p2, gamma2, jnp.concatenate([f_old, f_app])

    def expire_rows(self, idx, gamma: Array, f: Array):
        """(provider', gamma', f') with rows ``idx`` removed — O(e * m).

        Surviving scores lose the expired rows' kernel columns times
        their gamma (one rank-e sweep); no O(m^2) recompute. Host-side
        API (between solves, concrete indices).
        """
        idx = np.asarray(idx, np.int64).reshape(-1)
        keep = np.setdiff1d(np.arange(self.X.shape[0]), idx)
        Xe = self.X[jnp.asarray(idx)].reshape(-1, self.X.shape[1])
        ge = jnp.asarray(gamma)[jnp.asarray(idx)].reshape(-1)
        f2 = self.delta_scores(f, Xe, -ge)[jnp.asarray(keep)]
        return (self._rebuilt_shrunk(keep), jnp.asarray(gamma)[keep], f2)

    def _rebuilt_extended(self, Xa: Array):
        raise NotImplementedError

    def _rebuilt_shrunk(self, keep: np.ndarray):
        raise NotImplementedError


class PrecomputedGram(_ScoreDeltas):
    """Materialized m x m Gram matrix: every query is a gather/matmul."""

    name = "precomputed"

    def __init__(self, X: Array, kernel: KernelFn, precision: str = "f32",
                 *, _K: Array | None = None):
        self.precision = check_precision(precision)
        self.X = round_to_tile(X, precision)
        self.kernel = kernel
        self.K = kernel.gram(self.X) if _K is None else _K
        self._diag = kernel.diag(self.X)

    def _rebuilt_extended(self, Xa: Array) -> "PrecomputedGram":
        # Extend K with the new cross block — O(dm * m) kernel evals,
        # not a fresh O(m^2) gram.
        C = self.kernel.rows(self.X, Xa)              # (m, dm)
        Kaa = self.kernel.cross(Xa, Xa)
        K2 = jnp.block([[self.K, C], [C.T, Kaa]])
        return PrecomputedGram(jnp.concatenate([self.X, Xa], axis=0),
                               self.kernel, self.precision, _K=K2)

    def _rebuilt_shrunk(self, keep: np.ndarray) -> "PrecomputedGram":
        kj = jnp.asarray(keep)
        return PrecomputedGram(self.X[kj], self.kernel, self.precision,
                               _K=self.K[kj][:, kj])

    def diag(self) -> Array:
        return self._diag

    def column(self, i) -> Array:
        return self.K[:, i]

    def init_scores(self, gamma: Array) -> Array:
        return self.K @ gamma

    def prepare(self, sel: Selection) -> Selection:
        # Gather the 2P columns once; block() and apply_update() both
        # read them, halving the per-iteration gather traffic.
        if sel.rows is None:
            sel = sel._replace(rows=self.K[:, sel.ids])
        return sel

    def block(self, sel: Selection) -> Array:
        if sel.rows is not None:
            return sel.rows[sel.ids]
        return self.K[sel.ids][:, sel.ids]

    def diag_sel(self, sel: Selection) -> Array:
        return self._diag[sel.ids]

    def apply_update(self, f: Array, sel: Selection, delta: Array) -> Array:
        rows = self.K[:, sel.ids] if sel.rows is None else sel.rows
        return f + rows @ delta

    def scatter(self, gamma: Array, sel: Selection, delta: Array) -> Array:
        return gamma.at[sel.ids].add(delta)


class OnTheFlyGram(_ScoreDeltas):
    """Recompute the <= 2P needed kernel rows from X each iteration."""

    name = "on_the_fly"

    def __init__(self, X: Array, kernel: KernelFn, precision: str = "f32"):
        self.precision = check_precision(precision)
        self.X = round_to_tile(X, precision)
        self.kernel = kernel
        self._diag = kernel.diag(self.X)

    def _rebuilt_extended(self, Xa: Array) -> "OnTheFlyGram":
        return type(self)._clone(self, jnp.concatenate([self.X, Xa],
                                                       axis=0))

    def _rebuilt_shrunk(self, keep: np.ndarray) -> "OnTheFlyGram":
        return type(self)._clone(self, self.X[jnp.asarray(keep)])

    @classmethod
    def _clone(cls, proto: "OnTheFlyGram", X2: Array) -> "OnTheFlyGram":
        return cls(X2, proto.kernel, precision=proto.precision)

    def diag(self) -> Array:
        return self._diag

    def column(self, i) -> Array:
        return self.kernel.rows(self.X, self.X[i][None, :])[:, 0]

    def init_scores(self, gamma: Array) -> Array:
        return raw_scores_blocked(self.X, gamma, self.kernel)

    def prepare(self, sel: Selection) -> Selection:
        return sel   # rows are recomputed exactly where needed

    def block(self, sel: Selection) -> Array:
        if sel.rows is not None:
            return sel.rows[sel.ids]
        return self.kernel.cross(sel.X, sel.X)

    def diag_sel(self, sel: Selection) -> Array:
        return self._diag[sel.ids]

    def apply_update(self, f: Array, sel: Selection, delta: Array) -> Array:
        rows = (self.kernel.rows(self.X, sel.X) if sel.rows is None
                else sel.rows)
        return f + rows @ delta

    def scatter(self, gamma: Array, sel: Selection, delta: Array) -> Array:
        return gamma.at[sel.ids].add(delta)


class PallasGram(OnTheFlyGram):
    """on_the_fly with the rank-2P f update fused into the Pallas kernel."""

    name = "pallas"

    def __init__(self, X: Array, kernel: KernelFn,
                 interpret: bool | None = None, precision: str = "f32"):
        super().__init__(X, kernel, precision=precision)
        self.interpret = interpret   # None -> auto (True off-TPU)

    def init_scores(self, gamma: Array) -> Array:
        if self.X.shape[0] <= BLOCK:
            # f = 0 + k(X, X) @ gamma in one fused pass; the whole selected
            # block must fit VMEM, so only below the blocking threshold.
            zero = jnp.zeros((self.X.shape[0],), jnp.float32)
            return fupdate(self.X, self.X, gamma, zero, self.kernel,
                           interpret=self.interpret,
                           precision=self.precision)
        return raw_scores_blocked(self.X, gamma, self.kernel)

    def apply_update(self, f: Array, sel: Selection, delta: Array) -> Array:
        if sel.rows is not None:
            # A selector already produced the full columns (paper rule's
            # movability mask) — reusing them beats a second HBM pass.
            return f + sel.rows @ delta
        # self.X is already tile-rounded, so the in-kernel cast to the
        # 16-bit stream dtype is exact — kernel and jnp paths agree.
        return fupdate(self.X, sel.X, delta, f, self.kernel,
                       interpret=self.interpret, precision=self.precision)

    def delta_scores(self, f: Array, X_delta: Array,
                     g_delta: Array) -> Array:
        # The warm-start reconcile sweep IS the hot-loop rank-2P update
        # with the correction set as the selected block — same fused
        # kernel, one HBM pass over X. Above BLOCK the selected block
        # would not sit in VMEM; fall back to the jnp pass.
        if X_delta.shape[0] == 0:
            return f
        if X_delta.shape[0] > BLOCK:
            return super().delta_scores(f, X_delta, g_delta)
        return fupdate(self.X, X_delta, g_delta, f, self.kernel,
                       interpret=self.interpret, precision=self.precision)

    @classmethod
    def _clone(cls, proto: "PallasGram", X2: Array) -> "PallasGram":
        return cls(X2, proto.kernel, interpret=proto.interpret,
                   precision=proto.precision)


class ShardedGram(_ScoreDeltas):
    """Device-local rows under shard_map; f/gamma are local slices.

    ``gids`` are this shard's global row ids; selections carry gathered
    (2P, d) row blocks, so the per-iteration update needs no communication
    at all — only ``init_scores`` all-gathers (once, column-blocked).
    The rank-2P f update runs the SAME fused Pallas ``fupdate`` kernel as
    the single-device ``PallasGram``, applied to the local rows (interpret
    mode on CPU; ``interpret=None`` auto-detects like the local provider).

    ``comm`` is the facade's ``MeshComm`` over the data axes: the
    init-time gathers route through it so the ``CollectiveLedger`` (when
    attached) sees every collective this provider issues.

    Precision invariant: ``X_local`` is tile-rounded at construction
    (idempotent), and the selector feeding this provider must gather its
    candidate rows from the same rounded shard data — the distributed
    facade rounds once, before building both. ``fupdate`` then re-casts
    the already-rounded rows to the 16-bit stream dtype exactly, so the
    kernel and jnp paths agree bit-for-bit on the Gram entries.
    """

    name = "sharded"

    def __init__(self, X_local: Array, kernel: KernelFn, *, gids: Array,
                 rank: Array, m_local: int, m_pad: int, comm,
                 interpret: bool | None = None, precision: str = "f32"):
        self.precision = check_precision(precision)
        self.X = round_to_tile(X_local, precision)
        self.kernel = kernel
        self.gids = gids
        self.rank = rank
        self.m_local = m_local
        self.m_pad = m_pad
        self.comm = comm
        self.axes = comm.axes
        self.interpret = interpret   # None -> auto (True off-TPU)

    def init_scores(self, gamma_local: Array) -> Array:
        # Local f needs the *global* K gamma: gather X and gamma once, then
        # accumulate over column blocks — the full (m_local x m) cross-Gram
        # block would be hundreds of GB at m = 1M.
        X_all = self.comm.all_gather(self.X, tiled=True)
        g_all = self.comm.all_gather(gamma_local, tiled=True)
        blk = BLOCK
        nblk = (self.m_pad + blk - 1) // blk
        Xp = jnp.pad(X_all, ((0, nblk * blk - self.m_pad), (0, 0)))
        gp = jnp.pad(g_all, (0, nblk * blk - self.m_pad))  # pad 0: no-op

        def fblock(i, acc):
            xb = jax.lax.dynamic_slice_in_dim(Xp, i * blk, blk)
            gb = jax.lax.dynamic_slice_in_dim(gp, i * blk, blk)
            return acc + self.kernel.cross(self.X, xb) @ gb

        return jax.lax.fori_loop(
            0, nblk, fblock, jnp.zeros((self.m_local,), jnp.float32))

    def prepare(self, sel: Selection) -> Selection:
        return sel

    def block(self, sel: Selection) -> Array:
        return self.kernel.cross(sel.X, sel.X)

    def diag_sel(self, sel: Selection) -> Array:
        return self.kernel.diag(sel.X)

    def apply_update(self, f: Array, sel: Selection, delta: Array) -> Array:
        # Rank-2P update of the local rows only — no communication: the
        # same fused Pallas pass as PallasGram, per shard. self.X is
        # tile-rounded here and sel.X carries rows the selector gathered
        # from the SAME rounded shard data (the distributed facade rounds
        # X_local once, before building provider and selector), so the
        # in-kernel cast to the 16-bit stream dtype is exact. fupdate's
        # internal pads (selected block to a lane multiple, rows/features
        # to tile multiples) carry zero deltas / zero rows and contribute
        # exactly 0 to f (tests assert this bitwise, bf16/f16 included).
        return fupdate(self.X, sel.X, delta, f, self.kernel,
                       interpret=self.interpret, precision=self.precision)

    def scatter(self, gamma: Array, sel: Selection, delta: Array) -> Array:
        loc = sel.ids - self.rank * self.m_local
        in_range = (loc >= 0) & (loc < self.m_local)
        loc_c = jnp.clip(loc, 0, self.m_local - 1)
        return gamma.at[loc_c].add(jnp.where(in_range, delta, 0.0))

    def delta_scores(self, f: Array, X_delta: Array,
                     g_delta: Array) -> Array:
        # Rank-s delta of the LOCAL f slice against REPLICATED delta rows
        # — zero collectives, same fused Pallas pass as apply_update.
        # This is how the sharded warm start reconciles: f_seed rides
        # sharded like gamma, the correction set rides replicated, and
        # every shard folds its own slice independently.
        if X_delta.shape[0] == 0:
            return f
        if X_delta.shape[0] > BLOCK:
            return f + raw_scores_blocked(self.X, g_delta, self.kernel,
                                          Y=X_delta)
        return fupdate(self.X, X_delta, g_delta, f, self.kernel,
                       interpret=self.interpret, precision=self.precision)

    def append_rows(self, X_app, gamma: Array, f: Array, g_app=None):
        """Sharded append is a facade-level operation (row placement,
        gids and m_pad all change shape across every shard), so the
        provider's share is the score algebra only: ``delta_scores`` /
        ``reconcile_scores`` on the local slice. The distributed facade
        re-shards rows and rebuilds providers — see
        ``solve_blocked_distributed(..., warm=)``."""
        raise NotImplementedError(
            "sharded append is handled by the distributed facade "
            "(re-shard + warm=); use delta_scores for the local f algebra")

    def expire_rows(self, idx, gamma: Array, f: Array):
        raise NotImplementedError(
            "sharded expiry is handled by the distributed facade "
            "(re-shard + warm=); use delta_scores for the local f algebra")


def make_provider(gram_mode: str, X: Array, kernel: KernelFn,
                  interpret: bool | None = None, precision: str = "f32"):
    """Build a local provider by name ("sharded" is constructed explicitly
    by the distributed facade — it needs the shard topology)."""
    if gram_mode == "precomputed":
        return PrecomputedGram(X, kernel, precision=precision)
    if gram_mode == "on_the_fly":
        return OnTheFlyGram(X, kernel, precision=precision)
    if gram_mode == "pallas":
        return PallasGram(X, kernel, interpret=interpret,
                          precision=precision)
    raise ValueError(f"unknown gram_mode {gram_mode!r}")
