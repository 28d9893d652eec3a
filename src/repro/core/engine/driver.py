"""The one SMO driver: Gauss-Seidel pair solve + the lax.while_loop.

Every solver facade (sequential paper SMO, blocked, sharded, shrinking
rounds) runs THIS loop — the provider decides how Gram rows are produced,
the selector decides which rows move, and the stall/patience/gap logic
lives here exactly once.

Each iteration:

1. ``selector.select`` picks a 2P working set (grow half, shrink half),
2. ``gauss_seidel_pairs`` runs the paper's analytic 2-variable update
   (eq. 35-39) over the P pairs against the small (2P, 2P) Gram block,
   keeping the selected scores exact — a true block-coordinate-descent
   step, monotone on the dual, same fixed points as Algorithm 1,
3. the provider folds the step back: a rank-2P f-cache update (the Pallas
   ``fupdate`` kernel under ``gram_mode="pallas"``) and a gamma scatter,
4. ``stats_fn`` re-estimates rho1/rho2 and the convergence diagnostics.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.engine.types import Selection, SolverState

Array = jax.Array
_TINY = 1e-12

# stats_fn(gamma, f, rho1_prev, rho2_prev, recompute_rho)
#   -> (rho1, rho2, n_viol, max_viol, gap)
StatsFn = Callable[..., tuple]


def gauss_seidel_pairs(sel: Selection, Kblk: Array, dsl: Array, *,
                       hi: float, lo: float) -> Array:
    """Solve the P analytic 2-variable subproblems sequentially.

    Pair k couples row k (grow side) with row P+k (shrink side). Every
    step moves on the equality hyperplane and is clipped to the box, so
    feasibility is exact; the selected scores are updated against the
    (2P, 2P) block so each step sees the previous pairs' moves.
    Returns delta = gamma_sel_final - gamma_sel_0, shape (2P,).
    """
    P = sel.n_pairs
    tiny = jnp.asarray(_TINY, sel.f.dtype)

    def inner(k, carry):
        g_sel, f_sel = carry
        ib, ia = k, P + k
        eta = 1.0 / jnp.maximum(dsl[ia] + dsl[ib] - 2.0 * Kblk[ia, ib],
                                tiny)
        t = g_sel[ia] + g_sel[ib]
        L = jnp.maximum(t - hi, lo)
        H = jnp.minimum(hi, t - lo)
        gb_new = jnp.clip(g_sel[ib] + eta * (f_sel[ia] - f_sel[ib]), L, H)
        dgb = gb_new - g_sel[ib]
        # Degenerate pair (duplicate index from top_k ties): freeze.
        dgb = jnp.where(sel.ids[ia] == sel.ids[ib], 0.0, dgb)
        g_sel = g_sel.at[ib].add(dgb).at[ia].add(-dgb)
        f_sel = f_sel + dgb * (Kblk[:, ib] - Kblk[:, ia])
        return g_sel, f_sel

    g_fin, _ = jax.lax.fori_loop(0, P, inner, (sel.gamma, sel.f))
    return g_fin - sel.gamma


def init_state(provider, stats_fn: StatsFn, gamma0: Array,
               f_offset: Optional[Array] = None,
               ledger=None, warm=None) -> SolverState:
    """Score the initial gamma and measure the starting diagnostics.

    f_offset: constant per-row score contribution from coordinates OUTSIDE
    this problem (the shrinking driver freezes bound coordinates and solves
    the active subset; their kernel contribution rides along here).
    warm: optional ``engine.state.WarmStart`` — instead of the O(m^2)
    K @ gamma0 pass, the f-cache is RECONCILED from the prior fit's
    f_seed with one fused rank-s sweep over the correction set
    (``provider.reconcile_scores``, the Pallas ``fupdate`` kernel under
    the pallas/sharded providers). The caller passes
    ``gamma0 == warm.gamma0`` (its local slice when sharded) — the
    invariant ``reconcile_scores(warm) == K @ gamma0`` is what
    ``state.prepare_warm_start`` constructs.
    ledger: optional ``CollectiveLedger`` — everything traced here is
    one-time work, so it is tagged phase="init".
    """
    if ledger is not None:
        ledger.set_phase("init")
    if warm is not None:
        f = provider.reconcile_scores(warm)
    else:
        f = provider.init_scores(gamma0)
    if f_offset is not None:
        f = f + f_offset.astype(f.dtype)
    zero = jnp.zeros((), f.dtype)
    # Two passes: the first recovers rho, the second measures diagnostics
    # against it (free on a single device; 2 extra collectives sharded).
    rho1, rho2, _, _, _ = stats_fn(gamma0, f, zero, zero, True)
    rho1, rho2, n_viol, max_viol, gap = stats_fn(gamma0, f, rho1, rho2, True)
    return SolverState(gamma0, f, rho1, rho2,
                       jnp.zeros((), jnp.int32), n_viol, max_viol, gap,
                       jnp.zeros((), jnp.int32))


def run(provider, selector, stats_fn: StatsFn, state0: SolverState, *,
        hi: float, lo: float, tol: float, max_iters: int, patience: int,
        rho_every: int = 1, ledger=None) -> SolverState:
    """Iterate select -> pair-solve -> rank-2P update until converged.

    Termination (selector.criterion):
      "kkt" — paper Algorithm 1: at most one KKT violator (or a uniformly
              small max violation — same optimum);
      "gap" — Keerthi MVP duality gap <= tol.
    Both additionally stop at max_iters or after ``patience`` consecutive
    zero-progress steps (bound-blocked working sets).

    ledger: optional ``CollectiveLedger``. The while_loop body is traced
    exactly once, so collectives recorded from here on are tagged
    phase="iter" — the per-iteration collective bill.
    """
    if ledger is not None:
        ledger.set_phase("iter")
    criterion = selector.criterion
    tiny = jnp.asarray(_TINY, state0.f.dtype)

    def not_done(s: SolverState):
        if criterion == "kkt":
            unconverged = (s.n_viol > 1) & (s.max_viol > tol)
        else:
            unconverged = s.gap > tol
        return (s.it < max_iters) & unconverged & (s.stall < patience)

    def body(s: SolverState):
        # The scopes name each phase's operations in the compiled HLO's
        # metadata (a profile viewer groups device time by them); they
        # change no instruction.
        with jax.named_scope("select"):
            sel = provider.prepare(selector.select(s))
        with jax.named_scope("pair_solve"):
            Kblk = provider.block(sel)
            dsl = provider.diag_sel(sel)
            delta = gauss_seidel_pairs(sel, Kblk, dsl, hi=hi, lo=lo)

        with jax.named_scope("f_update"):
            gamma_new = provider.scatter(s.gamma, sel, delta)
            f_new = provider.apply_update(s.f, sel, delta)

        with jax.named_scope("stats"):
            recompute = (rho_every == 1) | ((s.it + 1) % rho_every == 0)
            r1, r2, n_viol, max_viol, gap = stats_fn(
                gamma_new, f_new, s.rho1, s.rho2, recompute)

            progressed = jnp.max(jnp.abs(delta)) > tiny * 10
            stall = jnp.where(progressed, 0, s.stall + 1).astype(jnp.int32)
        return SolverState(gamma_new, f_new, r1, r2, s.it + 1,
                           n_viol, max_viol, gap, stall)

    return jax.lax.while_loop(not_done, body, state0)


def has_converged(s: SolverState, criterion: str, tol: float) -> Array:
    if criterion == "kkt":
        return (s.n_viol <= 1) | (s.max_viol <= tol)
    return s.gap <= tol
