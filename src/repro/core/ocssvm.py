"""One-Class Slab SVM model state, in the paper's reduced gamma-space.

The paper's key reduction (eq. 29-32): the dual depends only on
``gamma = alpha - alpha_bar``, giving

    min_gamma  1/2 gamma^T K gamma
    s.t.       -eps/(nu2*m) <= gamma_i <= 1/(nu1*m),   sum(gamma) = 1 - eps

``raw score`` s_i = sum_j gamma_j k(x_i, x_j); the slab decision is
``sgn((s - rho1) * (rho2 - s))`` (eq. 19): +1 inside the slab, -1 outside.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import stats as _stats
from repro.core.engine.gram import SINGLE_PASS_MAX
from repro.core.kernel_fn import KernelFn, dot

Array = jax.Array


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SlabSpec:
    """Static problem specification (nu1, nu2, eps and the kernel)."""

    nu1: float = 0.5
    nu2: float = 0.01
    eps: float = 2.0 / 3.0
    kernel: KernelFn = dataclasses.field(default_factory=KernelFn)

    def tree_flatten(self):
        return (self.kernel,), (self.nu1, self.nu2, self.eps)

    @classmethod
    def tree_unflatten(cls, aux, children):
        (kernel,) = children
        nu1, nu2, eps = aux
        return cls(nu1=nu1, nu2=nu2, eps=eps, kernel=kernel)

    # Box bounds in gamma space (eq. 31) and the equality target (eq. 32).
    def upper(self, m: int) -> float:
        return 1.0 / (self.nu1 * m)

    def lower(self, m: int) -> float:
        return -self.eps / (self.nu2 * m)

    def total(self) -> float:
        return 1.0 - self.eps


class OCSSVMModel(NamedTuple):
    """Fitted model: dual coefficients + slab offsets + the training data."""

    gamma: Array  # (m,) dual coefficients alpha - alpha_bar
    rho1: Array   # lower-plane offset
    rho2: Array   # upper-plane offset
    X: Array      # (m, d) training points (support data)
    spec: SlabSpec

    def raw_scores(self, Xq: Array) -> Array:
        """s(x) = sum_j gamma_j k(x, x_j) for query points (n, d) -> (n,)."""
        return dot(self.spec.kernel.cross(Xq, self.X), self.gamma)

    def decision_function(self, Xq: Array) -> Array:
        """Signed slab margin value (eq. 19 before the sgn)."""
        s = self.raw_scores(Xq)
        return (s - self.rho1) * (self.rho2 - s)

    def predict(self, Xq: Array) -> Array:
        """+1 inside the slab (target class), -1 outside."""
        return jnp.where(self.decision_function(Xq) >= 0, 1, -1)


def concrete_spec(spec: SlabSpec) -> SlabSpec:
    """Pull the spec's (hyper-)parameters to host python floats.

    The jitted solver facades take the spec as a *static* argument (the
    Pallas provider must specialize on concrete kernel parameters), so it
    has to be hashable: 0-d jax arrays — e.g. a spec recovered from a
    fitted model's ``res.model.spec`` — are converted; tracers cannot be
    (call the solver outside jit, or with a spec built from floats).
    """

    def _f(v, name):
        if isinstance(v, jax.core.Tracer):
            raise TypeError(
                f"SlabSpec.{name} is a traced value; the solver facades "
                "take the spec as a static (hashable) argument — build it "
                "from concrete floats or call outside jit.")
        return float(v)

    kernel = dataclasses.replace(
        spec.kernel, gamma=_f(spec.kernel.gamma, "kernel.gamma"),
        coef0=_f(spec.kernel.coef0, "kernel.coef0"))
    return dataclasses.replace(
        spec, nu1=_f(spec.nu1, "nu1"), nu2=_f(spec.nu2, "nu2"),
        eps=_f(spec.eps, "eps"), kernel=kernel)


def feasible_init(m: int, spec: SlabSpec, dtype=jnp.float32) -> Array:
    """A strictly feasible gamma: water-fill ``1 - eps`` into the box.

    Uniform (1-eps)/m works whenever it is inside the box; otherwise fill
    the first ceil((1-eps)/hi) entries to the cap and put the remainder in
    the next slot (general water-filling, jit-safe).
    """
    hi = spec.upper(m)
    lo = spec.lower(m)
    total = spec.total()
    uniform = total / m
    inside = (uniform <= hi) & (uniform >= lo)

    def _uniform():
        return jnp.full((m,), uniform, dtype)

    def _waterfill():
        # total > 0 always (eps < 1): fill caps left to right.
        full = jnp.floor(total / hi).astype(jnp.int32)
        idx = jnp.arange(m)
        g = jnp.where(idx < full, hi, 0.0).astype(dtype)
        rem = total - full.astype(dtype) * hi
        return g.at[full].add(rem.astype(dtype))

    return jax.lax.cond(inside, _uniform, _waterfill)


def recover_rhos(
    gamma: Array,
    scores: Array,
    spec: SlabSpec,
    tol: float = 1e-6,
) -> Tuple[Array, Array]:
    """rho1 / rho2 from on-margin support vectors (eq. 20-21).

    Lower-plane SVs: 0 < gamma < 1/(nu1 m)  -> s = rho1.
    Upper-plane SVs: -eps/(nu2 m) < gamma < 0 -> s = rho2.

    When a plane has no free SV (all at bound), fall back to the KKT
    interval midpoint: rho1 in [max_{gamma=hi} s, min_{gamma<=0} s],
    rho2 in [max_{gamma>=0} s, min_{gamma=lo} s].

    This is the spec-based view of the one implementation in
    ``repro.core.engine.stats`` (which also serves the sharded solver).
    """
    m = gamma.shape[0]
    return _stats.recover_rhos(gamma, scores, hi=spec.upper(m),
                               lo=spec.lower(m), m=m, tol=tol)


def with_quantile_offsets(model: "OCSSVMModel") -> "OCSSVMModel":
    """Beyond-paper robustness: primal-consistent slab offsets.

    KKT analysis of the reduced dual (DESIGN.md §7) shows rho1 = rho2 at
    any optimum with free SVs on both planes — the slab collapses and the
    sign classifier degenerates (scores still RANK correctly, since the
    decision value is -(s - rho)^2). The primal, for the fitted w, is
    minimized by quantile offsets instead:

        d/drho1 [-rho1 + 1/(nu1 m) sum max(0, rho1 - s_i)] = 0
            -> rho1 = nu1-quantile of scores
        d/drho2 [ eps rho2 + eps/(nu2 m) sum max(0, s_i - rho2)] = 0
            -> rho2 = (1 - nu2)-quantile of scores

    which restores a usable slab whenever w != 0. Paper-faithful margin-SV
    recovery (eq. 20-21) stays the default everywhere else.
    """
    s = model.raw_scores(model.X)
    rho1 = jnp.quantile(s, model.spec.nu1)
    rho2 = jnp.quantile(s, 1.0 - model.spec.nu2)
    return model._replace(rho1=rho1, rho2=rho2)


def compact_support(model: "OCSSVMModel",
                    threshold: float = 1e-7) -> "OCSSVMModel":
    """Drop non-support rows: keep only |gamma_i| > threshold.

    Serving never needs the full training set — scoring cost is
    O(n_sv * d) per query, and after convergence most coordinates sit at
    exactly 0 or below ``threshold``. The returned model's
    ``decision_function`` differs from the full model's by at most
    ``sum(|dropped gamma|) * max_k |k|`` (each dropped coefficient is
    <= threshold), which is the bound ``docs/serving.md`` documents.

    Host-side (concrete arrays): compaction changes shapes, so it cannot
    live under jit; it runs once per fitted model in the serving cache.
    """
    g = np.asarray(model.gamma)
    idx = np.nonzero(np.abs(g) > threshold)[0]
    idx_j = jnp.asarray(idx, jnp.int32)
    return model._replace(gamma=jnp.asarray(model.gamma)[idx_j],
                          X=jnp.asarray(model.X)[idx_j])


def dual_objective(gamma: Array, K: Array) -> Array:
    """1/2 gamma^T K gamma (eq. 30)."""
    return 0.5 * dot(gamma, dot(K, gamma))


def dual_objective_matfree(gamma: Array, X: Array, kernel: KernelFn) -> Array:
    """Objective without materializing K.

    Below the engine's single-pass threshold (the same one
    ``raw_scores_blocked`` uses) one cross-kernel pass suffices; above it
    the quadratic form is accumulated over row blocks.
    """
    if X.shape[0] <= SINGLE_PASS_MAX:
        return 0.5 * dot(gamma, dot(kernel.cross(X, X), gamma))
    return _blocked_obj(gamma, X, kernel)


def _blocked_obj(gamma: Array, X: Array, kernel: KernelFn, block: int = 2048) -> Array:
    m = X.shape[0]
    nblk = (m + block - 1) // block
    pad = nblk * block - m
    Xp = jnp.pad(X, ((0, pad), (0, 0)))
    gp = jnp.pad(gamma, (0, pad))

    def body(i, acc):
        xb = jax.lax.dynamic_slice_in_dim(Xp, i * block, block)
        gb = jax.lax.dynamic_slice_in_dim(gp, i * block, block)
        return acc + dot(gb, dot(kernel.cross(xb, Xp), gp))

    return 0.5 * jax.lax.fori_loop(0, nblk, body, jnp.zeros((), gamma.dtype))
