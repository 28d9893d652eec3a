"""Mercer kernel functions for the OCSSVM dual.

All kernels expose three access patterns the SMO solver needs:

* ``gram(X)``        — full m x m Gram matrix (small-m / test path only).
* ``cross(X, Y)``    — m x n cross-kernel block (decision function, blocked SMO).
* ``rows(X, idx)``   — k(X, X[idx]) rows computed on the fly (large-m path;
                       this is what the Pallas ``fupdate`` kernel fuses).

Everything is pure jnp and jit-friendly; the kernel choice is static.

The dot products run at ``Precision.HIGHEST`` (``dot``): XLA's default
on a TPU computes an f32 matmul in one bf16 pass, and the RBF distance
``||x||^2 + ||y||^2 - 2 x.y`` cancels that error into the kernel value,
so the shrinking driver's KKT sweeps disagree with the f32 Pallas hot
loop. ``ocssvm.py``'s dual objectives and ``raw_scores`` use ``dot``
for their matvecs too, so the checks that read them are full f32; the
solver's own K @ gamma matvecs keep XLA's default. On the CPU it
changes nothing.
"""
from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp

Array = jax.Array


def dot(a: Array, b: Array) -> Array:
    """a @ b in full f32 on every backend (see the module docstring)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _dot_t(X: Array, Y: Array) -> Array:
    return dot(X, Y.T)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class KernelFn:
    """A Mercer kernel with static name and traced hyper-parameters.

    name: one of {"linear", "rbf", "poly"}.
    gamma: RBF width / poly scale (ignored for linear).
    coef0, degree: poly parameters.
    """

    name: str = "linear"
    gamma: float = 1.0
    coef0: float = 0.0
    degree: int = 3

    # -- pytree plumbing (name/degree static; gamma/coef0 traced) ----------
    def tree_flatten(self):
        return (self.gamma, self.coef0), (self.name, self.degree)

    @classmethod
    def tree_unflatten(cls, aux, children):
        gamma, coef0 = children
        name, degree = aux
        return cls(name=name, gamma=gamma, coef0=coef0, degree=degree)

    # -- core evaluations ---------------------------------------------------
    def cross(self, X: Array, Y: Array) -> Array:
        """K[i, j] = k(X[i], Y[j]); shapes (m, d), (n, d) -> (m, n)."""
        if self.name == "linear":
            return _dot_t(X, Y)
        if self.name == "rbf":
            # ||x - y||^2 = ||x||^2 + ||y||^2 - 2 x.y, computed in f32.
            xx = jnp.sum(X * X, axis=-1, keepdims=True)
            yy = jnp.sum(Y * Y, axis=-1, keepdims=True)
            sq = xx + yy.T - 2.0 * _dot_t(X, Y)
            return jnp.exp(-self.gamma * jnp.maximum(sq, 0.0))
        if self.name == "poly":
            return (self.gamma * _dot_t(X, Y) + self.coef0) ** self.degree
        raise ValueError(f"unknown kernel {self.name!r}")

    def gram(self, X: Array) -> Array:
        return self.cross(X, X)

    def rows(self, X: Array, Xsel: Array) -> Array:
        """k(X, Xsel) -> (m, k). ``Xsel`` is a gathered (k, d) block."""
        return self.cross(X, Xsel)

    def diag(self, X: Array) -> Array:
        """k(x_i, x_i) for every row — needed for eta without the Gram."""
        if self.name == "linear":
            return jnp.sum(X * X, axis=-1)
        if self.name == "rbf":
            return jnp.ones((X.shape[0],), X.dtype)
        if self.name == "poly":
            return (self.gamma * jnp.sum(X * X, axis=-1) + self.coef0) ** self.degree
        raise ValueError(f"unknown kernel {self.name!r}")


def linear() -> KernelFn:
    return KernelFn(name="linear")


def rbf(gamma: float = 1.0) -> KernelFn:
    return KernelFn(name="rbf", gamma=gamma)


def poly(gamma: float = 1.0, coef0: float = 1.0, degree: int = 3) -> KernelFn:
    return KernelFn(name="poly", gamma=gamma, coef0=coef0, degree=degree)
