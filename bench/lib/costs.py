"""Logical operations and bytes of one call of each kernel family.

These count the cell's work, not the program's layout: FLOPs from the
unpadded shapes, and bytes as each operand at its true width and dtype,
moved once per call. So a program change that drops lane or row padding
raises a kernel's roofline share and never changes these counts.

Only the contractions are counted as FLOPs (2 per multiply-add); the
RBF epilogue (a few VPU/EUP operations per kernel entry) is left out, so
a share computed from these is a lower bound on the useful work.
"""
from __future__ import annotations

from dataclasses import dataclass

F32 = 4


@dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def least_s(self, peaks) -> float:
        """The least time the chip could take: the larger of compute
        and memory time at the published peaks."""
        return max(self.flops / peaks.flops_per_s,
                   self.bytes / peaks.hbm_bytes_per_s)

    def bound(self, peaks) -> str:
        return ("compute" if self.flops / peaks.flops_per_s
                >= self.bytes / peaks.hbm_bytes_per_s else "memory")


def fupdate(m: int, d: int, s: int, itemsize: int = F32) -> Cost:
    """f + k(X, X_sel) @ delta for X (m, d) and a selected block of s rows:
    the (m, s) kernel block's contraction over d plus the rank-s matvec.
    Bytes: X and X_sel at d columns, delta, and f read and written."""
    flops = 2.0 * m * s * d + 2.0 * m * s
    nbytes = (m * d + s * d) * itemsize + s * F32 + 2 * m * F32
    return Cost(flops, nbytes)


def decision(nq: int, n_sv: int, d: int, itemsize: int = F32) -> Cost:
    """Slab decision values of nq live query rows against n_sv support
    rows of width d: the (nq, n_sv) kernel block's contraction over d plus
    the matvec with gamma. Bytes: queries and support rows at d columns,
    gamma, and the nq outputs."""
    flops = 2.0 * nq * n_sv * d + 2.0 * nq * n_sv
    nbytes = (nq * d + n_sv * d) * itemsize + n_sv * F32 + nq * F32
    return Cost(flops, nbytes)


def fupdate_calls(reduced, m: int):
    """(rows, launches) of the traced ``fupdate`` launches: the rows of f
    a launch updates are the leading size of its X and f operands (the
    largest among its operands), at most the cell's ``m`` (row padding
    is not work). A fit on a shrunken active set shows as launches with
    fewer rows."""
    for shapes, n in reduced.kernel_shapes.get("fupdate", {}).items():
        rows = max((s[0] for s in shapes if s), default=0)
        if rows:
            yield min(rows, m), n
