"""Shared tile plumbing for the Pallas kernel wrappers.

Lives in its own module (no ``repro.core`` dependency) so every kernel
family — and the engine providers that call them — can import these
helpers from any entry point without touching the
``repro.kernels <-> repro.core`` package boundary: importing
``repro.kernels`` first used to deadlock the partially-initialized
``gram.ops`` module when ``fupdate.ops`` pulled the helpers from it
mid-cycle.

Besides the padding/interpret helpers this module owns **trace-time
tile-config resolution**: each kernel wrapper (``gram/fupdate/decision
ops.py``) calls :func:`resolve_tiles` with its family, problem shape,
precision and backend, and gets back the block sizes to launch with.
Resolution precedence, highest first:

1. explicit ``tm=/tn=/tk=`` kwargs at the call site — passing ANY block
   kwarg opts the call out of the tuned table entirely (the remaining
   fields come from the defaults (4.), never from the table, so a
   hand-steered launch is fully predictable);
2. ``REPRO_NO_AUTOTUNE=1`` in the environment — the escape hatch that
   forces the defaults everywhere (read at trace time, like
   ``REPRO_INTERPRET``: flip it before the first kernel call of the
   process);
3. the committed tuned table ``tuned_configs.json`` (written by
   ``benchmarks/autotune_kernels.py --update-table``), keyed on
   ``(family, m, d, precision, backend)`` with nearest-shape fallback
   (log-distance over (m, d), capped at :data:`NEAREST_MAX_DIST`);
4. the defaults: :data:`DEFAULT_CONFIGS` for ``gram`` and ``decision``;
   for ``fupdate`` tiles derived from the shapes (:func:`fupdate_tk`,
   :func:`fupdate_tm`).

Resolution happens at trace time (shapes are static under ``jit``), so
a table swap after a shape's first trace does NOT retrace it — the
compiled executable keeps the config it was traced with. Tests that
install a synthetic table (:func:`set_tuned_table`) therefore use fresh
shapes to force a retrace. See docs/kernels.md.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass, replace
from functools import lru_cache
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.precision import tile_dtype

# MXU/VPU lane width: every block dimension must be a multiple of this.
LANE = 128

# In-flight buffer depths the autotuner may commit (double / quad
# buffering). Depth is consumed by the autotuner's VMEM-feasibility
# model and recorded in the table for the roofline rows; the Pallas
# pipeline itself is compiler-managed (double-buffered by default).
DEPTHS = (2, 4)

# Nearest-shape fallback cap: |log2(m/m')| + |log2(d/d')| beyond which a
# table entry is considered too far from the requested shape to trust.
NEAREST_MAX_DIST = 2.0

# The committed autotune table, produced by
# ``benchmarks/autotune_kernels.py --quick --update-table``.
TUNED_TABLE_PATH = Path(__file__).resolve().parent / "tuned_configs.json"


@dataclass(frozen=True)
class TileConfig:
    """Block sizes (and buffer depth) for one kernel launch.

    ``block_n`` / ``block_k`` are ``None`` where the family has no such
    axis (fupdate has no n-blocking — the selected block is resident;
    decision keeps the feature dim whole, so no k-blocking). ``source``
    records how the config was chosen: "default", "explicit",
    "table-exact" or "table-nearest".
    """

    block_m: int
    block_n: Optional[int]
    block_k: Optional[int]
    depth: int = 2
    source: str = "default"


# The fixed constants, still the fallback of gram and decision wherever
# the table has nothing to say. (gram: (tm, tn, tk); fupdate: (tm, -, tk);
# decision: (tm, tn, -).) fupdate's entry only says which axes it has:
# its default tiles come from the shapes (fupdate_tk, fupdate_tm).
DEFAULT_CONFIGS = {
    "gram": TileConfig(256, 256, 512),
    "fupdate": TileConfig(512, None, 512),
    "decision": TileConfig(256, 512, None),
}
FAMILIES = tuple(DEFAULT_CONFIGS)

# fupdate streams X at its real lane width: the k tile is d rounded up to
# a lane multiple (128 lanes at d=30, 768 at d=768), one k step up to
# this width and equal lane-multiple steps above it.
FUPDATE_TK_CAP = 1024
# fupdate's row tile: the largest power-of-two multiple of LANE up to
# the cap whose VMEM working set fits the budget. The (S, TM) accumulator
# grows with the selected block (S up to engine BLOCK = 2048 rows), so
# TM shrinks as S grows. The budget leaves room under v5e's 16 MiB
# default scoped VMEM.
FUPDATE_TM_CAP = 4096
FUPDATE_VMEM_BUDGET = 10 * 1024 * 1024


def round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


# fupdate rounds its selected block up to a multiple of this many rows:
# one sublane tile of a 16-bit stream, two of f32. The epilogue sums the
# block in groups of 8 rows, so its loop never runs a single trip, which
# XLA's interpreter path would inline and fuse differently: padded rows
# then leave f bit-identical there as on the chip.
SEL_ROWS = 16


def fupdate_tk(d: int) -> int:
    """fupdate's k tile from the feature width d (see FUPDATE_TK_CAP)."""
    dp = round_up(max(d, 1), LANE)
    nk = -(-dp // FUPDATE_TK_CAP)
    return round_up(-(-dp // nk), LANE)


def fupdate_tm(m: int, s: int, tk: int, precision: str) -> int:
    """fupdate's row tile for m rows, a selected block of s rows and a
    k tile of tk lanes (see FUPDATE_TM_CAP)."""
    item = jnp.dtype(tile_dtype(precision)).itemsize
    s_pad = round_up(max(s, 1), SEL_ROWS)
    # Resident in VMEM, double-buffered: the selected block, and its
    # norms and deltas as (s_pad, 1) columns (one lane tile wide).
    fixed = 2 * s_pad * tk * item + 2 * 2 * s_pad * LANE * 4
    # Per row of the tile: the X block (two buffers), the accumulator and
    # the dot's result (f32), and the three (1, TM) f32 vectors (norms,
    # f, out; two buffers each, a VMEM tile of 8 sublanes).
    per_row = 2 * tk * item + 2 * s_pad * 4 + 3 * 2 * 8 * 4
    tm = FUPDATE_TM_CAP
    while tm > LANE and fixed + tm * per_row > FUPDATE_VMEM_BUDGET:
        tm //= 2
    return min(tm, round_up(max(m, 1), LANE))


def default_tiles(family: str, *, m: int, d: int, precision: str,
                  s: Optional[int] = None,
                  block_k: Optional[int] = None) -> TileConfig:
    """A family's tiles where neither the call nor the table sets them:
    :data:`DEFAULT_CONFIGS` for gram and decision; for fupdate, tk from
    d (or the given ``block_k``) and tm from (m, s, tk, precision)."""
    if family != "fupdate":
        return DEFAULT_CONFIGS[family]
    tk = block_k if block_k is not None else fupdate_tk(d)
    return TileConfig(fupdate_tm(m, s if s is not None else 1, tk,
                                 precision), None, tk)


def _pad_to(a, mult, axis):
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _auto_interpret() -> bool:
    """interpret-mode default: REPRO_INTERPRET env override, else backend.

    CI sets REPRO_INTERPRET=1 so the kernels-interpret job is deterministic
    regardless of which backend jax resolves. Read at trace time: flip the
    variable before the first kernel call of the process.
    """
    env = os.environ.get("REPRO_INTERPRET", "").strip().lower()
    if env in ("1", "true", "on"):
        return True
    if env in ("0", "false", "off"):
        return False
    return jax.default_backend() != "tpu"


def _no_autotune() -> bool:
    """REPRO_NO_AUTOTUNE=1 disables the tuned table (trace-time read)."""
    return os.environ.get("REPRO_NO_AUTOTUNE", "").strip().lower() in (
        "1", "true", "on")


def backend_name(interpret: bool) -> str:
    """The backend key a kernel launch tunes under.

    Interpret-mode launches are their own backend ("interpret"): an
    emulated sweep says nothing about MXU timings, so a table produced
    on CPU CI never leaks configs into real TPU launches — those miss
    the table (backend "tpu") and fall back to the defaults until a
    sweep is run on hardware.
    """
    return "interpret" if interpret else jax.default_backend()


# ---------------------------------------------------------------------------
# tuned-table loading + validation
# ---------------------------------------------------------------------------

_REQUIRED_ENTRY_KEYS = ("family", "m", "d", "precision", "backend",
                        "block_m", "depth")

# Test hook: a dict/path installed via set_tuned_table, or None for the
# committed TUNED_TABLE_PATH.
_table_override = None


def _validate_entry(e: dict) -> dict:
    if not all(k in e for k in _REQUIRED_ENTRY_KEYS):
        missing = [k for k in _REQUIRED_ENTRY_KEYS if k not in e]
        raise ValueError(f"tuned-table entry missing keys {missing}: {e}")
    fam = e["family"]
    if fam not in FAMILIES:
        raise ValueError(f"tuned-table entry has unknown family {fam!r} "
                         f"(expected one of {FAMILIES})")
    tmpl = DEFAULT_CONFIGS[fam]
    for key, applicable in (("block_m", True),
                            ("block_n", tmpl.block_n is not None),
                            ("block_k", tmpl.block_k is not None)):
        v = e.get(key)
        if not applicable:
            if v is not None:
                raise ValueError(
                    f"tuned-table entry sets {key}={v} but family {fam!r} "
                    f"has no such axis: {e}")
            continue
        if not isinstance(v, int) or v <= 0 or v % LANE:
            raise ValueError(
                f"tuned-table entry {key}={v!r} must be a positive "
                f"multiple of {LANE}: {e}")
    if e["depth"] not in DEPTHS:
        raise ValueError(f"tuned-table entry depth={e['depth']!r} not in "
                         f"{DEPTHS}: {e}")
    if int(e["m"]) <= 0 or int(e["d"]) <= 0:
        raise ValueError(f"tuned-table entry needs positive m/d: {e}")
    return e


def _entries_from_doc(doc: dict) -> tuple:
    if not isinstance(doc, dict) or "entries" not in doc:
        raise ValueError("tuned table must be a dict with an 'entries' list")
    return tuple(_validate_entry(dict(e)) for e in doc["entries"])


@lru_cache(maxsize=None)
def _load_table_file(path_str: str) -> tuple:
    with open(path_str) as fh:
        return _entries_from_doc(json.load(fh))


def set_tuned_table(table) -> None:
    """Install a tuned table for this process (test hook).

    ``table`` is a dict in the ``tuned_configs.json`` format, a path to
    one, or ``None`` to restore the committed table. Validation happens
    eagerly for dicts (a broken synthetic table fails here, not at the
    first kernel launch). NOTE: already-traced shapes keep the configs
    they were traced with — use fresh shapes after swapping the table.
    """
    global _table_override
    if isinstance(table, dict):
        _entries_from_doc(table)   # eager validation
    _table_override = table
    _load_table_file.cache_clear()


def _table_entries() -> tuple:
    src = _table_override
    if src is None:
        if not TUNED_TABLE_PATH.exists():
            return ()
        return _load_table_file(str(TUNED_TABLE_PATH))
    if isinstance(src, (str, Path)):
        return _load_table_file(str(src))
    return _entries_from_doc(src)


def lookup_tuned(family: str, m: int, d: int, precision: str,
                 backend: str) -> Optional[TileConfig]:
    """Exact (family, m, d, precision, backend) hit, else the nearest
    same-(family, precision, backend) entry by |log2 m ratio| +
    |log2 d ratio| within :data:`NEAREST_MAX_DIST`, else ``None``.
    """
    best = None
    best_dist = None
    for e in _table_entries():
        if (e["family"] != family or e["precision"] != precision
                or e["backend"] != backend):
            continue
        dist = (abs(math.log2(max(m, 1) / e["m"]))
                + abs(math.log2(max(d, 1) / e["d"])))
        if dist > NEAREST_MAX_DIST:
            continue
        # prefer smaller distance; on ties, the larger tuned m (closer
        # to the asymptotic regime)
        if (best is None or dist < best_dist
                or (dist == best_dist and e["m"] > best["m"])):
            best, best_dist = e, dist
    if best is None:
        return None
    return TileConfig(
        block_m=best["block_m"], block_n=best.get("block_n"),
        block_k=best.get("block_k"), depth=best["depth"],
        source="table-exact" if best_dist == 0.0 else "table-nearest")


def resolve_tiles(family: str, *, m: int, d: int, precision: str,
                  backend: str, block_m: Optional[int] = None,
                  block_n: Optional[int] = None,
                  block_k: Optional[int] = None,
                  s: Optional[int] = None) -> TileConfig:
    """Pick the launch config for one kernel call (trace time).

    ``m``/``d`` are the family's table key: the streamed-majority row
    count (gram: max(M, N); fupdate: the X rows; decision: the support
    rows) and the logical feature dim. ``block_*`` are the wrapper's
    explicit kwargs — any of them being set wins over the table (the
    unset rest come from the defaults). ``s`` is fupdate's selected-block
    size, which its default row tile depends on. See the module
    docstring for the full precedence.
    """
    if family not in FAMILIES:
        raise ValueError(f"unknown kernel family {family!r}; "
                         f"expected one of {FAMILIES}")
    default = default_tiles(family, m=m, d=d, precision=precision, s=s,
                             block_k=block_k)
    if block_m is not None or block_n is not None or block_k is not None:
        return replace(
            default,
            block_m=block_m if block_m is not None else default.block_m,
            block_n=block_n if block_n is not None else default.block_n,
            block_k=block_k if block_k is not None else default.block_k,
            source="explicit")
    if _no_autotune():
        return default
    tuned = lookup_tuned(family, m, d, precision, backend)
    return tuned if tuned is not None else default
