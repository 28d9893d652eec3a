"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

``load`` reads the trace with ``jax.profiler.ProfileData`` into plain
lists: the operations each device ran, and the host's events (the
benchmark's own ``TraceAnnotation`` spans and the runtime's events, per
thread). Everything after that is arithmetic on intervals:

* busy time: the union of a device's operation intervals inside the
  window (the host span ``bench.window``), averaged over the devices;
* kernel time: the summed durations of a kernel's launches, and the
  operand shapes of each launch, read from its custom call's HLO text;
* idle gaps: the holes in the busy union inside the window, each
  labelled by the innermost host event that covers its midpoint.

The profiler keeps a device's operations only up to a point: in a long
window of short launches (a fit cell's loop) the device record stops
while the host runs on. Given the host span of one unit of work
(``span``, a fit), ``reduce`` cuts the window to what the record holds:
see ``recorded_window``.
"""
from __future__ import annotations

import glob
import os
import re
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
# Lines of a device plane that hold one event per operation executed.
OP_LINES = ("XLA Ops",)


@dataclass
class Op:
    name: str           # the HLO instruction's name, e.g. "fupdate.1"
    start: float        # ns
    end: float          # ns
    text: str = ""      # the whole HLO instruction, kept for custom
    #                     calls and control flow only (traces are large)


@dataclass
class HostEvent:
    name: str
    start: float
    end: float
    thread: str


@dataclass
class Trace:
    devices: Dict[str, List[Op]] = field(default_factory=dict)
    host: List[HostEvent] = field(default_factory=list)

    def window(self) -> Tuple[float, float]:
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
        w = max(spans, key=lambda e: e.end - e.start)
        return w.start, w.end


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    tr = Trace()
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            ops = []
            for line in plane.lines:
                if line.name not in OP_LINES:
                    continue
                for e in line.events:
                    text = e.name
                    keep = "custom-call(" in text or _CONTAINER.search(text)
                    ops.append(Op(sys.intern(op_name(text)), e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  text if keep else ""))
            if ops:
                ops.sort(key=lambda o: o.start)
                tr.devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        tr.host.append(HostEvent(e.name, e.start_ns,
                                                 e.start_ns + e.duration_ns,
                                                 line.name))
    return tr


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def recorded_window(tr: Trace, span: str) -> Tuple[float, float]:
    """The window, cut to the device record: where a ``span`` of the
    window opens after the last recorded device operation (on the device
    whose record stops first), the record has stopped inside the window,
    and the cut window ends at the end of the last ``span`` that closes
    at or before that operation (at the operation itself if none does).
    Otherwise the record reaches the window's end and the window stays
    whole. A record that stops inside the window's last ``span`` cannot
    be told from a whole one: that span's lost operations read as idle."""
    lo, hi = tr.window()
    if not tr.devices:
        return lo, hi
    last_op = min(max(o.end for o in ops) for ops in tr.devices.values())
    units = [e for e in tr.host
             if e.name == span and e.start >= lo and e.end <= hi]
    if not any(e.start > last_op for e in units):
        return lo, hi
    return lo, max((e.end for e in units if e.end <= last_op),
                   default=last_op)


def busy_ns(ops: List[Op], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(((o.start, o.end) for o in ops),
                                       lo, hi))


def gaps(ops: List[Op], lo: float, hi: float) -> List[Tuple[float, float]]:
    """Idle holes of the busy union inside [lo, hi]."""
    out, t = [], lo
    for s, e in union(((o.start, o.end) for o in ops), lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


# Control flow spans the operations it runs: kept in the busy union, left
# out of the per-operation breakdown so nothing is counted twice.
_CONTAINER = re.compile(r"\s(while|conditional|call)\(")
_INSTR = re.compile(r"^%?([^\s=]+)\s*=")
_SHAPE = re.compile(r"\b[a-z]+[0-9]*\[([0-9,]*)\]")


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``; a name that
    is not an HLO instruction is kept as it is."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def kernel_ops(ops: List[Op], kernel: str) -> List[Op]:
    """The launches of a Pallas kernel: custom calls that XLA names after
    the jitted function that holds the kernel (``fupdate.1 = ...
    custom-call(...)``)."""
    pat = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    return [o for o in ops if pat.match(o.name) and "custom-call(" in o.text]


def operand_shapes(text: str) -> Tuple[Tuple[int, ...], ...]:
    """The operand shapes of a custom call, in order, from its HLO text:
    ``... custom-call(f32[65536,1]{...} %a, f32[128,1]{...} %b), ...``
    -> ``((65536, 1), (128, 1))``."""
    i = text.find("custom-call(")
    if i < 0:
        return ()
    depth, j = 0, i + len("custom-call")
    for j in range(j, len(text)):
        depth += {"(": 1, ")": -1}.get(text[j], 0)
        if depth == 0:
            break
    return tuple(tuple(int(x) for x in m.group(1).split(",") if x)
                 for m in _SHAPE.finditer(text, i, j))


def label(t0: float, t1: float, host: List[HostEvent]) -> str:
    """The innermost host event covering the gap's midpoint, else 'none'."""
    mid = 0.5 * (t0 + t1)
    best: Optional[HostEvent] = None
    for e in host:
        if e.start <= mid <= e.end and e.name != WINDOW_SPAN:
            if best is None or e.end - e.start < best.end - best.start:
                best = e
    return best.name if best is not None else "none"


@dataclass
class Reduced:
    """What the per-layer readers see of a trace."""

    window_s: float
    busy_s: float                   # averaged over devices
    kernel_s: Dict[str, float]      # summed over devices
    kernel_calls: Dict[str, int]
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    # launches of each kernel by their operand shapes
    kernel_shapes: Dict[str, Dict[tuple, int]] = field(default_factory=dict)
    # the reduced window over the traced one (below 1 where it was cut)
    recorded_share: float = 1.0
    # the unit ``span``s wholly inside the reduced window: count, seconds
    span_count: int = 0
    span_s: float = 0.0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(tr: Trace, kernels=(), top: int = 10,
           span: Optional[str] = None) -> Reduced:
    """The device metrics of the window; with ``span``, of the window cut
    to the device record (``recorded_window``), with the count and the
    seconds of the ``span``s inside it."""
    if not tr.devices:
        raise ValueError("trace has no device operations")
    lo, whole_hi = tr.window()
    hi = recorded_window(tr, span)[1] if span else whole_hi
    units = [e.end - e.start for e in tr.host
             if span and e.name == span and e.start >= lo and e.end <= hi]
    busy = [busy_ns(ops, lo, hi) for ops in tr.devices.values()]
    kernel_s, kernel_calls, fam = {}, {}, {}
    shapes: Dict[str, Dict[tuple, int]] = {k: {} for k in kernels}
    all_gaps = []
    for ops in tr.devices.values():
        inside = [o for o in ops if o.end > lo and o.start < hi]
        for k in kernels:
            ko = kernel_ops(inside, k)
            kernel_s[k] = kernel_s.get(k, 0.0) + sum(o.end - o.start
                                                     for o in ko) * 1e-9
            kernel_calls[k] = kernel_calls.get(k, 0) + len(ko)
            for o in ko:
                sh = operand_shapes(o.text)
                shapes[k][sh] = shapes[k].get(sh, 0) + 1
        for o in inside:
            if not _CONTAINER.search(o.text):
                fam[o.name] = fam.get(o.name, 0.0) + (min(o.end, hi)
                                                      - max(o.start, lo))
        all_gaps.extend(gaps(ops, lo, hi))
    device_ops = sorted(((k, v * 1e-9) for k, v in fam.items()),
                        key=lambda kv: -kv[1])[:top]
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    idle = [(label(s, e, tr.host), (e - s) * 1e-9) for s, e in longest]
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / len(busy) * 1e-9,
                   kernel_s=kernel_s, kernel_calls=kernel_calls,
                   device_ops=device_ops, idle_gaps=idle,
                   kernel_shapes=shapes,
                   recorded_share=(hi - lo) / (whole_hi - lo),
                   span_count=len(units), span_s=sum(units) * 1e-9)
