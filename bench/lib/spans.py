"""Reduction of the program's own host spans in a profiler trace.

The program marks its phases with ``TraceAnnotation``s on the profiler's
clock: ``fit`` and ``fit.*`` in ``repro.fit`` and the shrinking driver,
``serve.*`` on the serving path. ``reduce`` sums them inside the window
(the host span ``bench.window``), per name:

* ``count``: spans that overlap the window;
* ``total_s``: their durations, clipped to the window;
* ``self_s``: the same less the time of program spans nested inside them
  on the same thread;
* ``idle_s``: the device's idle time that the span accounts for. Each
  piece of an idle interval goes to the innermost program span covering
  it: of the spans covering it, on any thread, the one that started
  last. A piece no program span covers goes to ``UNCOVERED``. Over all
  names, including ``UNCOVERED``, the parts sum to the window's idle
  time (averaged over devices, as ``trace.Reduced.busy_s`` is).

The split rests on the trace's alignment of the host's clock with the
device's. It has been seen off by about a millisecond: idle time then
moves between neighbouring short spans, while their sum holds.

A trace of a program without spans reduces to its idle time under
``UNCOVERED`` alone, so a reader of a program span's numbers finds
nothing there and reports nothing.

Self times need each span's thread. ``trace.load`` names a host event's
thread by its line's name, and the profiler names every Python thread's
line ``python``; ``host_events`` reads the host events again with each
line told apart.
"""
from __future__ import annotations

import heapq
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from bench.lib.trace import HostEvent, Trace, gaps

UNCOVERED = "(no program span)"


def host_events(path: str) -> List[HostEvent]:
    """The host events of a trace file, as ``trace.load`` reads them but
    with each line's thread named ``<line name>#<plane>.<line>``."""
    from jax.profiler import ProfileData
    out = []
    for p, plane in enumerate(ProfileData.from_file(path).planes):
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            thread = f"{line.name}#{p}.{k}"
            out.extend(HostEvent(e.name, e.start_ns,
                                 e.start_ns + e.duration_ns, thread)
                       for e in line.events if e.duration_ns > 0)
    return out


def is_program_span(name: str) -> bool:
    return name == "fit" or name.startswith(("fit.", "serve."))


@dataclass
class SpanStats:
    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0
    idle_s: float = 0.0


def program_spans(tr: Trace, lo: float, hi: float) -> List[HostEvent]:
    """The program's spans that overlap [lo, hi], clipped to it, in order
    of start."""
    out = [HostEvent(e.name, max(e.start, lo), min(e.end, hi), e.thread)
           for e in tr.host
           if is_program_span(e.name) and e.end > lo and e.start < hi]
    out.sort(key=lambda e: (e.start, -e.end))
    return out


def _self_times(spans: List[HostEvent]) -> List[float]:
    """Each span's duration less its direct children's, thread by
    thread. Spans on one thread nest (a ``with`` block inside another)."""
    own = [e.end - e.start for e in spans]
    stacks: Dict[str, List[int]] = {}
    for i, e in enumerate(spans):
        st = stacks.setdefault(e.thread, [])
        while st and spans[st[-1]].end <= e.start:
            st.pop()
        if st:
            own[st[-1]] -= e.end - e.start
        st.append(i)
    return own


def timeline(spans: List[HostEvent]
             ) -> List[Tuple[float, float, Optional[str]]]:
    """The time the spans cover, cut at their edges, each piece with the
    name of the covering span that started last (of two that started
    together, the one that ends first). Pieces no span covers are left
    out. One sweep over the edges, with a heap of the open spans."""
    edges = sorted({x for e in spans for x in (e.start, e.end)})
    order = sorted(spans, key=lambda e: (e.start, e.end))
    heap: List[Tuple[float, float, int]] = []
    k, out = 0, []
    for a, b in zip(edges, edges[1:]):
        while k < len(order) and order[k].start <= a:
            heapq.heappush(heap, (-order[k].start, order[k].end, k))
            k += 1
        # the top started last; once it has ended it never covers again
        while heap and heap[0][1] <= a:
            heapq.heappop(heap)
        if heap:
            out.append((a, b, order[heap[0][2]].name))
    return out


def split(intervals: List[Tuple[float, float]],
          pieces: List[Tuple[float, float, Optional[str]]]
          ) -> List[Tuple[int, float, float, str]]:
    """Sorted disjoint intervals cut by a ``timeline``: each part with the
    index of its interval and its span's name, or ``UNCOVERED``. The
    parts tile the intervals, in order."""
    out, j = [], 0
    for n, (t0, t1) in enumerate(intervals):
        while j < len(pieces) and pieces[j][1] <= t0:
            j += 1
        t, i = t0, j
        while t < t1:
            if i < len(pieces) and pieces[i][0] < t1:
                a, b, name = pieces[i]
                if a > t:
                    out.append((n, t, a, UNCOVERED))
                    t = a
                out.append((n, t, min(b, t1), name))
                t = min(b, t1)
                i += 1
            else:
                out.append((n, t, t1, UNCOVERED))
                t = t1
    return out


def reduce(tr: Trace) -> Dict[str, SpanStats]:
    lo, hi = tr.window()
    spans = program_spans(tr, lo, hi)
    stats: Dict[str, SpanStats] = {}
    for e, own in zip(spans, _self_times(spans)):
        s = stats.setdefault(e.name, SpanStats())
        s.count += 1
        s.total_s += (e.end - e.start) * 1e-9
        s.self_s += own * 1e-9
    pieces = timeline(spans)
    for ops in tr.devices.values():
        for _, a, b, name in split(gaps(ops, lo, hi), pieces):
            s = stats.setdefault(name, SpanStats())
            s.idle_s += (b - a) * 1e-9 / len(tr.devices)
    return stats


def idle_gaps(tr: Trace, min_s: float = 0.0
              ) -> List[Tuple[float, float, str]]:
    """Idle intervals of the devices inside the window at least ``min_s``
    long, longest first: (seconds from the window's start, seconds, the
    program span that covers most of the interval, else ``UNCOVERED``)."""
    lo, hi = tr.window()
    pieces = timeline(program_spans(tr, lo, hi))
    out = []
    for ops in tr.devices.values():
        long = [g for g in gaps(ops, lo, hi)
                if (g[1] - g[0]) * 1e-9 >= min_s]
        cover: List[Dict[str, float]] = [{} for _ in long]
        for n, a, b, name in split(long, pieces):
            cover[n][name] = cover[n].get(name, 0.0) + (b - a)
        out.extend(((g0 - lo) * 1e-9, (g1 - g0) * 1e-9, max(c, key=c.get))
                   for (g0, g1), c in zip(long, cover))
    return sorted(out, key=lambda g: -g[1])


# -- readings a per-layer metric would report (None where nothing to read) --
def fit_solve_iter_ms(stats: Dict[str, SpanStats], iters: int
                      ) -> Optional[float]:
    """Summed ``fit.solve`` time over the solver iterations, in ms."""
    s = stats.get("fit.solve")
    return s.total_s / iters * 1e3 if s and iters else None


def fit_driver_ms(stats: Dict[str, SpanStats]) -> Optional[float]:
    """Time of a ``fit`` outside its ``fit.solve`` spans, per fit, in ms."""
    f, s = stats.get("fit"), stats.get("fit.solve")
    return (f.total_s - s.total_s) / f.count * 1e3 if f and s else None


def host_io_ms(stats: Dict[str, SpanStats]) -> Optional[float]:
    """Device idle time under ``serve.pad`` and ``serve.fetch``, per
    ``serve.launch``, in ms."""
    n = stats.get("serve.launch")
    if not n:
        return None
    return sum(stats[k].idle_s for k in ("serve.pad", "serve.fetch")
               if k in stats) / n.count * 1e3
