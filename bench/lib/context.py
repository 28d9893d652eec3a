"""State of one run: the set-up clock, the measured window, the compile
counter, the optional trace, and the numbers compared with their limits.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from bench.lib import trace as tracing
from bench.lib.compiles import CompileCounter

# Pallas kernels by the name XLA gives their custom call.
KERNELS = ("fupdate", "decision_packed")


class Context:
    def __init__(self, *, name: str, config: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool, devices,
                 t_start: float):
        self.name = name
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = devices
        self.t_start = t_start
        self.counters: Dict[str, float] = {}
        # What the window produced, kept for the controls (bench/control.py)
        self.kept: dict = {}
        self.e2e: Dict[str, float] = {}
        self.checks: List[Tuple[str, float, float]] = []
        self.attempted = 0
        self.failed = 0
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.memory_peak_bytes = 0
        self.reduced: Optional[tracing.Reduced] = None
        self.compiles = CompileCounter()
        self._annotation = None
        self._tmp: Optional[str] = None
        self._t_window = 0.0

    # -- the window ----------------------------------------------------------
    def begin_window(self) -> float:
        """End of set-up: everything after this is measured."""
        import jax
        self.setup_s = time.perf_counter() - self.t_start
        self.say(f"setup_s {self.setup_s!r}")
        # Set-up's objects go to the permanent generation, so the
        # collector's full passes inside the window scan only what the
        # window itself allocates.
        gc.collect()
        gc.freeze()
        if self.trace:
            self._tmp = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # runtime and bench spans only
            jax.profiler.start_trace(self._tmp, profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation(
                tracing.WINDOW_SPAN)
            self._annotation.__enter__()
        self.compiles.on = True
        self._gc_pauses = []
        gc.callbacks.append(self._gc_timer)
        self._t_window = time.perf_counter()
        return self._t_window

    def _gc_timer(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self._gc_pauses.append(
                (info["generation"], time.perf_counter() - self._gc_t0))

    def end_window(self) -> None:
        """Close the window: stop counting and tracing, read the device
        memory peak. The reference runs after this."""
        import jax
        self.window_s = time.perf_counter() - self._t_window
        self.compiles.on = False
        gc.callbacks.remove(self._gc_timer)
        full = [t for g, t in self._gc_pauses if g == 2]
        self.counters.update(
            gc_pause_s=sum(t for _, t in self._gc_pauses),
            gc_full_passes=len(full), gc_full_max_s=max(full, default=0.0))
        self.say(f"garbage collector in the window: "
                 f"{len(self._gc_pauses)} passes, "
                 f"{self.counters['gc_pause_s']:.4f} s, {len(full)} full, "
                 f"longest full {self.counters['gc_full_max_s']:.4f} s")
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.say(f"trace: written in {time.perf_counter() - t:.1f} s")
        stats = [d.memory_stats() or {} for d in self.devices]
        self.memory_peak_bytes = max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)
        self.counters["window_lowerings"] = self.compiles.lowerings
        self.check("window_compiles", self.compiles.compiles, 0)

    def reduce_trace(self, span: Optional[str] = None) -> None:
        """Reduce the window's trace; with ``span``, the host span of one
        unit of work, cut to what the device record holds
        (``trace.recorded_window``)."""
        if not self.trace:
            return
        t = time.perf_counter()
        try:
            tr = tracing.load(tracing.find_xplane(self._tmp))
            t_load = time.perf_counter() - t
            self.reduced = r = tracing.reduce(tr, kernels=KERNELS, span=span)
        finally:
            shutil.rmtree(self._tmp, ignore_errors=True)
        self.say(f"trace: loaded in {t_load:.1f} s, reduced in "
                 f"{time.perf_counter() - t - t_load:.1f} s")
        self.say(f"trace: reduced window {r.window_s!r} s, "
                 f"{100.0 * r.recorded_share!r}% of the traced window"
                 + (f", {r.span_count} {span} spans inside it" if span
                    else ""))

    # -- results -------------------------------------------------------------
    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim
                                         for _, v, lim in self.checks)

    def say(self, msg: str) -> None:
        print(f"[{self.name}] {msg}", file=sys.stderr, flush=True)
