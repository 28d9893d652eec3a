"""The program's host spans: recorded by the profiler on the CPU, read
back with the benchmark's trace loader (``bench.lib.trace.load``, with
each thread told apart by ``bench.lib.spans.host_events``), and checked
for their names, threads and nesting."""
import time

import jax
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

import repro
from bench.lib import spans, trace as tracing
from repro.core import SlabSpec, rbf
from repro.data import make_toy
from repro.serve import AdmissionController, AsyncDriver, ModelRegistry

SPEC = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))


def record(path, fn):
    with jax.profiler.trace(str(path)):
        fn()
    xplane = tracing.find_xplane(str(path))
    tr = tracing.load(xplane)
    tr.host = spans.host_events(xplane)
    return tr


def named(tr, prefix):
    return sorted((e for e in tr.host
                   if e.name == prefix or e.name.startswith(prefix + ".")),
                  key=lambda e: e.start)


def inside(child, parent):
    return (child.thread == parent.thread and parent.start <= child.start
            and child.end <= parent.end)


def test_shrinking_fit_spans_nest_inside_fit(tmp_path):
    """A cold fit that repacks once: phase 1, a sweep, a repack, the
    repacked solve, a sweep that finds it converged, the rescoring."""
    X = make_toy(jax.random.PRNGKey(0), 1024)[0]

    def fit():
        res = repro.fit(X, SPEC, strategy="shrinking",
                        gram_mode="precomputed", tol=1e-3, warm_iters=30)
        jax.block_until_ready(res.f)
        assert bool(res.converged)

    fit()                                   # compiled outside the trace
    tr = record(tmp_path, fit)
    spans = named(tr, "fit")
    top = [e for e in spans if e.name == "fit"]
    assert len(top) == 1
    inner = [e for e in spans if e.name != "fit"]
    assert [e.name for e in inner] == [
        "fit.solve", "fit.kkt_sweep", "fit.repack", "fit.solve",
        "fit.kkt_sweep", "fit.rescore"]
    assert all(inside(e, top[0]) for e in inner)
    assert all(a.end <= b.start for a, b in zip(inner, inner[1:]))


@pytest.fixture()
def served():
    X = make_toy(jax.random.PRNGKey(5), 48)[0]
    reg = ModelRegistry()
    reg.register("a", X, SPEC, tol=1e-2, max_outer=60)
    ctrl = AdmissionController(reg, max_wait_s=0.0)
    ctrl.service("a").warmup()              # fit and compile up front
    return ctrl, np.asarray(X[:3], np.float32)


def test_served_flush_spans_nest_in_order(tmp_path, served):
    """One request through the driver thread: its poll holds the flush,
    and the flush holds pad, launch, fetch and scatter, in that order."""
    ctrl, q = served

    def serve():
        with AsyncDriver(ctrl), TraceAnnotation("test.client"):
            h = ctrl.submit("a", q)
            t0 = time.monotonic()
            while not h.done and time.monotonic() - t0 < 30.0:
                time.sleep(0.001)           # the driver flushes, not us
            assert h.done
        assert h.result().shape == (3,)

    tr = record(tmp_path, serve)
    flushes = named(tr, "serve.flush")
    assert len(flushes) == 1
    flush = flushes[0]
    parts = [e for e in named(tr, "serve") if inside(e, flush)
             and e is not flush]
    assert [e.name for e in parts] == ["serve.pad", "serve.launch",
                                       "serve.fetch", "serve.scatter"]
    assert all(a.end <= b.start for a, b in zip(parts, parts[1:]))
    polls = [e for e in named(tr, "serve.poll") if inside(flush, e)]
    assert len(polls) == 1
    # the driver thread parked, and it is not the thread that submitted
    assert any(e.name == "serve.park" and e.thread == flush.thread
               for e in tr.host)
    assert flush.thread != next(e for e in tr.host
                                if e.name == "test.client").thread
