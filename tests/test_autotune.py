"""Autotune-table tests: round-trip through write_table, exact/nearest
lookup, the resolution precedence (explicit kwargs > REPRO_NO_AUTOTUNE >
table > defaults), bitwise parity of tuned vs default tile configs, the
table actually steering ``repro.fit(strategy="pallas")`` launches, and
the BENCH_autotune.json schema surviving check_regression's flattener.

jit caches by (shapes, statics): a table swap does NOT retrace a shape
that already compiled, so every test here uses its own fresh (m, d) to
force a trace under the table it installed (see kernels/tiling.py).
"""
import importlib
import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import rbf
from repro.core.ocssvm import SlabSpec
from repro.kernels import decision, fupdate, gram
from repro.kernels.autotune import (Cell, sweep, winners_to_entries,
                                    write_table)
from repro.kernels.tiling import (TUNED_TABLE_PATH, TileConfig,
                                  default_tiles, lookup_tuned,
                                  resolve_tiles, set_tuned_table)

REPO = os.path.join(os.path.dirname(__file__), "..")


def _entry(family="fupdate", m=512, d=16, precision="f32",
           backend="interpret", block_m=128, block_n=None, block_k=128,
           depth=2, **extra):
    e = dict(family=family, m=m, d=d, precision=precision, backend=backend,
             block_m=block_m, block_n=block_n, block_k=block_k, depth=depth)
    e.update(extra)
    return e


def _table(*entries):
    return {"version": 1, "entries": list(entries)}


@pytest.fixture(autouse=True)
def _restore_table():
    yield
    set_tuned_table(None)


# ---------------------------------------------------------------------------
# table loading / validation / round-trip
# ---------------------------------------------------------------------------

def test_write_table_roundtrip(tmp_path):
    path = tmp_path / "tuned.json"
    doc = write_table([_entry(block_m=256, best_s=1e-3)], path)
    assert path.exists() and len(doc["entries"]) == 1
    set_tuned_table(str(path))
    cfg = lookup_tuned("fupdate", 512, 16, "f32", "interpret")
    assert cfg == TileConfig(256, None, 128, 2, "table-exact")


def test_write_table_merges_on_key(tmp_path):
    path = tmp_path / "tuned.json"
    write_table([_entry(block_m=256), _entry(family="gram", block_n=128)],
                path)
    # same key -> replaced; new key -> appended
    doc = write_table([_entry(block_m=512),
                       _entry(m=1024, block_m=1024)], path)
    keys = {(e["family"], e["m"]) for e in doc["entries"]}
    assert keys == {("fupdate", 512), ("gram", 512), ("fupdate", 1024)}
    by_m = {e["m"]: e for e in doc["entries"] if e["family"] == "fupdate"}
    assert by_m[512]["block_m"] == 512 and by_m[1024]["block_m"] == 1024


@pytest.mark.parametrize("bad", [
    _entry(block_m=100),                       # not a lane multiple
    _entry(family="nope"),                     # unknown family
    _entry(depth=3),                           # depth not in DEPTHS
    _entry(block_n=256),                       # fupdate has no n axis
    _entry(family="decision", block_k=128, block_n=512),  # no k axis
    {k: v for k, v in _entry().items() if k != "block_m"},  # missing key
])
def test_bad_table_rejected_eagerly(bad):
    with pytest.raises(ValueError):
        set_tuned_table(_table(bad))


def test_lookup_exact_and_nearest():
    set_tuned_table(_table(_entry(m=512, block_m=128),
                           _entry(m=4096, block_m=512)))
    assert lookup_tuned("fupdate", 512, 16, "f32",
                        "interpret").source == "table-exact"
    near = lookup_tuned("fupdate", 700, 16, "f32", "interpret")
    assert near.source == "table-nearest" and near.block_m == 128
    # beyond the log-distance cap: both entries too far -> None
    assert lookup_tuned("fupdate", 512, 512, "f32", "interpret") is None
    # other precision / backend / family never match
    assert lookup_tuned("fupdate", 512, 16, "f16", "interpret") is None
    assert lookup_tuned("fupdate", 512, 16, "f32", "tpu") is None
    assert lookup_tuned("gram", 512, 16, "f32", "interpret") is None


def test_lookup_tie_prefers_larger_m():
    # m=1024 is log-equidistant from 512 and 2048
    set_tuned_table(_table(_entry(m=512, block_m=128),
                           _entry(m=2048, block_m=512)))
    assert lookup_tuned("fupdate", 1024, 16, "f32",
                        "interpret").block_m == 512


# ---------------------------------------------------------------------------
# resolution precedence
# ---------------------------------------------------------------------------

def test_explicit_kwargs_beat_table():
    set_tuned_table(_table(_entry(block_m=1024, block_k=256)))
    cfg = resolve_tiles("fupdate", m=512, d=16, precision="f32",
                        backend="interpret", block_m=256)
    # any explicit kwarg opts out of the table entirely: the rest come
    # from the defaults (tk from d: 128), not the table (tk=256)
    assert cfg == TileConfig(256, None, 128, 2, "explicit")


def test_env_escape_hatch_beats_table(monkeypatch):
    set_tuned_table(_table(_entry(block_m=1024)))
    monkeypatch.setenv("REPRO_NO_AUTOTUNE", "1")
    cfg = resolve_tiles("fupdate", m=512, d=16, precision="f32",
                        backend="interpret")
    assert cfg == default_tiles("fupdate", m=512, d=16, precision="f32")
    # explicit kwargs still work under the hatch
    cfg = resolve_tiles("fupdate", m=512, d=16, precision="f32",
                        backend="interpret", block_k=128)
    assert cfg.block_k == 128 and cfg.source == "explicit"


def test_table_then_default():
    set_tuned_table(_table(_entry(block_m=1024, block_k=128)))
    hit = resolve_tiles("fupdate", m=512, d=16, precision="f32",
                        backend="interpret")
    assert (hit.block_m, hit.block_k) == (1024, 128)
    miss = resolve_tiles("fupdate", m=512, d=16, precision="f32",
                        backend="tpu")
    assert miss == default_tiles("fupdate", m=512, d=16, precision="f32")


# ---------------------------------------------------------------------------
# bitwise parity: tuned configs change nothing but speed
# ---------------------------------------------------------------------------

def _bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a).view(np.uint32),
                                  np.asarray(b).view(np.uint32))


def test_gram_bitwise_tuned_vs_default():
    kern = rbf(gamma=0.35)
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    X = jax.random.normal(k1, (512, 16), jnp.float32)
    Y = jax.random.normal(k2, (512, 16), jnp.float32)
    base = gram(X, Y, kern, tm=256, tn=256, tk=512, interpret=True)
    tuned = gram(X, Y, kern, tm=512, tn=512, tk=128, interpret=True)
    _bitwise(base, tuned)


def test_fupdate_bitwise_tuned_vs_default():
    kern = rbf(gamma=0.35)
    keys = jax.random.split(jax.random.PRNGKey(8), 3)
    X = jax.random.normal(keys[0], (512, 16), jnp.float32)
    delta = jax.random.normal(keys[1], (16,), jnp.float32) * 0.1
    f = jax.random.normal(keys[2], (512,), jnp.float32)
    base = fupdate(X, X[:16], delta, f, kern, tm=512, tk=512,
                   interpret=True)
    tuned = fupdate(X, X[:16], delta, f, kern, tm=512, tk=128,
                    interpret=True)
    _bitwise(base, tuned)


def test_decision_bitwise_tuned_vs_default():
    kern = rbf(gamma=0.35)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(k1, (128, 16), jnp.float32)
    t = jax.random.normal(k2, (512, 16), jnp.float32)
    gv = jnp.abs(jax.random.normal(k3, (512,), jnp.float32))
    base = decision(q, t, gv, 0.1, 0.9, kern, tm=256, tn=512,
                    interpret=True)
    tuned = decision(q, t, gv, 0.1, 0.9, kern, tm=128, tn=512,
                     interpret=True)
    _bitwise(base, tuned)


# ---------------------------------------------------------------------------
# the table steers real launches (trace-time recorder)
# ---------------------------------------------------------------------------

@pytest.fixture
def fupdate_recorder(monkeypatch):
    """Record the (tm, tk) every fupdate_pallas launch traces with."""
    # importlib: ``repro.kernels.fupdate`` the *attribute* is the jit'd
    # function (re-exported over the subpackage), so plain dotted import
    # syntax can't reach the ops module
    fops = importlib.import_module("repro.kernels.fupdate.ops")
    real = fops.fupdate_pallas
    seen = []

    def spy(*args, **kwargs):
        seen.append((kwargs["tm"], kwargs["tk"]))
        return real(*args, **kwargs)

    monkeypatch.setattr(fops, "fupdate_pallas", spy)
    return seen


def test_kernel_launch_uses_synthetic_table(fupdate_recorder):
    # fresh shape (m=832, d=24) so the trace happens under this table
    set_tuned_table(_table(_entry(m=832, d=24, block_m=128, block_k=128)))
    kern = rbf(gamma=0.5)
    X = jax.random.normal(jax.random.PRNGKey(3), (832, 24), jnp.float32)
    fupdate(X, X[:8], jnp.ones((8,)) * 0.1, jnp.zeros((832,)), kern,
            interpret=True).block_until_ready()
    assert fupdate_recorder and fupdate_recorder[-1] == (128, 128)


def test_fit_pallas_uses_committed_table(fupdate_recorder):
    # m=576, d=16: a fresh shape that nearest-matches the committed
    # (fupdate, 512, 16, f32, interpret) row. The acceptance path: the
    # table on disk -> resolve_tiles -> the engine's fupdate launches.
    want = lookup_tuned("fupdate", 576, 16, "f32", "interpret")
    assert want is not None, "committed tuned_configs.json lost its " \
        "(fupdate, 512, 16, f32, interpret) row"
    assert want.source == "table-nearest"
    X = jax.random.normal(jax.random.PRNGKey(4), (576, 16), jnp.float32)
    res = repro.fit(X, SlabSpec(), strategy="pallas", interpret=True,
                    max_outer=3)
    assert res.model.gamma.shape == (576,)
    assert fupdate_recorder
    assert all(tmtk == (want.block_m, want.block_k)
               for tmtk in fupdate_recorder)


def test_fit_pallas_rejects_contradictory_gram_mode():
    X = jnp.zeros((64, 4))
    with pytest.raises(ValueError, match="pins gram_mode"):
        repro.fit(X, SlabSpec(), strategy="pallas",
                  gram_mode="precomputed")


def test_fit_bitwise_parity_table_vs_no_autotune():
    """REPRO_NO_AUTOTUNE=1 (fixed constants) and the committed table give
    bit-identical fits. Env + jit caches are per-process state, so each
    side runs in its own subprocess."""
    code = textwrap.dedent("""
        import hashlib, jax, jax.numpy as jnp, numpy as np
        import repro
        from repro.core.ocssvm import SlabSpec
        X = jax.random.normal(jax.random.PRNGKey(11), (640, 16),
                              jnp.float32)
        r = repro.fit(X, SlabSpec(), strategy="pallas", interpret=True,
                      max_outer=25)
        m = r.model
        h = hashlib.sha256(np.asarray(m.gamma).tobytes()).hexdigest()
        print(h, float(m.rho1), float(m.rho2))
    """)
    outs = []
    for no_autotune in ("0", "1"):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
                   JAX_PLATFORMS="cpu", REPRO_NO_AUTOTUNE=no_autotune)
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, env=env,
                           timeout=600)
        assert p.returncode == 0, p.stderr[-3000:]
        outs.append(p.stdout.strip().splitlines()[-1])
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# the sweep itself + the bench JSON schema
# ---------------------------------------------------------------------------

def test_sweep_smoke_and_winner_entries(tmp_path):
    cell = Cell("gram", 256, 256, 8)
    result = sweep((cell,), mode="quick", precisions=("f32",), repeats=1,
                   interpret=True)
    assert result["backend"] == "interpret" and result["winners"]
    for row in result["candidates"]:
        assert row["bound"] in ("memory", "compute")
        assert row["time_s"] > 0 and row["depth"] == 2
    # winners must survive table validation end to end
    doc = write_table(winners_to_entries(result), tmp_path / "t.json")
    set_tuned_table(str(tmp_path / "t.json"))
    assert lookup_tuned("gram", 256, 8, "f32", "interpret") is not None


def test_committed_table_is_valid_and_loaded():
    assert TUNED_TABLE_PATH.exists(), \
        "src/repro/kernels/tuned_configs.json must be committed"
    set_tuned_table(None)
    with open(TUNED_TABLE_PATH) as fh:
        doc = json.load(fh)
    set_tuned_table(doc)   # eager validation of every committed entry
    for fam in ("gram", "fupdate", "decision"):
        assert lookup_tuned(fam, 512, 16, "f32", "interpret") is not None


def test_bench_json_gates_through_check_regression(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "check_regression",
        os.path.join(REPO, "benchmarks", "check_regression.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    baseline = os.path.join(REPO, "results", "BENCH_autotune.json")
    r = mod.compare_pair(baseline, baseline, tolerance=0.25,
                         min_seconds=0.0005, gate_only=r"winners\[")
    # self-compare is clean; only winner rows are gated, candidates are
    # reported below the line
    assert r["ok"] and r["checked_timings"] > 0
    # nothing outside winners[...] is ever gated
    assert all("winners[" in e["path"] or "candidates[" in e["path"]
               for e in r["below_noise_floor"])
    assert any("candidates[" in e["path"] for e in r["below_noise_floor"])
    # a dropped winner row must fail even under --gate-only
    with open(baseline) as fh:
        doc = json.load(fh)
    doc["winners"] = doc["winners"][1:]
    pruned = tmp_path / "pruned.json"
    pruned.write_text(json.dumps(doc))
    r2 = mod.compare_pair(str(pruned), baseline, tolerance=0.25,
                          min_seconds=0.0005, gate_only=r"winners\[")
    assert not r2["ok"] and r2["missing_rows"]
