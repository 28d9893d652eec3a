"""Pallas kernel tests: shape/dtype sweeps, assert_allclose vs ref.py
oracles, interpret=True (CPU) execution of the same BlockSpec tiling, and
the mixed-precision parity matrix
{f32, bf16, f16} x {gram, fupdate, decision_packed} x {rbf, linear, poly}
(dtype-matched refs at tight tolerance; f32-truth at the documented
per-dtype tolerance; precision="f32" bit-identical to the default path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import linear, poly, rbf
from repro.kernels import decision, fupdate, gram
from repro.kernels.decision.ops import decision_packed
from repro.kernels.decision.ref import decision_ref
from repro.kernels.fupdate.ops import prepare_x
from repro.kernels.fupdate.ref import fupdate_ref
from repro.kernels.gram.ref import gram_ref
from repro.kernels.precision import (PRECISIONS, round_to_tile, tile_dtype,
                                     truth_tolerance)
from repro.kernels.tiling import fupdate_tk

KERNELS = [linear(), rbf(gamma=0.35), poly(gamma=0.2, coef0=1.0, degree=2)]
SHAPES = [(16, 8, 3), (100, 50, 7), (256, 256, 64), (300, 130, 129),
          (512, 600, 40)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kern", KERNELS, ids=lambda k: k.name)
def test_kernel_fn_dots_request_full_f32(kern):
    """The jnp kernel evaluations the solver runs outside Pallas must ask
    for HIGHEST precision: on a TPU the default computes an f32 matmul
    in one bf16 pass."""
    X = jnp.ones((8, 3), jnp.float32)
    jaxpr = jax.make_jaxpr(kern.cross)(X, X)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert dots
    hi = jax.lax.Precision.HIGHEST
    for e in dots:
        assert e.params["precision"] in (hi, (hi, hi)), e.params["precision"]


def _dot_generals(jaxpr):
    """Every dot_general in ``jaxpr`` and its sub-jaxprs (the Pallas
    kernel body included)."""
    for e in jaxpr.eqns:
        if e.primitive.name == "dot_general":
            yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _dot_generals(sub)


@pytest.mark.parametrize("precision", ["f32", "bf16"])
@pytest.mark.parametrize("family", ["gram", "fupdate", "decision"])
def test_pallas_f32_dots_request_full_f32(family, precision):
    """Every in-kernel dot on f32 operands asks for HIGHEST: Mosaic's
    default on a TPU contracts f32 in one bf16 pass. 16-bit tiles keep
    the default (their products are exact in f32)."""
    kern = rbf(gamma=0.35)
    X = jnp.ones((16, 8), jnp.float32)
    v = jnp.ones((16,), jnp.float32)
    fn = {"gram": lambda: gram(X, X, kern, interpret=True,
                               precision=precision),
          "fupdate": lambda: fupdate(X, X[:4], v[:4], v, kern,
                                     interpret=True, precision=precision),
          "decision": lambda: decision(X, X, v, 0.1, 0.2, kern,
                                       interpret=True, precision=precision),
          }[family]
    dots = list(_dot_generals(jax.make_jaxpr(fn)().jaxpr))
    assert dots
    hi = jax.lax.Precision.HIGHEST
    for e in dots:
        f32 = all(a.aval.dtype == jnp.float32 for a in e.invars)
        full = e.params["precision"] in (hi, (hi, hi))
        assert full == f32, (e.params["precision"],
                             [a.aval.dtype for a in e.invars])


@pytest.mark.parametrize("kern", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_gram_matches_ref(kern, shape, dtype):
    m, n, d = shape
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    X = jax.random.normal(k1, (m, d), dtype)
    Y = jax.random.normal(k2, (n, d), dtype)
    out = gram(X, Y, kern, interpret=True)
    ref = gram_ref(X, Y, kind=kern.name, gamma=kern.gamma,
                   coef0=kern.coef0, degree=kern.degree)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol(dtype))


# fupdate's layout cases: m never a tile multiple; d at one lane (1), the
# fraud width (30), one lane tile exactly (128) and one over (129), and
# the embedding width (768); the selected block at its smallest (2), a
# fit's 2P (16), over the 128 lanes (130) and at the engine's BLOCK (2048).
FUPDATE_LAYOUTS = [(1000, 1, 2), (1000, 30, 16), (333, 128, 130),
                   (333, 129, 16), (2100, 30, 2048), (500, 768, 16),
                   (2050, 768, 2048)]


@pytest.mark.parametrize("kern", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("m,d,s", [(64, 16, 2), (200, 33, 5), (512, 128, 16),
                                   (700, 64, 2)] + FUPDATE_LAYOUTS)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_fupdate_matches_ref(kern, m, d, s, dtype):
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    X = jax.random.normal(keys[0], (m, d), dtype)
    Xs = X[:s]
    delta = jax.random.normal(keys[1], (s,), jnp.float32) * 0.1
    f = jax.random.normal(keys[2], (m,), jnp.float32)
    out = fupdate(X, Xs, delta, f, kern, interpret=True)
    ref = fupdate_ref(X, Xs, delta[:, None], f[:, None], kind=kern.name,
                      gamma=kern.gamma, coef0=kern.coef0,
                      degree=kern.degree)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_tol(dtype))


@pytest.mark.parametrize("kern", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("nq,m,d", [(32, 64, 8), (150, 333, 20),
                                    (256, 512, 128)])
def test_decision_matches_ref(kern, nq, m, d):
    keys = jax.random.split(jax.random.PRNGKey(2), 3)
    Q = jax.random.normal(keys[0], (nq, d), jnp.float32)
    T = jax.random.normal(keys[1], (m, d), jnp.float32)
    gv = jax.random.normal(keys[2], (m,), jnp.float32) * 0.05
    out = decision(Q, T, gv, 0.2, 0.8, kern, interpret=True)
    ref = decision_ref(Q, T, gv[:, None], 0.2, 0.8, kind=kern.name,
                       gamma=kern.gamma, coef0=kern.coef0,
                       degree=kern.degree)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_gram_tiling_variants():
    """Different BlockSpec tile sizes give identical results."""
    kern = rbf(gamma=0.5)
    X = jax.random.normal(jax.random.PRNGKey(3), (300, 70), jnp.float32)
    ref = gram_ref(X, X, kind="rbf", gamma=0.5)
    for tm, tn, tk in [(128, 128, 128), (256, 512, 512), (512, 256, 256)]:
        out = gram(X, X, kern, tm=tm, tn=tn, tk=tk, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


def test_fupdate_zero_delta_is_identity():
    kern = linear()
    X = jax.random.normal(jax.random.PRNGKey(4), (128, 32), jnp.float32)
    f = jax.random.normal(jax.random.PRNGKey(5), (128,), jnp.float32)
    out = fupdate(X, X[:4], jnp.zeros((4,)), f, kern, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f), atol=1e-6)


@pytest.mark.parametrize("kern", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("precision", PRECISIONS)
def test_fupdate_pad_region_contributes_exactly_zero(kern, precision):
    """fupdate internally pads the selected block to a lane multiple (and
    rows/features to tile multiples) with zeros. The padded columns carry
    delta == 0, so they must contribute EXACTLY 0 to the f-cache — even
    for RBF, where a zero-padded selected row still has a nonzero kernel
    value against every x (exp(-gamma ||x||^2)), and even in bf16/f16,
    where the norms are computed from the rounded rows (a rounded zero row
    is still exactly zero, so the norms-of-rounded-rows path cannot leak
    a nonzero product into the padded columns). Asserted bitwise: the
    same call with MANUALLY zero-padded (xsel, delta) — crossing the 128
    lane boundary, so the pad geometry actually changes — must return
    f_new bit-for-bit identical to the unpadded call. This is what makes
    ShardedGram.apply_update's per-shard fupdate safe under tile
    rounding."""
    m, d, s = 96, 17, 5          # none of them tile-aligned
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    X = jax.random.normal(keys[0], (m, d), jnp.float32)
    Xs = X[:s]
    delta = jax.random.normal(keys[1], (s,), jnp.float32) * 0.1
    f = jax.random.normal(keys[2], (m,), jnp.float32)

    out = fupdate(X, Xs, delta, f, kern, interpret=True,
                  precision=precision)
    # Push the selected block past the next lane multiple with explicit
    # zero rows / zero deltas: fupdate now pads to 256 instead of 128.
    extra = 128
    Xs_pad = jnp.concatenate([Xs, jnp.zeros((extra, d), jnp.float32)])
    delta_pad = jnp.concatenate([delta, jnp.zeros((extra,), jnp.float32)])
    out_pad = fupdate(X, Xs_pad, delta_pad, f, kern, interpret=True,
                      precision=precision)
    assert bool(jnp.all(out == out_pad)), (
        f"zero-padded selected rows perturbed f ({precision})")


@pytest.mark.parametrize("kern", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("m,d,s", FUPDATE_LAYOUTS)
@pytest.mark.parametrize("precision", ["f32", "bf16"])
def test_fupdate_prepared_x_matches_raw_and_ref(kern, m, d, s, precision):
    """X prepared once (the providers' path) gives f bit-for-bit as X
    prepared inside the call, and both match the dtype-matched ref. The
    prepared form streams X at its lane width from d, keeps m rows
    padded to a lane multiple, and holds the norms lane-dense."""
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    X = jax.random.normal(keys[0], (m, d), jnp.float32)
    Xs = jax.random.normal(keys[3], (s, d), jnp.float32)
    delta = jax.random.normal(keys[1], (s,), jnp.float32) * 0.1
    f = jax.random.normal(keys[2], (m,), jnp.float32)
    prep = prepare_x(X, precision=precision, interpret=True)
    m_pad = -(-m // 128) * 128
    assert prep.x.shape == (m_pad, fupdate_tk(d))
    assert prep.x.dtype == tile_dtype(precision)
    assert prep.xn.shape == (1, m_pad)
    out = fupdate(prep, Xs, delta, f, kern, interpret=True,
                  precision=precision)
    raw = fupdate(X, Xs, delta, f, kern, interpret=True,
                  precision=precision)
    assert bool(jnp.all(out == raw))
    ref = fupdate_ref(X, Xs, delta[:, None], f[:, None], kind=kern.name,
                      gamma=kern.gamma, coef0=kern.coef0,
                      degree=kern.degree, precision=precision)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_MATRIX_TOL)


def test_fupdate_prepared_x_rejects_other_precision_or_tk():
    X = jnp.ones((200, 30), jnp.float32)
    prep = prepare_x(X, precision="f32", interpret=True)
    args = (X[:4], jnp.zeros((4,)), jnp.zeros((200,)), KERNELS[1])
    with pytest.raises(ValueError, match="precision"):
        fupdate(prep, *args, interpret=True, precision="bf16")
    with pytest.raises(ValueError, match="tk"):
        fupdate(prep, *args, interpret=True, tk=256)


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (tuple, list)) else (v,)):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield sub


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and its sub-jaxprs, Pallas kernel
    bodies left out (their operands are VMEM tiles, not HBM arrays)."""
    for e in jaxpr.eqns:
        yield e
        if e.primitive.name != "pallas_call":
            for sub in _sub_jaxprs(e):
                yield from _eqns(sub)


@pytest.mark.parametrize("m", [256, 250], ids=["aligned", "unaligned"])
def test_pallas_solve_loop_streams_prepared_x(m):
    """The pallas solve's while body runs the fupdate kernel on X as the
    provider prepared it: no pad and no norm reduction of an (m, .)
    operand in the loop, and the f-cache reaches the kernel as a (1, m)
    lane-dense row by a reshape (a pad of f alone when m is not a lane
    multiple)."""
    from repro.core import SlabSpec, solve_blocked
    spec = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
    X = jax.random.normal(jax.random.PRNGKey(3), (m, 30), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda X: solve_blocked(
        X, spec, P=4, gram_mode="pallas", interpret=True, tol=1e-3,
        max_outer=5).model.gamma)(X).jaxpr
    loops = [e for e in _eqns(jaxpr) if e.primitive.name == "while"
             and any(b.primitive.name == "pallas_call"
                     for b in _eqns(e.params["body_jaxpr"].jaxpr))]
    assert len(loops) == 1
    body = list(_eqns(loops[0].params["body_jaxpr"].jaxpr))
    m_pad = -(-m // 128) * 128

    def rows(e):
        return {v.aval.shape[0] for v in e.invars
                if getattr(v.aval, "ndim", 0) == 2}

    for e in body:
        if e.primitive.name in ("pad", "reduce_sum"):
            assert not rows(e) & {m, m_pad}, (e.primitive, rows(e))
    calls = [e for e in body if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    shapes = [v.aval.shape for v in calls[0].invars]
    assert shapes[0] == (1, m_pad) and shapes[3] == (1, m_pad)  # xn, f
    assert shapes[4] == (m_pad, 128)                            # X
    pads = [e for e in body if e.primitive.name == "pad"
            and e.invars[0].aval.shape == (m,)]
    assert len(pads) == (0 if m == m_pad else 1)


# -- mixed-precision parity matrix ------------------------------------------
# Each cell checks two things: (1) the Pallas kernel matches the
# dtype-parameterized ref at near-f32 tolerance (both see identical input
# rounding, so only accumulation order differs), and (2) the low-precision
# output is within the DOCUMENTED per-dtype tolerance of f32 truth — the
# bound docs/serving.md advertises.

_MATRIX_TOL = dict(rtol=5e-4, atol=5e-4)


def _matrix_data(m=200, n=130, d=70):
    keys = jax.random.split(jax.random.PRNGKey(42), 4)
    X = jax.random.normal(keys[0], (m, d), jnp.float32)
    Y = jax.random.normal(keys[1], (n, d), jnp.float32)
    gv = jax.random.normal(keys[2], (n,), jnp.float32) * 0.05
    f = jax.random.normal(keys[3], (m,), jnp.float32)
    return X, Y, gv, f


@pytest.mark.parametrize("kern", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("precision", PRECISIONS)
def test_precision_matrix_gram(kern, precision):
    X, Y, _, _ = _matrix_data()
    out = gram(X, Y, kern, interpret=True, precision=precision)
    ref = gram_ref(X, Y, kind=kern.name, gamma=kern.gamma,
                   coef0=kern.coef0, degree=kern.degree,
                   precision=precision)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_MATRIX_TOL)
    truth = gram_ref(X, Y, kind=kern.name, gamma=kern.gamma,
                     coef0=kern.coef0, degree=kern.degree)
    np.testing.assert_allclose(np.asarray(out), np.asarray(truth),
                               **truth_tolerance(precision, truth))


@pytest.mark.parametrize("kern", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("precision", PRECISIONS)
def test_precision_matrix_fupdate(kern, precision):
    X, _, _, f = _matrix_data()
    Xs = X[:6]
    delta = jnp.linspace(-0.1, 0.1, 6, dtype=jnp.float32)
    out = fupdate(X, Xs, delta, f, kern, interpret=True,
                  precision=precision)
    ref = fupdate_ref(X, Xs, delta[:, None], f[:, None], kind=kern.name,
                      gamma=kern.gamma, coef0=kern.coef0,
                      degree=kern.degree, precision=precision)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_MATRIX_TOL)
    truth = fupdate_ref(X, Xs, delta[:, None], f[:, None], kind=kern.name,
                        gamma=kern.gamma, coef0=kern.coef0,
                        degree=kern.degree)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(truth),
                               **truth_tolerance(precision, truth))


def _pack_for_decision(t, gv, precision, tn=512):
    """The pack_model layout at kernel level: t in the serving dtype,
    gamma/norms f32, rows padded to tn, features to 128."""
    m, d = t.shape
    m_pad = -(-m // tn) * tn
    d_pad = -(-d // 128) * 128
    t_pad = jnp.zeros((m_pad, d_pad), jnp.float32).at[:m, :d].set(t)
    t_pad = t_pad.astype(tile_dtype(precision))
    tf = t_pad.astype(jnp.float32)
    t_norms = jnp.sum(tf * tf, axis=-1, keepdims=True)
    gamma_pad = jnp.zeros((m_pad, 1), jnp.float32).at[:m, 0].set(gv)
    return t_pad, gamma_pad, t_norms, d_pad


@pytest.mark.parametrize("kern", KERNELS, ids=lambda k: k.name)
@pytest.mark.parametrize("precision", PRECISIONS)
def test_precision_matrix_decision_packed(kern, precision):
    X, Y, gv, _ = _matrix_data()
    t_pad, gamma_pad, t_norms, d_pad = _pack_for_decision(Y, gv, precision)
    nq = 100
    q_pad = jnp.zeros((256, d_pad), jnp.float32).at[:nq, :X.shape[1]].set(
        X[:nq])
    out = decision_packed(q_pad, t_pad, gamma_pad, t_norms, 0.2, 0.8,
                          kern, interpret=True, precision=precision)[:nq]
    ref = decision_ref(X[:nq], Y, gv[:, None], 0.2, 0.8, kind=kern.name,
                       gamma=kern.gamma, coef0=kern.coef0,
                       degree=kern.degree, precision=precision)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **_MATRIX_TOL)
    truth = decision_ref(X[:nq], Y, gv[:, None], 0.2, 0.8, kind=kern.name,
                         gamma=kern.gamma, coef0=kern.coef0,
                         degree=kern.degree)[:, 0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(truth),
                               **truth_tolerance(precision, truth))


@pytest.mark.parametrize("kern", KERNELS, ids=lambda k: k.name)
def test_precision_f32_bit_identical(kern):
    """precision="f32" must be a no-op: bitwise-equal outputs on every
    kernel family (guards the refactor and any future default change)."""
    X, Y, gv, f = _matrix_data()
    assert bool(jnp.all(
        gram(X, Y, kern, interpret=True) ==
        gram(X, Y, kern, interpret=True, precision="f32")))
    delta = jnp.linspace(-0.1, 0.1, 6, dtype=jnp.float32)
    assert bool(jnp.all(
        fupdate(X, X[:6], delta, f, kern, interpret=True) ==
        fupdate(X, X[:6], delta, f, kern, interpret=True,
                precision="f32")))
    assert bool(jnp.all(
        decision(X, Y, gv, 0.2, 0.8, kern, interpret=True) ==
        decision(X, Y, gv, 0.2, 0.8, kern, interpret=True,
                 precision="f32")))


def test_precision_rejects_unknown():
    X, Y, _, _ = _matrix_data(m=16, n=16, d=8)
    with pytest.raises(ValueError):
        gram(X, Y, KERNELS[0], interpret=True, precision="tf32")
    with pytest.raises(ValueError):
        round_to_tile(X, "int8")


def test_round_to_tile_halves_mantissa_not_values():
    """bf16/f16 round-trips quantize; f32 is the identity."""
    x = jnp.asarray([1.0, 1.0 + 2.0 ** -20, -3.14159], jnp.float32)
    assert bool(jnp.all(round_to_tile(x, "f32") == x))
    xb = round_to_tile(x, "bf16")
    assert xb[1] == xb[0]                      # 2^-20 is below bf16 ulp
    assert float(jnp.max(jnp.abs(xb - x))) <= 2.0 ** -8 * 3.2
    xh = round_to_tile(x, "f16")
    assert float(jnp.max(jnp.abs(xh - x))) <= 2.0 ** -11 * 3.2
