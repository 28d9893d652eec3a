"""v5e compile rehearsals of the main-path Pallas kernels.

The TPU compiler is installed even where no chip is attached: each case
compiles a kernel wrapper with ``interpret=False`` for one chip of a
described ``v5e:2x2`` topology, at the deployment widths (d=30 tabular,
d=768 embeddings) and stream precisions (f32, bf16). That catches what
interpret mode cannot — tiling, alignment and VMEM refusals — without a
chip. Nothing runs, so these say nothing about results or times.

The topology is described inside a module-scoped fixture only: loading
the TPU library takes a process-wide lock, so it must never happen while
a module is imported or collected. Keep these tests in this one file.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.core import rbf
from repro.kernels.decision.ops import decision_packed
from repro.kernels.fupdate.ops import fupdate
from repro.kernels.gram.ops import gram

KERNEL = rbf(gamma=0.05)
WIDTHS = (30, 768)
PRECISIONS = ("f32", "bf16")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _launch(family, sharding, d, precision):
    """(jitted wrapper, arg shapes) of one family at a deployment size:
    the training f-update over 65,536 rows with a 16-row selected block,
    a 4096^2 Gram, and a top-bucket serving launch against 8192 packed
    support rows."""
    s = partial(_spec, sharding)
    if family == "fupdate":
        fn = partial(fupdate, kernel=KERNEL, interpret=False,
                     precision=precision)
        return fn, (s((65_536, d)), s((16, d)), s((16,)), s((65_536,)))
    if family == "gram":
        fn = partial(gram, kernel=KERNEL, interpret=False,
                     precision=precision)
        return fn, (s((4096, d)), s((4096, d)))
    d_pad = -(-d // 128) * 128
    tile = jnp.bfloat16 if precision == "bf16" else jnp.float32
    fn = partial(decision_packed, kernel=KERNEL, tm=256, tn=512,
                 interpret=False, precision=precision)
    return fn, (s((4096, d_pad)), s((8192, d_pad), tile), s((8192, 1)),
                s((8192, 1)), s(()), s(()))


@pytest.mark.parametrize("precision", PRECISIONS)
@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("family", ("fupdate", "gram", "decision_packed"))
def test_kernel_compiles_for_v5e(one_chip, family, d, precision):
    fn, args = _launch(family, one_chip, d, precision)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("family", ("fupdate", "gram", "decision_packed"))
def test_f16_compiled_launch_is_refused_before_mosaic(one_chip, family):
    """Mosaic refuses f16 vector loads on v5e with an internal compiler
    error; the wrapper must refuse first, with a ValueError that says
    why."""
    fn, args = _launch(family, one_chip, 30, "f16")
    with pytest.raises(ValueError, match="f16.*compiled Pallas kernel"):
        jax.jit(fn).lower(*args).compile()
