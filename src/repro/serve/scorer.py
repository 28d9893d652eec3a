"""Batched scoring engine: padding buckets over the Pallas decision kernel.

Every request is padded up to one of ``BUCKETS`` row counts before it
reaches the kernel, so the whole service compiles at most one executable
per (bucket, model) pair — a request of 63, 64 or 65 rows never triggers
a fresh trace. Requests larger than the top bucket are chunked through
it (each chunk reuses the same cached executable).

Two execution paths share the packing:

* local  — ``decision_packed`` (jit; Pallas on TPU, interpret on CPU),
* sharded — the same call inside ``shard_map`` over a mesh data axis:
  queries are row-sharded, the packed support set is replicated, and no
  collective is needed (each shard owns its output rows) — pod-scale
  batches cost one kernel launch per shard.

Both paths score at the model's packed ``precision``: the support block
is already stored in the serving tile dtype, queries are cast per launch,
and the accumulate/epilogue stays f32 (``repro.kernels.precision``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

from repro.kernels.decision.ops import decision_packed
from repro.serve.model_cache import ServingModel

Array = jax.Array

# Request row-counts are padded up to one of these; the top bucket is also
# the chunk size for larger batches. Powers of 4: adjacent buckets stay a
# small constant factor apart, so padding waste is bounded by 4x rows (and
# by far less wall-clock — the kernel is support-set bound).
BUCKETS = (64, 256, 1024, 4096)


def bucket_for(n: int) -> int:
    """Smallest bucket >= n (the top bucket for anything larger)."""
    if n < 1:
        raise ValueError(f"need at least one query row, got {n}")
    for b in BUCKETS:
        if n <= b:
            return b
    return BUCKETS[-1]


class BatchScorer:
    """Scores query batches against one ``ServingModel``.

    ``mesh`` switches on the sharded path: queries are padded to
    ``bucket * mesh.shape[data_axis]`` rows and ``shard_map``-ed so each
    device scores its own slice against the replicated support set.
    """

    def __init__(self, model: ServingModel, *, interpret: bool | None = None,
                 mesh=None, data_axis: str = "data"):
        self.model = model
        self.interpret = interpret
        self.mesh = mesh
        self.data_axis = data_axis
        self._d_pad = int(model.t_pad.shape[1])
        # Buckets whose executable warmup() has pre-compiled: the service
        # reads this to avoid recording a warmed bucket's first launch as
        # a cold (compile-laden) observation.
        self.warmed_buckets: set = set()
        if mesh is not None and data_axis not in mesh.shape:
            raise ValueError(f"mesh has no axis {data_axis!r}: "
                             f"{tuple(mesh.shape)}")
        if mesh is not None:
            # Replicate the packed support set onto the mesh once: a
            # model fitted on one committed device could not otherwise
            # enter a program that spans the mesh.
            self._replicated = jax.device_put(
                (model.t_pad, model.gamma_pad, model.t_norms, model.rho1,
                 model.rho2),
                jax.sharding.NamedSharding(mesh,
                                           jax.sharding.PartitionSpec()))
        # per-shard bucket -> the jitted shard_map'd launch: built once,
        # so a warm bucket never traces or compiles again
        self._sharded_fns: dict = {}

    # -- padding ------------------------------------------------------------
    def _pad_queries(self, q, rows: int) -> Array:
        """(n, d) -> (rows, d_pad) f32 with zero padding.

        numpy inputs (the service boundary) are padded host-side into one
        bucket-shaped buffer — no per-request-shape device programs at
        all; jax-array inputs stay on device via jnp.pad (the pad op
        itself is trivial to compile).
        """
        if isinstance(q, np.ndarray):
            out = np.zeros((rows, self._d_pad), np.float32)
            out[:q.shape[0], :q.shape[1]] = q
            return jnp.asarray(out)
        q = q.astype(jnp.float32)
        return jnp.pad(q, ((0, rows - q.shape[0]),
                           (0, self._d_pad - q.shape[1])))

    @staticmethod
    def _tm(bucket: int) -> int:
        # Query tile: whole bucket when it fits the default tile, else the
        # default (grid over the bucket). Keeps bucket 64 a 1-tile launch.
        return min(bucket, 256)

    def _check(self, q):
        if q.ndim != 2:
            raise ValueError(f"queries must be (n, d), got {q.shape}")
        if q.shape[1] != self.model.d:
            raise ValueError(f"query feature dim {q.shape[1]} != model "
                             f"feature dim {self.model.d}")

    # -- local path ---------------------------------------------------------
    def _score_bucket(self, q_pad: Array) -> Array:
        m = self.model
        return decision_packed(q_pad, m.t_pad, m.gamma_pad, m.t_norms,
                               m.rho1, m.rho2, m.spec.kernel,
                               tm=self._tm(q_pad.shape[0]), tn=m.tn,
                               interpret=self.interpret,
                               precision=m.precision)

    def chunk_rows(self) -> int:
        """Rows one launch can take: the top bucket, times the data-axis
        size on the sharded path (each shard gets a top-bucket slice)."""
        nd = int(self.mesh.shape[self.data_axis]) if self.mesh is not None \
            else 1
        return BUCKETS[-1] * nd

    def bucket_used(self, n: int) -> int:
        """The padding bucket one single-launch n-row request lands in —
        the per-shard bucket on the sharded path (that is what keys the
        compiled executable and therefore the stats)."""
        if self.mesh is not None:
            nd = int(self.mesh.shape[self.data_axis])
            return bucket_for(max(1, -(-n // nd)))
        return bucket_for(n)

    def launch_plan(self, n: int):
        """(rows, bucket) per kernel launch for an n-row request — full
        top-capacity chunks first, then the remainder in its own (often
        smaller) bucket. Single source for the service's stats keys."""
        cap = self.chunk_rows()
        sizes = [cap] * (n // cap) + ([n % cap] if n % cap else [])
        return [(rows, self.bucket_used(rows)) for rows in sizes]

    def score(self, q) -> Array:
        """Slab decision values (n, d) -> (n,); every shape hits a cached
        bucket executable. Batches beyond one launch's capacity are
        chunked (each chunk reuses its cached executable). numpy inputs
        (the service boundary) come back as numpy — see ``_unpad``."""
        self._check(q)
        n = int(q.shape[0])
        cap = self.chunk_rows()
        if n > cap:
            chunks = [self._score_once(q[i:i + cap])
                      for i in range(0, n, cap)]
            xp = np if isinstance(chunks[0], np.ndarray) else jnp
            # only the last chunk carries padding rows
            return xp.concatenate(chunks)[:n]
        return self._score_once(q)

    def _unpad(self, out: Array, n: int, host: bool):
        """Drop the padding rows of one launch's output.

        The device slice ``out[:n]`` compiles one slice program per
        DISTINCT (n, bucket) pair — under a coalescing service the
        window row count varies freely, so that is a fresh ~10-30ms
        trace+compile on nearly every flush, an order of magnitude over
        the launch it trims. numpy requests (the service boundary)
        therefore unpad host-side, completing ``_pad_queries``'s
        no-per-request-shape-device-programs promise on the way out;
        jax-array requests keep a device result.
        """
        if host:
            return np.asarray(out)[:n]
        return out[:n]

    def _score_once(self, q) -> Array:
        n = int(q.shape[0])
        host = isinstance(q, np.ndarray)
        if self.mesh is not None:
            return self._score_sharded(q, n)
        out = self._score_bucket(self._pad_queries(q, bucket_for(n)))
        return self._unpad(out, n, host)

    # -- sharded path -------------------------------------------------------
    def _sharded_fn(self, per_shard: int):
        fn = self._sharded_fns.get(per_shard)
        if fn is None:
            m = self.model
            P = jax.sharding.PartitionSpec

            def shard_fn(qs, t_pad, gamma_pad, t_norms, rho1, rho2):
                return decision_packed(qs, t_pad, gamma_pad, t_norms, rho1,
                                       rho2, m.spec.kernel,
                                       tm=self._tm(per_shard), tn=m.tn,
                                       interpret=self.interpret,
                                       precision=m.precision)

            fn = self._sharded_fns[per_shard] = jax.jit(shard_map(
                shard_fn, mesh=self.mesh,
                in_specs=(P(self.data_axis, None),) + (P(),) * 5,
                out_specs=P(self.data_axis), check_vma=False))
        return fn

    def _score_sharded(self, q, n: int) -> Array:
        nd = int(self.mesh.shape[self.data_axis])
        per_shard = bucket_for(max(1, -(-n // nd)))
        q_pad = self._pad_queries(q, per_shard * nd)
        out = self._sharded_fn(per_shard)(q_pad, *self._replicated)
        return self._unpad(out, n, isinstance(q, np.ndarray))

    def warmup(self) -> None:
        """Pre-compile every bucket executable the scorer will serve with.

        Warms the path ``score()`` actually takes: with ``mesh`` set that
        is the ``shard_map``'d executable (one per per-shard bucket) —
        warming the local bucket programs instead would leave exactly the
        pod-scale path cold on its first real request. Each warm request
        is sized so ``_score_once`` lands on per-shard bucket ``b``
        (``b * n_devices`` rows sharded == ``b`` rows local).
        """
        nd = int(self.mesh.shape[self.data_axis]) if self.mesh is not None \
            else 1
        for b in BUCKETS:
            q = jnp.zeros((b * nd, self.model.d), jnp.float32)
            jax.block_until_ready(self._score_once(q))
            self.warmed_buckets.add(b)
