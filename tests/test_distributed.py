"""Distribution tests that need >1 host device run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count (jax locks the device
count at first import, and the main pytest process must stay 1-device)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

# The documented per-dtype tolerance floors, shared with the kernel and
# engine parity suites (importing jax here is fine — the main process
# just has to stay single-device, which importing does not change).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
from repro.kernels.precision import truth_tolerance  # noqa: E402

from conftest import REPO_SRC, run_forced_devices as _run  # noqa: E402


def test_distributed_smo_matches_single_device():
    res = _run("""
        import json
        import jax, jax.numpy as jnp
        from repro.core import SlabSpec, rbf, solve_blocked, dual_objective
        from repro.core.distributed_smo import solve_blocked_distributed
        from repro.data import make_toy
        X, _ = make_toy(jax.random.PRNGKey(1), 256)
        spec = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
        K = spec.kernel.gram(X.astype(jnp.float32))
        mesh = jax.make_mesh((4,), ("data",))
        rd = solve_blocked_distributed(X, spec, mesh, data_axes=("data",),
                                       P_pairs=8, tol=1e-4)
        rs = solve_blocked(X, spec, P=8, tol=1e-4)
        print(json.dumps({
            "obj_dist": float(dual_objective(rd.model.gamma, K)),
            "obj_single": float(dual_objective(rs.model.gamma, K)),
            "sum_dist": float(rd.model.gamma.sum()),
            "expected_sum": spec.total(),
            "converged": bool(rd.converged),
        }))
    """)
    assert res["converged"]
    assert abs(res["sum_dist"] - res["expected_sum"]) < 1e-4
    assert res["obj_dist"] == pytest.approx(res["obj_single"], abs=2e-3)


def test_distributed_smo_multi_axis_pod_mesh():
    res = _run("""
        import json
        import jax, jax.numpy as jnp
        from repro.core import SlabSpec, rbf, dual_objective, solve_qp
        from repro.core.distributed_smo import solve_blocked_distributed
        from repro.data import make_toy
        X, _ = make_toy(jax.random.PRNGKey(2), 240)   # pad test: 240 % 8 = 0
        spec = SlabSpec(nu1=0.4, nu2=0.1, eps=0.5, kernel=rbf(gamma=0.8))
        K = spec.kernel.gram(X.astype(jnp.float32))
        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        rd = solve_blocked_distributed(X, spec, mesh,
                                       data_axes=("pod", "data"),
                                       P_pairs=4, tol=1e-4)
        qp = solve_qp(X, spec, max_iters=50000, tol=1e-10)
        print(json.dumps({
            "obj_dist": float(dual_objective(rd.model.gamma, K)),
            "obj_qp": float(qp.objective),
            "converged": bool(rd.converged),
        }))
    """, devices=8)
    assert res["converged"]
    assert res["obj_dist"] == pytest.approx(res["obj_qp"], abs=3e-3)


def test_sharded_train_step_matches_single_device():
    """pjit train step on a (2,2) mesh == unsharded result."""
    res = _run("""
        import json
        import jax, jax.numpy as jnp
        from repro.configs import ARCHS
        from repro.launch.mesh import make_test_mesh
        from repro.launch.specs import (batch_sds_and_shardings,
                                        train_state_shardings)
        from repro.sharding.specs import make_constrain
        from repro.models.transformer import init_params
        from repro.train.train_step import make_train_step, init_train_state
        from repro.data.synthetic import SyntheticPipeline

        cfg = ARCHS["minitron-8b"].reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        pipe = SyntheticPipeline(cfg, batch=4, seq_len=16, seed=0)
        batch = pipe.next_batch()

        # single-device reference
        s0 = init_train_state(cfg, params)
        step0 = jax.jit(make_train_step(cfg, peak_lr=1e-3, warmup_steps=2,
                                        total_steps=10))
        s0, m0 = step0(s0, batch)

        mesh = make_test_mesh((2, 2), ("data", "model"))
        constrain = make_constrain(mesh, fsdp=True)
        shd = train_state_shardings(cfg, mesh, fsdp=True)
        _, bshd = batch_sds_and_shardings(cfg, mesh, 4, 16)
        with mesh:
            step1 = jax.jit(make_train_step(cfg, peak_lr=1e-3,
                                            warmup_steps=2, total_steps=10,
                                            constrain=constrain),
                            in_shardings=(shd, bshd),
                            out_shardings=(shd, None))
            s1 = jax.device_put(init_train_state(cfg, params), shd)
            batch1 = {k: jax.device_put(v, bshd[k]) for k, v in batch.items()}
            s1, m1 = step1(s1, batch1)
        diff = max(float(jnp.abs(a - b).max()) for a, b in
                   zip(jax.tree.leaves(s0.params), jax.tree.leaves(s1.params)))
        print(json.dumps({"loss0": float(m0["loss"]),
                          "loss1": float(m1["loss"]),
                          "max_param_diff": diff}))
    """)
    assert res["loss0"] == pytest.approx(res["loss1"], abs=2e-3)
    assert res["max_param_diff"] < 5e-2


def test_moe_shard_map_matches_global_path():
    """The shard_map MoE (production) == the dense global path."""
    res = _run("""
        import json, dataclasses
        import jax, jax.numpy as jnp
        from repro.configs import ARCHS
        from repro.launch.mesh import make_test_mesh
        from repro.sharding.specs import make_constrain
        from repro.models.moe import moe_forward, moe_init

        d, E = 16, 4
        key = jax.random.PRNGKey(0)
        p = moe_init(key, d, E, 32, "swiglu", jnp.float32)
        x = jax.random.normal(key, (4, 8, d), jnp.float32)
        # global path (no ctx)
        y0, aux0 = moe_forward(p, x, n_experts=E, top_k=2,
                               capacity_factor=float(E), mlp_type="swiglu")
        mesh = make_test_mesh((2, 2), ("data", "model"))
        constrain = make_constrain(mesh, fsdp=False)
        with mesh:
            y1, aux1 = jax.jit(lambda p, x: moe_forward(
                p, x, n_experts=E, top_k=2, capacity_factor=float(E),
                mlp_type="swiglu", constrain=constrain))(p, x)
        print(json.dumps({
            "max_diff": float(jnp.abs(y0 - y1).max()),
            "aux0": float(aux0), "aux1": float(aux1)}))
    """)
    assert res["max_diff"] < 5e-4
    # aux is computed per data shard then averaged (GShard computes the
    # balance loss per group) — close to, but not identical with, the
    # global-batch statistic.
    assert res["aux0"] == pytest.approx(res["aux1"], rel=0.25, abs=0.05)


def test_sharded_shrinking_matches_blocked_and_collective_budget():
    """The row-sharded shrinking repack driver must land on the same slab
    as the single-device blocked solver for every (kernel, precision)
    cell — objective AND both offsets, within the documented per-dtype
    truth tolerances plus the solver-convergence floor — and the engine's
    collective-bytes ledger must certify the O(P d) per-iteration budget:
    bytes independent of m, bounded by c * P * d with c covering the
    candidate-packing constant (4 scalar lanes per row) and the shard
    fan-in. One subprocess covers the whole matrix: jax start-up is paid
    once."""
    res = _run("""
        import json
        import jax, jax.numpy as jnp
        from repro.core import (SlabSpec, rbf, linear, solve_blocked,
                                dual_objective)
        from repro.core.distributed_smo import solve_blocked_distributed
        from repro.core.engine import CollectiveLedger
        from repro.core.shrinking import solve_sharded_shrinking
        from repro.data import make_toy
        from repro.launch.mesh import make_solver_mesh

        mesh, axes = make_solver_mesh()
        kernels = {"rbf": rbf(gamma=0.5), "linear": linear()}
        out = {"cells": {}}
        X, _ = make_toy(jax.random.PRNGKey(2), 1024)
        for kname, kern in kernels.items():
            spec = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=kern)
            K = spec.kernel.gram(X.astype(jnp.float32))
            for precision in ("f32", "bf16"):
                r_shr = solve_sharded_shrinking(
                    X, spec, mesh, data_axes=axes, P_pairs=8, tol=1e-4,
                    warm_iters=60, precision=precision)
                r_blk = solve_blocked(X, spec, P=8, tol=1e-4,
                                      precision=precision)
                out["cells"][f"{kname}-{precision}"] = {
                    "obj_shr": float(dual_objective(r_shr.model.gamma, K)),
                    "obj_blk": float(dual_objective(r_blk.model.gamma, K)),
                    "rho_shr": [float(r_shr.model.rho1),
                                float(r_shr.model.rho2)],
                    "rho_blk": [float(r_blk.model.rho1),
                                float(r_blk.model.rho2)],
                    "converged": bool(r_shr.converged),
                }

        # Collective budget: per-iteration bytes from the stats-hook
        # ledger must not depend on m and must stay <= c * P * d.
        spec = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
        P_pairs, d = 8, X.shape[1]
        iter_bytes = {}
        for m in (256, 2048):
            Xm, _ = make_toy(jax.random.PRNGKey(3), m)
            led = CollectiveLedger()
            solve_blocked_distributed(Xm, spec, mesh, data_axes=axes,
                                      P_pairs=P_pairs, tol=1e-3,
                                      max_outer=50, ledger=led)
            iter_bytes[m] = led.iteration_bytes
        n_shards = 1
        for ax in axes:
            n_shards *= int(mesh.shape[ax])
        out["iter_bytes"] = iter_bytes
        out["P"] = P_pairs
        out["d"] = d
        out["n_shards"] = n_shards

        # Pod-mesh wiring: multi_pod=True on 8 devices must give the
        # scaled-down (2, 4) ("pod", "data") topology and land on the
        # same optimum as the single-device solver.
        mesh2, axes2 = make_solver_mesh(multi_pod=True)
        Xs, _ = make_toy(jax.random.PRNGKey(3), 256)
        Ks = spec.kernel.gram(Xs.astype(jnp.float32))
        r_pod = solve_blocked_distributed(Xs, spec, mesh2,
                                          data_axes=axes2, P_pairs=8,
                                          tol=1e-4)
        r_loc = solve_blocked(Xs, spec, P=8, tol=1e-4)
        out["pod"] = {
            "axes": list(axes2),
            "shape": [int(mesh2.shape[a]) for a in axes2],
            "obj_pod": float(dual_objective(r_pod.model.gamma, Ks)),
            "obj_loc": float(dual_objective(r_loc.model.gamma, Ks)),
            "converged": bool(r_pod.converged),
        }
        print(json.dumps(out))
    """, devices=8)
    pod = res["pod"]
    assert pod["axes"] == ["pod", "data"] and pod["shape"] == [2, 4]
    assert pod["converged"]
    assert pod["obj_pod"] == pytest.approx(pod["obj_loc"], abs=2e-3)
    for cell, c in res["cells"].items():
        assert c["converged"], cell
        # floors mirror tests/test_engine_parity.py (SOLVER_ATOL_FLOOR on
        # top of the per-dtype kernel tolerances)
        precision = cell.split("-")[1]
        tol_obj = truth_tolerance(precision, np.asarray([c["obj_blk"]]))
        np.testing.assert_allclose(
            c["obj_shr"], c["obj_blk"], rtol=tol_obj["rtol"],
            atol=max(tol_obj["atol"], 5e-3), err_msg=cell)
        tol_rho = truth_tolerance(precision, np.asarray(c["rho_blk"]))
        np.testing.assert_allclose(
            np.asarray(c["rho_shr"]), np.asarray(c["rho_blk"]),
            rtol=tol_rho["rtol"], atol=max(tol_rho["atol"], 5e-3),
            err_msg=cell)

    # O(P d) budget: the candidate gather packs (value, gid, gamma, f)
    # plus the d features per row, both sides, every shard — so
    # c = 8 * n_shards * (1 + 4/d) covers it with 2x headroom; the fused
    # psum/pmax pair adds O(1). Crucially the bill is IDENTICAL across m.
    bytes_by_m = set(res["iter_bytes"].values())
    assert len(bytes_by_m) == 1, f"iter bytes vary with m: {res['iter_bytes']}"
    P_pairs, d, n_shards = res["P"], res["d"], res["n_shards"]
    budget = 4 * n_shards * P_pairs * (d + 4) * 4 + 256
    assert bytes_by_m.pop() <= budget


def test_two_process_jax_distributed_smoke():
    """2-process jax.distributed bring-up on CPU: both processes must
    initialize against one coordinator, see the global 2-device topology,
    and — where the jax build supports cross-process CPU collectives —
    agree on a process_allgather. A jax build that reports multiprocess
    CPU computations as unimplemented still gates coordinator +
    topology."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    child = textwrap.dedent("""
        import json, sys
        import jax
        pid = int(sys.argv[1])
        jax.distributed.initialize(
            coordinator_address=f"127.0.0.1:{port}",
            num_processes=2, process_id=pid)
        import jax.numpy as jnp
        allgather = None
        try:
            import jax.experimental.multihost_utils as mhu
            g = mhu.process_allgather(jnp.full((1,), float(pid + 1)))
            allgather = [float(x) for x in g.ravel()]
        except Exception as e:
            if "aren't implemented on the CPU backend" not in str(e):
                raise
        print(json.dumps({
            "pid": pid,
            "processes": jax.process_count(),
            "devices": jax.device_count(),
            "local_devices": jax.local_device_count(),
            "allgather": allgather,
        }))
    """.replace("{port}", str(port)))

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)   # 1 local CPU device per process
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_SRC
    procs = [subprocess.Popen([sys.executable, "-c", child, str(i)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, env=env)
             for i in range(2)]
    results = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-3000:]
        results.append(json.loads(out.strip().splitlines()[-1]))
    for r in results:
        assert r["processes"] == 2
        assert r["devices"] == 2          # global view spans both procs
        assert r["local_devices"] == 1
        if r["allgather"] is not None:
            assert r["allgather"] == [1.0, 2.0]


def test_compressed_gradient_allreduce():
    res = _run("""
        import json
        import jax, jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import init_error_state, psum_compressed
        mesh = jax.make_mesh((4,), ("data",))
        g = {"w": jnp.arange(32, dtype=jnp.float32).reshape(4, 8) / 7.0}
        err = init_error_state(g)

        def f(g, err):
            return psum_compressed(g, err, ("data",))

        out, new_err = shard_map(
            f, mesh=mesh, in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None)),
            check_vma=False)(g, err)
        # mean over the data axis of identical shards == original/1? no:
        # shards differ; compare against the true mean of shards
        true_mean = g["w"].reshape(4, 1, 8).mean(axis=0)
        # each shard holds the mean of the 4 device-local rows
        errmax = float(jnp.abs(out["w"] - jnp.tile(true_mean, (4, 1))).max())
        rel = errmax / float(jnp.abs(true_mean).max())
        print(json.dumps({"rel_err": rel}))
    """)
    # single-shot int8 quantization error; the error-feedback residual
    # cancels it across steps (test_substrate asserts the cumulative
    # stream is lossless to <10%)
    assert res["rel_err"] < 0.3
