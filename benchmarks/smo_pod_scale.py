"""The paper's solver at pod scale: compile the distributed blocked SMO
for m = 1M training points on the single-pod (16x16) and multi-pod
(2x16x16) meshes and report the per-iteration communication profile.

Run standalone (needs 512 host devices BEFORE jax init):

    PYTHONPATH=src python -m benchmarks.smo_pod_scale

Inside `benchmarks.run` (1-device process) it reports from the cached
results file if present; otherwise it compiles in a child process pinned
to the CPU (the parent may hold the chip), and a failed child fails the
run.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

RESULTS = "results/smo_pod_scale.json"

_CHILD = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
import jax, jax.numpy as jnp
from repro.core import SlabSpec, rbf
from repro.core.distributed_smo import solve_blocked_distributed
from repro.core.engine import CollectiveLedger
from repro.launch.mesh import make_solver_mesh
from repro.utils import hlo_analysis as H

spec = SlabSpec(nu1=0.5, nu2=0.05, eps=0.5, kernel=rbf(gamma=0.5))
out = []
for multi_pod in (False, True):
    mesh, axes = make_solver_mesh(multi_pod=multi_pod)
    m = 1_048_576
    d = 64
    X = jax.ShapeDtypeStruct((m, d), jnp.float32)
    ledger = CollectiveLedger()   # fills when .lower() traces the solve
    lowered = jax.jit(lambda X: solve_blocked_distributed(
        X, spec, mesh, data_axes=axes, P_pairs=32, tol=1e-4,
        fused_stats=True, ledger=ledger)).lower(X)
    compiled = lowered.compile()
    text = compiled.as_text()
    comps, entry = H._parse_computations(text)
    body = None
    best = -1
    for c in comps.values():
        for inst in c.insts:
            if inst.op == "while":
                cb = H._COND_BODY_RE.search(inst.line)
                if cb and comps.get(cb.group(2)) and \
                        len(comps[cb.group(2)].insts) > best:
                    body = comps[cb.group(2)]
                    best = len(body.insts)
    n_coll = sum(1 for i in body.insts
                 if any(i.op.startswith(k) for k in H.COLLECTIVES)
                 and not i.op.endswith("-done"))
    coll_b = sum(H._collective_operand_bytes(i, mesh.size)[1]
                 for i in body.insts
                 if any(i.op.startswith(k) for k in H.COLLECTIVES)
                 and not i.op.endswith("-done"))
    mem = compiled.memory_analysis()
    out.append({
        "mesh": "2x16x16" if multi_pod else "16x16",
        "m": m, "d": d, "P": 32,
        "m_per_shard": m // (32 if multi_pod else 16),
        "collective_ops_per_iter": n_coll,
        "collective_bytes_per_iter_per_dev": coll_b,
        # the engine's own trace-time accounting hook, for cross-checking
        # the HLO-derived numbers above (and for asserting the O(P d)
        # budget in CI without an HLO parse)
        "ledger_iter_ops": ledger.iteration_ops,
        "ledger_iter_bytes": ledger.iteration_bytes,
        "ledger_init_bytes": ledger.phase_bytes("init"),
        "peak_bytes_per_device": int(mem.argument_size_in_bytes
                                     + mem.output_size_in_bytes
                                     + mem.temp_size_in_bytes
                                     - mem.alias_size_in_bytes),
    })
print(json.dumps(out))
'''


def run():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src"
    proc = subprocess.run([sys.executable, "-c", _CHILD],
                          capture_output=True, text=True, env=env,
                          timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-1500:])
    rows = json.loads(proc.stdout.strip().splitlines()[-1])
    os.makedirs("results", exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


def main():
    if os.path.exists(RESULTS):
        rows = json.load(open(RESULTS))
    else:
        rows = run()
    for r in rows:
        ledger = (f",ledger_iter_bytes={r['ledger_iter_bytes']}"
                  if "ledger_iter_bytes" in r else "")
        print(f"smo_pod_scale,mesh={r['mesh']},m={r['m']},"
              f"m_per_shard={r['m_per_shard']},"
              f"coll_ops_per_iter={r['collective_ops_per_iter']},"
              f"coll_bytes_per_iter={r['collective_bytes_per_iter_per_dev']:.0f},"
              f"peak_gb_per_dev={r['peak_bytes_per_device']/1e9:.3f}"
              f"{ledger}")


if __name__ == "__main__":
    main()
