"""MoE dispatch collective schedule: medusa ring vs XLA all-to-all.

Ports-as-experts (DESIGN.md §3.2): with even static capacity the expert
all-to-all can run as N-1 ppermute rotations.  On 8 host devices we verify
equivalence and compare lowered collective ops + wall time; on real ICI the
rotations are neighbour-aligned and overlap with expert compute.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.parallel.collectives import ring_all_to_all, xla_all_to_all
from benchmarks.common import emit, time_us, hlo_op_census


def run() -> list:
    n = min(8, jax.device_count())
    if n < 2:
        if jax.default_backend() != "cpu":
            # this process holds the chip: a child could never reach it
            raise RuntimeError(
                f"moe_dispatch needs >= 2 devices; this "
                f"{jax.default_backend()} process has {jax.device_count()} "
                f"and holds it, so no child process can run the cells")
        # re-exec ourselves with 8 host devices and relay the CSV rows
        import os
        import subprocess
        import sys
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=8")
        r = subprocess.run([sys.executable, "-m", "benchmarks.moe_dispatch"],
                           env=env, capture_output=True, text=True,
                           timeout=420)
        rows = []
        for line in r.stdout.splitlines():
            parts = line.split(",")
            if len(parts) == 3 and parts[0].startswith("moe_dispatch/"):
                rows.append((parts[0],
                             float(parts[1]) if parts[1] else None, parts[2]))
        return rows or [("moe_dispatch/subprocess_failed", None,
                         r.stderr[-120:].replace(",", ";"))]
    mesh = make_mesh((n,), ("x",))
    cap, d = 64, 256
    # each rank holds one [cap, d] block per destination expert
    x = jax.random.normal(jax.random.PRNGKey(0), (n * n, cap, d),
                          dtype=jnp.bfloat16)

    ring = jax.jit(jax.shard_map(lambda a: ring_all_to_all(a, "x"),
                                 mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    xla = jax.jit(jax.shard_map(lambda a: xla_all_to_all(a, "x"),
                                mesh=mesh, in_specs=P("x"), out_specs=P("x")))
    r1, r2 = np.asarray(ring(x), np.float32), np.asarray(xla(x), np.float32)
    assert np.allclose(r1, r2)

    rows = []
    for name, fn in (("ring", ring), ("xla_a2a", xla)):
        census = hlo_op_census(fn, x)
        rows.append((f"moe_dispatch/{name}/us", time_us(fn, x), ""))
        rows.append((f"moe_dispatch/{name}/permutes", None,
                     census.get("collective-permute", 0)))
        rows.append((f"moe_dispatch/{name}/all_to_alls", None,
                     census.get("all-to-all", 0)))
    return rows


if __name__ == "__main__":
    emit(run())
