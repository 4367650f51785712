"""Ports-as-experts: the Medusa collective schedule for MoE dispatch.

Runs an expert-parallel dispatch on 8 host devices two ways — XLA's
monolithic all-to-all ("crossbar") and N-1 ring rotations (the paper's
diagonal schedule, §III-A, on chips) — and verifies identical results.

    python examples/moe_dispatch_demo.py     (re-executes itself with 8 devices)
"""

import os
import subprocess
import sys

if os.environ.get("_MOE_DEMO_CHILD") != "1":
    env = dict(os.environ, _MOE_DEMO_CHILD="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.exit(subprocess.call([sys.executable, __file__], env=env))

import jax                                     # noqa: E402
import jax.numpy as jnp                        # noqa: E402
import numpy as np                             # noqa: E402
from jax.sharding import PartitionSpec as P    # noqa: E402

from repro.launch.mesh import make_mesh  # noqa: E402
from repro.parallel.collectives import ring_all_to_all, xla_all_to_all  # noqa: E402

E = jax.device_count()                         # experts = devices = ports
CAP, D = 16, 64
mesh = make_mesh((E,), ("expert",))
print(f"{E} experts on {E} devices; capacity {CAP} tokens x d={D}")

# every rank holds one CAP-token block per destination expert:
# local view [E(block per peer), CAP, D]
tokens = jax.random.normal(jax.random.PRNGKey(0), (E * E, CAP, D))

ring = jax.jit(jax.shard_map(lambda t: ring_all_to_all(t, "expert"),
                             mesh=mesh, in_specs=P("expert"),
                             out_specs=P("expert")))
xla = jax.jit(jax.shard_map(lambda t: xla_all_to_all(t, "expert"),
                            mesh=mesh, in_specs=P("expert"),
                            out_specs=P("expert")))

a, b = np.asarray(ring(tokens)), np.asarray(xla(tokens))
assert np.allclose(a, b)
print("ring schedule (N-1 ppermute rotations) == XLA all-to-all ✓")

txt = jax.jit(jax.shard_map(lambda t: ring_all_to_all(t, "expert"),
                            mesh=mesh, in_specs=P("expert"),
                            out_specs=P("expert"))).lower(tokens).compile().as_text()
n_perm = txt.count(" collective-permute(") + txt.count(" collective-permute-start(")
print(f"lowered HLO uses {n_perm} collective-permutes (= N-1 = {E-1} "
      f"diagonal steps, paper §III-A on the chip fabric)")
