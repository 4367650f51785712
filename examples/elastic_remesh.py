"""Elastic re-mesh: restore a checkpoint onto a different mesh.

Saves training state sharded one way, then restores it onto a different
topology (what happens when a pod is lost and the job resumes on fewer
slices).  Checkpoints are host-side and layout-free, so this is exact.

    python examples/elastic_remesh.py       (re-executes itself with 8 devices)
"""

import os
import subprocess
import sys
import tempfile

if os.environ.get("_REMESH_CHILD") != "1":
    env = dict(os.environ, _REMESH_CHILD="1",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    sys.exit(subprocess.call([sys.executable, __file__], env=env))

import jax                                     # noqa: E402
import jax.numpy as jnp                        # noqa: E402
import numpy as np                             # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from repro.checkpoint import save_checkpoint, restore_checkpoint  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

big = make_mesh((4, 2), ("data", "model"))
small = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2), ("data", "model"))

state = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8),
                             NamedSharding(big, P("data", "model"))),
         "step": jnp.int32(7)}
d = tempfile.mkdtemp()
save_checkpoint(d, 7, state)
print(f"saved on 4x2 mesh: {state['w'].sharding}")

template = {"w": jnp.zeros((8, 8)), "step": jnp.int32(0)}
shardings = {"w": NamedSharding(small, P("data", "model")),
             "step": NamedSharding(small, P())}
restored, _ = restore_checkpoint(d, 7, template, shardings)
print(f"restored on 2x2 mesh: {restored['w'].sharding}")
assert np.allclose(np.asarray(restored["w"]), np.arange(64.0).reshape(8, 8))
print("values identical after re-mesh ✓ — elastic recovery path works")
