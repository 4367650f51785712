"""Multi-device behaviours (ring collectives, shard_map DP, dry-run cell) —
each in a subprocess with its own XLA_FLAGS (never set globally)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code, devices=8, timeout=420, env_extra=None):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_ring_all_to_all_equals_xla():
    r = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.collectives import ring_all_to_all, xla_all_to_all
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("x",))
x = jax.random.normal(jax.random.PRNGKey(0), (64, 4))
ring = jax.shard_map(lambda a: ring_all_to_all(a, "x"), mesh=mesh,
                     in_specs=P("x"), out_specs=P("x"))
xla = jax.shard_map(lambda a: xla_all_to_all(a, "x"), mesh=mesh,
                    in_specs=P("x"), out_specs=P("x"))
np.testing.assert_allclose(np.asarray(ring(x)), np.asarray(xla(x)))
print("OK")
""")
    assert "OK" in r.stdout, r.stderr[-2000:]


def test_shard_map_dp_with_compression():
    r = _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.collectives import dp_grad_mean
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("dp",))
w = jnp.ones((16,))
def step(w, xb):
    # params enter as an explicit replicated input (realistic DP pattern)
    g = jax.grad(lambda w: jnp.sum((xb @ w.reshape(16, 1)) ** 2))(w)
    return dp_grad_mean({"w": g}, "dp", compression="int8")["w"]
x = jax.random.normal(jax.random.PRNGKey(0), (32, 16))
out = jax.shard_map(step, mesh=mesh, in_specs=(P(), P("dp")),
                    out_specs=P(), check_vma=False)(w, x)
ref = jax.grad(lambda w: jnp.mean(jax.vmap(
    lambda xb: jnp.sum((xb @ w.reshape(16, 1)) ** 2))(x.reshape(8, 4, 16))))(w)
rel = np.abs(np.asarray(out - ref)).max() / np.abs(np.asarray(ref)).max()
assert rel < 0.05, rel
print("OK")
""")
    assert "OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.slow
def test_dryrun_cell_subprocess(tmp_path):
    """One full dry-run cell on the 512-device production mesh."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "stablelm-1.6b", "--shape", "decode_32k", "--mesh", "single",
         "--force"],
        env={**os.environ, "PYTHONPATH": os.path.join(ROOT, "src"),
             "REPRO_RESULTS_DIR": str(tmp_path)},
        capture_output=True, text=True, timeout=560, cwd=ROOT)
    assert "ok:" in r.stdout, (r.stdout[-2000:], r.stderr[-2000:])
    out = json.load(open(os.path.join(
        str(tmp_path), "stablelm-1.6b__decode_32k__single.json")))
    assert out["status"] == "ok"
    assert out["roofline"]["dominant"] in ("compute", "memory", "collective")
