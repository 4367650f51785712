"""Pipeline parallelism numerics: pipelined == sequential, fwd and bwd."""
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_pipeline_matches_sequential():
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.pipeline import pipeline_forward, pipeline_loss, bubble_fraction

P_STAGES, M, MB, D = 4, 6, 2, 8
from repro.launch.mesh import make_mesh
mesh = make_mesh((P_STAGES,), ("pipe",))
ws = jax.random.normal(jax.random.PRNGKey(0), (P_STAGES, D, D)) * 0.3
xs = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))
tg = jax.random.normal(jax.random.PRNGKey(2), (M, MB, D))

stage = lambda w, x: jnp.tanh(x @ w[0])

def run(ws_all, xs):
    return pipeline_forward(stage, ws_all, xs, "pipe")

piped = jax.shard_map(run, mesh=mesh, in_specs=(P("pipe"), P()),
                      out_specs=P(), check_vma=False)(ws, xs)

seq = xs
for s in range(P_STAGES):
    seq = jnp.tanh(seq @ ws[s])
np.testing.assert_allclose(np.asarray(piped), np.asarray(seq), atol=1e-5)

# backward: grads through the pipeline match sequential grads
def loss_piped(ws_all):
    f = jax.shard_map(
        lambda w, x, t: pipeline_loss(stage, lambda o, t: jnp.mean((o - t) ** 2),
                                      w, x, t, "pipe")[None],
        mesh=mesh, in_specs=(P("pipe"), P(), P()), out_specs=P(None),
        check_vma=False)
    return f(ws_all, xs, tg).sum()

def loss_seq(ws_all):
    h = xs
    for s in range(P_STAGES):
        h = jnp.tanh(h @ ws_all[s])
    return jax.vmap(lambda o, t: jnp.mean((o - t) ** 2))(h, tg).mean()

g1 = jax.grad(loss_piped)(ws)
g2 = jax.grad(loss_seq)(ws)
np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)
assert abs(bubble_fraction(6, 4) - 3 / 9) < 1e-9
print("OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert "OK" in r.stdout, (r.stdout[-1500:], r.stderr[-1500:])
