"""Medusa-schedule shard_map MoE ≡ GSPMD MoE (ample capacity, 8 ranks)."""
import os
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_shardmap_moe_matches_gspmd():
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import ModelConfig, MoEConfig
from repro.models.moe import moe_params, moe_apply
from repro.models.moe_shardmap import moe_apply_shardmap, shard_expert_params

N = 8
cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32, n_heads=2,
                  n_kv_heads=2, d_ff=0, vocab_size=64,
                  moe=MoEConfig(n_experts=16, top_k=2, expert_d_ff=64,
                                capacity_factor=16.0))
key = jax.random.PRNGKey(0)
p = moe_params(key, cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (N * 2, 4, 32))

ref = moe_apply(p, x, cfg)                      # GSPMD/pjit layer, unsharded

from repro.launch.mesh import make_mesh
mesh = make_mesh((N,), ("model",))

def body(p_full, xb):
    rank = jax.lax.axis_index("model")
    p_loc = shard_expert_params(p_full, rank, N, cfg)
    return moe_apply_shardmap(p_loc, xb, cfg, "model")

out = jax.shard_map(body, mesh=mesh, in_specs=(P(), P("model")),
                    out_specs=P("model"), check_vma=False)(p, x)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)

# and the lowering uses only rotations — no all-to-all, no payload scatter
txt = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P("model")),
                            out_specs=P("model"), check_vma=False)
              ).lower(p, x).compile().as_text()
n_perm = txt.count(" collective-permute(") + txt.count(" collective-permute-start(")
assert n_perm >= 2 * (N - 1), n_perm           # fwd + reverse rings
assert " all-to-all(" not in txt and " all-to-all-start(" not in txt
print("OK", n_perm)
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=420)
    assert "OK" in r.stdout, (r.stdout[-1500:], r.stderr[-1500:])


def test_shardmap_moe_trains():
    """Gradients flow through the 2(N-1) ring rotations: a tiny MoE regression
    trained end-to-end under the medusa dispatch schedule reduces loss."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import ModelConfig, MoEConfig
from repro.models.moe import moe_params
from repro.models.moe_shardmap import moe_apply_shardmap, shard_expert_params

N = 8
cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=16, n_heads=2,
                  n_kv_heads=2, d_ff=0, vocab_size=64,
                  moe=MoEConfig(n_experts=8, top_k=2, expert_d_ff=32,
                                capacity_factor=8.0))
key = jax.random.PRNGKey(0)
p = moe_params(key, cfg, jnp.float32)
from repro.launch.mesh import make_mesh
mesh = make_mesh((N,), ("model",))
x = jax.random.normal(jax.random.PRNGKey(1), (N * 2, 4, 16))
target = jnp.tanh(x @ jax.random.normal(jax.random.PRNGKey(2), (16, 16)))

def loss_fn(p_full, xb, tb):
    rank = jax.lax.axis_index("model")
    p_loc = shard_expert_params(p_full, rank, N, cfg)
    out = moe_apply_shardmap(p_loc, xb, cfg, "model")
    return jax.lax.pmean(jnp.mean((out - tb) ** 2), "model")

smap = jax.shard_map(loss_fn, mesh=mesh, in_specs=(P(), P("model"), P("model")),
                     out_specs=P(), check_vma=False)
step = jax.jit(jax.value_and_grad(lambda p_: smap(p_, x, target)))
losses = []
for i in range(40):
    l, g = step(p)
    p = jax.tree.map(lambda a, b: a - 0.3 * b, p, g)
    losses.append(float(l))
assert losses[-1] < 0.75 * losses[0], losses[::8]
print("OK", round(losses[0], 4), "->", round(losses[-1], 4))
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=420)
    assert "OK" in r.stdout, (r.stdout[-1500:], r.stderr[-1500:])
