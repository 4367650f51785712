"""The dropless expert layer: ``moe_apply`` when ``capacity_factor >=
n_experts / top_k``.

* agreement — in float32 it matches the plain reference
  (``chipbench.reference.moe.ffn``, which imports nothing of the program),
  under a random router and under a skewed one that the 1.25 capacity path
  would drop from, with one expert routed every token and one routed none;
* parity — the burst dispatch/combine streams equal the bare
  ``fabric.route`` gathers of the same sorted layout, bit for bit, across
  the pack × fold × kernel matrix;
* choice — the dropless path runs exactly when the capacity can never drop;
* no host work — a jitted engine step of a tiny MoE model holds no host
  callback (the capacity path's drop counter is one);
* sharded — the training step of the granite smoke model under the
  expert-sharding profiles reads the same losses on a (data 2, model 4)
  CPU mesh as on one device (in a subprocess with its own XLA_FLAGS).
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import common as ref
from chipbench.reference import moe as ref_moe
from repro.configs import get_smoke
from repro.configs.base import FabricConfig, ModelConfig, MoEConfig
from repro.fabric.scheduler import SchedulerStats
from repro.kernels import ops
from repro.models import api
from repro.models.moe import moe_apply, moe_params, routes_dropless
from repro.serving import ServingEngine

KEY = jax.random.PRNGKey(0)
E, K = 4, 2


def _cfg(capacity_factor=E / K, pack="packed", fold="auto"):
    return ModelConfig(
        name="t", family="moe", n_layers=1, d_model=16, n_heads=2,
        n_kv_heads=2, d_ff=0, vocab_size=64,
        moe=MoEConfig(n_experts=E, top_k=K, expert_d_ff=32,
                      capacity_factor=capacity_factor),
        fabric=FabricConfig(n_ports=2, lane_width=8, pack=pack,
                            word_fold=fold))


def _inputs(router, cfg):
    """Seeded weights and tokens; ``skewed`` makes every token's first
    choice expert 0 and keeps expert E-1 out of every top-k."""
    p = moe_params(KEY, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model))
    if router == "skewed":
        x = x.at[..., 0].set(4.0)
        r = p["router"].at[0, 0].set(3.0).at[0, E - 1].set(-3.0)
        p = dict(p, router=r)
    return p, x


def _assignments(p, x):
    probs = jax.nn.softmax(x.reshape(-1, x.shape[-1]) @ p["router"], -1)
    top_e = np.asarray(jax.lax.top_k(probs, K)[1]).reshape(-1)
    return np.bincount(top_e, minlength=E)


@pytest.mark.parametrize("router", ("random", "skewed"))
def test_dropless_matches_the_reference(router):
    cfg = _cfg()
    p, x = _inputs(router, cfg)
    t = x.shape[0] * x.shape[1]
    counts = _assignments(p, x)
    if router == "skewed":
        assert counts[0] == t and counts[E - 1] == 0
        # the 1.25 capacity path would drop assignments from expert 0
        capped = SchedulerStats()
        moe_apply(p, x, _cfg(capacity_factor=1.25), stats=capped)
        assert capped.tokens_dropped > 0
    stats = SchedulerStats()
    got = moe_apply(p, x, cfg, stats=stats)
    assert stats.tokens_dropped == 0
    conf = {"num_local_experts": E, "num_experts_per_tok": K}
    w = {"router": p["router"], "gate": p["w_gate"], "up": p["w_up"],
         "down": p["w_out"]}
    want = ref_moe.ffn(conf, w, x.reshape(t, -1), ref.identity)
    np.testing.assert_allclose(np.asarray(got).reshape(t, -1),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pack", ("packed", "pad"))
@pytest.mark.parametrize("fold", (1, 2, "auto"))
@pytest.mark.parametrize("kernels", (False, True))
def test_dropless_burst_route_parity(pack, fold, kernels):
    cfg = _cfg(pack=pack, fold=fold)
    p, x = _inputs("skewed", cfg)
    prev = ops.kernels_enabled()
    ops.use_kernels(kernels)
    try:
        stats = SchedulerStats()
        got = moe_apply(p, x, cfg, stats=stats, payload="burst")
        want = moe_apply(p, x, cfg, payload="route")
    finally:
        ops.use_kernels(prev)
    assert np.array_equal(np.asarray(got), np.asarray(want))   # bit parity
    # dispatch and combine each ran as one sparse-extent read stream of
    # exactly T*k rows (no capacity slots), nothing dropped
    t = x.shape[0] * x.shape[1]
    assert stats.streams_served == 2
    assert stats.flushes == 2
    assert stats.words_live == 2 * t * K * cfg.d_model
    assert stats.words_written == 0
    assert stats.tokens_dropped == 0
    if kernels:
        assert stats.kernel_bursts == 2


@pytest.mark.parametrize("capacity_factor", (1.25, 1.99, 2.0, 4.0))
def test_dropless_chosen_exactly_when_capacity_cannot_drop(capacity_factor):
    cfg = _cfg(capacity_factor=capacity_factor)
    p, x = _inputs("random", cfg)
    jaxpr = str(jax.make_jaxpr(lambda xx: moe_apply(p, xx, cfg))(x))
    dropless = capacity_factor >= E / K
    assert routes_dropless(cfg.moe) == dropless
    assert ("ragged_dot" in jaxpr) == dropless


@pytest.mark.parametrize("capacity_factor", (2.0, 1.25),
                         ids=["dropless", "capacity"])
def test_engine_step_callbacks(capacity_factor):
    """The engine's jitted decode step of a tiny MoE model holds no host
    callback on the dropless path; the capacity path's runtime drop
    counter is one, which shows the check can see it."""
    smoke = get_smoke("granite-moe-3b-a800m")
    cfg = dataclasses.replace(
        smoke, dtype="float32",
        moe=dataclasses.replace(smoke.moe, capacity_factor=capacity_factor))
    eng = ServingEngine(cfg, api.init_params(cfg, KEY), max_slots=2,
                        t_max=32, page_size=4)
    text = eng._decode.lower(*eng._decode_args()).as_text(debug_info=False)
    assert ("callback" in text) == (capacity_factor < 2.0)


_MESH_STEP = """
import dataclasses, sys
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke
from repro.configs.base import TrainConfig, ShapeConfig
from repro.data import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_train_step
from repro.models import api
from repro.optim import init_opt_state

profile, pad = sys.argv[1], int(sys.argv[2])
smoke = get_smoke("granite-moe-3b-a800m")
cfg = dataclasses.replace(smoke, sharding_profile=profile,
                          moe=dataclasses.replace(smoke.moe, pad_to=pad))
tcfg = TrainConfig(lr=1e-2, warmup_steps=2, total_steps=100, grad_accum=1,
                   zero1=False)
shape = ShapeConfig("t", seq_len=16, global_batch=4, kind="train")
data = SyntheticLM(cfg, batch=4, seq=16, seed=0)

def losses(mesh):
    built = build_train_step(cfg, shape, mesh, tcfg)
    step = jax.jit(built.fn, in_shardings=built.in_shardings,
                   out_shardings=built.out_shardings)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    state = {"params": params,
             "opt": init_opt_state(params, tcfg, master=False)}
    out = []
    with mesh:
        for i in range(3):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
            state, m = step(state, batch)
            out.append(float(m["loss"]))
    return np.asarray(out)

axes = ("pod", "data", "model")
one = losses(make_mesh((1, 1, 1), axes))
eight = losses(make_mesh((1, 2, 4), axes))
assert np.isfinite(eight).all(), eight
np.testing.assert_allclose(eight, one, atol=5e-3)
print("OK", one, eight)
"""


@pytest.mark.parametrize("profile,pad", [("moe_cap", 0), ("ep_2d", 8)])
def test_dropless_train_step_on_expert_mesh(profile, pad):
    """The dropless rows carry ``expert_cap`` constraints like the capacity
    slots: under ``moe_cap`` (rows over model) and ``ep_2d`` (experts over
    model, rows over data) the train step runs on eight CPU devices."""
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(root, "src"))
    r = subprocess.run([sys.executable, "-c", _MESH_STEP, profile, str(pad)],
                       env=env, capture_output=True, text=True, timeout=420)
    assert "OK" in r.stdout, r.stderr[-2000:]
