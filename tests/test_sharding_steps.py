"""Sharding rules + an end-to-end pjit step on a 1x1 CPU mesh (numerics)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_smoke
from repro.configs.base import TrainConfig, ShapeConfig
from repro.data import SyntheticLM
from repro.launch.steps import (build_train_step, build_prefill_step,
                                build_decode_step, make_sharder, param_specs,
                                zero1_specs, _eval_params)
from repro.models import api
from repro.parallel.sharding import Sharder, rules_for


def _mesh11():
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


def test_spec_mapping():
    s = Sharder(_mesh11(), rules_for("tp_heads"))
    assert s.spec("batch", "seq", "d_model") == P("data")
    assert s.spec("batch", None, "heads") == P("data", None, "model")
    # duplicate axis collapses
    assert s.spec("heads", "d_ff") == P("model")


def test_safe_spec_divisibility():
    s = Sharder(_mesh11(), rules_for("tp_heads"))
    # batch=1 cannot shard over data → dropped
    assert s.safe_spec((1, 8), ("batch", None)) == P()


def test_param_specs_cover_tree():
    cfg = get_smoke("starcoder2-15b")
    mesh = _mesh11()
    sharder = make_sharder(cfg, mesh)
    shapes = _eval_params(cfg)
    specs = param_specs(shapes, cfg, sharder)
    assert (jax.tree_util.tree_structure(specs)
            == jax.tree_util.tree_structure(shapes))


def test_zero1_adds_data_axis():
    mesh = _mesh11()
    # fake 4-way data mesh via rules only (structure test, mesh is 1x1)
    cfg = get_smoke("stablelm-1.6b")
    sharder = make_sharder(cfg, mesh)
    shapes = _eval_params(cfg)
    pspecs = param_specs(shapes, cfg, sharder)
    zspecs = zero1_specs(pspecs, shapes, sharder)
    assert (jax.tree_util.tree_structure(zspecs)
            == jax.tree_util.tree_structure(pspecs))


@pytest.mark.parametrize("arch", ["stablelm-1.6b", "granite-moe-3b-a800m",
                                  "mamba2-780m", "recurrentgemma-2b"])
def test_train_step_numerics_on_mesh(arch):
    """The actual pjit train step (grad accum path) runs and reduces loss."""
    cfg = get_smoke(arch)
    mesh = _mesh11()
    shape = ShapeConfig("t", seq_len=16, global_batch=4, kind="train")
    tcfg = TrainConfig(lr=1e-2, warmup_steps=2, total_steps=100,
                       grad_accum=2, zero1=False)
    built = build_train_step(cfg, shape, mesh, tcfg)
    step = jax.jit(built.fn, in_shardings=built.in_shardings,
                   out_shardings=built.out_shardings,
                   donate_argnums=built.donate_argnums)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    from repro.optim import init_opt_state
    state = {"params": params, "opt": init_opt_state(params, tcfg,
                                                     master=False)}
    data = SyntheticLM(cfg, batch=4, seq=16, seed=0)
    with mesh:
        losses = []
        for i in range(20):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])


def test_serve_steps_on_mesh():
    cfg = get_smoke("gemma3-12b")
    mesh = _mesh11()
    shape = ShapeConfig("d", seq_len=32, global_batch=2, kind="decode")
    pshape = ShapeConfig("p", seq_len=16, global_batch=2, kind="prefill")
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    pre = build_prefill_step(cfg, pshape, mesh)
    dec = build_decode_step(cfg, shape, mesh)
    with mesh:
        pre_fn = jax.jit(pre.fn, in_shardings=pre.in_shardings,
                         out_shardings=pre.out_shardings)
        logits, caches = pre_fn(params, {"tokens": jnp.zeros((2, 16),
                                                             jnp.int32)})
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        # decode built for t_max=32 but prefill cache is 16 — rebuild cache
        caches = api.init_cache(cfg, 2, 32)
        dec_fn = jax.jit(dec.fn, in_shardings=dec.in_shardings,
                         out_shardings=dec.out_shardings,
                         donate_argnums=dec.donate_argnums)
        l2, caches = dec_fn(params, caches, jnp.zeros((2, 1), jnp.int32),
                            jnp.int32(16))
        assert np.isfinite(np.asarray(l2, np.float32)).all()
