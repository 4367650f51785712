"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Production path: builds the pjit train step for the requested mesh, wires the
fault-tolerant runner (checkpoint/restart + straggler detection) around it,
and streams the deterministic synthetic pipeline.  On this CPU container use
``--smoke`` (reduced config, 1x1 mesh) — the same code path end to end.
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke, TrainConfig
from repro.configs.base import ShapeConfig
from repro.checkpoint import CheckpointManager, latest_step, restore_checkpoint
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import build_train_step
from repro.models import api
from repro.optim import init_opt_state
from repro.runtime import TrainingRunner, StragglerDetector, FaultInjector


def make_mesh_for(args):
    if args.smoke:
        return jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                                 ("data", "model"))
    from repro.launch.mesh import make_production_mesh
    return make_production_mesh(multi_pod=args.multi_pod)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject node failures at these steps (FT demo)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()
    logging.basicConfig(level=logging.INFO)

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_mesh_for(args)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    tcfg = TrainConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                       total_steps=args.steps, grad_accum=args.grad_accum,
                       zero1=not args.smoke, checkpoint_dir=args.ckpt_dir,
                       checkpoint_every=args.ckpt_every)

    built = build_train_step(cfg, shape, mesh, tcfg)
    jit_step = jax.jit(built.fn, in_shardings=built.in_shardings,
                       out_shardings=built.out_shardings,
                       donate_argnums=built.donate_argnums)

    params = api.init_params(cfg, jax.random.PRNGKey(tcfg.seed))
    state = {"params": params,
             "opt": init_opt_state(params, tcfg, master=False)}
    start = 0
    ckpt = CheckpointManager(args.ckpt_dir, every=args.ckpt_every, keep=3)
    if args.resume and (last := latest_step(args.ckpt_dir)) is not None:
        state, extra = restore_checkpoint(args.ckpt_dir, last, state)
        start = extra.get("data_step", last)
        print(f"resumed from step {start}")

    data = SyntheticLM(cfg, batch=args.batch, seq=args.seq, seed=tcfg.seed)

    def step_fn(state, batch):
        with mesh:
            return jit_step(state, {k: jnp.asarray(v)
                                    for k, v in batch.items()})

    t0 = time.time()

    def on_metrics(step, metrics):
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.2f} "
                  f"({(time.time() - t0) / max(step - start, 1):.2f}s/step)",
                  flush=True)

    runner = TrainingRunner(
        step_fn, data, ckpt, straggler=StragglerDetector(),
        fault_injector=FaultInjector(tuple(args.fail_at)) if args.fail_at
        else None)
    state, end = runner.run(state, start, args.steps, on_metrics=on_metrics)
    print(f"done at step {end}; restarts={runner.restarts}, "
          f"stragglers flagged={runner.straggler.flagged}")


if __name__ == "__main__":
    main()
