"""Pipeline parallelism: a functional GPipe/1F1B-style microbatch pipeline.

Stages live on a ``pipe`` mesh axis (shard_map); activations move stage to
stage with ``lax.ppermute`` — neighbour-aligned on the ICI ring, the same
rotation primitive as the Medusa collective schedule.  The schedule runs
``M + P - 1`` ticks for M microbatches over P stages (bubble fraction
``(P-1)/(M+P-1)``); autodiff through the scan-of-ppermutes yields the
reversed pipeline for the backward pass.

The assigned production meshes are 2-axis (data, model) — layer-scan + ZeRO
covers them better (DESIGN.md §8) — but the substrate supports a third
``pipe`` axis; ``tests/test_pipeline.py`` validates numerics on a host mesh.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax



def pipeline_forward(stage_fn: Callable, stage_params, microbatches,
                     axis_name: str = "pipe"):
    """Run ``microbatches [M, mb, ...]`` through P pipelined stages.

    ``stage_fn(stage_params, x) -> y`` is THIS stage's compute (stage_params
    are already sharded over ``axis_name`` by the enclosing shard_map).
    Returns ``[M, mb, ...]`` outputs of the final stage.  Microbatch ``m``
    occupies stage ``s`` at tick ``m + s`` — the diagonal schedule again.
    """
    p = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m = microbatches.shape[0]
    ticks = m + p - 1

    def tick(h, t):
        inject = microbatches[jnp.clip(t, 0, m - 1)]
        h = jnp.where((idx == 0) & (t < m), inject, h)
        h = stage_fn(stage_params, h)
        # only the last stage emits; psum replicates it to every rank
        emit = lax.psum(jnp.where(idx == p - 1, h, jnp.zeros_like(h)),
                        axis_name)
        # shift to the next stage (no wraparound: stage 0 re-injects)
        h_next = lax.ppermute(h, axis_name,
                              [(i, i + 1) for i in range(p - 1)])
        return h_next, emit

    _, emits = lax.scan(tick, jnp.zeros_like(microbatches[0]),
                        jnp.arange(ticks))
    # microbatch m finishes at tick m + p - 1 on the last stage
    return emits[p - 1:]


def pipeline_loss(stage_fn: Callable, loss_fn: Callable, stage_params,
                  microbatches, targets, axis_name: str = "pipe"):
    """Mean loss over microbatches; differentiable → pipelined backward."""
    outs = pipeline_forward(stage_fn, stage_params, microbatches, axis_name)
    losses = jax.vmap(loss_fn)(outs, targets)
    return losses.mean()


def bubble_fraction(num_microbatches: int, num_stages: int) -> float:
    """Pipeline bubble overhead of the schedule (EXPERIMENTS.md §Perf)."""
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
