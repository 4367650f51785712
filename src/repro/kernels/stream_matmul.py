"""Pallas TPU kernel: double-buffered streaming matmul (the layer processor).

The paper's evaluation couples the interconnect to a convolutional layer
processor built from vector dot-product units that double-buffer their inputs
and "perform perfect prefetch of data into the idle buffers" (§III-E) — which
is why Medusa's constant transposition latency is free.  On TPU this maps to a
K-streamed matmul: the grid walks K-tiles, the Pallas pipeline prefetches the
next operand tiles into the second VMEM slot while the MXU consumes the
current one, and a VMEM scratch accumulator carries partial sums in fp32.

The weight operand is consumed in the *banked, port-major* layout produced by
the Medusa read network, demonstrating the interconnect feeding the compute
units at full bandwidth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def stream_matmul(x: jax.Array, w: jax.Array, bm: int = 128, bn: int = 128,
                  bk: int = 128, *, interpret: bool) -> jax.Array:
    """``x [M, K] @ w [K, N]`` with K-streaming and fp32 accumulation.

    Block shapes are MXU-aligned (multiples of 128 on hardware); the K grid
    axis is "arbitrary" (sequential) so the accumulator carries across steps —
    the double-buffer/pipeline structure of the layer processor.
    """
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{k})x({k2},{n}) not divisible by "
                         f"blocks ({bm},{bn},{bk})")
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)
