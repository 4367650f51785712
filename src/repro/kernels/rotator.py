"""Pallas TPU kernel: the barrel rotation unit (paper §III-B, Fig. 5).

Rotates ``N`` port-words by a per-group dynamic amount using ``log2(N)``
stages; stage ``l`` is a *static* roll by ``2**l`` (slice+concat — a full-width
vector move) selected by bit ``l`` of the rotation amount, read from SMEM via
scalar prefetch.  A data-dependent rotation thus never emits a gather: the
dynamic part is only in the per-stage select bit, exactly like the FPGA
barrel shifter whose stage enables come from the cycle counter.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@functools.partial(jax.jit, static_argnames=("interpret",))
def barrel_rotate_groups(x: jax.Array, amounts: jax.Array, *,
                         interpret: bool) -> jax.Array:
    """Left-rotate each group ``x[g] : [N, W]`` by ``amounts[g]`` positions.

    ``N`` must be a power of two.  Grid over groups; the rotation amount is a
    scalar-prefetch operand (SMEM), the data rides in VMEM blocks.
    """
    g, n, w = x.shape
    if n & (n - 1):
        raise ValueError(f"N={n} must be a power of two")
    amounts = amounts.astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g,),
        in_specs=[pl.BlockSpec((1, n, w), lambda i, amt: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, n, w), lambda i, amt: (i, 0, 0)),
    )

    def kernel(amt_ref, x_ref, o_ref):
        x_blk = x_ref[...]
        i = pl.program_id(0)
        amount = amt_ref[i] % n
        for level in range(int(math.log2(n))):
            bit = ((amount >> level) & 1) == 1
            rolled = jnp.roll(x_blk, -(1 << level), axis=1)
            x_blk = jnp.where(bit, rolled, x_blk)
        o_ref[...] = x_blk

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, n, w), x.dtype),
        interpret=interpret,
    )(amounts, x)
