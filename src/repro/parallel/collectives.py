"""Medusa collective schedule: all-to-all as N-1 ring rotations.

The paper replaces a crossbar with a rotation unit because bandwidth is
evenly, statically partitioned.  The inter-chip analogue: an all-to-all whose
per-peer payload is uniform (MoE dispatch with fixed capacity — even static
partition by construction) can run as ``N-1`` steps of ``lax.ppermute`` with
rotation ``s = 1..N-1``; step ``s`` moves the "diagonal" blocks ``(d → d+s)``,
exactly the §III-A diagonal schedule.  On a physical ICI ring/torus each step
is a neighbour-aligned permute that XLA can overlap with expert compute,
whereas the monolithic ``all_to_all`` "crossbar" serialises against it.

Also here: ``compressed_psum`` (int8 gradient all-reduce) and a plain ring
all-gather used by the weight-streaming demo.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.runtime.compression import int8_quantize, int8_dequantize


def ring_all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
    """All-to-all of ``x [N, ...]`` (block j destined to rank j) using N-1
    rotation steps.  Equivalent to ``lax.all_to_all`` with uniform blocks.

    Must run inside ``shard_map``/``pmap`` with ``axis_name`` bound.
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    out = jnp.zeros_like(x)
    # my own block stays put
    own = lax.dynamic_index_in_dim(x, idx, axis=0, keepdims=True)
    out = lax.dynamic_update_index_in_dim(out, own, idx, axis=0)
    for s in range(1, n):
        # step s: every rank sends the block destined for rank (idx+s)%N
        send = lax.dynamic_index_in_dim(x, (idx + s) % n, axis=0,
                                        keepdims=True)
        perm = [(i, (i + s) % n) for i in range(n)]
        recv = lax.ppermute(send, axis_name, perm)
        out = lax.dynamic_update_index_in_dim(out, recv, (idx - s) % n, axis=0)
    return out


def xla_all_to_all(x: jax.Array, axis_name: str) -> jax.Array:
    """The "crossbar": XLA's monolithic all-to-all on the same layout."""
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                          tiled=True).reshape(x.shape)


def ring_all_gather(x: jax.Array, axis_name: str) -> jax.Array:
    """All-gather as N-1 neighbour rotations (overlap-friendly weight
    streaming: each step's block can feed compute while the next streams)."""
    n = lax.axis_size(axis_name)
    blocks = [x]
    cur = x
    perm = [(i, (i + 1) % n) for i in range(n)]
    for _ in range(n - 1):
        cur = lax.ppermute(cur, axis_name, perm)
        blocks.append(cur)
    idx = lax.axis_index(axis_name)
    stacked = jnp.stack(blocks)                    # [N, ...] rotated order
    # stacked[s] is the block of rank (idx - s) % n; restore rank order
    ranks = (idx - jnp.arange(n)) % n
    out = jnp.zeros_like(stacked)
    out = out.at[ranks].set(stacked)
    return out.reshape((n * x.shape[0],) + x.shape[1:])


def compressed_psum(g: jax.Array, axis_name: str) -> jax.Array:
    """int8-compressed gradient all-reduce: quantise locally, sum int32,
    dequantise with a shared (max) scale — 8x DP all-reduce bytes."""
    scale = lax.pmax(jnp.maximum(jnp.max(jnp.abs(g)), 1e-12) / 127.0,
                     axis_name)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    total = lax.psum(q.astype(jnp.int32), axis_name)
    return total.astype(jnp.float32) * scale


def dp_grad_mean(grads, axis_name: str, compression: str = "none"):
    """Data-parallel gradient mean with optional compression (shard_map DP)."""
    n = lax.axis_size(axis_name)
    if compression == "int8":
        return jax.tree.map(lambda g: compressed_psum(g, axis_name) / n, grads)
    return jax.tree.map(lambda g: lax.pmean(g, axis_name), grads)
