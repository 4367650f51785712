"""Public, jit-friendly wrappers around the Pallas kernels.

These are what the framework calls.  Every op:

* validates/pads shapes to kernel tile requirements,
* dispatches to the Pallas kernel, compiled by Mosaic on TPU and run in the
  Pallas interpreter on every other backend (:func:`interpret_mode` — the
  one place that choice is made; the kernel body is identical),
* has a pure-jnp oracle in :mod:`repro.kernels.ref` which tests sweep against.

``use_kernels(False)`` (or the ``REPRO_NO_KERNELS`` env var) routes every op
to its oracle — used by the dry-run, where we want the XLA-native HLO of the
surrounding program rather than interpret-mode custom calls.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.medusa_transpose import (burst_network_tiles,
                                            gather_burst_network_tiles,
                                            medusa_transpose_tiles,
                                            read_network_tiles,
                                            scatter_burst_network_tiles)
from repro.kernels.rotator import barrel_rotate_groups
from repro.kernels.stream_matmul import stream_matmul

_USE_KERNELS = os.environ.get("REPRO_NO_KERNELS", "") == ""


def use_kernels(enabled: bool) -> None:
    """Globally route ops to Pallas kernels (True) or jnp oracles (False)."""
    global _USE_KERNELS
    _USE_KERNELS = enabled


def kernels_enabled() -> bool:
    return _USE_KERNELS


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: on every backend but
    TPU.  On a TPU the kernels always compile — nothing falls back."""
    return jax.default_backend() != "tpu"


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def transpose_rc(x: jax.Array, tile: int = 0) -> jax.Array:
    """Swap the two leading axes of ``x [R, C, W]`` → ``[C, R, W]`` via the
    Medusa exchange-network kernel (padding to square power-of-two tiles)."""
    if not _USE_KERNELS:
        return ref.transpose_ref(x)
    r, c, w = x.shape
    if tile == 0:
        tile = min(_pow2_floor(max(r, 1)), _pow2_floor(max(c, 1)), 64)
    pr, pc = (-r) % tile, (-c) % tile
    xp = jnp.pad(x, ((0, pr), (0, pc), (0, 0))) if (pr or pc) else x
    out = medusa_transpose_tiles(xp, tile=tile, interpret=interpret_mode())
    return out[:c, :r]


def kv_line_to_port(kv: jax.Array) -> jax.Array:
    """KV-cache layout engine: line-major ``[T, H, D]`` (one timestep = one
    wide line across heads) → port-major ``[H, T, D]`` (one stream per head).
    This is the production read-network application (DESIGN.md §3.1)."""
    if not _USE_KERNELS:
        return ref.kv_layout_ref(kv)
    return transpose_rc(kv)


def interconnect_read(lines: jax.Array, n_ports: int) -> jax.Array:
    """Banked read network on tiles (kernel form of core.read_network_medusa)."""
    if not _USE_KERNELS:
        from repro.core.transpose import read_network_oracle
        return read_network_oracle(lines, n_ports)
    return read_network_tiles(lines, n_ports, interpret=interpret_mode())


def burst_read(tile: jax.Array, n_ports: int) -> jax.Array:
    """Packed read burst ``[N, N, W]`` (N lines of N words) → banked
    ``[N, N, W]`` as ONE fused kernel launch (the burst scheduler's hot
    path; see :func:`repro.kernels.medusa_transpose.burst_network_tiles`)."""
    if not _USE_KERNELS:
        from repro.core.transpose import read_network_oracle
        return read_network_oracle(tile, n_ports)[0]
    return burst_network_tiles(tile, n_ports, interpret=interpret_mode())


def burst_write(banked: jax.Array, n_ports: int) -> jax.Array:
    """Packed write burst: banked ``[N, N, W]`` → line tile ``[N, N, W]``
    as one fused kernel launch (the square exchange is an involution, so
    this is the same network run in the write direction)."""
    if not _USE_KERNELS:
        from repro.core.transpose import write_network_oracle
        return write_network_oracle(banked[None], n_ports)
    return burst_network_tiles(banked, n_ports, interpret=interpret_mode())


def burst_gather_read(lines: jax.Array, idx: jax.Array,
                      n_ports: int) -> jax.Array:
    """Fused page-table gather + read network: pool lines ``[L, N, W]`` and
    frame indices ``idx [K]`` (sentinels ``>= L`` read as zero frames) →
    banked ``[K//N, N, N, W]`` of exactly the addressed frames, one launch
    with the indices as a scalar-prefetched operand (vLLM paged-attention
    style — the network moves live frames, not the pool)."""
    if not _USE_KERNELS:
        from repro.core.transpose import read_network_oracle
        taken = jnp.take(lines, idx, axis=0, mode="fill", fill_value=0)
        return read_network_oracle(taken, n_ports)
    return gather_burst_network_tiles(lines, idx, n_ports,
                                      interpret=interpret_mode())


def burst_scatter_write(banked: jax.Array, idx: jax.Array, into: jax.Array,
                        n_ports: int) -> jax.Array:
    """Fused write network + page-table scatter: banked ``[G, N, N, W]`` →
    frames landed at rows ``idx [G*N]`` of the pool stream ``into [L, N, W]``
    (sentinels drop; untouched rows keep their frames without moving), one
    input-output-aliased launch."""
    if not _USE_KERNELS:
        from repro.core.transpose import write_network_oracle
        lines = write_network_oracle(banked, n_ports)
        return into.at[idx].set(lines, mode="drop")
    return scatter_burst_network_tiles(banked, idx, into, n_ports,
                                       interpret=interpret_mode())


def rotate_groups(x: jax.Array, amounts: jax.Array) -> jax.Array:
    """Barrel-rotate each ``x[g] [N, W]`` left by ``amounts[g]``."""
    if not _USE_KERNELS:
        return jax.vmap(ref.rotate_ref)(x, amounts)
    return barrel_rotate_groups(x, amounts, interpret=interpret_mode())


def matmul(x: jax.Array, w: jax.Array, bm: int = 0, bn: int = 0,
           bk: int = 0) -> jax.Array:
    """Streaming double-buffered matmul; falls back to the oracle when shapes
    do not tile cleanly (kernels are for the aligned hot path)."""
    if not _USE_KERNELS:
        return ref.matmul_ref(x, w)
    m, k = x.shape
    _, n = w.shape
    bm = bm or min(128, _pow2_floor(m))
    bn = bn or min(128, _pow2_floor(n))
    bk = bk or min(128, _pow2_floor(k))
    if m % bm or n % bn or k % bk:
        return ref.matmul_ref(x, w)
    return stream_matmul(x, w, bm=bm, bn=bn, bk=bk,
                         interpret=interpret_mode())
