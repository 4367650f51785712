"""Pallas TPU kernel: Medusa transposition unit on VMEM tiles.

The paper's transposition unit moves a ``W_line``-bit line per cycle between
lane-banked and port-banked layouts using a barrel rotator instead of a
crossbar.  On TPU the equivalent hot spot is the (sublane, lane) transpose of
VMEM tiles in the layout-conversion path (KV cache line-major → head-major,
banked weight streams, interconnect re-banking).  This kernel performs it with
the binary-exchange network: ``log2(T)`` stages, each one static slab pick
plus one *static* sublane roll (a full-width vector move — the VPU analogue
of a barrel-shifter layer) and a 2-to-1 select on iota masks
(:func:`_exchange_slabs`).  No gathers and no index tensors are emitted,
which is exactly the resource contrast the paper draws against crossbars.

Every kernel takes ``interpret`` explicitly: :mod:`repro.kernels.ops`
decides it from the backend (compiled by Mosaic on TPU, interpreted
elsewhere), and word tiles are sized from a VMEM byte budget
(:func:`_word_tile_cap`), so the same bodies lower on the chip.

Layout contract: operands are ``[R, C, W]`` with payload ``W`` innermost
(lanes; use W multiple of 128 on hardware) and the transposed pair in the two
leading dims (sublanes).  Grid tiles are square ``T x T`` with ``T`` a power
of two; block (i, j) of the input writes block (j, i) of the output — the tile
*grid* transpose is free (BlockSpec index maps), the intra-tile movement is
the exchange network.

:func:`burst_network_tiles` is the burst-scheduler entry point: one packed
``[N, N, W_total]`` burst tile (every queued stream of a dtype, word-packed)
moves through a single ``pallas_call`` with a word-tiled grid — the whole
§III-A transposition as one kernel launch per direction per dtype, instead of
the unrolled per-stage HLO chain.  The square-tile network is an involution,
so the same kernel serves both the read (lines → banked) and write (banked →
lines) directions; only the surrounding group reshapes differ.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One exchange tile ``[N, N, tw]`` is sized to this many bytes of VMEM.  The
# pipeline double-buffers the input and output blocks and the network's
# stage values live beside them, so 2 MiB per tile stays inside the 16 MiB
# of VMEM a v5e kernel may use by default (a 4 MiB u32 tile at N = 32 does
# not compile there).
_TILE_VMEM_BYTES = 2 << 20
_LANES = 128


def _exchange_slabs(slabs: list) -> list:
    """log2(T)-stage binary-exchange transpose of a ``[T, T, W]`` tile held
    as ``T`` slabs ``[T, W]`` (slab ``i`` = row ``i``): returns the slabs of
    the transposed tile.

    Stage ``l`` (``s = 2**l``) swaps bit ``l`` between the row index ``i``
    and the in-slab index ``j``: where the bits differ, ``out[i][j] =
    x[i^s][j^s]``.  The row flip ``i^s`` is static, so it picks a whole slab
    (a vreg relabel, free); the ``j^s`` flip is one static sublane rotation
    of that partner slab by ``±s`` (slice + concat — the wires of one
    barrel-shifter layer), and a 2-to-1 select on a static iota mask keeps
    the rotated word only where the bits differ.  No reverse, no gather, no
    index tensor — every op Mosaic lowers for 16- and 32-bit words alike."""
    n = len(slabs)
    rows, w = slabs[0].shape
    for level in range(int(math.log2(n))):
        s = 1 << level
        low = ((jax.lax.broadcasted_iota(jnp.int32, (rows, w), 0) >> level)
               & 1) == 0
        out = []
        for i in range(n):
            p = slabs[i ^ s]
            if i & s:      # bit set in i: take x[i^s][j+s] where bit_l(j)=0
                out.append(jnp.where(
                    low, jnp.concatenate([p[s:], p[:s]], axis=0), slabs[i]))
            else:          # bit clear in i: take x[i^s][j-s] where bit_l(j)=1
                out.append(jnp.where(
                    low, slabs[i],
                    jnp.concatenate([p[rows - s:], p[:rows - s]], axis=0)))
        slabs = out
    return slabs


def _exchange_ref(x_ref, o_ref) -> None:
    """Run the exchange network from ``x_ref [T, T, W]`` into ``o_ref``."""
    out = _exchange_slabs([x_ref[i] for i in range(x_ref.shape[0])])
    for i, slab in enumerate(out):
        o_ref[i] = slab


def _word_tile_cap(n: int, itemsize: int) -> int:
    """Most lanes per grid step that keep one ``[N, N, tw]`` tile of
    ``itemsize``-byte words within ``_TILE_VMEM_BYTES``, lane-aligned and
    never below one 128-lane column."""
    return max(_LANES,
               _TILE_VMEM_BYTES // (n * n * itemsize) // _LANES * _LANES)


def _pick_word_tile(w: int, cap: int, divisor: bool = False) -> int:
    """Word tile for a burst of ``w`` lanes under a lane-aligned ``cap``
    (:func:`_word_tile_cap`): the whole burst when it fits, else the
    largest 128-multiple dividing ``w`` in (cap/2, cap] (one clean grid),
    else the evenest split at the same grid depth rounded up to whole lane
    columns — ``ceil(w / ceil(w/cap))`` pads at most ``grid-1`` lanes plus
    the column rounding instead of up to ``cap-1``.

    ``divisor=True`` is the gather-operand mode: the tile must DIVIDE ``w``
    so the index operand tiles cleanly with the word grid.  The gather and
    scatter burst kernels address whole frames through a prefetched index
    list; a padded edge tile would read (and, on the aliased scatter, write)
    past the frame's word extent at an indexed row — so instead of the pad
    fallback the search takes the largest lane-aligned divisor ≤ cap, else
    the largest divisor (worst case 1 for a prime ``w``; only the
    interpreter lowers an unaligned partial tile, so pick lane counts that
    factor into multiples of 128)."""
    if w <= cap:
        return w
    aligned = [t for t in range(cap - cap % _LANES, 0, -_LANES) if w % t == 0]
    if aligned and (divisor or aligned[0] > cap // 2):
        return aligned[0]
    if divisor:
        return max(t for t in range(1, cap + 1) if w % t == 0)
    grid = -(-w // cap)
    return -(-(-(-w // grid)) // _LANES) * _LANES


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def medusa_transpose_tiles(x: jax.Array, tile: int = 8, *,
                           interpret: bool) -> jax.Array:
    """Transpose the two leading axes of ``x [R, C, W]`` → ``[C, R, W]``.

    ``R`` and ``C`` must be multiples of ``tile`` (a power of two); ``ops.py``
    wraps this with padding for arbitrary shapes.  ``W`` rides along in
    lanes, word-tiled to the VMEM budget.  On hardware use ``tile`` >= the
    sublane count for the dtype and ``W`` a multiple of 128; ``interpret``
    runs the same kernel body on CPU.
    """
    r, c, w = x.shape
    if r % tile or c % tile:
        raise ValueError(f"R={r}, C={c} must be multiples of tile={tile}")
    if tile & (tile - 1):
        raise ValueError(f"tile must be a power of two, got {tile}")
    tw = _pick_word_tile(w, _word_tile_cap(tile, x.dtype.itemsize),
                         divisor=True)
    return pl.pallas_call(
        _exchange_ref,
        grid=(r // tile, c // tile, w // tw),
        in_specs=[pl.BlockSpec((tile, tile, tw), lambda i, j, k: (i, j, k))],
        out_specs=pl.BlockSpec((tile, tile, tw), lambda i, j, k: (j, i, k)),
        out_shape=jax.ShapeDtypeStruct((c, r, w), x.dtype),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("n_ports", "interpret"))
def read_network_tiles(lines: jax.Array, n_ports: int, *,
                       interpret: bool) -> jax.Array:
    """Kernel form of :func:`repro.core.transpose.read_network_medusa`:
    ``lines [L, N, W]`` → banked ``[G, N, N, W]``; one group tile per grid
    step (word-tiled to the VMEM budget), double-buffered by the Pallas
    pipeline (the paper's prefetch) — the §III-A read transposition."""
    n = n_ports
    l, n_words, w = lines.shape
    if n_words != n or l % n:
        raise ValueError(f"bad line stream {lines.shape} for N={n}")
    groups = l // n
    tw = _pick_word_tile(w, _word_tile_cap(n, lines.dtype.itemsize),
                         divisor=True)
    x = lines.reshape(groups, n, n, w)
    spec = pl.BlockSpec((None, n, n, tw), lambda g, k: (g, 0, 0, k))
    return pl.pallas_call(
        _exchange_ref,
        grid=(groups, w // tw),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((groups, n, n, w), lines.dtype),
        interpret=interpret,
    )(x)


@functools.partial(jax.jit, static_argnames=("n_ports", "word_tile",
                                             "interpret"))
def burst_network_tiles(tile: jax.Array, n_ports: int, word_tile: int = 0, *,
                        interpret: bool) -> jax.Array:
    """One packed burst ``[N, N, W]`` through the transposition unit as a
    single fused kernel — the whole burst is one launch per direction per
    dtype (vs the unrolled per-stage HLO chain of
    :func:`repro.core.transpose.medusa_transpose`).

    The square ``[N, N]`` exchange is an involution, so the same kernel is
    the read network (``lines[p, y] → banked[y, p]``) and the write network
    (banked → lines); callers do their own group reshapes.  The grid tiles
    the word axis: ``word_tile`` lanes per step, default the whole burst
    when one ``[N, N, W]`` tile fits the VMEM budget, else the largest
    lane-aligned divisor of W under it (or an even lane-aligned split with
    pad, sliced off after — VMEM tiling fill, not network traffic).
    ``interpret`` runs the same body on CPU."""
    n = n_ports
    if tile.ndim != 3 or tile.shape[0] != n or tile.shape[1] != n:
        raise ValueError(f"bad burst tile {tile.shape} for N={n}")
    w = tile.shape[2]
    if w == 0:
        return tile
    tw = word_tile or _pick_word_tile(w, _word_tile_cap(n,
                                                        tile.dtype.itemsize))
    pad = (-w) % tw
    x = jnp.pad(tile, ((0, 0), (0, 0), (0, pad))) if pad else tile
    out = pl.pallas_call(
        _exchange_ref,
        grid=((w + pad) // tw,),
        in_specs=[pl.BlockSpec((n, n, tw), lambda i: (0, 0, i))],
        out_specs=pl.BlockSpec((n, n, tw), lambda i: (0, 0, i)),
        out_shape=jax.ShapeDtypeStruct((n, n, w + pad), tile.dtype),
        interpret=interpret,
    )(x)
    return out[:, :, :w] if pad else out


# ----------------------------------------------------------------------------
# fused page-table gather/scatter bursts (sparse-extent streams)
# ----------------------------------------------------------------------------
#
# The paged KV pool names its live frames through a logical→physical table;
# these kernels make that indirection part of the transposition unit itself
# (vLLM paged-attention style): the frame-index list rides the launch as a
# *scalar-prefetched* operand, the BlockSpec index maps dereference it, and
# the network banks ONLY the addressed frames — one launch that does
# indirection + exchange, with no materialized full-pool intermediate and
# traffic proportional to live tokens instead of pool capacity.  Sentinel
# indices (>= the pool's line count) gather as zero frames on the read side
# and drop on the (input-output-aliased) write side, so index lists pad to
# the N-line group granularity for free.  The index contract is
# non-negative-or-sentinel: entries must lie in [0, L) or at/above L — a
# negative entry is undefined (the unrolled take/scatter would wrap it
# NumPy-style while the kernel's block clamp would not), and every producer
# (``page_live_plan`` asserts the table's mapped-prefix invariant,
# admission maps only allocated pages, ``page_gather_indices`` rewrites
# unmapped rows to the sentinel) guarantees it by construction.

def _gather_burst_kernel(n: int, n_lines: int, idx_ref, x_ref, o_ref,
                         scratch):
    # grid (G, Wt, N): steps r = 0..N-1 of a (group, word-tile) pair gather
    # one addressed frame each into the scratch tile; the last step runs the
    # exchange network on the assembled [N, N, tw] tile and banks it.
    g, r = pl.program_id(0), pl.program_id(2)
    frame = x_ref[...]
    valid = idx_ref[g * n + r] < n_lines
    scratch[r] = jnp.where(valid, frame, jnp.zeros_like(frame))

    @pl.when(r == n - 1)
    def _():
        _exchange_ref(scratch, o_ref)


@functools.partial(jax.jit, static_argnames=("n_ports", "word_tile",
                                             "interpret"))
def gather_burst_network_tiles(lines: jax.Array, idx: jax.Array,
                               n_ports: int, word_tile: int = 0, *,
                               interpret: bool) -> jax.Array:
    """Fused gather + read network: pool line stream ``lines [L, N, W]`` and
    frame indices ``idx [K]`` (``K`` a multiple of N; entries ``>= L`` are
    sentinels) → banked ``[K//N, N, N, W]`` holding exactly the addressed
    frames, zeros at sentinels.  The index list is a scalar-prefetched
    operand: each grid step's input block is ``lines[idx[...]]`` — the
    indirection happens in the BlockSpec index map, so only live frames move
    through VMEM and the exchange stages (a sentinel reads the last frame
    and selects zeros: reads are hazard-free).  Equivalent to
    ``take(lines, idx, fill=0)`` followed by :func:`burst_network_tiles`
    groupwise, as one launch."""
    n = n_ports
    l, n_words, w = lines.shape
    k = idx.shape[0]
    if n_words != n or k % n:
        raise ValueError(f"bad gather burst: lines {lines.shape}, "
                         f"idx {idx.shape} for N={n}")
    tw = word_tile or _pick_word_tile(
        w, _word_tile_cap(n, lines.dtype.itemsize), divisor=True)
    if w % tw:
        raise ValueError(
            f"gather word_tile={tw} must divide the frame word count {w} "
            f"(the index operand must tile with the word grid)")
    groups = k // n
    idx = idx.astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(groups, w // tw, n),
        in_specs=[pl.BlockSpec(
            (None, n, tw), lambda g, wt, r, idx_ref: (
                jnp.minimum(idx_ref[g * n + r], l - 1), 0, wt))],
        out_specs=pl.BlockSpec((None, n, n, tw),
                               lambda g, wt, r, idx_ref: (g, 0, 0, wt)),
        scratch_shapes=[pltpu.VMEM((n, n, tw), lines.dtype)],
    )
    return pl.pallas_call(
        functools.partial(_gather_burst_kernel, n, l),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((groups, n, n, w), lines.dtype),
        interpret=interpret,
    )(idx, lines)


# Per-step action of the scatter kernel (one entry per index-list slot).
_SKIP, _LAND, _KEEP = 0, 1, 2


def _scatter_plan(idx: jax.Array, n_lines: int):
    """Hazard-free block schedule for the aliased scatter: ``(rows, acts)``.

    On the chip an output block is never loaded from HBM, and the pipeline
    writes a block back only when the next step names another one.  So a
    sentinel step must not move the output onto a row it would have to
    preserve: it *revisits* the block of the nearest valid step of its word
    pass — the last one before it, or (for a leading run) the first one
    after it, which then overwrites the whole block — and writes nothing
    (``_SKIP``), so no stale or unloaded data ever reaches HBM.  Only when
    every entry is a sentinel does the pass sit on row 0 and copy it onto
    itself from the aliased input (``_KEEP``).  Valid steps land their
    line (``_LAND``)."""
    k = idx.shape[0]
    slot = jnp.arange(k, dtype=jnp.int32)
    valid = idx < n_lines
    before = jax.lax.cummax(jnp.where(valid, slot, -1))
    after = jax.lax.cummin(jnp.where(valid, slot, k), reverse=True)
    src = jnp.minimum(jnp.where(before >= 0, before, after), k - 1)
    any_valid = after[0] < k
    rows = jnp.where(any_valid, idx[src], 0)
    acts = jnp.where(valid, _LAND, jnp.where(any_valid, _SKIP, _KEEP))
    return rows.astype(jnp.int32), acts.astype(jnp.int32)


def _scatter_burst_kernel(n: int, row_ref, act_ref, x_ref, dest_ref, o_ref,
                          lines):
    # grid (Wt, G, N): the first step of each group exchanges its banked
    # tile into the `lines` scratch (the write direction of the involution);
    # step r then lands line r at its pool row (see _scatter_plan for why
    # sentinel steps revisit a valid row and write nothing).
    g, r = pl.program_id(1), pl.program_id(2)

    @pl.when(r == 0)
    def _():
        _exchange_ref(x_ref, lines)

    act = act_ref[g * n + r]

    @pl.when(act == _LAND)
    def _():
        o_ref[...] = lines[r]

    @pl.when(act == _KEEP)
    def _():
        o_ref[...] = dest_ref[...]


@functools.partial(jax.jit, static_argnames=("n_ports", "word_tile",
                                             "interpret"))
def scatter_burst_network_tiles(banked: jax.Array, idx: jax.Array,
                                into: jax.Array, n_ports: int,
                                word_tile: int = 0, *,
                                interpret: bool) -> jax.Array:
    """Fused write network + scatter: banked ``[G, N, N, W]`` → line frames
    scattered into the pool stream ``into [L, N, W]`` at rows ``idx [G*N]``
    (sentinel entries ``>= L`` drop).  ``into`` aliases the output, so rows
    the indices never touch keep their frames without moving — the write
    traffic is the live frames only.  The grid walks one word column at a
    time (``(Wt, G, N)``), in index order; producers name each live row
    once.  Sentinels are scheduled by :func:`_scatter_plan`, which keeps
    every step hazard-free on hardware as in the interpreter."""
    n = n_ports
    g_count, n0, n1, w = banked.shape
    l = into.shape[0]
    if n0 != n or n1 != n or idx.shape[0] != g_count * n:
        raise ValueError(f"bad scatter burst: banked {banked.shape}, "
                         f"idx {idx.shape} for N={n}")
    if into.shape[1] != n or into.shape[2] != w:
        raise ValueError(f"scatter target {into.shape} does not match "
                         f"banked frames [{n}, {w}]")
    tw = word_tile or _pick_word_tile(
        w, _word_tile_cap(n, banked.dtype.itemsize), divisor=True)
    if w % tw:
        raise ValueError(
            f"scatter word_tile={tw} must divide the frame word count {w} "
            f"(the index operand must tile with the word grid)")
    rows, acts = _scatter_plan(idx.astype(jnp.int32), l)
    frame = lambda wt, g, r, row_ref, act_ref: (row_ref[g * n + r], 0, wt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(w // tw, g_count, n),
        in_specs=[
            pl.BlockSpec((None, n, n, tw),
                         lambda wt, g, r, *_: (g, 0, 0, wt)),
            # read only by an all-sentinel pass, which sits on row 0: a
            # fixed block is fetched once per word column, not per step
            pl.BlockSpec((None, n, tw), lambda wt, g, r, *_: (0, 0, wt)),
        ],
        out_specs=pl.BlockSpec((None, n, tw), frame),
        scratch_shapes=[pltpu.VMEM((n, n, tw), banked.dtype)],
    )
    return pl.pallas_call(
        functools.partial(_scatter_burst_kernel, n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(into.shape, into.dtype),
        input_output_aliases={3: 0},
        interpret=interpret,
    )(rows, acts, banked, into)
