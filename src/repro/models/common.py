"""Shared model components: norms, RoPE, attention (train/prefill/decode),
MLP variants, embeddings, losses, initialisation.

All functions are pure; parameters are plain dicts of arrays.  Activation
sharding is annotated through :func:`repro.parallel.sharding.shard` with
logical axis names, so the same code runs unsharded on CPU and pjit-sharded
on the production mesh.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.fabric.fabric import Fabric, pm_to_banked
from repro.fabric.scheduler import FRAME_SENTINEL as _SENTINEL
from repro.parallel.sharding import shard


# ----------------------------------------------------------------------------
# init
# ----------------------------------------------------------------------------

def trunc_normal(key, shape, dtype, scale: float) -> jax.Array:
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def dense_init(key, d_in, d_out, dtype) -> jax.Array:
    return trunc_normal(key, (d_in, d_out), dtype, 1.0 / math.sqrt(d_in))


def pad_vocab(v: int, multiple: int = 128) -> int:
    return -(-v // multiple) * multiple


# ----------------------------------------------------------------------------
# norms
# ----------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def apply_norm(x, p, kind: str):
    if kind == "rms":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def init_norm(key, d, dtype, kind: str):
    del key
    if kind == "rms":
        return {"scale": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


# ----------------------------------------------------------------------------
# RoPE
# ----------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding on ``x [..., S, H, D]`` with ``positions [..., S]``."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq        # [..., S, half]
    ang = ang[..., None, :]                                      # [..., S, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# attention
# ----------------------------------------------------------------------------

def _gqa_scores(q, k):
    """q [B,Sq,Hkv,G,D] x k [B,Sk,Hkv,D] → [B,Hkv,G,Sq,Sk] (no KV repeat)."""
    return jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                      preferred_element_type=jnp.float32)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              q_positions: jax.Array, kv_positions: jax.Array,
              causal: bool = True, window: int = 0,
              kv_chunk: int = 0) -> jax.Array:
    """Memory-efficient multi-query attention.

    ``q [B, Sq, H, D]``, ``k/v [B, Sk, Hkv, D]``; grouped heads are folded so
    KV is never materialised H/Hkv times.  When ``kv_chunk > 0`` the KV axis
    is processed in chunks with an online-softmax (flash-style) scan — the
    form used for the 32k prefill and all long-context cells, bounding live
    intermediates to one [B, H, Sq, kv_chunk] tile per step.
    ``window > 0`` restricts attention to the last ``window`` positions
    (sliding-window / local layers).
    """
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d) * (d ** -0.5)
    scale_mask = lambda s, qp, kp: _mask_scores(s, qp, kp, causal, window)

    if not kv_chunk or kv_chunk >= sk:
        scores = _gqa_scores(qg, k)                              # f32
        scores = scale_mask(scores, q_positions, kv_positions)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v)
        return out.reshape(b, sq, h, d)

    n_chunks = sk // kv_chunk
    k_c = k.reshape(b, n_chunks, kv_chunk, hkv, d)
    v_c = v.reshape(b, n_chunks, kv_chunk, hkv, d)
    kp_c = kv_positions.reshape(n_chunks, kv_chunk)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        kc, vc, kpc = inp
        s = _gqa_scores(qg, kc)                                  # [B,hkv,g,Sq,C]
        s = scale_mask(s, q_positions, kpc)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        l_cur = l_prev * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc)
        acc = acc * alpha[..., None].astype(acc.dtype) + pv
        return (m_cur, l_cur, acc), None

    m0 = jnp.full((b, hkv, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, sq, d), v.dtype)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, acc0),
        (jnp.moveaxis(k_c, 1, 0), jnp.moveaxis(v_c, 1, 0), kp_c))
    out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
    return jnp.moveaxis(out, -2, 1).reshape(b, sq, h, d)


def _mask_scores(scores, q_pos, k_pos, causal, window):
    """Apply causal / sliding-window masking in position space."""
    qp = q_pos[..., :, None] if q_pos.ndim == 1 else q_pos[:, None, None, :, None]
    kp = k_pos[..., None, :] if k_pos.ndim == 1 else k_pos[:, None, None, None, :]
    neg = jnp.float32(-1e30)
    if causal:
        scores = jnp.where(qp >= kp, scores, neg)
    if window:
        scores = jnp.where(qp - kp < window, scores, neg)
    return scores


def attention_block_params(key, cfg, dtype) -> dict:
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dtype),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype),
    }


def sinusoidal_positions(seq: int, d: int) -> jax.Array:
    """Standard sinusoidal absolute position embedding [seq, d] (whisper)."""
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10_000.0, 2.0 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def sinusoidal_at(pos: jax.Array, d: int) -> jax.Array:
    """Sinusoidal embedding for a single (traced) position → [d]."""
    dim = jnp.arange(d // 2, dtype=jnp.float32)
    ang = pos.astype(jnp.float32) / jnp.power(10_000.0, 2.0 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])


def _qkv_project(p, x, cfg, *, positions, layer_kind: str,
                 apply_rope: bool = True):
    """Shared attention prologue: QKV projection, activation sharding, and
    RoPE with the layer-kind theta selection.  Both decode paths (per-layer
    and burst-scheduled) must stay bit-identical, so this lives in one
    place.  Returns ``(q, k, v, window)``."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    theta = cfg.rope_theta
    if layer_kind == "A" and cfg.rope_theta_global:
        theta = cfg.rope_theta_global
    window = cfg.sliding_window if layer_kind == "L" else 0
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    k = (x @ p["wk"]).reshape(b, s, hkv, hd)
    v = (x @ p["wv"]).reshape(b, s, hkv, hd)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    if apply_rope:
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    return q, k, v, window


def _attn_output(p, out):
    """Shared attention epilogue: output projection + sharding."""
    b, s = out.shape[:2]
    out = shard(out, "batch", "seq", "heads", "head_dim")
    y = out.reshape(b, s, -1) @ p["wo"]
    return shard(y, "batch", "seq", "d_model")


def attention_apply(p, x, cfg, *, positions, layer_kind: str,
                    cache: Optional[dict] = None, kv_chunk: int = 0,
                    apply_rope: bool = True, causal: bool = True):
    """Self-attention with optional KV cache.

    Training/prefill: ``cache`` is None → keys from current sequence; returns
    (out, new_kv) where new_kv is the line-major KV for cache installation.
    Decode: ``cache = {"k": [B,T,Hkv,D] line-major, "v": ..., "pos": scalar}``;
    the cache is read through the Medusa KV layout engine (port-major
    head streams) — the paper's read network in production (DESIGN.md §3.1).
    """
    q, k, v, window = _qkv_project(p, x, cfg, positions=positions,
                                   layer_kind=layer_kind,
                                   apply_rope=apply_rope)
    if cache is None:
        out = attention(q, k, v, positions, positions, causal=causal,
                        window=window, kv_chunk=kv_chunk)
        new_kv = {"k": k, "v": v}
    else:
        pos = cache["pos"]            # scalar, or [B] for per-slot serving
        ck = _cache_write(cache["k"], k, pos)
        cv = _cache_write(cache["v"], v, pos)
        t = ck.shape[1]
        kv_pos = jnp.arange(t)
        # single-token decode: q position == pos; [B, T] mask when per-slot
        valid = (kv_pos <= pos if pos.ndim == 0
                 else kv_pos[None, :] <= pos[:, None])
        out = cached_attention(q, ck, cv, pos, kv_pos, valid, window, cfg)
        new_kv = {"k": ck, "v": cv, "pos": pos}
    return _attn_output(p, out), new_kv


def _kv_port_major(c: jax.Array, cfg) -> jax.Array:
    """[B, T, Hkv, D] line-major → [B, Hkv, T, D] port-major via the model's
    fabric (medusa kernel / crossbar / oracle — ``cfg.resolved_fabric``)."""
    return Fabric.for_model(cfg).kv_port_major(c)


# ----------------------------------------------------------------------------
# burst-scheduled KV banking (serving decode)
# ----------------------------------------------------------------------------
#
# The scheduled decode step hoists every full-attention leaf's port-major
# conversion out of the per-layer scan into ONE read-network burst (and the
# conversion back into one write-network burst).  These helpers are the
# relabels between a leaf's natural layout and the network's line/banked
# forms; they assume the port-per-KV-head geometry (leaf Hkv axis == N).

def kv_leaf_to_lines(leaf: jax.Array) -> jax.Array:
    """Line-major KV leaf ``[..., T, Hkv, D]`` → line stream ``[L, N, D]``
    (one timestep = one line across the head ports; leading axes flatten)."""
    return leaf.reshape((-1,) + leaf.shape[-2:])


def banked_to_port_major(banked: jax.Array, lead_shape) -> jax.Array:
    """Read-network output ``[G, N, N, D]`` → port-major ``[..., Hkv, T, D]``
    where ``lead_shape = leaf.shape[:-2]`` (e.g. ``(layers, B, T)``).  A pure
    relabel of the banked buffer: each port reads its own deep-narrow bank."""
    g, n, _, d = banked.shape
    pm = banked.transpose(1, 0, 2, 3).reshape((n,) + tuple(lead_shape) + (d,))
    return jnp.moveaxis(pm, 0, len(lead_shape) - 1)


def port_major_to_banked(pm: jax.Array) -> jax.Array:
    """Port-major ``[..., Hkv, T, D]`` → write-network input ``[G, N, N, D]``
    (inverse of :func:`banked_to_port_major`; the banked layout invariant
    itself lives in :func:`repro.fabric.fabric.pm_to_banked`)."""
    x = jnp.moveaxis(pm, pm.ndim - 3, 0)          # [Hkv, ..., T, D]
    n, d = x.shape[0], x.shape[-1]
    return pm_to_banked(x.reshape(n, -1, d), n)   # [Hkv, L, D] streams


# ----------------------------------------------------------------------------
# shared physical page pool: gather-based decode
# ----------------------------------------------------------------------------
#
# Under ``FabricConfig.paged_pool`` the serving engine backs every
# full-attention leaf with one shared ``[n_pages, page_size, Hkv, D]``
# physical region; a per-slot logical→physical page table indirects each
# slot's time axis into it.  Two decode forms exist:
#
# * **Fused gather** (``FabricConfig.fused_gather``, the default under the
#   pool): the logical→physical indirection is part of the fabric contract.
#   The engine plans the step's live frames host-side
#   (:func:`page_live_plan`) and the scheduler's sparse-extent streams bank
#   ONLY those — the network's traffic scales with live tokens, not pool
#   capacity — with :func:`gather_pool_frames` reduced to the cheap
#   compact→dense relabel on the (live-sized) banked output.
# * **Gather-after-burst** (the fallback): the burst banks the pool's F
#   frames once and the decode step takes the table as an operand,
#   gathering each slot's mapped frames from the network's output in
#   port-major space.
#
# Every valid position gathers exactly the frame the dense layout would
# hold either way, so logits are bit-identical to the dense engine.

# unmapped-frame sentinel: gathers fill zeros, scatters drop (the shared
# sparse-extent value — repro.fabric.scheduler.FRAME_SENTINEL)


def page_gather_indices(page_table: jax.Array, page_size: int,
                        t_depth: int) -> jax.Array:
    """Per-slot page table ``[B, pages_per_slot]`` (``-1`` = unmapped) →
    physical **frame** indices ``[B, t_depth]`` into the pool's flattened
    ``n_pages * page_size`` frame axis.  Unmapped positions get a far
    out-of-range sentinel: gathers fill them with zeros (always behind the
    decode position mask), scatters drop them."""
    t = jnp.arange(t_depth, dtype=jnp.int32)
    pt = page_table[:, t // page_size]                       # [B, T]
    return jnp.where(pt < 0, jnp.int32(_SENTINEL),
                     pt * jnp.int32(page_size) + t % page_size)


def page_live_plan(page_table, page_size: int, t_depth: int, n_ports: int,
                   bucket: int = 0):
    """Host-side plan of a step's live frames for the fused-gather decode.

    ``page_table`` is the host ``int32 [S, pages_per_slot]`` table (``-1``
    unmapped; a slot's mapped logical pages are a prefix — the pool
    allocates them in order).  Returns three ``int32`` numpy arrays:

    * ``live_idx [L_pad]`` — the physical frame index of every live frame,
      slot-major in logical order, sentinel-padded to a multiple of
      ``n_ports`` (then of ``bucket``, to bound retrace churn — padding
      frames gather as zeros and scatter as drops, so they cost only lanes);
    * ``expand [S, t_depth]`` — each dense position's index into the
      compact live list (sentinel where unmapped), i.e. the cheap
      compact→dense relabel applied to the network's live-sized output;
    * ``dense_pos [L_pad]`` — each live frame's flattened dense position
      ``s * t_depth + t`` (the inverse of ``expand`` on the live set),
      used to compact the updated dense view before the write scatter.

    A slot's live extent is ``min(mapped_pages * page_size, t_depth)`` —
    the tail of a partially-used last page is live (it backs upcoming
    decode growth), but frames past the dense depth are not addressable
    and never move."""
    table = np.asarray(page_table)
    s_count = table.shape[0]
    mapped = (table >= 0).sum(axis=1)
    # the mapped-prefix invariant underwrites the whole plan (and the
    # sparse-extent index contract: entries are physical frames or the
    # sentinel, never negative) — a hole inside a row would emit -1-derived
    # frame indices, so fail loudly here rather than corrupt a gather
    if not np.array_equal(table >= 0,
                          np.arange(table.shape[1])[None, :] < mapped[:, None]):
        raise ValueError("page table rows must map a logical-page prefix "
                         "(-1 entries only after the mapped pages)")
    live = np.minimum(mapped * page_size, t_depth)
    unit = max(n_ports, 1)
    l_pad = -(-max(int(live.sum()), 1) // unit) * unit
    if bucket:
        l_pad = -(-l_pad // bucket) * bucket
    live_idx = np.full((l_pad,), _SENTINEL, np.int32)
    expand = np.full((s_count, t_depth), _SENTINEL, np.int32)
    dense_pos = np.full((l_pad,), _SENTINEL, np.int32)
    off = 0
    for s in range(s_count):
        m = int(live[s])
        if not m:
            continue
        t = np.arange(m)
        live_idx[off:off + m] = (table[s, t // page_size] * page_size
                                 + t % page_size)
        expand[s, :m] = off + t
        dense_pos[off:off + m] = s * t_depth + t
        off += m
    return live_idx, expand, dense_pos


def pool_rep_indices(idx: jax.Array, reps: int, frames: int) -> jax.Array:
    """Tile per-pool frame indices ``idx [K]`` over a leaf's leading layer
    axis: rep ``r``'s pool occupies lines ``[r*frames, (r+1)*frames)`` of
    the flattened line stream, so valid entries shift by ``r*frames`` and
    sentinels stay sentinels.  Returns ``[reps*K]``."""
    offs = jnp.arange(reps, dtype=jnp.int32)[:, None] * jnp.int32(frames)
    tiled = jnp.broadcast_to(idx[None, :], (reps, idx.shape[0]))
    return jnp.where(tiled < frames, tiled + offs,
                     jnp.int32(_SENTINEL)).reshape(-1)


def step_frame_indices(live_idx: jax.Array, expand: jax.Array,
                       pos: jax.Array) -> jax.Array:
    """Each slot's physical frame for the position a decode step writes,
    ``live_idx[expand[b, pos[b]]]`` over the :func:`page_live_plan`
    operands (``pos`` scalar or [B], clamped into the dense depth as the
    step's own update clamps it).  An unmapped position — an idle slot's
    included — gives the sentinel.  Returns ``[B]``."""
    b, t = expand.shape
    at = jnp.clip(jnp.broadcast_to(pos, (b,)), 0, t - 1)
    compact = jnp.take_along_axis(expand, at[:, None], axis=1)[:, 0]
    return jnp.take(live_idx, compact, mode="fill", fill_value=_SENTINEL)


def gather_pool_frames(pool_flat: jax.Array, phys: jax.Array,
                       axis: int) -> jax.Array:
    """Gather per-slot frames from a flattened frame axis at ``axis``:
    ``phys`` (any shape; sentinel/out-of-range = zeros) replaces that axis
    with its own shape in the result.

    This is the thin consumer-side dispatch over the fused-gather contract:
    under ``FabricConfig.fused_gather`` the pool-sized indirection happens
    inside ``Fabric.read_burst(..., indices=)`` (the network banks only
    live frames) and this helper only relabels the live-sized output
    (``expand`` from :func:`page_live_plan`); on the fallback it is the
    full logical→physical gather over the banked pool
    (:func:`page_gather_indices`)."""
    return jnp.take(pool_flat, phys, axis=axis, mode="fill", fill_value=0)


def scatter_pool_frames(pool_flat: jax.Array, dense: jax.Array,
                        phys: jax.Array, axis: int) -> jax.Array:
    """Inverse of :func:`gather_pool_frames`: write the per-slot dense
    frames (``[B, T]`` at ``axis``) back to their mapped physical frames;
    unmapped positions drop.  Mapped frames are owned by exactly one slot
    (the pool's free list never double-maps), so the scatter is exact.
    Under the fused contract the pool-sized form of this lives in
    ``Fabric.write_burst(..., indices=, into=)`` (the gather-after-burst
    fallback is the only remaining pool-sized caller)."""
    idx = [slice(None)] * pool_flat.ndim
    idx[axis] = phys.reshape(-1)
    upd = dense.reshape(dense.shape[:axis] + (-1,) + dense.shape[axis + 2:])
    return pool_flat.at[tuple(idx)].set(upd, mode="drop")


def pm_cache_write(cache_pm: jax.Array, new: jax.Array,
                    pos: jax.Array) -> jax.Array:
    """Write each slot's new K/V frame at ``pos`` directly in port-major
    space: ``cache_pm [..., B, Hkv, T, D]``, ``new [..., B, Hkv, D]``, pos
    scalar or [B]; leading axes (a layer stack) ride along.

    Banking is a permutation, so updating after banking is bit-identical to
    the unscheduled path's update-then-bank."""
    upd = new[..., None, :]                       # [..., B, Hkv, 1, D]
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache_pm, upd, pos,
                                                   axis=cache_pm.ndim - 2)
    b_axis = cache_pm.ndim - 4
    return jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice_in_dim(
        c, u, p, axis=c.ndim - 2), in_axes=(b_axis, b_axis, 0),
        out_axes=b_axis)(cache_pm, upd, pos)


def attention_apply_banked(p, x, cfg, *, positions, layer_kind: str,
                           cache: dict):
    """Decode self-attention against a pre-banked port-major KV cache.

    ``cache = {"k_pm"/"v_pm": [B, Hkv, T, D], "pos": scalar or [B]}`` — the
    read network's output for this layer, hoisted into the step's single
    burst by the scheduler.  The new token's K/V is written at ``pos`` in
    port-major space and attention runs on the updated port-major cache —
    bit-identical to :func:`attention_apply`'s cached branch, which updates
    line-major and re-banks per layer.  Returns ``(out, {"k_new",
    "v_new"})``: the step's fresh frames ``[B, Hkv, D]``, not the updated
    view — the step's write burst carries only those (or rebuilds the
    updated view from them outside the layer scan, see
    :func:`repro.models.lm.decode_step`)."""
    q, k, v, window = _qkv_project(p, x, cfg, positions=positions,
                                   layer_kind=layer_kind)
    pos = cache["pos"]
    k_new, v_new = k[:, 0], v[:, 0]
    ck_p = pm_cache_write(cache["k_pm"], k_new, pos)
    cv_p = pm_cache_write(cache["v_pm"], v_new, pos)
    ck_p = shard(ck_p, "batch", "kv_heads", "kv_seq", "head_dim")
    cv_p = shard(cv_p, "batch", "kv_heads", "kv_seq", "head_dim")
    t = ck_p.shape[2]
    kv_pos = jnp.arange(t)
    valid = (kv_pos <= pos if pos.ndim == 0
             else kv_pos[None, :] <= pos[:, None])
    out = _decode_attention(q, ck_p, cv_p, pos, kv_pos, valid, window)
    return _attn_output(p, out), {"k_new": k_new, "v_new": v_new}


def _cache_write(cache: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Write the new token's K/V at ``pos`` (scalar, or per-row [B])."""
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache, new, pos, axis=1)
    return jax.vmap(lambda c, n, p:
                    jax.lax.dynamic_update_slice_in_dim(c, n, p, axis=0)
                    )(cache, new, pos)


def _expand_mask(mask: jax.Array) -> jax.Array:
    """[T] or [B, T] decode mask → broadcastable over [B,hkv,g,1,T]."""
    if mask.ndim == 1:
        return mask[None, None, None, None, :]
    return mask[:, None, None, None, :]


def cached_attention(q, ck, cv, pos, kv_pos, valid, window, cfg):
    """Decode attention over a line-major cache, dispatching on the model's
    fabric (``cfg.resolved_fabric.impl`` — the single switch, whether named
    by ``kv_layout`` or an explicit ``FabricConfig``).

    ``medusa``/``crossbar``/``oracle``: re-bank the cache to port-major head
    streams first (the paper's read network; on TPU the medusa form is the
    Pallas exchange-network kernel).  ``fused``: beyond-paper optimisation —
    contract directly against the line-major cache (no materialised copy; the
    layout conversion happens implicitly in the MXU operand load), halving
    cache HBM traffic per step.  All fabrics are value-identical.
    """
    fabric = Fabric.for_model(cfg)
    if fabric.impl == "fused":
        ck = shard(ck, "batch", "kv_seq", "kv_heads", "head_dim")
        cv = shard(cv, "batch", "kv_seq", "kv_heads", "head_dim")
        return _decode_attention_linemajor(q, ck, cv, pos, kv_pos, valid,
                                           window)
    ck_p, cv_p = fabric.kv_port_major(ck), fabric.kv_port_major(cv)
    ck_p = shard(ck_p, "batch", "kv_heads", "kv_seq", "head_dim")
    cv_p = shard(cv_p, "batch", "kv_heads", "kv_seq", "head_dim")
    return _decode_attention(q, ck_p, cv_p, pos, kv_pos, valid, window)


def _decode_attention_linemajor(q, k, v, pos, kv_pos, valid, window):
    """Fused decode attention: ``q [B,1,H,D]`` x ``k/v [B,T,Hkv,D]``.

    The cache-side dots run in the cache dtype (bf16 x bf16 is MXU-native;
    forcing an f32 ``preferred_element_type`` makes XLA carry an f32 COPY of
    the whole cache through the layer scan).  Only the tiny score tensor is
    upcast for the softmax.
    """
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d) * (d ** -0.5)
    s = jnp.einsum("bqhgd,bthd->bhgqt", qg.astype(k.dtype), k)
    s = s.astype(jnp.float32)
    mask = valid
    if window:
        dist = (pos - kv_pos if pos.ndim == 0
                else pos[:, None] - kv_pos[None, :])
        mask = mask & (dist < window)
    s = jnp.where(_expand_mask(mask), s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqt,bthd->bqhgd", p.astype(v.dtype), v)
    return out.reshape(b, sq, h, d)


def _decode_attention(q, k_pm, v_pm, pos, kv_pos, valid, window):
    """Single-step decode attention over a port-major cache.

    ``q [B,1,H,D]``, ``k_pm/v_pm [B,Hkv,T,D]``.  Cache-side dots in cache
    dtype (see ``_decode_attention_linemajor``).

    The operands pass an optimization barrier, so the attention compiles
    alike whatever produced its port-major cache (the per-layer path's
    update-then-bank, or the scheduled step's update in port-major space):
    left free, XLA fuses each producer into the score and softmax loops
    differently and the two paths round apart by about 1e-6 in float32."""
    q, k_pm, v_pm = jax.lax.optimization_barrier((q, k_pm, v_pm))
    b, sq, h, d = q.shape
    hkv = k_pm.shape[1]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d) * (d ** -0.5)
    s = jnp.einsum("bqhgd,bhkd->bhgqk", qg.astype(k_pm.dtype), k_pm)
    s = s.astype(jnp.float32)
    mask = valid
    if window:
        dist = (pos - kv_pos if pos.ndim == 0
                else pos[:, None] - kv_pos[None, :])
        mask = mask & (dist < window)
    s = jnp.where(_expand_mask(mask), s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bqhgd", p.astype(v_pm.dtype), v_pm)
    return out.reshape(b, sq, h, d)


# ----------------------------------------------------------------------------
# MLP
# ----------------------------------------------------------------------------

def mlp_params(key, d_model, d_ff, kind, dtype) -> dict:
    ks = jax.random.split(key, 3)
    p = {"w_out": dense_init(ks[2], d_ff, d_model, dtype)}
    if kind in ("swiglu", "geglu"):
        p["w_gate"] = dense_init(ks[0], d_model, d_ff, dtype)
        p["w_up"] = dense_init(ks[1], d_model, d_ff, dtype)
    else:
        p["w_up"] = dense_init(ks[1], d_model, d_ff, dtype)
    return p


def mlp_apply(p, x, kind: str) -> jax.Array:
    if kind in ("swiglu", "geglu"):
        act = jax.nn.silu if kind == "swiglu" else (
            lambda u: jax.nn.gelu(u, approximate=True))
        h = act(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"], approximate=True)
    h = shard(h, "batch", "seq", "d_ff")
    return shard(h @ p["w_out"], "batch", "seq", "d_model")


# ----------------------------------------------------------------------------
# embeddings / head / loss
# ----------------------------------------------------------------------------

def embed_params(key, cfg, dtype) -> dict:
    v = pad_vocab(cfg.vocab_size)
    p = {"table": trunc_normal(key, (v, cfg.d_model), dtype,
                               1.0 / math.sqrt(cfg.d_model))}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(jax.random.fold_in(key, 1), cfg.d_model, v, dtype)
    return p


def embed_apply(p, tokens: jax.Array) -> jax.Array:
    table = shard(p["table"], "vocab", "d_model")
    return shard(jnp.take(table, tokens, axis=0), "batch", "seq", "d_model")


def logits_apply(p, x: jax.Array, cfg) -> jax.Array:
    if cfg.tie_embeddings:
        w = shard(p["table"], "vocab", "d_model")
        logits = jnp.einsum("bsd,vd->bsv", x, w,
                            preferred_element_type=jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["head"],
                            preferred_element_type=jnp.float32)
    return shard(logits, "batch", "seq", "vocab")


def draft_head_params(key, cfg, dtype) -> dict:
    """Medusa-style draft heads: ``cfg.spec_heads`` residual projections
    (``d_model → d_model``, SiLU) off the final-norm hidden state; logits
    come from the shared (tied) unembedding, so a head adds ``d²`` params,
    not ``d·V``."""
    return {"w": jnp.stack([
        dense_init(k, cfg.d_model, cfg.d_model, dtype)
        for k in jax.random.split(key, cfg.spec_heads)])}


def draft_logits(p_draft, x: jax.Array, p_embed, cfg) -> jax.Array:
    """``x [B, S, d]`` (final-norm hidden state) → ``[B, k, V]`` draft-head
    logits off the last position — head i proposes the token i+1 steps
    ahead of the one the real unembedding scores."""
    last = x[:, -1]                                         # [B, d]
    h = last[:, None, :] + jax.nn.silu(
        jnp.einsum("bd,kde->bke", last, p_draft["w"]))      # [B, k, d]
    return logits_apply(p_embed, h, cfg)


def softmax_xent(logits: jax.Array, targets: jax.Array,
                 vocab_size: int) -> jax.Array:
    """Mean cross-entropy; padded vocab entries masked out of the softmax."""
    v = logits.shape[-1]
    if v > vocab_size:
        pad_mask = jnp.arange(v) >= vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
