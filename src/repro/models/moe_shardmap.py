"""Medusa-schedule MoE layer: explicit shard_map dispatch.

The pjit/GSPMD MoE (`moe.py`) lets the compiler insert collectives for the
token↔expert redistribution; its cost shows up as all-gathers in §Perf cell
B.  This module is the paper-native alternative: the interconnect's **even
static partition + rotation schedule** made explicit —

1. every rank routes ITS OWN tokens locally (top-k, rank-local capacity —
   paper obs. 1: bandwidth statically, evenly partitioned per port);
2. per-destination fixed-size blocks ``[E_ranks, cap_block, d]`` are
   exchanged with the **ring all-to-all** (N−1 ``ppermute`` rotations — the
   §III-A diagonal schedule on chips, neighbour-aligned and overlappable);
3. each rank runs its local experts over the arrived blocks;
4. results return on the reverse ring and combine locally.

No dynamic cross-shard scatter/gather exists anywhere in the path; every
transfer is a fixed-shape neighbour rotation, exactly the crossbar→rotation
substitution of the paper.  Equivalence with the GSPMD layer (ample
capacity) is asserted in ``tests/test_moe_shardmap.py``.

Usage: experts must divide the mesh axis; each rank owns ``E / n`` experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.fabric.fabric import Fabric
from repro.parallel.collectives import ring_all_to_all


def moe_apply_shardmap(p, x: jax.Array, cfg, axis_name: str = "model"):
    """Per-rank body (run under shard_map, tokens sharded over axis_name).

    ``x [B_loc, S, d]``; expert weight leaves in ``p`` hold only this rank's
    experts ``[e_loc, ...]``.  Returns ``[B_loc, S, d]``.
    """
    m = cfg.moe
    fabric = Fabric.for_model(cfg)
    n = lax.axis_size(axis_name)
    e_total = m.n_experts_padded
    e_loc = e_total // n
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)

    # 1. local routing (router weights are replicated)
    logits = xt.astype(jnp.float32) @ p["router"]               # [t, E_real]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m.top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    a = top_e.reshape(-1)                                       # [t*k]
    order = jnp.argsort(a, stable=True)
    a_sorted = a[order]
    first = jnp.searchsorted(a_sorted, a_sorted, side="left")
    rank_in_e = jnp.zeros_like(a).at[order].set(
        jnp.arange(t * m.top_k) - first)
    # rank-local capacity per expert: even static partition of the rank's
    # token bandwidth across experts (paper obs. 1)
    cap = max(int(t * m.top_k * m.capacity_factor / m.n_experts), 1)
    keep = rank_in_e < cap
    slot = jnp.where(keep, a * cap + rank_in_e, e_total * cap)

    # gather-only payload staging into [E_total * cap, d] send blocks; the
    # payload moves through the fabric's routing primitive (data-dependent
    # destinations — the one consumer that genuinely needs a crossbar hop)
    inv = jnp.full((e_total * cap,), t * m.top_k, jnp.int32)
    inv = inv.at[slot].set(jnp.arange(t * m.top_k, dtype=jnp.int32),
                           mode="drop")
    valid_slot = inv < t * m.top_k
    src_tok = jnp.clip(inv // m.top_k, 0, t - 1)
    send = jnp.where(valid_slot[:, None], fabric.route(xt, src_tok), 0)

    # 2. ring exchange: block r = the cap*e_loc slots destined to rank r
    send_blocks = send.reshape(n, e_loc * cap, d)
    recv = ring_all_to_all(send_blocks, axis_name)              # [n, e_loc*cap, d]

    # 3. local expert FFN over arrived tokens: [e_loc, n*cap, d]
    buf = recv.reshape(n, e_loc, cap, d).transpose(1, 0, 2, 3) \
              .reshape(e_loc, n * cap, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h, p["w_out"])               # [e_loc, n*cap, d]

    # 4. reverse ring: block r returns to its source rank
    back = y.reshape(e_loc, n, cap, d).transpose(1, 0, 2, 3) \
            .reshape(n, e_loc * cap, d)
    returned = ring_all_to_all(back, axis_name)                 # [n, e_loc*cap, d]
    y_full = returned.reshape(e_total * cap, d)

    # local combine (gather + static top-k reduce)
    gathered = jnp.where(keep[:, None],
                         fabric.route(y_full,
                                      jnp.clip(slot, 0, e_total * cap - 1)), 0)
    w = top_p.reshape(-1)[:, None].astype(x.dtype)
    out = (gathered * w).reshape(t, m.top_k, d).sum(axis=1)
    return out.reshape(b, s, d).astype(x.dtype)


def shard_expert_params(p, rank: jax.Array, n: int, cfg):
    """Slice this rank's experts from full stacked weights (test helper;
    production passes pre-sharded leaves via shard_map in_specs)."""
    m = cfg.moe
    e_loc = m.n_experts_padded // n
    sl = lambda w: lax.dynamic_slice_in_dim(w, rank * e_loc, e_loc, axis=0)
    return {"router": p["router"], "w_gate": sl(p["w_gate"]),
            "w_up": sl(p["w_up"]), "w_out": sl(p["w_out"])}
