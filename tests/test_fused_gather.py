"""Fused page-table gather: the pool's logical→physical indirection as part
of the fabric contract (sparse-extent streams) instead of a consumer-side
postprocess on the banked full pool.

The acceptance bar:

* kernel level — the fused gather/scatter burst kernels (indices as a
  scalar-prefetched operand) are bit-identical to take/scatter around the
  exchange network, including sentinel padding rows and odd word tiles;
* scheduler level — sparse-extent streams are bit-identical to their dense
  take-after equivalents under pack × word_fold × {kernel, unrolled}, and
  the traffic census counts live words, not pool words;
* decode level — the fused scheduled step, the gather-after-burst scheduled
  step and the per-layer paged fallback agree bit-for-bit on logits AND the
  written-back pools, over churny page tables (holes, ``-1`` unmapped rows,
  reused non-contiguous physical pages);
* engine level — fused on/off produce identical tokens while ``words_moved``
  drops to the live-frame count and ``gather_fused_bursts`` distinguishes
  the contracts in the printed census;
* admission — the fused sparse-write install is bit-identical to the
  per-layer splice and widens burst eligibility to odd spans.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.fabric import BurstScheduler, Fabric, PagedKVCache, SchedulerStats
from repro.kernels import ops
from repro.kernels.medusa_transpose import (_pick_word_tile,
                                            gather_burst_network_tiles,
                                            scatter_burst_network_tiles)
from repro.models import api, common as cm, lm
from repro.serving import Request, ServingEngine

from repro.fabric.scheduler import FRAME_SENTINEL as SENTINEL
from tests.hypothesis_compat import given, settings, st

KEY = jax.random.PRNGKey(11)


def _cfg():
    return dataclasses.replace(get_smoke("starcoder2-15b"), dtype="float32")


_PARAMS = {}


def _params(cfg):
    if cfg.name not in _PARAMS:
        _PARAMS[cfg.name] = api.init_params(cfg, KEY)
    return _PARAMS[cfg.name]


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", (2, 4, 8))
@pytest.mark.parametrize("word_tile", (0, 3))
def test_gather_kernel_matches_take_then_network(n, word_tile):
    """One fused launch == take (sentinels → zero frames) + banked
    transpose, for power-of-two N and both whole-burst and odd dividing
    word tiles."""
    l, w, k = 5 * n, 6, 2 * n
    lines = jax.random.normal(jax.random.fold_in(KEY, n), (l, n, w),
                              jnp.float32)
    idx = np.full((k,), SENTINEL, np.int32)
    perm = np.random.RandomState(n).permutation(l)
    idx[: k - 2] = perm[: k - 2]                   # 2 sentinel pads
    idx = jnp.asarray(idx)
    out = gather_burst_network_tiles(lines, idx, n, word_tile=word_tile,
                                     interpret=True)
    ref = jnp.take(lines, idx, axis=0, mode="fill",
                   fill_value=0).reshape(k // n, n, n, w).swapaxes(1, 2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("n", (2, 4, 8))
def test_scatter_kernel_matches_network_then_scatter(n):
    """The aliased scatter launch == write network + at[].set(drop):
    addressed rows land, sentinel rows drop, untouched rows keep their
    frames bit-for-bit."""
    l, w, k = 6 * n, 4, 2 * n
    banked = jax.random.normal(jax.random.fold_in(KEY, n), (k // n, n, n, w),
                               jnp.float32)
    pool = jax.random.normal(jax.random.fold_in(KEY, 100 + n), (l, n, w),
                             jnp.float32)
    idx = np.full((k,), SENTINEL, np.int32)
    idx[: k - 1] = np.random.RandomState(n).permutation(l)[: k - 1]
    idx = jnp.asarray(idx)
    out = scatter_burst_network_tiles(banked, idx, pool, n, interpret=True)
    lines = banked.swapaxes(1, 2).reshape(k, n, w)
    ref = pool.at[idx].set(lines, mode="drop")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # untouched rows really are the original pool
    untouched = sorted(set(range(l))
                       - set(np.asarray(idx[: k - 1]).tolist()))
    np.testing.assert_array_equal(np.asarray(out)[untouched],
                                  np.asarray(pool)[untouched])


@pytest.mark.parametrize("layout", ["leading", "middle", "all_sentinel"])
def test_scatter_kernel_sentinel_schedule(layout):
    """Sentinel runs anywhere in the index list — before the first live
    row, between live rows, or filling the whole list — drop without
    touching any pool row, including a live last row (on hardware each
    sentinel step revisits a live block and writes nothing)."""
    n, l, w = 4, 24, 6
    k = 3 * n
    banked = jax.random.normal(jax.random.fold_in(KEY, 7), (k // n, n, n, w),
                               jnp.float32)
    pool = jax.random.normal(jax.random.fold_in(KEY, 8), (l, n, w),
                             jnp.float32)
    rows = list(np.random.RandomState(3).permutation(l - 1)[: k - 5])
    s = [SENTINEL]
    idx = {"leading": s * 4 + rows + [l - 1],
           "middle": rows[:3] + s * 2 + [l - 1] + s * 2 + rows[3:],
           "all_sentinel": s * k}[layout]
    idx = jnp.asarray(np.asarray(idx, np.int32))
    out = scatter_burst_network_tiles(banked, idx, pool, n, interpret=True)
    lines = banked.swapaxes(1, 2).reshape(k, n, w)
    ref = pool.at[idx].set(lines, mode="drop")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_pick_word_tile_respects_gather_block_shape():
    """Regression (odd word_tile × sparse extent): the gather-operand mode
    must return a divisor of the frame word count — a padded edge tile
    would read/write past an indexed frame's extent — while the dense mode
    keeps its padded fallback; a non-dividing explicit tile is a loud
    error, not a silent misread."""
    cap = 4096
    assert _pick_word_tile(4099, cap) == 2176       # even split, 128-aligned
    assert _pick_word_tile(4099, cap, divisor=True) == 1  # prime: worst case
    assert 4100 % _pick_word_tile(4100, cap, divisor=True) == 0
    assert _pick_word_tile(3 * 2048, cap, divisor=True) == 3072  # aligned
    w = 6000                                              # no divisor in (2048, 4096]
    t = _pick_word_tile(w, cap, divisor=True)
    assert w % t == 0 and t <= 4096
    lines = jnp.zeros((4, 4, 6), jnp.float32)
    idx = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="word_tile"):
        gather_burst_network_tiles(lines, idx, 4, word_tile=4,
                                   interpret=True)
    with pytest.raises(ValueError, match="word_tile"):
        scatter_burst_network_tiles(jnp.zeros((1, 4, 4, 6), jnp.float32),
                                    idx, lines, 4, word_tile=4,
                                    interpret=True)


# ---------------------------------------------------------------------------
# scheduler level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pack", ("packed", "pad"))
@pytest.mark.parametrize("fold", (1, 2, "auto"))
@pytest.mark.parametrize("kernels", (False, True))
def test_scheduler_sparse_streams_parity(pack, fold, kernels):
    """Sparse-extent reads/writes mixed with dense streams are bit-identical
    to take-after-the-fact across every pack × fold × kernel combination,
    and the census counts live words for them."""
    n, d, frames, k = 4, 8, 32, 12
    pool = jax.random.normal(KEY, (frames, n, d), jnp.bfloat16)
    dense = jax.random.normal(jax.random.fold_in(KEY, 1), (2 * n, n, 6),
                              jnp.bfloat16)
    banked_upd = jax.random.normal(jax.random.fold_in(KEY, 2),
                                   (k // n, n, n, d), jnp.bfloat16)
    idx = np.full((k,), SENTINEL, np.int32)
    idx[:10] = np.random.RandomState(0).permutation(frames)[:10]
    idx = jnp.asarray(idx)
    prev = ops.kernels_enabled()
    ops.use_kernels(kernels)
    try:
        stats = SchedulerStats()
        sched = BurstScheduler(Fabric.make(n, "medusa", pack=pack,
                                           word_fold=fold), stats=stats)
        sched.enqueue_read("kv", pool, gather=idx)
        sched.enqueue_read("wt", dense)
        sched.enqueue_write("kv_w", banked_upd, scatter=idx, into=pool)
        out = sched.flush()
    finally:
        ops.use_kernels(prev)
    ref_read = jnp.take(pool, idx, axis=0, mode="fill",
                        fill_value=0).reshape(k // n, n, n, d).swapaxes(1, 2)
    ref_pool = pool.at[idx].set(
        banked_upd.swapaxes(1, 2).reshape(k, n, d), mode="drop")
    np.testing.assert_array_equal(*map(np.asarray, (out["kv"], ref_read)))
    np.testing.assert_array_equal(*map(np.asarray, (out["kv_w"], ref_pool)))
    live = 2 * (k * n * d)                        # read + write live words
    assert stats.words_live == live
    assert stats.words_moved == live + 2 * n * n * 6
    assert stats.gather_fused_bursts >= 1
    # the spec records the sparse extent: live words vs the pool extent
    assert stats.words_padded == 0 or pack == "pad"


def test_portspec_sparse_extent_fields():
    """The sparse-extent mode is visible on the PortSpec: live ``words``
    plus the ``pool_words`` the gather-after fallback would have moved."""
    n, d, frames, k = 4, 8, 32, 8
    pool = jnp.zeros((frames, n, d), jnp.float32)
    idx = jnp.zeros((k,), jnp.int32)
    sched = BurstScheduler(Fabric.make(n, "medusa"))
    spec = sched.enqueue_read("kv", pool, gather=idx)
    assert spec.gathered and spec.words == (k // n) * d
    assert spec.pool_words == (frames // n) * d
    dense_spec = sched.enqueue_read("wt", jnp.zeros((n, n, 3), jnp.float32))
    assert not dense_spec.gathered and dense_spec.pool_words == 0


# ---------------------------------------------------------------------------
# decode level: fused vs gather-after vs per-layer, churny tables
# ---------------------------------------------------------------------------

def test_page_live_plan_rejects_non_prefix_rows():
    """The live plan (and the sparse-extent index contract: non-negative
    frame indices or the sentinel) rests on the pool's mapped-prefix
    invariant — a hole inside a row must fail loudly, not emit
    -1-derived frame indices into a gather."""
    bad = np.array([[3, -1, 5, -1]], np.int32)     # hole at logical page 1
    with pytest.raises(ValueError, match="prefix"):
        cm.page_live_plan(bad, 4, 16, 2)
    ok = np.array([[3, 5, -1, -1]], np.int32)
    live_idx, expand, dense_pos = cm.page_live_plan(ok, 4, 16, 2)
    assert (live_idx[:8] >= 0).all() and (live_idx[8:] == SENTINEL).all()


def _pool_decode_setup(cfg, table, pos, page_size, t_alloc, pool_pages):
    """Pool caches with random (arbitrary) frame content + the step inputs."""
    b = table.shape[0]
    caches = api.init_cache(cfg, b, t_alloc, pool_pages=pool_pages,
                            page_size=page_size)
    leaves, treedef = jax.tree_util.tree_flatten(caches)
    leaves = [jax.random.normal(jax.random.fold_in(KEY, 200 + i),
                                leaf.shape, leaf.dtype)
              for i, leaf in enumerate(leaves)]
    caches = jax.tree_util.tree_unflatten(treedef, leaves)
    token = jax.random.randint(jax.random.fold_in(KEY, 300), (b, 1), 0,
                               cfg.vocab_size)
    return caches, token, jnp.asarray(pos, jnp.int32)


def _decode_three_ways(cfg, caches, token, pos, table, ps, t_alloc):
    pt = jnp.asarray(table)
    plan = tuple(jnp.asarray(a) for a in cm.page_live_plan(
        table, ps, t_alloc, cfg.resolved_fabric.n_ports))
    ref = api.decode_fn(_params(cfg), token, caches, pos, cfg,
                        page_table=pt, page_size=ps, t_depth=t_alloc)
    sched = BurstScheduler(Fabric(cfg.resolved_fabric))
    ga = api.decode_fn(_params(cfg), token, caches, pos, cfg, sched=sched,
                       page_table=pt, page_size=ps, t_depth=t_alloc)
    sched = BurstScheduler(Fabric(cfg.resolved_fabric))
    fused = api.decode_fn(_params(cfg), token, caches, pos, cfg, sched=sched,
                          page_table=pt, page_size=ps, t_depth=t_alloc,
                          live_plan=plan)
    return ref, ga, fused


def _assert_step_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a[1], b[1])


def _steps_three_ways(cfg, caches, token, pos, table, ps, t_alloc,
                      steps, fresh_pages):
    """``steps`` decode steps through the fused contract, the
    gather-after-burst scheduled step and the dense scheduled step (the
    dense engine's, on the dense view the table reconstructs), each fed its
    own caches and argmax tokens.  Before every step but the first each
    live slot advances one position, mapping its next page from
    ``fresh_pages`` when the position opens one; a slot whose row is all
    ``-1`` stays idle.  After each step: logits and tokens equal across the
    three, both pools (K and V) equal frame for frame between fused and
    gather-after, and every valid position of the pool equal to the dense
    cache."""
    params = _params(cfg)
    table = table.copy()
    pos = np.asarray(pos, np.int32).copy()
    idle = (table < 0).all(axis=1)
    fresh_pages = list(fresh_pages)
    fused_c = ga_c = caches
    dense_c = jax.tree.map(
        lambda leaf: cm.gather_pool_frames(
            lm._flat_frames(leaf), cm.page_gather_indices(
                jnp.asarray(table), ps, t_alloc), leaf.ndim - 4),
        caches)
    tok = {"fused": token, "ga": token, "dense": token}
    for step in range(steps):
        if step:
            for s in np.flatnonzero(~idle):
                pos[s] += 1
                if table[s, pos[s] // ps] < 0:
                    table[s, pos[s] // ps] = fresh_pages.pop(0)
        pt, p = jnp.asarray(table), jnp.asarray(pos)
        plan = tuple(jnp.asarray(a) for a in cm.page_live_plan(
            table, ps, t_alloc, cfg.resolved_fabric.n_ports))

        def sched():
            return BurstScheduler(Fabric(cfg.resolved_fabric))

        fused = api.decode_fn(params, tok["fused"], fused_c, p, cfg,
                              sched=sched(), page_table=pt, page_size=ps,
                              t_depth=t_alloc, live_plan=plan)
        ga = api.decode_fn(params, tok["ga"], ga_c, p, cfg, sched=sched(),
                           page_table=pt, page_size=ps, t_depth=t_alloc)
        dense = api.decode_fn(params, tok["dense"], dense_c, p, cfg,
                              sched=sched())
        for out in (ga, dense):
            np.testing.assert_array_equal(np.asarray(fused[0]),
                                          np.asarray(out[0]))
        fused_c, ga_c, dense_c = fused[1], ga[1], dense[1]
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), fused_c, ga_c)
        phys = cm.page_gather_indices(pt, ps, t_alloc)
        valid = np.arange(t_alloc)[None, :] <= pos[:, None]
        valid[idle] = False
        for pool, dn in zip(jax.tree.leaves(fused_c),
                            jax.tree.leaves(dense_c)):
            seen = cm.gather_pool_frames(lm._flat_frames(pool), phys,
                                         pool.ndim - 4)
            np.testing.assert_array_equal(np.asarray(seen)[..., valid, :, :],
                                          np.asarray(dn)[..., valid, :, :])
        for name, out in (("fused", fused), ("ga", ga), ("dense", dense)):
            tok[name] = jnp.argmax(out[0][:, :1], axis=-1).astype(jnp.int32)
        assert all(np.array_equal(np.asarray(tok["fused"]), np.asarray(t))
                   for t in tok.values())
    return fused_c


_CHURNY_CASES = [
    pytest.param(pack, fold, kernels, 1, id=f"{kernels}-{fold}-{pack}")
    for pack in ("packed", "pad") for fold in (1, 2, "auto")
    for kernels in (False, True)] + [
    pytest.param("packed", "auto", kernels, 4, id=f"steps-{kernels}")
    for kernels in (False, True)]


@pytest.mark.parametrize("pack, fold, kernels, steps", _CHURNY_CASES)
def test_decode_fused_vs_fallbacks_churny_table(pack, fold, kernels, steps):
    """A churny page table — a hole slot (all ``-1``), a partially-mapped
    slot, reused non-contiguous physical pages — decodes bit-identically
    through the fused contract, the gather-after-burst scheduled step and
    the per-layer paged fallback: logits AND written-back pools.

    The ``steps`` cases run four steps against the gather-after step and
    the dense engine's step, covering an idle slot (its write must drop), a
    position that opens a freshly mapped page, and a write to the pool's
    last frame: the fused write burst carries only the fresh frames, so
    every other frame must come back exactly as it went in."""
    cfg = _cfg()
    cfg = dataclasses.replace(
        cfg, fabric=dataclasses.replace(cfg.resolved_fabric, pack=pack,
                                        word_fold=fold))
    ps, t_alloc, pool_pages = 3, 16, 14            # odd page size, slack pool
    prev = ops.kernels_enabled()
    ops.use_kernels(kernels)
    try:
        if steps > 1:
            table = np.array([[5, 2, -1, -1, -1, -1],   # opens page 2 at pos 6
                              [-1, -1, -1, -1, -1, -1],  # idle, stale pos
                              [0, 13, 7, 4, -1, -1]],    # pos 5: frame 41
                             np.int32)
            pos = [5, 7, 3]
            caches, token, _ = _pool_decode_setup(cfg, table, pos, ps,
                                                  t_alloc, pool_pages)
            out = _steps_three_ways(cfg, caches, token, pos, table, ps,
                                    t_alloc, steps, fresh_pages=[9])
            # the third step wrote the last frame (page 13, offset 2)
            last = pool_pages * ps - 1
            before = lm._flat_frames(caches["unit"][0]["k"])[:, last]
            after = lm._flat_frames(out["unit"][0]["k"])[:, last]
            assert not np.array_equal(np.asarray(before), np.asarray(after))
            return
        table = np.array([[5, 2, 9, -1, -1, -1],       # non-contiguous physmap
                          [-1, -1, -1, -1, -1, -1],    # hole: retired slot
                          [0, 13, 7, 4, -1, -1]], np.int32)
        pos = [4, 0, 10]
        caches, token, pos = _pool_decode_setup(cfg, table, pos, ps,
                                                t_alloc, pool_pages)
        ref, ga, fused = _decode_three_ways(cfg, caches, token, pos, table,
                                            ps, t_alloc)
    finally:
        ops.use_kernels(prev)
    _assert_step_equal(ga, ref)
    _assert_step_equal(fused, ref)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_property_fused_decode_churny_tables(data):
    """Hypothesis sweep (nightly lane): random page tables — holes, partial
    rows, shuffled physical pages — × pack × fold × kernel, fused vs
    gather-after vs per-layer bit-parity on logits and pools."""
    cfg = _cfg()
    pack = data.draw(st.sampled_from(("packed", "pad")), label="pack")
    fold = data.draw(st.sampled_from((1, 2, "auto")), label="fold")
    kernels = data.draw(st.booleans(), label="kernels")
    ps = data.draw(st.sampled_from((1, 3, 4)), label="page_size")
    b = data.draw(st.integers(2, 3), label="slots")
    t_alloc = 12
    pages_per_slot = -(-t_alloc // ps)
    pool_pages = b * pages_per_slot + 2
    while (pool_pages * ps) % cfg.resolved_fabric.n_ports:
        pool_pages += 1
    perm = np.random.RandomState(
        data.draw(st.integers(0, 999), label="seed")).permutation(pool_pages)
    table = np.full((b, pages_per_slot), -1, np.int32)
    pos = []
    off = 0
    for s in range(b):
        mapped = data.draw(st.integers(0, pages_per_slot), label=f"m{s}")
        table[s, :mapped] = perm[off:off + mapped]
        off += mapped
        hi = min(mapped * ps, t_alloc)
        pos.append(data.draw(st.integers(0, max(hi - 1, 0)), label=f"p{s}"))
    cfg = dataclasses.replace(
        cfg, fabric=dataclasses.replace(cfg.resolved_fabric, pack=pack,
                                        word_fold=fold))
    caches, token, pos = _pool_decode_setup(cfg, table, pos, ps, t_alloc,
                                            pool_pages)
    prev = ops.kernels_enabled()
    ops.use_kernels(kernels)
    try:
        ref, ga, fused = _decode_three_ways(cfg, caches, token, pos, table,
                                            ps, t_alloc)
    finally:
        ops.use_kernels(prev)
    _assert_step_equal(ga, ref)
    _assert_step_equal(fused, ref)


# ---------------------------------------------------------------------------
# engine level
# ---------------------------------------------------------------------------

def test_engine_fused_census_scales_with_live_frames():
    """The whole point: at low pool occupancy the fused engine's decode
    traffic is the live-frame count (words_live == words_moved for the KV
    streams), a fraction of what the gather-after engine banks, with
    identical tokens — and ``gather_fused_bursts`` tells the two apart."""
    ops.use_kernels(False)
    cfg = _cfg()
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)

    def run(fused):
        eng = ServingEngine(cfg, _params(cfg), max_slots=4, t_max=64,
                            page_size=4, fused_gather=fused)
        req = Request(0, prompt, max_new_tokens=3)
        eng.submit(req)
        eng.run_to_completion(max_steps=8)
        return req.generated, eng.fabric_stats

    gen_f, fs = run(True)
    gen_g, gs = run(False)
    assert gen_f == gen_g
    assert fs.gather_fused_bursts > 0 and gs.gather_fused_bursts == 0
    assert fs.words_live > 0 and gs.words_live == 0
    # 1 slot live of 4, page-bucketed: far under the full-pool banking
    assert fs.words_moved < gs.words_moved / 2


def test_engine_fused_matches_dense_engine_bit_identical():
    """Fused engine vs the dense (unpaged) engine: same churny workload,
    bit-identical logits on live slots (the tightest reference we have)."""
    ops.use_kernels(False)
    cfg = _cfg()
    arrivals = [(0, 5, 4), (1, 9, 3), (3, 2, 5)]
    from tests.test_paged_pool import _assert_bit_identical_runs
    eng = _assert_bit_identical_runs(cfg, arrivals)
    assert eng.fused                               # default contract engaged
    assert eng.fabric_stats.gather_fused_bursts > 0


def _scan_outputs(jaxpr):
    """The output shapes of every ``scan`` in ``jaxpr``, sub-jaxprs too."""
    shapes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            shapes += [tuple(v.aval.shape) for v in eqn.outvars]
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    shapes += _scan_outputs(inner)
    return shapes


def test_engine_fused_write_census_is_fresh_frames_only():
    """One engine step at two page-table occupancies writes the same words:
    two streams (K, V) per paged layer stack of ``reps x B`` fresh frames,
    padded to whole N-groups, whatever the live count its reads carry.  The
    fused decode step carries no ``[reps, B, Hkv, T, D]`` view out of its
    layer scan, and the sparse scatter kernel is not in its program."""
    cfg = _cfg()
    n, d = cfg.resolved_fabric.n_ports, cfg.resolved_head_dim
    prev = ops.kernels_enabled()
    ops.use_kernels(True)
    try:
        census = []
        for prompts in ([[3, 1, 4, 1, 5]],
                        [list(range(1, 41)), list(range(2, 38))]):
            eng = ServingEngine(cfg, _params(cfg), max_slots=2, t_max=64,
                                page_size=4)
            for i, p in enumerate(prompts):
                eng.submit(Request(i, np.asarray(p, np.int32),
                                   max_new_tokens=4))
            eng._admit()                       # install before the census
            live = int(np.count_nonzero(
                np.asarray(eng._decode_args()[5]) != SENTINEL))
            before = eng.fabric_stats.words_written
            eng.step()
            census.append((live, eng.fabric_stats.words_written - before))
        args = eng._decode_args()
        text = eng._decode.lower(*args).as_text()
        scans = _scan_outputs(jax.make_jaxpr(eng._decode)(*args).jaxpr)
    finally:
        ops.use_kernels(prev)
    (live_lo, written_lo), (live_hi, written_hi) = census
    assert live_lo < live_hi
    reps, b, t = cfg.n_layers, eng.max_slots, eng.t_alloc
    per_leaf = -(-reps * b // n) * n * n * d
    assert written_lo == written_hi == 2 * per_leaf
    assert (reps, b, n, d) in scans                # the fresh frames
    assert (reps, b, n, t, d) not in scans
    assert "gather_burst_network_tiles" in text
    assert "scatter_burst_network_tiles" not in text


# ---------------------------------------------------------------------------
# admission: fused sparse-write install
# ---------------------------------------------------------------------------

def _fused_kv(cfg, fabric, max_slots, t_alloc, ps, fused=True):
    pages_per_slot = -(-t_alloc // ps)
    pool_pages = max_slots * pages_per_slot
    while (pool_pages * ps) % fabric.n_ports:
        pool_pages += 1
    caches = api.init_cache(cfg, max_slots, t_alloc, pool_pages=pool_pages,
                            page_size=ps)
    return PagedKVCache(caches, max_slots, t_alloc, ps,
                        pool_pages=pool_pages,
                        paged_entries=lm.paged_entries(cfg), fabric=fabric,
                        fused_gather=fused)


@pytest.mark.parametrize("kernels", (False, True))
def test_fused_prefill_install_matches_splice(kernels):
    """The fused sparse-write admission — one scatter-indexed stream per
    leaf for the whole wave — is bit-identical to the per-layer splice,
    including an odd span the banked install had to splice (eligibility
    widens: sentinel pad rows are free)."""
    cfg = dataclasses.replace(_cfg(), n_layers=1, name="starcoder2-smoke-1lf")
    t_alloc, ps = 12, 3
    lengths = (2, 4)                     # spans 3 (odd vs N=2) and 6
    from tests.test_paged_pool import _req_caches
    rcs = _req_caches(cfg, lengths, t_alloc)
    entries = [(s, rc, ln) for s, (rc, ln) in enumerate(zip(rcs, lengths))]
    fab = Fabric(cfg.resolved_fabric)
    prev = ops.kernels_enabled()
    ops.use_kernels(kernels)
    try:
        kv_fused = _fused_kv(cfg, fab, 2, t_alloc, ps)
        kv_fused.admit_wave(entries)
        kv_splice = _fused_kv(cfg, fab, 2, t_alloc, ps)
        kv_splice.admit_wave(entries, burst=False)
    finally:
        ops.use_kernels(prev)
    # the odd-span slot rides the burst now — no splice fallback at all
    assert kv_fused.prefill_bursts == 1 and kv_fused.prefill_splices == 0
    assert np.array_equal(kv_fused.pool.table, kv_splice.pool.table)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), kv_fused.caches, kv_splice.caches)
