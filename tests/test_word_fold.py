"""Machine-word lane folding + fused single-kernel bursts: parity and
accounting.

The PR 3 acceptance bar: every ``word_fold`` ∈ {auto, 1, 2, 4} × burst path
{fused kernel, unrolled} × layout {packed, pad} combination is a bit-exact
round trip on arbitrary stream mixes (dtypes × widths × group counts, odd
word counts included), the fold resolution degrades gracefully instead of
erroring, and the new ``SchedulerStats`` counters (``words_folded``,
``kernel_bursts``) reflect the post-fold traffic.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FabricConfig
from repro.core.transpose import read_network_oracle
from repro.fabric import BurstScheduler, Fabric, SchedulerStats
from repro.fabric import scheduler as sched_mod
from repro.kernels import ops
from repro.kernels.medusa_transpose import burst_network_tiles

from tests.hypothesis_compat import given, settings, st

KEY = jax.random.PRNGKey(11)
IMPLS = ("medusa", "crossbar", "oracle")


def _stream(i: int, n: int, groups: int, width, dtype):
    k = jax.random.fold_in(KEY, i)
    shape = (groups * n, n) + (() if width is None else (width,))
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        return jax.random.randint(k, shape, 0, 97).astype(dtype)
    return jax.random.normal(k, shape).astype(dtype)


def _roundtrip(impl, pack, fold, streams, n):
    """Read-burst every stream, then write-burst the results back; assert
    both directions bit-identical to the per-stream oracle."""
    sched = BurstScheduler(Fabric.make(n, impl, pack=pack), word_fold=fold)
    for name, x in streams.items():
        sched.enqueue_read(name, x)
    out = sched.flush()
    for name, x in streams.items():
        assert out[name].dtype == x.dtype
        np.testing.assert_array_equal(
            np.asarray(out[name], np.float32),
            np.asarray(read_network_oracle(x, n), np.float32),
            err_msg=f"read {impl}/{pack}/fold={fold}/{name}")
    for name in streams:
        sched.enqueue_write(name, out[name])
    back = sched.flush()
    for name, x in streams.items():
        np.testing.assert_array_equal(
            np.asarray(back[name], np.float32), np.asarray(x, np.float32),
            err_msg=f"write {impl}/{pack}/fold={fold}/{name}")
    return sched.stats


# ---------------------------------------------------------------------------
# deterministic parity matrix (fast lane)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fold", ("auto", 1, 2, 4))
@pytest.mark.parametrize("pack", ("packed", "pad"))
@pytest.mark.parametrize("kernels", (False, True))
def test_fold_kernel_pack_matrix_bit_identical(fold, pack, kernels):
    """The acceptance matrix on a fixed mixed mix: even widths (in-group
    fold), an odd width with even groups (cross-group fold), a wordless
    stream, and an odd-by-odd stream that blocks folding for its dtype
    group — every combination is a bit-exact round trip."""
    n = 4
    streams = {
        "kv": _stream(0, n, 8, 16, jnp.bfloat16),
        "wt_odd_width": _stream(1, n, 2, 5, jnp.bfloat16),
        "moe": _stream(2, n, 4, None, jnp.float32),
        "stage_i32": _stream(3, n, 2, 3, jnp.int32),
        "odd_odd": _stream(4, n, 3, 7, jnp.float32),
    }
    prev = ops.kernels_enabled()
    ops.use_kernels(kernels)
    try:
        for impl in IMPLS:
            stats = _roundtrip(impl, pack, fold, streams, n)
            kernelized = (impl == "medusa" and kernels and pack == "packed")
            assert (stats.kernel_bursts > 0) == kernelized
    finally:
        ops.use_kernels(prev)


def test_fold_resolution_degrades_gracefully():
    """auto folds the widest the dtype/geometry allow: bf16 pairs → u32
    without x64 (quads need the u64 lane); a stream odd in both width and
    groups pins its whole dtype group at fold 1; the pad layout folds on
    its padded width (including the padding, which rides the wider lanes
    too — that's what isolates packing from lane width in the A/B)."""
    n = 4
    even = {"a": _stream(0, n, 2, 8, jnp.bfloat16),
            "b": _stream(1, n, 4, 3, jnp.bfloat16)}   # odd width, even groups
    sched = BurstScheduler(Fabric.make(n, "oracle"), word_fold="auto")
    for name, x in even.items():
        sched.enqueue_read(name, x)
    sched.flush()
    moved = sum(2 * n * n * 8 + 4 * n * n * 3 for _ in (1,))
    assert sched.stats.words_moved == moved
    assert sched.stats.words_folded == moved // 2     # fold 2, not 4 (no x64)

    blocker = {"a": _stream(0, n, 2, 8, jnp.bfloat16),
               "odd": _stream(2, n, 3, 5, jnp.bfloat16)}  # 3 groups x 5 words
    sched = BurstScheduler(Fabric.make(n, "oracle"), word_fold="auto")
    for name, x in blocker.items():
        sched.enqueue_read(name, x)
    sched.flush()
    assert sched.stats.words_folded == 0              # group degraded to 1

    sched = BurstScheduler(Fabric.make(n, "oracle", pack="pad"),
                           word_fold="auto")
    for name, x in even.items():
        sched.enqueue_read(name, x)
    sched.flush()
    # pad folds the padded lane view (w_max=8 divides 2): half the
    # moved+padded elements ride inside u32 machine words
    lane_view = sched.stats.words_moved + sched.stats.words_padded
    assert sched.stats.words_folded == lane_view // 2

    sched = BurstScheduler(Fabric.make(n, "oracle", pack="pad"), word_fold=1)
    for name, x in even.items():
        sched.enqueue_read(name, x)
    sched.flush()
    assert sched.stats.words_folded == 0              # fold=1: raw baseline


def test_word_fold_validates():
    with pytest.raises(ValueError):
        FabricConfig(word_fold=3).validate()
    with pytest.raises(ValueError):
        BurstScheduler(Fabric.make(4, "oracle"), word_fold="wide")
    assert FabricConfig(word_fold=4).validate().word_fold == 4


def test_scheduler_stats_kernel_bursts_counter():
    """kernel_bursts counts exactly the network calls that lowered through
    the fused Pallas burst (medusa + kernels enabled); the crossbar and the
    kernels-off path never kernelize."""
    n = 4
    prev = ops.kernels_enabled()
    try:
        ops.use_kernels(True)
        stats = _roundtrip("medusa", "packed", 1,
                           {"a": _stream(0, n, 2, 4, jnp.float32)}, n)
        assert stats.kernel_bursts == 2               # 1 read + 1 write
        assert stats.network_calls == 2
        stats = _roundtrip("crossbar", "packed", 1,
                           {"a": _stream(0, n, 2, 4, jnp.float32)}, n)
        assert stats.kernel_bursts == 0
        ops.use_kernels(False)
        stats = _roundtrip("medusa", "packed", 1,
                           {"a": _stream(0, n, 2, 4, jnp.float32)}, n)
        assert stats.kernel_bursts == 0
    finally:
        ops.use_kernels(prev)


def test_word_view_u64_under_x64():
    """The 8-byte ``_WORD_VIEW`` entry: float64 payloads ride the u64
    integer-view fast path when x64 is enabled (they used to silently skip
    it), and bf16 groups fold x4 into u64 lanes."""
    with jax.enable_x64(True):
        n = 4
        f64 = jax.random.normal(KEY, (2 * n, n, 6), jnp.float64)
        assert sched_mod._int_view(f64).dtype == jnp.uint64
        bf = jax.random.normal(KEY, (4 * n, n, 8)).astype(jnp.bfloat16)
        sched = BurstScheduler(Fabric.make(n, "medusa"), word_fold="auto")
        sched.enqueue_read("f64", f64)
        sched.enqueue_read("bf", bf)
        out = sched.flush()
        np.testing.assert_array_equal(np.asarray(out["f64"]),
                                      np.asarray(read_network_oracle(f64, n)))
        np.testing.assert_array_equal(
            np.asarray(out["bf"], np.float32),
            np.asarray(read_network_oracle(bf, n), np.float32))
        # bf16 stream folds x4 (2B * 4 = u64); f64 cannot widen past 8B
        bf_elems = 4 * n * n * 8
        assert sched.stats.words_folded == bf_elems - bf_elems // 4


def test_word_view_f64_skips_without_x64():
    """Without x64 an 8-byte payload has no machine-word view — the helper
    returns None instead of a dtype jax would silently truncate."""
    assert sched_mod.machine_word_dtype(8) is None
    assert sched_mod.machine_word_dtype(4) == jnp.uint32


# ---------------------------------------------------------------------------
# fused burst kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,w", [(2, 6), (4, 37), (8, 129), (8, 4097),
                                 (8, 16385)])
def test_burst_network_tiles_matches_oracle(n, w):
    """The single-kernel burst (word-tiled grid, pad-and-slice for widths
    past the tile cap) is the read network on one [N, N, W] tile — and its
    own inverse (write direction)."""
    x = jax.random.randint(jax.random.fold_in(KEY, w), (n, n, w), 0, 2**16,
                           jnp.uint32).astype(jnp.uint16)
    out = burst_network_tiles(x, n, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(read_network_oracle(x, n)[0]))
    back = burst_network_tiles(out, n, interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_fabric_burst_contract_validates():
    fab = Fabric.make(4, "medusa")
    with pytest.raises(ValueError):
        fab.read_burst(jnp.zeros((4, 3, 8)))
    with pytest.raises(ValueError):
        fab.write_burst(jnp.zeros((2, 4, 4, 8)))      # banked rank-4 is not a tile
    out = fab.read_burst(jnp.arange(4 * 4 * 2, dtype=jnp.float32
                                    ).reshape(4, 4, 2))
    assert out.shape == (4, 4, 2)


def test_complex_payloads_skip_fold_and_kernel():
    """Complex streams round-trip on the unrolled path: bitcast rejects
    complex (no integer view, no fold) and Pallas interpret on this jax
    cannot stage complex buffers (no fused kernel) — both degrade silently
    instead of crashing."""
    n = 4
    k1, k2 = jax.random.split(KEY)
    c64 = (jax.random.normal(k1, (2 * n, n, 3))
           + 1j * jax.random.normal(k2, (2 * n, n, 3))).astype(jnp.complex64)
    prev = ops.kernels_enabled()
    ops.use_kernels(True)
    try:
        sched = BurstScheduler(Fabric.make(n, "medusa"), word_fold="auto")
        sched.enqueue_read("c", c64)
        out = sched.flush()
        np.testing.assert_array_equal(np.asarray(out["c"]),
                                      np.asarray(read_network_oracle(c64, n)))
        assert sched.stats.words_folded == 0
        assert sched.stats.kernel_bursts == 0
    finally:
        ops.use_kernels(prev)


def test_non_pow2_ports_fall_back_to_unrolled():
    """A 3-port medusa fabric cannot run the log2-stage kernel; the burst
    contract silently takes the unrolled path (and the scheduler's counter
    agrees)."""
    fab = Fabric.make(3, "oracle")
    assert not fab.burst_kernelized
    stats = _roundtrip("oracle", "packed", "auto",
                       {"a": _stream(0, 3, 2, 4, jnp.float32)}, 3)
    assert stats.kernel_bursts == 0


# ---------------------------------------------------------------------------
# hypothesis sweep: random stream mixes (slow lane)
# ---------------------------------------------------------------------------

_DTYPES = (jnp.bfloat16, jnp.float32, jnp.int32, jnp.uint8)


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(st.data())
def test_fold_kernel_parity_random_mixes(data):
    """Random stream mixes — dtypes × widths × group counts, odd word
    counts included — are bit-identical round trips under every
    word_fold × {kernel, unrolled} × {packed, pad} combination."""
    n = data.draw(st.sampled_from((2, 4, 8)), label="n_ports")
    n_streams = data.draw(st.integers(1, 4), label="n_streams")
    streams = {}
    for i in range(n_streams):
        dtype = data.draw(st.sampled_from(_DTYPES), label=f"dtype{i}")
        groups = data.draw(st.integers(1, 5), label=f"groups{i}")
        width = data.draw(st.sampled_from((None, 1, 2, 3, 4, 7, 8)),
                          label=f"width{i}")
        streams[f"s{i}"] = _stream(i, n, groups, width, dtype)
    fold = data.draw(st.sampled_from(("auto", 1, 2, 4)), label="fold")
    pack = data.draw(st.sampled_from(("packed", "pad")), label="pack")
    kernels = data.draw(st.booleans(), label="kernels")
    impl = data.draw(st.sampled_from(IMPLS), label="impl")
    prev = ops.kernels_enabled()
    ops.use_kernels(kernels)
    try:
        _roundtrip(impl, pack, fold, streams, n)
    finally:
        ops.use_kernels(prev)


# ---------------------------------------------------------------------------
# scheduled serving decode stays bit-identical under fold x kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fold", (1, 2, "auto"))
@pytest.mark.parametrize("kernels", (False, True))
def test_scheduled_decode_bit_identical_under_fold_kernel(fold, kernels):
    """The production consumer: a burst-scheduled decode step (KV banking +
    serve_fsdp weight stream) returns bit-identical logits and caches to
    the unscheduled per-layer reference under every fold/kernel
    combination."""
    from repro.configs import get_smoke
    from repro.models import api

    prev = ops.kernels_enabled()
    ops.use_kernels(kernels)
    try:
        cfg = dataclasses.replace(get_smoke("starcoder2-15b"),
                                  dtype="float32", serve_fsdp=True)
        cfg = dataclasses.replace(
            cfg, fabric=dataclasses.replace(cfg.resolved_fabric,
                                            word_fold=fold))
        params = api.init_params(cfg, KEY)
        toks = jax.random.randint(KEY, (2, 9), 0, cfg.vocab_size)
        _, caches = api.prefill_fn(params, {"tokens": toks[:, :8]}, cfg, 12)
        ref_logits, ref_caches = api.decode_fn(params, toks[:, 8:9], caches,
                                               jnp.int32(8), cfg)
        stats = SchedulerStats()
        sched = BurstScheduler(Fabric(cfg.resolved_fabric), stats=stats)
        logits, new_caches = api.decode_fn(params, toks[:, 8:9], caches,
                                           jnp.int32(8), cfg, sched=sched)
        assert stats.flushes == 2
        if kernels:
            assert stats.kernel_bursts == stats.network_calls
        # f32 folds need u64 (x64 off) → fold degrades to 1 silently
        assert stats.words_folded == 0
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(ref_logits))
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), ref_caches, new_caches)
    finally:
        ops.use_kernels(prev)
