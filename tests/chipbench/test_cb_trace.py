"""The reduction from a profiler trace to the per-layer numbers, on a
small hand-built trace whose answers are known, and on a trace recorded
on the chip (``chipbench/testdata/``)."""

import glob
import gzip
import os

import pytest

from chipbench import trace

MS = 1_000_000_000            # picoseconds in a millisecond


def _line(lid, name, events):
    ev = "\n".join(f"events {{ metadata_id: {m} offset_ps: {int(s * MS)} "
                   f"duration_ps: {int(d * MS)} }}" for m, s, d in events)
    return f"lines {{ id: {lid} name: \"{name}\" timestamp_ns: 0 {ev} }}"


def _plane(pid, name, lines, names):
    meta = "\n".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                     f"name: \"{n}\" }} }}" for i, n in names.items())
    return f"planes {{ id: {pid} name: \"{name}\" {' '.join(lines)} {meta} }}"


def _synthetic():
    """A 100 ms window: two decode calls (10-30 and 50-70 ms) with two
    burst kernels of 5 ms each inside, one prefill program (35-45 ms),
    a kernel outside the decode program, and the host's spans."""
    dev_names = {1: "jit__step(77)", 2: "jit_scan(5)",
                 3: "%gather_burst_network_tiles.2 = u32[8] custom-call(s32[8] %i)",
                 4: "%scatter_burst_network_tiles = u32[8] custom-call(s32[8] %i)",
                 5: "%fusion.12 = bf16[8] fusion(bf16[8] %a)",
                 6: "%copy.3 = u32[8] copy(u32[8] %gather_burst_network_tiles.2)"}
    modules = [(1, 10, 20), (2, 35, 10), (1, 50, 20)]
    ops = [(3, 10, 5), (5, 15, 5), (4, 20, 5), (6, 25, 5),
           (4, 36, 8),
           (3, 50, 5), (5, 55, 10), (4, 65, 5)]
    host_names = {1: "chipbench.window", 2: "chipbench.step",
                  3: "chipbench.submit", 4: "chipbench.wait",
                  5: "PjitFunction(_step)"}
    spans = [(1, 0, 100), (2, 8, 24), (5, 8, 1), (3, 33, 1), (2, 34, 38),
             (4, 75, 25)]
    text = (_plane(1, "/device:TPU:0",
                   [_line(1, "XLA Modules", modules),
                    _line(2, "XLA Ops", ops)], dev_names)
            + _plane(2, "/host:CPU", [_line(3, "python", spans)],
                     host_names))
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_reduction_of_a_known_trace():
    r = trace.reduce_profile(_synthetic())
    assert r["window_s"] == pytest.approx(0.100)
    # ops cover 10-30, 36-44 and 50-70 ms
    assert r["busy_s"] == pytest.approx(0.048)
    assert r["decode_calls"] == 2
    assert r["decode_s"] == pytest.approx(0.040)
    assert r["other_s"] == pytest.approx(0.010)
    # the scatter in the prefill program is not the decode step's
    assert r["kernel_calls"] == 4
    assert r["kernel_s"] == pytest.approx(0.020)
    ops = dict((k, v) for k, v in r["breakdown"]["device_ops"])
    assert ops["jit__step:fusion"] == pytest.approx(0.015)
    assert ops["jit__step:copy"] == pytest.approx(0.005)
    assert ops["jit_scan:scatter_burst_network_tiles"] == pytest.approx(0.008)
    gaps = r["breakdown"]["idle_gaps"]
    assert [round(g[1], 6) for g in gaps] == [0.03, 0.01, 0.006, 0.006]
    assert gaps[0][0] == "chipbench.wait"
    assert gaps[1][0] == "outside a benchmark span"
    assert [g[0] for g in gaps[2:]] == ["chipbench.submit", "chipbench.step"]


def test_reduction_refuses_a_trace_without_its_window():
    from jax.profiler import ProfileData
    empty = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(
            _plane(1, "/device:TPU:0", [], {})))
    with pytest.raises(ValueError, match="chipbench.window"):
        trace.reduce_profile(empty)


RECORDED = sorted(glob.glob(os.path.join(
    os.path.dirname(trace.__file__), "testdata", "*.xplane.pb.gz")))


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_reduction_of_a_recorded_chip_trace(path):
    from jax.profiler import ProfileData
    with gzip.open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    r = trace.reduce_profile(pd)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["decode_calls"] >= 1
    assert 0 < r["kernel_s"] < r["decode_s"] <= r["busy_s"]
    assert r["kernel_calls"] % r["decode_calls"] == 0
    assert len(r["breakdown"]["device_ops"]) == trace.TOP
    assert all(0 < s <= r["window_s"] for _, s in
               r["breakdown"]["idle_gaps"])
