"""The reduction of the serving engine's ``serve.*`` host spans against the
device's busy time (``chipbench/spans.py``), on a small hand-built trace
whose answers are known and on a trace recorded on the chip from a program
without them; and ``queue_wait_p95_s`` on hand-built runs."""

import gzip

import numpy as np
import pytest

from chipbench import spans as spans_mod
from chipbench.spec import metric_module

from test_cb_arithmetic import _Req, _run, _track
from test_cb_trace import MS, RECORDED, _line

STATS = {1: "rid", 2: "prompt_len", 3: "live", 4: "bucket"}


def _events_with_stats(lid, name, events):
    """A line whose events may carry integer stats: ``(metadata_id, start
    ms, duration ms, {stat_id: value})``."""
    def one(m, s, d, stats):
        st = " ".join(f"stats {{ metadata_id: {k} int64_value: {v} }}"
                      for k, v in stats.items())
        return (f"events {{ metadata_id: {m} offset_ps: {int(s * MS)} "
                f"duration_ps: {int(d * MS)} {st} }}")
    ev = "\n".join(one(*e) for e in events)
    return f"lines {{ id: {lid} name: \"{name}\" timestamp_ns: 0 {ev} }}"


def _plane(pid, name, lines, names, stats=None):
    meta = "\n".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                     f"name: \"{n}\" }} }}" for i, n in names.items())
    meta += "\n".join(f"stat_metadata {{ key: {i} value {{ id: {i} "
                      f"name: \"{n}\" }} }}" for i, n in (stats or {}).items())
    return f"planes {{ id: {pid} name: \"{name}\" {' '.join(lines)} {meta} }}"


def _synthetic():
    """A 100 ms window of two engine steps around an arrival wait.  Step
    one (1-59 ms) admits a request, whose prefill (2-25) re-traces on the
    host for 3-14 ms and runs on the device at 15-24, installs it (26-29,
    device 27-28), plans 3000 of 4096 frames and decodes (device 36-54,
    which the sample waits on).  Step two (81-99) admits nothing, plans
    1000 of 2048 frames and decodes (device 87-96)."""
    dev_names = {1: "%fusion.1 = bf16[8] fusion(bf16[8] %a)"}
    ops = [(1, 15, 9), (1, 27, 1), (1, 36, 18), (1, 87, 9)]
    host_names = {1: "chipbench.window", 2: "chipbench.step",
                  3: "chipbench.wait", 4: "serve.step", 5: "serve.admit",
                  6: "serve.prefill", 7: "serve.install", 8: "serve.plan",
                  9: "serve.decode", 10: "serve.sample", 11: "serve.commit",
                  12: "trace_to_jaxpr_dynamic"}
    host = [(1, 0, 100, {}), (2, 0, 60, {}), (4, 1, 58, {}), (5, 1, 29, {}),
            (6, 2, 23, {1: 7, 2: 320}), (12, 3, 11, {}), (7, 26, 3, {}),
            (8, 31, 2, {3: 3000, 4: 4096}), (9, 33, 2, {}), (10, 35, 20, {}),
            (11, 55, 3, {}), (3, 60, 20, {}),
            (2, 80, 20, {}), (4, 81, 18, {}), (5, 81, 1, {}),
            (8, 82, 2, {3: 1000, 4: 2048}), (9, 84, 2, {}), (10, 86, 11, {}),
            (11, 97, 2, {})]
    text = (_plane(1, "/device:TPU:0", [_line(1, "XLA Ops", ops)], dev_names)
            + _plane(2, "/host:CPU", [_events_with_stats(3, "python", host)],
                     host_names, STATS))
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_serve_spans_of_a_known_trace():
    r = spans_mod.reduce_profile(_synthetic())
    s = r["spans"]
    expect = {  # count, seconds, device-idle seconds inside
        "serve.step": (2, 0.076, 0.039), "serve.admit": (2, 0.030, 0.020),
        "serve.prefill": (1, 0.023, 0.014), "serve.install": (1, 0.003, 0.002),
        "serve.plan": (2, 0.004, 0.004), "serve.decode": (2, 0.004, 0.004),
        "serve.sample": (2, 0.031, 0.004), "serve.commit": (2, 0.005, 0.005)}
    assert set(s) == set(expect)
    for name, (n, sec, idle) in expect.items():
        assert s[name]["count"] == n, name
        assert s[name]["seconds"] == pytest.approx(sec), name
        assert s[name]["idle_s"] == pytest.approx(idle), name
    assert s["serve.prefill"]["metadata"] == [[7, 320]]
    assert s["serve.plan"]["metadata"] == [[3000, 4096], [1000, 2048]]
    # busy 37 of 100 ms; outside the wait (60-80 ms) 43 ms idle, 39 of
    # them inside an engine step
    assert r["idle_s"] == pytest.approx(0.063)
    assert r["idle_outside_wait_s"] == pytest.approx(0.043)
    assert r["idle_outside_wait_in_step_s"] == pytest.approx(0.039)
    assert spans_mod.admit_host_ms_per_request(s) == pytest.approx(20.0)
    assert spans_mod.step_host_ms(s) == pytest.approx((39 - 20) / 2)
    assert spans_mod.decode_bucket_fill(s) == pytest.approx(
        100 * 4000 / 6144)


def test_idle_gaps_name_the_innermost_serve_span():
    gaps = spans_mod.reduce_profile(_synthetic())["idle_gaps"]
    assert [round(g[1], 6) for g in gaps] == [0.033, 0.015, 0.008, 0.004,
                                              0.003]
    assert [g[0] for g in gaps] == [
        "chipbench.wait",
        "chipbench.step > serve.prefill > trace_to_jaxpr_dynamic",
        "chipbench.step > serve.plan",
        "chipbench.step > serve.commit",
        "chipbench.step > serve.admit"]


@pytest.mark.parametrize("path", RECORDED)
def test_a_program_without_serve_spans_reads_nothing(path):
    from jax.profiler import ProfileData
    with gzip.open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    r = spans_mod.reduce_profile(pd)
    assert r["spans"] == {}
    assert 0 < r["idle_s"] and r["idle_outside_wait_in_step_s"] == 0
    assert all(not g[0].count("serve.") for g in r["idle_gaps"])
    for f in (spans_mod.admit_host_ms_per_request, spans_mod.step_host_ms,
              spans_mod.decode_bucket_fill):
        assert f(r["spans"]) is None


class _Stamped(_Req):
    def __init__(self, n, admitted_s):
        super().__init__(n)
        self.admitted_s = admitted_s


def _stamped(rid, times, arrival, admitted_s):
    tr = _track(rid, times, arrival)
    tr.req = _Stamped(len(times), admitted_s)
    return tr


def test_queue_wait_counts_from_the_scheduled_arrival_to_the_prefill():
    # arrivals 1.0 and 0.2 s into the window (opened at 100 s), admitted
    # at 101.75 and 100.3 s; a third arrived at 9.0 s and was never
    # admitted, so it waits until the drain gave up at 125 s
    tracks = {0: _stamped(0, [102.5], 1.0, 101.75),
              1: _stamped(1, [100.4], 0.2, 100.3),
              2: _stamped(2, [], 9.0, None),
              3: _stamped(3, [99.0], None, 98.0)}      # set-up, not counted
    run = _run(tracks)
    run.drained_at = 125.0
    waits = [0.75, 0.1, 16.0]
    assert metric_module("queue_wait_p95_s").value(run) == pytest.approx(
        float(np.percentile(waits, 95)))


def test_queue_wait_reads_nothing_without_the_stamp():
    assert metric_module("queue_wait_p95_s").value(
        _run({0: _track(0, [102.5], arrival=1.0)})) is None
    assert metric_module("queue_wait_p95_s").value(_run({})) is None
