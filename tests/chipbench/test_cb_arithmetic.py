"""Rate, tail and idle arithmetic of the chip benchmark's metrics over a
window that holds a stall, on a synthetic run (no engine, no device)."""

import numpy as np
import pytest

from chipbench import costs
from chipbench.harness import Run, StepRecord, Track
from chipbench.loadgen.lengths import Ask
from chipbench.spec import metric_module

import cb_fixtures


class _Req:
    def __init__(self, n):
        self.generated = [0] * n
        self.prompt = np.zeros(8, np.int32)


class _Cell:
    conf = cb_fixtures.TINY_MOE


def _run(tracks, steps=(), trace=None, open_t=100.0, close_t=110.0):
    return Run(cell=_Cell(), seed=0, seconds=close_t - open_t, setup_s=7.5,
               open=open_t, close=close_t, tracks=tracks, steps=list(steps),
               deploy={}, device={},
               peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
               trace=trace)


def _track(rid, times, arrival=None):
    return Track(Ask(rid, np.zeros(8, np.int32), len(times), arrival),
                 _Req(len(times)), list(times))


def test_rate_counts_all_of_the_window_across_a_stall():
    # 0.1 s per token, then a 4 s stall, then 0.1 s per token again
    times = [100.0 + 0.1 * i for i in range(1, 31)]            # 30 tokens
    times += [107.0 + 0.1 * i for i in range(1, 21)]           # 20 tokens
    before = _track(0, [99.0, 99.5])                           # set-up
    run = _run({0: _track(1, times), 1: before})
    rate = metric_module("output_tokens_per_s").value(run)
    assert rate == pytest.approx(50 / 10.0)


def test_tail_holds_the_stall_gap():
    times = [100.0 + 0.1 * i for i in range(1, 31)] + [107.1]
    run = _run({0: _track(0, times)})
    gaps = run.itl_gaps()
    assert len(gaps) == 30 and max(gaps) == pytest.approx(4.1)
    p95 = metric_module("itl_p95_s").value(run)
    assert p95 == pytest.approx(float(np.percentile(gaps, 95)))
    assert p95 > 0.1                    # the stall reaches the tail
    # a gap that starts before the window opens is not the window's
    run = _run({0: _track(0, [99.9, 100.2, 100.4])})
    assert run.itl_gaps() == pytest.approx([0.2])


def test_ttft_counts_from_the_scheduled_arrival():
    # arrived 1.0 s into the window; the engine took it later; the first
    # token came at 2.5 s: TTFT is 1.5 s, whenever it was submitted
    tr = _track(0, [102.5, 102.6], arrival=1.0)
    early = _track(1, [100.3], arrival=0.2)
    run = _run({0: tr, 1: early})
    assert sorted(run.ttfts()) == pytest.approx([0.1, 1.5])
    assert metric_module("ttft_p95_s").value(run) == pytest.approx(
        float(np.percentile([0.1, 1.5], 95)))


def test_idle_share_from_busy_union():
    trace = {"busy_s": 2.5, "window_s": 10.0, "decode_calls": 4,
             "decode_s": 2.0, "kernel_s": 1.0, "kernel_calls": 8,
             "other_s": 0.4}
    run = _run({}, trace=trace)
    assert metric_module("device_idle_share").value(run) == \
        pytest.approx(75.0)
    assert metric_module("decode_step_ms").value(run) == pytest.approx(500.0)
    assert metric_module("burst_kernel_ms_per_step").value(run) == \
        pytest.approx(250.0)


def test_per_step_metrics_from_step_records():
    steps = [StepRecord(100.0 + i, 100.5 + i, batch=2, live_frames=128,
                        context=100, admitted=int(i == 0))
             for i in range(4)]
    trace = {"busy_s": 2.5, "window_s": 10.0, "decode_calls": 4,
             "decode_s": 2.0, "kernel_s": 1.0, "kernel_calls": 8,
             "other_s": 0.4}
    run = _run({}, steps=steps, trace=trace)
    assert metric_module("decode_slots_mean").value(run) == 2.0
    assert metric_module("admit_ms_per_request").value(run) == \
        pytest.approx(400.0)
    need = costs.burst_kernel_bytes(_Cell.conf, 128, 2)
    assert metric_module("burst_kernel_roofline").value(run) == \
        pytest.approx(100 * need / 819e9 / 0.25)
    least, bound = costs.least_time(
        costs.decode_step_flops(_Cell.conf, 2, 100),
        costs.decode_step_bytes(_Cell.conf, 2, 100), run.peaks)
    assert bound == "bytes"
    assert metric_module("decode_mfu").value(run) == \
        pytest.approx(100 * least / 0.5)


def test_nothing_to_read_returns_none():
    run = _run({})
    for name in ("itl_p95_s", "ttft_p95_s", "decode_step_ms",
                 "decode_mfu", "burst_kernel_roofline",
                 "admit_ms_per_request", "device_idle_share",
                 "decode_slots_mean", "burst_kernel_ms_per_step"):
        assert metric_module(name).value(run) is None, name


def test_costs_of_a_published_width():
    conf = {"family": "dense", "hidden_size": 2048, "intermediate_size": 5632,
            "num_attention_heads": 32, "num_key_value_heads": 32,
            "num_hidden_layers": 24, "vocab_size": 100352}
    assert costs.kv_bytes_per_position(conf) == 196608
    assert costs.frame_bytes(conf) == 4096
    # one token: weights once, the untied head, 1k positions of cache
    b = costs.decode_step_bytes(conf, 1, 1024)
    params = 24 * (4 * 2048 * 2048 + 3 * 2048 * 5632) + 2048 * 100352
    assert b == 2 * params + 2 * 2048 + 196608 * 1025
