"""The prefill's required operations and bytes (``chipbench.prefill_costs``)
against hand counts of the two configurations, and ``prefill_mfu`` on a
synthetic traced run (no engine, no device)."""

import os

import numpy as np
import pytest

from chipbench import model, prefill_costs
from chipbench.harness import Run, Track
from chipbench.loadgen.lengths import Ask
from chipbench.spec import metric_module

import cb_fixtures

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# hand counts of one layer: (attention, FFN used per token, FFN held, router)
LAYER = {
    # q and o 1536 x 24 x 64, k and v 1536 x 8 x 64; 8 of 40 SwiGLU experts
    # of width 512; a float32 1536 x 40 router
    "granite-moe-3b-a800m": (2 * 1536 * 1536 + 2 * 1536 * 512,
                             8 * 3 * 1536 * 512, 40 * 3 * 1536 * 512,
                             1536 * 40),
    # 32 full heads of 64; one SwiGLU of width 5632
    "stablelm-1.6b": (4 * 2048 * 2048, 3 * 2048 * 5632, 3 * 2048 * 5632, 0),
}


@pytest.mark.parametrize("name", sorted(LAYER))
@pytest.mark.parametrize("p", (320, 832, 1856))
def test_prefill_costs_by_hand(name, p):
    conf = model.load(ROOT, name)
    attn, used, held, router = LAYER[name]
    layers, d = conf["num_hidden_layers"], conf["hidden_size"]
    vocab = -(-conf["vocab_size"] // 128) * 128
    flops = (2 * layers * (attn + used + router) * p
             + 2 * layers * d * p * p + 2 * d * vocab)
    table = vocab * d * 2                   # the unembedding, tied or not
    kv = layers * 2 * conf["num_key_value_heads"] * (d // conf[
        "num_attention_heads"]) * 2 * p
    nbytes = layers * ((attn + held) * 2 + router * 4) + table + p * d * 2 + kv
    assert prefill_costs.prefill_flops(conf, p) == flops
    assert prefill_costs.prefill_bytes(conf, p) == nbytes
    assert prefill_costs.least_time(conf, p, PEAKS) == max(
        flops / PEAKS["bf16_flops_per_s"], nbytes / PEAKS["hbm_bytes_per_s"])


def test_granite_prefill_is_bytes_bound_short_and_flops_bound_long():
    conf = model.load(ROOT, "granite-moe-3b-a800m")
    # 25.2 M active parameters a layer, 18.9 M of them in the experts
    attn, used, _, router = LAYER["granite-moe-3b-a800m"]
    assert attn + used + router == 25_227_264 and used == 18_874_368
    t = lambda p, k: prefill_costs.prefill_flops(conf, p) / PEAKS[
        "bf16_flops_per_s"] if k == "f" else prefill_costs.prefill_bytes(
            conf, p) / PEAKS["hbm_bytes_per_s"]
    assert t(320, "b") > t(320, "f")
    assert t(1856, "f") > t(1856, "b")


class _Req:
    def __init__(self, n):
        self.prompt = np.zeros(n, np.int32)
        self.generated = [0, 0]


class _Cell:
    conf = cb_fixtures.TINY_MOE


def _run(tracks, trace):
    return Run(cell=_Cell(), seed=0, seconds=10.0, setup_s=1.0, open=100.0,
               close=110.0, tracks=tracks, steps=[], deploy={}, device={},
               peaks=PEAKS, trace=trace)


def test_prefill_mfu_counts_requests_first_served_in_the_window():
    conf = cb_fixtures.TINY_MOE
    tracks = {0: Track(Ask(0, None, 2, 0.5), _Req(40), [101.0, 101.2]),
              1: Track(Ask(1, None, 2, 2.0), _Req(24), [103.0, 103.1]),
              # first served before the window opened: its prefill is not
              # the window's
              2: Track(Ask(2, None, 2, None), _Req(56), [99.0, 100.5]),
              3: Track(Ask(3, None, 2, 9.9), _Req(8), [])}
    other_s = 0.002
    run = _run(tracks, {"other_s": other_s})
    least = (prefill_costs.least_time(conf, 40, PEAKS)
             + prefill_costs.least_time(conf, 24, PEAKS))
    got = metric_module("prefill_mfu").value(run)
    assert got == pytest.approx(100.0 * least / other_s)
    assert 0 < got < 100
    assert metric_module("prefill_mfu").value(_run(tracks, None)) is None
    assert metric_module("prefill_mfu").value(
        _run(tracks, {"other_s": 0.0})) is None
    assert metric_module("prefill_mfu").value(
        _run({3: tracks[3]}, {"other_s": other_s})) is None
