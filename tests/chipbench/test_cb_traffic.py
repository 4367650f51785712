"""The chip benchmark's load generators: seeded, deterministic, drawing the
shapes their mix states, and the same work for every seed in another
order."""

import json
import os

import numpy as np
import pytest

from chipbench.loadgen import generator_module, lengths

import cb_fixtures

RAG = {"kind": "open_loop", "rate_per_s": 3.0,
       "prompt": {"instruction_tokens": 64, "chunk_tokens": 256,
                  "chunks": {"median": 3, "sigma": 0.6, "min": 1, "max": 8}},
       "output": {"median": 4, "sigma": 0.6, "min": 2, "max": 16}}
LONG = {"kind": "closed_loop", "rounds": 4,
        "prompt": {"instruction_tokens": 64, "chunk_tokens": 256,
                   "chunks": {"median": 4, "sigma": 0.5, "min": 2, "max": 8}},
        "output": {"median": 64, "sigma": 0.5, "min": 32, "max": 160}}


def _asks(traffic, seed, slots=4, seconds=40.0):
    gen = generator_module(traffic["kind"]).make(traffic, seed, slots,
                                                 seconds, 49155)
    if traffic["kind"] == "open_loop":
        return gen, list(gen.asks)
    return gen, list(gen.asks)


@pytest.mark.parametrize("traffic", [RAG, LONG], ids=["open", "closed"])
def test_same_seed_same_requests(traffic):
    _, a = _asks(traffic, 2 ** 31 + 11)
    _, b = _asks(traffic, 2 ** 31 + 11)
    assert [(x.rid, x.arrival, x.max_new_tokens) for x in a] == \
        [(x.rid, x.arrival, x.max_new_tokens) for x in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("traffic", [RAG, LONG], ids=["open", "closed"])
def test_seeds_share_the_sizes_in_another_order(traffic):
    _, a = _asks(traffic, 5)
    _, b = _asks(traffic, 6)
    if traffic["kind"] == "open_loop":
        # the same gaps: the window's count differs at most at its edge
        assert abs(len(a) - len(b)) <= 2
        n = min(len(a), len(b))
        a, b = a[:n], b[:n]
    sizes = lambda asks: sorted((len(x.prompt), x.max_new_tokens)
                                for x in asks)
    if traffic["kind"] == "closed_loop":
        assert sorted(len(x.prompt) for x in a) == \
            sorted(len(x.prompt) for x in b)
        assert sorted(x.max_new_tokens for x in a) == \
            sorted(x.max_new_tokens for x in b)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b] \
        or sizes(a) != sizes(b) or \
        [x.max_new_tokens for x in a] != [x.max_new_tokens for x in b]


@pytest.mark.parametrize("traffic", [RAG, LONG], ids=["open", "closed"])
def test_draws_the_stated_shapes(traffic):
    gen, asks = _asks(traffic, 123)
    p, c = traffic["prompt"], traffic["prompt"]["chunks"]
    shapes = {p["instruction_tokens"] + k * p["chunk_tokens"]
              for k in range(c["min"], c["max"] + 1)}
    out = traffic["output"]
    assert {len(a.prompt) for a in asks} <= shapes
    assert all(out["min"] <= a.max_new_tokens <= out["max"] for a in asks)
    assert all(0 <= a.prompt.min() and a.prompt.max() < 49155 for a in asks)
    k = [(len(a.prompt) - 64) // 256 for a in asks]
    assert abs(np.median(k) - traffic["prompt"]["chunks"]["median"]) <= 1
    assert lengths.max_reach(asks) == max(len(a.prompt) + a.max_new_tokens
                                          for a in asks) + 1
    assert gen.prompt_shapes() == sorted({len(a.prompt) for a in asks})


def test_closed_loop_spans_the_stated_range_over_the_run():
    """The benchmark's closed-loop mix at its deployment's slots: the run's
    requests are one stratified multiset, not the same few quantiles each
    round, so both ends of each stated range are drawn, every prompt shape
    among them, and the clients are dealt equal shares."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench", "traffic",
                           "decode-long.json")) as f:
        long = json.load(f)
    gen, asks = _asks(long, 2 ** 31 + 17, slots=2)
    assert len(asks) == long["rounds"] * 2
    out, chunks = long["output"], long["prompt"]["chunks"]
    tokens = [a.max_new_tokens for a in asks]
    assert min(tokens) == out["min"] and max(tokens) == out["max"]
    assert len(set(tokens)) > len(asks) // 2
    k = sorted({(len(a.prompt) - 64) // 256 for a in asks})
    assert k == list(range(chunks["min"], chunks["max"] + 1))
    assert sorted(a.client for a in asks) == [0] * long["rounds"] + \
        [1] * long["rounds"]


def test_open_loop_schedule_is_wall_clock():
    gen, asks = _asks(RAG, 9, seconds=40.0)
    t = np.array([a.arrival for a in asks])
    assert np.all(np.diff(t) >= 0) and t[-1] < 40.0
    assert abs(len(asks) / 40.0 - RAG["rate_per_s"]) < 0.5
    # arrivals are due by the clock alone, never by the engine's progress
    assert gen.arrivals(0.0) == [] or gen.arrivals(0.0)[0].arrival <= 0.0
    due = gen.arrivals(10.0)
    assert due == [a for a in asks if a.arrival <= 10.0]
    assert gen.next_arrival() == next(a.arrival for a in asks
                                      if a.arrival > 10.0)


def test_a_schedule_seed_fixes_sizes_and_times_but_not_tokens():
    fixed = dict(RAG, schedule_seed=7)
    _, a = _asks(fixed, 2 ** 31 + 5)
    _, b = _asks(fixed, 12)
    assert [(x.arrival, len(x.prompt), x.max_new_tokens) for x in a] == \
        [(x.arrival, len(x.prompt), x.max_new_tokens) for x in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    _, c = _asks(RAG, 12)
    assert [x.arrival for x in c] != [x.arrival for x in b]


def test_closed_loop_sends_next_on_finish():
    gen, _ = _asks(LONG, 3, slots=2)
    first = gen.setup_requests()
    assert [a.client for a in first] == [0, 1]
    assert gen.arrivals(0.5) == []
    gen.finished(first[1], 1.25)
    nxt = gen.arrivals(1.3)
    assert len(nxt) == 1 and nxt[0].client == 1 and nxt[0].arrival == 1.25


def test_tiny_fixture_mixes_fit_their_deployments():
    for conf, traffic in ((cb_fixtures.TINY_DENSE, cb_fixtures.CLOSED),
                          (cb_fixtures.TINY_MOE, cb_fixtures.OPEN)):
        dep = conf["deployment"]
        gen = generator_module(traffic["kind"]).make(
            traffic, 4, dep["max_slots"], 2.0, conf["vocab_size"])
        assert dep["t_max"] >= gen.max_reach()
