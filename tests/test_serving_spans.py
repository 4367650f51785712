"""The serving engine's host spans: what a profiler trace of a few engine
steps holds (names, nesting, metadata), and that with the profiler on or
off the engine serves the same tokens through the same decode program."""

import dataclasses
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke
from repro.kernels import ops
from repro.models import api
from repro.serving import Request, ServingEngine

KEY = jax.random.PRNGKey(5)
PROMPTS = [(0, 5, 4), (1, 9, 3), (2, 7, 5)]        # rid, prompt length, new


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The same requests served with the profiler off, then on: both
    engines, their requests, and the traced run's ``serve.*`` spans."""
    prev = ops.kernels_enabled()
    ops.use_kernels(False)
    try:
        cfg = dataclasses.replace(get_smoke("starcoder2-15b"),
                                  dtype="float32")
        params = api.init_params(cfg, KEY)
        trace_dir = str(tmp_path_factory.mktemp("trace"))
        off = _serve(cfg, params)
        on = _serve(cfg, params, trace_dir)
    finally:
        ops.use_kernels(prev)
    return off, on, _serve_spans(trace_dir)


def _serve(cfg, params, trace_dir=None):
    """Three requests over two slots (one waits for a retirement); returns
    the engine and the requests after every one retired."""
    eng = ServingEngine(cfg, params, max_slots=2, t_max=32, page_size=4)
    rng = np.random.default_rng(0)
    reqs = [Request(rid, rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=new) for rid, n, new in PROMPTS]
    for r in reqs:
        eng.submit(r)
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        eng.run_to_completion(max_steps=16)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return eng, reqs


def _serve_spans(trace_dir):
    """Every ``serve.*`` host event: ``(name, start, end, metadata)``."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith("serve.")]
    return sorted(out, key=lambda s: s[1])


def _parent(span, spans):
    """The innermost other span that holds ``span``."""
    around = [s for s in spans if s is not span
              and s[1] <= span[1] and span[2] <= s[2]]
    return min(around, key=lambda s: s[2] - s[1])[0] if around else None


def test_engine_spans_nest_and_carry_metadata(served):
    _, (eng, reqs), spans = served
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert set(by) == {"serve.step", "serve.admit", "serve.prefill",
                       "serve.install", "serve.plan", "serve.decode",
                       "serve.sample", "serve.commit"}
    # every step of this run has a live slot, so every step decodes
    for name in ("serve.step", "serve.admit", "serve.plan", "serve.decode",
                 "serve.sample", "serve.commit"):
        assert len(by[name]) == eng.step_count, name
    parents = {"serve.admit": "serve.step", "serve.prefill": "serve.admit",
               "serve.install": "serve.admit", "serve.plan": "serve.step",
               "serve.decode": "serve.step", "serve.sample": "serve.step",
               "serve.commit": "serve.step"}
    for s in spans:
        assert _parent(s, spans) == parents.get(s[0]), s
    # one prefill per request, in admission order, with its id and length
    assert [(p[3]["rid"], p[3]["prompt_len"]) for p in by["serve.prefill"]] \
        == [(rid, n) for rid, n, _ in PROMPTS]
    assert not any("moe_rows" in p[3] for p in by["serve.prefill"])
    # a wave of two, then one installed after the first retirement
    assert len(by["serve.install"]) == 2
    for p in by["serve.plan"]:
        assert 0 < p[3]["live"] <= p[3]["bucket"]
        assert p[3]["bucket"] % eng.live_bucket == 0
    # each fresh admission stamps the host clock, in admission order
    stamps = [r.admitted_s for r in reqs]
    assert stamps == sorted(stamps) and None not in stamps


def test_tokens_identical_with_the_profiler_on_and_off(served):
    (_, off), (_, on), _ = served
    assert [r.generated for r in on] == [r.generated for r in off]
    assert all(len(r.generated) == new for r, (_, _, new) in zip(on, PROMPTS))


def test_decode_step_has_no_host_callback(served, tmp_path):
    """The jitted step holds no callback to the host, and lowers to the
    same program whether or not the profiler is running."""
    (eng, _), _, _ = served

    def text():
        return eng._decode.lower(*eng._decode_args()).as_text(
            debug_info=False)

    off = text()
    jax.profiler.start_trace(str(tmp_path))
    try:
        on = text()
    finally:
        jax.profiler.stop_trace()
    assert "callback" not in off
    assert on == off


def test_moe_prefill_span_counts_routed_rows(tmp_path):
    """A mixture-of-experts model's ``serve.prefill`` spans also carry
    ``moe_rows``: the prompt's tokens times ``top_k``."""
    prev = ops.kernels_enabled()
    ops.use_kernels(False)
    try:
        cfg = dataclasses.replace(get_smoke("granite-moe-3b-a800m"),
                                  dtype="float32")
        _serve(cfg, api.init_params(cfg, KEY), str(tmp_path))
    finally:
        ops.use_kernels(prev)
    prefills = [s[3] for s in _serve_spans(str(tmp_path))
                if s[0] == "serve.prefill"]
    assert [(p["rid"], p["prompt_len"], p["moe_rows"]) for p in prefills] \
        == [(rid, n, n * cfg.moe.top_k) for rid, n, _ in PROMPTS]
