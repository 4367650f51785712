"""One run of one cell: set-up, the measured window, the optional traced
window, and the check that decides ``correct``.

The window drives the program's normal serving path, ``ServingEngine.submit``
and ``ServingEngine.step`` on the default fabric, as
``python -m repro.launch.serve --engine`` does.  Everything else here is
the benchmark's own: the clock, the load, the spans, the reference.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import glob
import json
import os
import resource
import shutil
import tempfile
import time

import numpy as np

from chipbench import correct as correct_mod
from chipbench import model
from chipbench.loadgen import generator_module
from chipbench.loadgen.lengths import Ask

CACHE_DIR = ".jax_cache"          # JAX's persistent compile cache, per checkout
DRAIN_S = 60.0                    # longest wait for a late first token
PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class CompileCounter:
    """Backend compile requests, and how many of them the persistent
    compile cache answered: ``count - cache_hits`` programs were compiled.
    A load is not a compile, but its trace and lowering are host work."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        jax.monitoring.register_event_listener(self._on_count)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _on_count(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class Recorder:
    """The engine's lifecycle observer (``ServingEngine(recorder=...)``):
    stamps the host clock when a request's first token is known, which is
    inside the engine's step, before that step's decode."""

    def __init__(self):
        self.first = {}
        self.shed = {}

    def record_admit(self, req, step):
        pass

    def record_first_token(self, req, step):
        self.first.setdefault(req.rid, time.perf_counter())

    def record_retire(self, req, step):
        pass

    def record_shed(self, req, step, reason):
        self.shed[req.rid] = reason


@dataclasses.dataclass(eq=False)
class Track:
    ask: Ask
    req: object
    times: list = dataclasses.field(default_factory=list)   # per token


@dataclasses.dataclass
class StepRecord:
    start: float
    end: float
    batch: int          # requests the step's decode advanced
    live_frames: int    # KV frames the step's decode holds live, per layer
    context: int        # positions attended, summed over the batch
    admitted: int       # requests admitted (prefilled) in the step


def annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Feeder:
    """Feeds the engine from a generator and records what comes back."""

    def __init__(self, eng, gen, recorder):
        self.eng = eng
        self.gen = gen
        self.rec = recorder
        self.pending = collections.deque()
        self.tracks = {}                       # rid -> Track
        self.live = {}                         # rid -> Track, not done
        self.steps = []
        self.open = 0.0

    def submit_one(self):
        """Hand the oldest waiting request to the engine, if the engine's
        own queue is empty: at most one admission per engine step."""
        from repro.serving import Request
        if not self.pending or self.eng.queue:
            return
        ask = self.pending.popleft()
        req = Request(ask.rid, ask.prompt, max_new_tokens=ask.max_new_tokens)
        with annotate("chipbench.submit"):
            self.eng.submit(req)
        tr = Track(ask, req)
        self.tracks[ask.rid] = tr
        self.live[ask.rid] = tr

    def step(self):
        before = {rid: len(tr.req.generated) for rid, tr in self.live.items()}
        start = time.perf_counter()
        with annotate("chipbench.step"):
            self.eng.step()
        end = time.perf_counter()
        ps = self.eng.page_size
        batch = live = context = admitted = 0
        for rid, tr in list(self.live.items()):
            g = len(tr.req.generated)
            new = g - before[rid]
            if new <= 0:
                if tr.req.done:                  # shed
                    del self.live[rid]
                continue
            if before[rid] == 0:
                tr.times.append(self.rec.first.get(rid, end))
                new -= 1
                admitted += 1
            tr.times.extend([end] * new)
            batch += 1
            pos = len(tr.req.prompt) + g - 2     # the decode's write position
            context += pos + 1
            live += min(-(-(pos + 1) // ps) * ps, self.eng.t_alloc)
            if tr.req.done:
                del self.live[rid]
                if rid >= 0:                     # not a warm-up request
                    self.gen.finished(tr.ask, end - self.open)
        self.steps.append(StepRecord(start, end, batch, live, context,
                                     admitted))

    def busy(self):
        return bool(self.pending) or not self.eng.drained


def decode_args_per_bucket(eng):
    """The engine's decode-step operands at every live-frame bucket up to
    the pool's capacity (the engine retraces its step per bucket), with
    every live index empty."""
    import jax.numpy as jnp
    from repro.fabric.scheduler import FRAME_SENTINEL
    bucket = eng.live_bucket
    frames = eng.kv.pool.n_pages * eng.page_size
    top = -(-min(eng.max_slots * eng.t_alloc, frames) // bucket) * bucket
    args = list(eng._decode_args())
    expand = jnp.full(args[6].shape, FRAME_SENTINEL, jnp.int32)
    for live in range(bucket, top + 1, bucket):
        idx = jnp.full((live,), FRAME_SENTINEL, jnp.int32)
        yield (*args[:5], idx, expand, idx)


def warm_up(eng, feeder, shapes, seed, vocab):
    """Compile and load every program the window can call: the prefill and
    the admission's install for each prompt length the mix draws, one
    request per engine step as the window admits them, and the decode step
    at every live-frame bucket up to the pool's capacity."""
    import jax
    rng = np.random.default_rng([seed, 1])
    for j, p in enumerate(shapes):
        feeder.pending.append(Ask(-1 - j, rng.integers(
            0, vocab, p).astype(np.int32), 2))
        feeder.submit_one()
        while feeder.busy():
            feeder.step()
    for args in decode_args_per_bucket(eng):
        out = eng._decode(*args)
        jax.block_until_ready(out)
        del out
    feeder.tracks.clear()
    feeder.steps.clear()


def device_info(devices, chips):
    d = devices[0]
    peak = 0
    for dev in devices[:chips]:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind, "count": chips,
            "memory_peak_bytes": peak}


@dataclasses.dataclass
class Run:
    """Everything a metric may read from a finished run."""
    cell: object
    seed: int
    seconds: float
    setup_s: float
    open: float
    close: float
    tracks: dict
    steps: list
    deploy: dict
    device: dict
    peaks: dict
    trace: dict | None = None
    checked: tuple = (0, 0)     # requests and served tokens compared
    readings: list = dataclasses.field(default_factory=list)  # widest gaps
    drained_at: float = 0.0     # end of the unmeasured wait after the window

    @property
    def window_s(self):
        return self.close - self.open

    def in_window(self, t):
        return self.open <= t <= self.close

    def window_tokens(self):
        return [t for tr in self.tracks.values() for t in tr.times
                if self.in_window(t)]

    def itl_gaps(self):
        return [b - a for tr in self.tracks.values()
                for a, b in zip(tr.times, tr.times[1:])
                if a >= self.open and b <= self.close]

    def arrived_in_window(self):
        return [tr for tr in self.tracks.values()
                if tr.ask.arrival is not None
                and tr.ask.arrival <= self.close - self.open]

    def ttfts(self):
        """From each arrival in the window to its first token; a request
        still without one when the wait after the window gave up counts
        that wait."""
        return [(tr.times[0] if tr.times else self.drained_at)
                - (self.open + tr.ask.arrival)
                for tr in self.arrived_in_window()]

    def window_steps(self):
        return [s for s in self.steps
                if s.start >= self.open and s.end <= self.close]


@dataclasses.dataclass
class Rig:
    """A set-up engine and what surrounds it, ready for a window."""
    cell: object
    seed: int
    devices: list
    compiles: CompileCounter
    peaks: dict
    deploy: dict
    gen: object
    eng: object
    feeder: Feeder
    rec: Recorder
    notes: list
    drained_at: float = 0.0


def setup(cell, seed: int, seconds: float, require_tpu: bool = True):
    """Check the chips, make the parameters on the device from the seed,
    build the engine, warm every program the window can call, and send the
    mix's set-up requests."""
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        raise NoChip(f"the cell needs {cell.chips} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    if devices[0].platform == "tpu":
        cache = os.path.join(cell.root, CACHE_DIR)
        os.makedirs(cache, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_compilation_cache_max_size", 4 << 30)
    compiles = CompileCounter()
    with open(PEAKS) as f:
        peaks_table = json.load(f)
    kind = devices[0].device_kind
    if require_tpu and kind not in peaks_table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")

    from repro.models import api
    from repro.serving import ServingEngine
    conf = cell.conf
    cfg = model.model_config(conf)
    deploy = conf["deployment"]
    slots = deploy["max_slots"]
    gen = generator_module(cell.traffic["kind"]).make(
        cell.traffic, seed, slots, seconds, conf["vocab_size"])
    if gen.max_reach() > deploy["t_max"]:
        raise ValueError(f"{cell.name}: the mix reaches {gen.max_reach()} "
                         f"positions, the deployment holds {deploy['t_max']}")
    params = jax.jit(api.init_params, static_argnums=0)(
        cfg, model.param_key(seed))
    rec = Recorder()
    eng = ServingEngine(cfg, params, max_slots=slots, t_max=deploy["t_max"],
                        pool_pages=deploy.get("pool_pages", 0),
                        recorder=rec)
    feeder = Feeder(eng, gen, rec)
    warm_up(eng, feeder, gen.prompt_shapes(), seed, conf["vocab_size"])
    for ask in gen.setup_requests():
        feeder.pending.append(ask)
    while feeder.pending:
        feeder.submit_one()
        feeder.step()
    notes = [f"set-up: {compiles.count - compiles.cache_hits} compiles, "
             f"{compiles.cache_hits} programs loaded from the compile cache "
             f"({compiles.seconds:.3f} s), {slots} slots, t_max "
             f"{deploy['t_max']}, pool {eng.kv.pool.n_pages} pages"]
    return Rig(cell, seed, devices, compiles,
                   peaks_table.get(kind, {}), deploy, gen, eng, feeder, rec,
                   notes)


def measure(sess: Rig, seconds: float, trace_into: str | None = None):
    """The measured window: send the mix's requests as they fall due and
    step the engine until ``seconds`` have passed, then wait (unmeasured)
    for the first token of every request that arrived in it.  With
    ``trace_into``, a profiler trace of the window is written there.
    Returns ``(open, close)`` on the host clock."""
    import jax
    feeder, gen, compiles = sess.feeder, sess.gen, sess.compiles
    feeder.gen = gen
    if trace_into:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0          # host spans only: less overhead
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_into, profiler_options=opts)
    gc.collect()
    gc.disable()
    compiles_before = compiles.count
    hits_before = compiles.cache_hits
    window = annotate("chipbench.window")
    window.__enter__()
    open_t = time.perf_counter()
    feeder.open = open_t
    deadline = open_t + seconds
    while True:
        now = time.perf_counter()
        if now >= deadline:
            break
        feeder.pending.extend(gen.arrivals(now - open_t))
        feeder.submit_one()
        if feeder.busy():
            feeder.step()
            continue
        nxt = gen.next_arrival()
        wake = deadline if nxt is None else min(deadline, open_t + nxt)
        with annotate("chipbench.wait"):
            time.sleep(max(0.0, wake - time.perf_counter()))
    close_t = max(time.perf_counter(),
                  feeder.steps[-1].end if feeder.steps else open_t)
    window.__exit__(None, None, None)
    in_window = compiles.count - compiles_before
    hits = compiles.cache_hits - hits_before
    if trace_into:
        jax.profiler.stop_trace()
    gc.enable()
    sess.notes.append(
        f"window: {in_window - hits} compiles, {hits} programs loaded "
        f"from the compile cache, "
        f"{len([s for s in feeder.steps if s.start >= open_t])} engine "
        f"steps, {close_t - open_t:.3f} s, host memory peak "
        f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss >> 10} MiB")

    waiting = lambda: [tr for tr in feeder.tracks.values()
                       if tr.ask.arrival is not None and not tr.times]
    drain_end = time.perf_counter() + DRAIN_S
    late = len(feeder.pending) + len(waiting())
    while (feeder.pending or waiting()) and time.perf_counter() < drain_end:
        feeder.submit_one()
        feeder.step()
    if late:
        sess.notes.append(f"drain: {late} requests that arrived in the "
                          f"window had no first token at its close")
    sess.drained_at = time.perf_counter()
    return open_t, close_t


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             require_tpu: bool = True, trace_dir: str | None = None,
             control: bool = False):
    """Set up, measure, check; returns ``(result, run, notes)``: the
    result line's object, the finished :class:`Run`, and the lines printed
    before the result.  With ``control``, the float8 control stands in the
    program's place in the check (:func:`chipbench.correct.check`)."""
    sess = setup(cell, seed, seconds, require_tpu)
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    open_t, close_t = measure(sess, seconds, tdir)
    notes = sess.notes
    run = Run(cell=cell, seed=seed, seconds=seconds,
              setup_s=open_t - t_start, open=open_t, close=close_t,
              tracks=dict(sess.feeder.tracks),
              steps=list(sess.feeder.steps),
              deploy=sess.deploy, device=device_info(sess.devices,
                                                     cell.chips),
              peaks=sess.peaks, drained_at=sess.drained_at)
    dropped = int(sess.eng.fabric_stats.tokens_dropped)
    shed = len(sess.rec.shed)
    sess.eng = sess.feeder = None          # free the program's device state
    gc.collect()

    if trace:
        from chipbench import trace as trace_mod
        files = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                          recursive=True)
        run.trace = trace_mod.reduce(files[0], cell.chips)
        if trace_dir:
            os.makedirs(trace_dir, exist_ok=True)
            shutil.copy(files[0], os.path.join(
                trace_dir, f"{cell.name}.{seed}.xplane.pb"))
        shutil.rmtree(tdir, ignore_errors=True)
        run.device["busy_s"] = run.trace["busy_s"]
        run.device["window_s"] = run.trace["window_s"]

    t_check = time.perf_counter()
    checks = correct_mod.check(run, cell, seed, dropped, control)
    notes.append(f"check: {run.checked[0]} requests, {run.checked[1]} "
                 f"served tokens compared with the reference in "
                 f"{time.perf_counter() - t_check:.3f} s")
    from chipbench.spec import metric_module
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = metric_module(m["name"]).value(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    failed = shed + sum(1 for tr in run.arrived_in_window() if not tr.times)
    result = {"correct": correct_mod.passed(checks),
              "attempted": len(run.tracks), "failed": failed,
              "metrics": metrics, "device": run.device}
    if trace:
        result["breakdown"] = run.trace["breakdown"]
    result["checks"] = checks
    return result, run, notes
