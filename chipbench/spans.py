"""The serving engine's own host spans in a profiler trace of one measured
window, held against the device's busy time.

``repro.serving.engine`` writes ``serve.*`` spans with
``jax.profiler.TraceAnnotation``: ``serve.step`` round each engine step;
inside it ``serve.admit`` (holding one ``serve.prefill`` per fresh request,
metadata ``rid`` and ``prompt_len``, and ``serve.install``), ``serve.plan``
(metadata ``live`` and ``bucket`` frames), ``serve.decode``,
``serve.sample`` and ``serve.commit``.  They share the clock of the device
planes, so the device-idle time inside each is an interval intersection.
A trace of a program without them reduces to empty counts, and the
numbers below come out as None.

Keep a traced window's trace with ``run.py --trace 1 --trace-dir <dir>``,
then read it here:

    python chipbench/spans.py <dir>/<cell>.<seed>.xplane.pb [--chips 1]

which prints, per trace, one JSON object: the reduction of every
``serve.*`` span, the three host-side numbers below, and the window's
longest idle gaps named down to the innermost ``serve.*`` span.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from chipbench import trace                                   # noqa: E402

SERVE = "serve."
METADATA = {"serve.prefill": ("rid", "prompt_len"),
            "serve.plan": ("live", "bucket")}


def _host_events(pd):
    """Every host event: ``(name, start_ns, end_ns, metadata)``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats) if ev.name in METADATA else {}
                out.append((ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns, stats))
    return out


def _busy(pd, chips, lo, hi):
    """The merged intervals, clipped to ``[lo, hi]``, in which any
    operation ran on any of the cell's chips."""
    devices = sorted((p for p in pd.planes
                      if p.name.startswith("/device:TPU:")),
                     key=lambda p: p.name)[:chips]
    ops = []
    for plane in devices:
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s, e = trace._clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                   lo, hi)
                if e > s:
                    ops.append((s, e))
    return trace._union(ops)[1]


def _cover(merged):
    """``f(s, e)``: the length of ``[s, e]`` that the merged intervals
    cover, by bisection over their running total."""
    starts = [m[0] for m in merged]
    total = [0.0]
    for ms, me in merged:
        total.append(total[-1] + me - ms)

    def before(t):
        i = bisect.bisect_right(starts, t)
        return total[i - 1] + min(t, merged[i - 1][1]) - starts[i - 1] \
            if i else 0.0
    return lambda s, e: before(e) - before(s)


def _minus(gap, merged):
    """``gap`` less the merged intervals, as a list of intervals."""
    out, s = [], gap[0]
    for ms, me in merged:
        if me <= s or ms >= gap[1]:
            continue
        if ms > s:
            out.append((s, ms))
        s = max(s, me)
    if s < gap[1]:
        out.append((s, gap[1]))
    return out


def _innermost(cands, mid):
    around = [c for c in cands if c[1] <= mid <= c[2]]
    return min(around, key=lambda c: c[2] - c[1]) if around else None


def _label(mid, events):
    """What the host was doing at ``mid``: the innermost benchmark span,
    the innermost ``serve.*`` span, then the innermost other host event
    inside the last of these."""
    bench = _innermost([e for e in events if e[0].startswith(trace.HOST_SPAN)
                        and e[0] != trace.WINDOW_SPAN], mid)
    serve = _innermost([e for e in events if e[0].startswith(SERVE)], mid)
    outer = serve or bench
    other = _innermost([e for e in events
                        if not e[0].startswith((trace.HOST_SPAN, SERVE))
                        and (outer is None
                             or outer[1] <= e[1] <= outer[2])], mid)
    parts = [bench[0] if bench else "outside a benchmark span"]
    parts += [x[0][:80] for x in (serve, other) if x]
    return " > ".join(parts)


def reduce_profile(pd, chips: int = 1) -> dict:
    """Per ``serve.*`` name, the spans that start in the window: their
    ``count``, their ``seconds`` and the ``idle_s`` of them in which no
    device operation ran (both clipped to the window), and the metadata
    of each in order where the name carries any; the window's idle time,
    the part of it outside ``chipbench.wait`` and the part of that inside
    a ``serve.step``; and the longest idle gaps, labelled."""
    window = [s for s in trace.host_spans(pd) if s[0] == trace.WINDOW_SPAN]
    if not window:
        raise ValueError("the trace holds no chipbench.window span")
    _, lo, hi = window[0]
    busy = _busy(pd, chips, lo, hi)
    covered = _cover(busy)
    events = _host_events(pd)
    spans = {}
    for name, s, e, stats in sorted(events, key=lambda ev: ev[1]):
        if not name.startswith(SERVE) or not lo <= s <= hi:
            continue
        s, e = trace._clip(s, e, lo, hi)
        r = spans.setdefault(name, {"count": 0, "seconds": 0.0,
                                    "idle_s": 0.0})
        r["count"] += 1
        r["seconds"] += (e - s) * 1e-9
        r["idle_s"] += ((e - s) - covered(s, e)) * 1e-9
        if name in METADATA:
            r.setdefault("metadata", []).append(
                [stats.get(k) for k in METADATA[name]])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    waits = trace._union([(s, e) for n, s, e in trace.host_spans(pd)
                          if n == "chipbench.wait"])[1]
    in_step = _cover(trace._union([ev[1:3] for ev in events
                                   if ev[0] == "serve.step"])[1])
    outside_wait = [(s, e) for g in gaps for s, e in _minus(g, waits)]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:trace.TOP]
    return {
        "spans": spans,
        "idle_s": sum(e - s for s, e in gaps) * 1e-9,
        "idle_outside_wait_s": sum(e - s for s, e in outside_wait) * 1e-9,
        "idle_outside_wait_in_step_s": sum(
            in_step(s, e) for s, e in outside_wait) * 1e-9,
        "idle_gaps": [[_label((s + e) / 2, events), (e - s) * 1e-9]
                      for s, e in longest],
    }


def _get(spans, name, key):
    return spans.get(name, {}).get(key, 0)


def admit_host_ms_per_request(spans: dict):
    """Device-idle time inside ``serve.admit`` over the requests
    prefilled (``serve.prefill``) in the window."""
    n = _get(spans, "serve.prefill", "count")
    return 1e3 * _get(spans, "serve.admit", "idle_s") / n if n else None


def step_host_ms(spans: dict):
    """Device-idle time inside ``serve.step`` and outside ``serve.admit``,
    per decode dispatched (``serve.decode``)."""
    n = _get(spans, "serve.decode", "count")
    idle = (_get(spans, "serve.step", "idle_s")
            - _get(spans, "serve.admit", "idle_s"))
    return 1e3 * idle / n if n else None


def decode_bucket_fill(spans: dict):
    """Live frames over the bucket frames the burst kernels walk, summed
    over the window's decode plans (``serve.plan``), in percent."""
    plans = spans.get("serve.plan", {}).get("metadata", [])
    plans = [(live, bucket) for live, bucket in plans if bucket]
    if not plans:
        return None
    return 100.0 * sum(p[0] for p in plans) / sum(p[1] for p in plans)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("paths", nargs="+")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData
    for path in args.paths:
        r = reduce_profile(ProfileData.from_file(path), args.chips)
        s = r["spans"]
        r.update(admit_host_ms_per_request=admit_host_ms_per_request(s),
                 step_host_ms=step_host_ms(s),
                 decode_bucket_fill=decode_bucket_fill(s))
        for v in s.values():
            v.pop("metadata", None)
        print(json.dumps({"trace": os.path.basename(path), **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
