"""Reduction of a profiler trace (``.xplane.pb``) of one measured window to
the numbers the per-layer metrics read.

The window is the host span ``chipbench.window`` that the harness wraps
around it.  On each device plane (``/device:TPU:<i>``), the line ``XLA Ops``
holds one event per operation run and ``XLA Modules`` one per program run.
Programs are named ``jit_<function>(<fingerprint>)``: the engine's jitted
decode step is ``jit__step``.  The burst kernels are the Mosaic custom
calls named after their wrappers in ``repro.kernels.medusa_transpose``
(``gather_burst_network_tiles``, ``scatter_burst_network_tiles``).
"""

from __future__ import annotations

import re

DECODE_PROGRAM = "jit__step("
KERNEL_OP = re.compile(r"^%(\w*burst_network_tiles)(\.\d+)? = .*custom-call\(")
OP_NAME = re.compile(r"^%([A-Za-z_\-]+?)(\.\d+)*( =|$)")
WINDOW_SPAN = "chipbench.window"
HOST_SPAN = "chipbench."
TOP = 10


def _union(intervals):
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals in order."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _op_name(name: str) -> str:
    m = OP_NAME.match(name)
    return m.group(1) if m else name.split(" ")[0][:60]


def _program_name(name: str) -> str:
    return name.split("(")[0]


def host_spans(pd):
    """Every ``chipbench.*`` host span: ``(name, start_ns, end_ns)``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_SPAN):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def host_events(pd):
    """Every host event on the threads that hold a benchmark span, for
    naming what the host did during an idle gap: ``(name, start, end)``."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = list(line.events)
            if not any(e.name.startswith(HOST_SPAN) for e in evs):
                continue
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in evs)
    return out


def _label(mid, spans, events):
    """What the host was doing at ``mid``: the innermost benchmark span
    around it, then the innermost other host event inside that."""
    def inner(cands):
        around = [c for c in cands if c[1] <= mid <= c[2]]
        return min(around, key=lambda c: c[2] - c[1]) if around else None
    span = inner([s for s in spans if s[0] != WINDOW_SPAN])
    ev = inner([e for e in events if not e[0].startswith(HOST_SPAN)
                and (span is None or span[1] <= e[1] <= span[2])])
    parts = [span[0] if span else "outside a benchmark span"]
    if ev:
        parts.append(ev[0][:80])
    return " > ".join(parts)


def reduce_profile(pd, chips: int = 1) -> dict:
    """The window's device numbers from a loaded ``ProfileData``."""
    spans = host_spans(pd)
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    if not window:
        raise ValueError("the trace holds no chipbench.window span")
    _, lo, hi = window[0]
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    devices = sorted(devices, key=lambda p: p.name)[:chips]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    busy_total = decode_s = other_s = kernel_s = 0.0
    decode_calls = kernel_calls = 0
    op_time = {}
    gaps = []
    for plane in devices:
        lines = {ln.name: list(ln.events) for ln in plane.lines}
        modules = [(e.name, *_clip(e.start_ns, e.start_ns + e.duration_ns,
                                   lo, hi))
                   for e in lines.get("XLA Modules", [])]
        modules = [m for m in modules if m[2] > m[1]]
        decode = [(s, e) for n, s, e in modules
                  if n.startswith(DECODE_PROGRAM)]
        decode_calls += len(decode)
        decode_s += sum(e - s for s, e in decode) * 1e-9
        other_s += sum(e - s for n, s, e in modules
                       if not n.startswith(DECODE_PROGRAM)) * 1e-9
        ops = []
        for ev in lines.get("XLA Ops", []):
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if e <= s:
                continue
            ops.append((s, e))
            prog = next((_program_name(n) for n, ms, me in modules
                         if ms <= s < me), "?")
            key = f"{prog}:{_op_name(ev.name)}"
            op_time[key] = op_time.get(key, 0.0) + (e - s) * 1e-9
            if KERNEL_OP.match(ev.name) and any(ds <= s < de
                                                for ds, de in decode):
                kernel_s += (e - s) * 1e-9
                kernel_calls += 1
        busy, merged = _union(ops)
        busy_total += busy * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n = len(devices)
    events = host_events(pd)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / n,
        "decode_calls": decode_calls // n,
        "decode_s": decode_s / n,
        "other_s": other_s / n,
        "kernel_s": kernel_s / n,
        "kernel_calls": kernel_calls // n,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                op_time.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[_label((s + e) / 2, spans, events),
                           (e - s) * 1e-9] for s, e in longest]},
    }


def reduce(path: str, chips: int = 1) -> dict:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), chips)
