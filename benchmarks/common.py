"""Shared benchmark helpers: timing, HLO op census, CSV emission."""

from __future__ import annotations

import time
from collections import Counter

import jax
import numpy as np


def time_us(fn, *args, iters: int = 20, warmup: int = 3) -> float:
    """Median wall time per call in microseconds (CPU, jitted fn)."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(times))


def hlo_op_census(fn, *args) -> Counter:
    """Counter of HLO opcodes in the optimized module for fn(*args)."""
    txt = jax.jit(fn).lower(*args).compile().as_text()
    census: Counter = Counter()
    for line in txt.splitlines():
        line = line.strip()
        if not line.startswith(("%", "ROOT")) or "=" not in line:
            continue
        rhs = line.split("=", 1)[1]
        import re
        m = re.search(r"\b([a-z][\w\-]*)\(", rhs)
        if m:
            census[m.group(1)] += 1
    return census


def _cost_dict(compiled) -> dict:
    return compiled.cost_analysis() or {}


def bytes_accessed(fn, *args) -> float:
    cost = _cost_dict(jax.jit(fn).lower(*args).compile())
    return float(cost.get("bytes accessed", 0.0))


def flops_of(fn, *args) -> float:
    cost = _cost_dict(jax.jit(fn).lower(*args).compile())
    return float(cost.get("flops", 0.0))


def emit(rows: list) -> None:
    """Print ``name,us_per_call,derived`` CSV rows."""
    for name, us, derived in rows:
        print(f"{name},{us if us is not None else ''},{derived}")
