"""Load generators, one module per traffic ``kind``
(``chipbench/loadgen/<kind>.py``), found by the kind's name.  Each module
has ``make(params, seed, slots, seconds, vocab)`` returning an object with
``setup_requests()``, ``arrivals(now)``, ``finished(req, now)``,
``next_arrival()`` and ``max_reach()``; times are seconds from the opening
of the measured window."""

import importlib


def generator_module(kind: str):
    return importlib.import_module(f"chipbench.loadgen.{kind}")
