"""Requests advanced by each engine step's decode, averaged over the
window's decode steps (read from the engine's requests after each step)."""


def value(run):
    b = [s.batch for s in run.window_steps() if s.batch]
    return sum(b) / len(b) if b else None
