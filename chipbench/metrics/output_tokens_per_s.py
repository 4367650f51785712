"""Every output token the window completed, over the window's seconds
(host clock; a token is complete when the host holds it)."""


def value(run):
    return len(run.window_tokens()) / run.window_s
