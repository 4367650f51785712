"""Open loop: requests arrive on a schedule in wall seconds from the
opening of the window, whether or not earlier ones have finished, at a
mean rate of ``rate_per_s``.  Gaps between arrivals are the stratified
quantiles of an exponential with that mean (a Poisson process's gaps), in
an order drawn from the seed, so every seed sends the same number of
requests with the same sizes, in another order.  A mix that states a
``schedule_seed`` draws that order from it instead: every run then sends
the same sizes at the same times, and its seed draws the token ids."""

from __future__ import annotations

import numpy as np

from chipbench.loadgen import lengths


class OpenLoop:
    def __init__(self, params, seed, slots, seconds, vocab):
        rng = np.random.default_rng(seed)
        order = (np.random.default_rng(params["schedule_seed"])
                 if "schedule_seed" in params else rng)
        rate = params["rate_per_s"]
        count = int(np.ceil(rate * seconds)) + 1
        gaps = order.permutation(lengths.exponential_quantiles(1.0 / rate,
                                                               count))
        times = np.cumsum(gaps)
        asks = lengths.sized_asks(params, order, count, vocab, tokens=rng)
        for ask, t in zip(asks, times):
            ask.arrival = float(t)
        self.asks = [a for a in asks if a.arrival < seconds]
        self._next = 0

    def setup_requests(self):
        return []

    def arrivals(self, now):
        start = self._next
        while self._next < len(self.asks) and \
                self.asks[self._next].arrival <= now:
            self._next += 1
        return self.asks[start:self._next]

    def finished(self, ask, now):
        pass

    def next_arrival(self):
        if self._next < len(self.asks):
            return self.asks[self._next].arrival
        return None

    def max_reach(self):
        return lengths.max_reach(self.asks)

    def prompt_shapes(self):
        return lengths.prompt_shapes(self.asks)


def make(params, seed, slots, seconds, vocab):
    return OpenLoop(params, seed, slots, seconds, vocab)
