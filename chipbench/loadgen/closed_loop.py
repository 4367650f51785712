"""Closed loop: one client per engine slot; each client sends its next
request the moment its previous one finishes.  All clients send their
first request during set-up, so the window opens on a full batch.

The run's ``rounds`` x ``slots`` requests carry one stratified multiset of
prompt and output lengths, shuffled by the seed and dealt to the clients in
turn: every seed sends the same sizes, in another order."""

from __future__ import annotations

import numpy as np

from chipbench.loadgen import lengths


class ClosedLoop:
    def __init__(self, params, seed, slots, seconds, vocab):
        rng = np.random.default_rng(seed)
        self.asks = lengths.sized_asks(params, rng, params["rounds"] * slots,
                                       vocab)
        self.queues = [[] for _ in range(slots)]
        for j, ask in enumerate(self.asks):
            ask.client = j % slots
            self.queues[ask.client].append(ask)
        self._due = []

    def setup_requests(self):
        return [q.pop(0) for q in self.queues]

    def arrivals(self, now):
        due, self._due = self._due, []
        return due

    def finished(self, ask, now):
        q = self.queues[ask.client]
        if q:
            nxt = q.pop(0)
            nxt.arrival = now
            self._due.append(nxt)

    def next_arrival(self):
        return None

    def max_reach(self):
        return lengths.max_reach(self.asks)

    def prompt_shapes(self):
        return lengths.prompt_shapes(self.asks)


def make(params, seed, slots, seconds, vocab):
    return ClosedLoop(params, seed, slots, seconds, vocab)
