"""Closed loop: every tenant keeps a fixed backlog of full batches.

Each step, every tenant tops its pending injects up to
``outstanding_batches`` batches of ``batch_pkts`` packets, then one
``Platform.run()`` and one retire.  A tenant's batches are written into
``outstanding_batches`` buffers, each from its own base batch made from the
seed and stamped with its packets' indices (``cell.stamp``), so no packet
repeats; a buffer is written again only once its batch is retired.  The
window runs whole steps until ``seconds`` have passed, and its length is
that of the steps it ran, so the rate takes all the work and all the time.
Every step is the same, so warming up ``warm_steps`` of them reaches every
program shape the window uses.  After the window, what is still pending
gets ``drain_limit_s`` to come back; what does not is failed.
"""
from __future__ import annotations

import time
from dataclasses import dataclass

from chipbench.cell import Bench, Sent, Window, make_packets, seed_rng

@dataclass
class Slot:
    """One batch buffer of a tenant: its base packets, the buffer that is
    injected, and the inject that holds it (free once answered)."""
    base: tuple
    work: tuple
    sent: Sent | None = None

    @property
    def free(self) -> bool:
        return self.sent is None or self.sent.t_ready is not None


class Loop:
    def __init__(self, bench: Bench, traffic: dict):
        self.bench = bench
        self.batch = int(traffic["batch_pkts"])
        self.outstanding = int(traffic["outstanding_batches"])
        self.warm_steps = int(traffic["warm_steps"])
        self.drain_limit = float(traffic["drain_limit_s"])
        self.slots = []
        for t in bench.tenants:
            rng = seed_rng(bench.seed, t.index, 2)
            bases = [make_packets(rng, self.batch)
                     for _ in range(self.outstanding)]
            self.slots.append([Slot(b, tuple(a.copy() for a in b))
                               for b in bases])

    def _step(self) -> float:
        bench = self.bench
        for t, slots in enumerate(self.slots):
            free = (s for s in slots if s.free)
            for _ in range(self.outstanding - len(bench.pending[t])):
                s = next(free)
                s.sent = bench.inject(t, *s.work, base=s.base)
        bench.run()
        return bench.retire()[0]

    def warm(self) -> None:
        for _ in range(self.warm_steps):
            self._step()
        self.bench.drain(self.drain_limit)

    def prepare(self, seconds: float) -> None:
        """Nothing to draw: the backlog is the same every step."""

    def window(self, seconds: float) -> Window:
        bench = self.bench
        bench.start_window()
        t0 = time.perf_counter()
        while True:
            end = self._step()
            if end - t0 >= seconds:
                break
        win = Window(t0, end, list(bench.delivered), list(bench.attempted),
                     step_ends=[t for t, _ in bench.marks[1:]])
        bench.drain(self.drain_limit)
        return win
