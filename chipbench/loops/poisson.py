"""Open loop: Poisson arrivals of packet trains at a fixed aggregate rate.

Packets arrive in trains of ``train_pkts``; each tenant's trains are a
Poisson process, the aggregate ``rate_mpps`` (in packets) split across
tenants by weight, and every arrival time is drawn from the seed before the
window opens.  A poll loop injects, for every tenant, the trains that are
due as one batch of at most ``max_batch_pkts``, then runs
``Platform.run()`` and retires.  When nothing is due and nothing pending it
sleeps until the next train arrives.  Every batch is a whole number of
trains, so warming up each such size reaches every program shape the
window uses.  Packets are drawn in turn from a pool of ``pool_pkts`` per
tenant, each stamped with its index (``cell.stamp``), so none repeats; a
tenant whose pending packets would wrap its pool waits, and its lag shows
it.

A packet's latency runs from its train's arrival to its result being
ready; its generator lag from the arrival to its inject.  After
``seconds`` the loop keeps going until every train that arrived is
delivered, for at most ``drain_limit_s``; a packet still undelivered then
counts as failed.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from chipbench.cell import Bench, Window, make_packets, seed_rng


class Loop:
    def __init__(self, bench: Bench, traffic: dict):
        self.bench = bench
        self.rate = float(traffic["rate_mpps"]) * 1e6
        self.train = int(traffic["train_pkts"])
        cap = int(traffic["max_batch_pkts"])
        self.pool_pkts = int(traffic["pool_pkts"])
        self.drain_limit = float(traffic["drain_limit_s"])
        if cap % self.train or self.pool_pkts % self.train \
                or cap > self.pool_pkts:
            raise ValueError("max_batch_pkts and pool_pkts must be whole "
                             "trains, and a batch no larger than the pool")
        self.cap_trains = cap // self.train
        # each pool is followed by a copy of its head, so a batch starting
        # anywhere in the pool is one contiguous slice; ``work`` is the
        # buffer the stamped packets are written into
        self.base, self.work = [], []
        for t in bench.tenants:
            h, p = make_packets(seed_rng(bench.seed, t.index, 2),
                                self.pool_pkts)
            base = (np.concatenate([h, h[:cap]]),
                    np.concatenate([p, p[:cap]]))
            self.base.append(base)
            self.work.append(tuple(a.copy() for a in base))
        self.arrivals: list[np.ndarray] = []

    def _inject(self, t: int, start: int, n: int):
        off = start % self.pool_pkts
        rows = slice(off, off + n)
        base = tuple(a[rows] for a in self.base[t])
        return self.bench.inject(t, *(a[rows] for a in self.work[t]),
                                 base=base)

    def _room(self, t: int) -> int:
        """Trains tenant ``t`` may inject before its pool wraps onto a
        pending batch."""
        held = sum(len(s.headers) for s in self.bench.pending[t])
        return (self.pool_pkts - held) // self.train

    def warm(self) -> None:
        """Every batch size the window can inject, for every tenant."""
        for n in range(1, self.cap_trains + 1):
            for t in range(len(self.base)):
                self._inject(t, 0, n * self.train)
            self.bench.run()
            self.bench.retire()
        self.bench.drain(self.drain_limit)

    def prepare(self, seconds: float) -> None:
        """Draw each tenant's train arrival times in ``[0, seconds)``."""
        w = np.array([t.weight for t in self.bench.tenants])
        self.arrivals = []
        for t, share in zip(self.bench.tenants, w / w.sum()):
            rate = self.rate * share / self.train
            rng = seed_rng(self.bench.seed, t.index, 3)
            want = rate * seconds
            n = int(want + 8 * np.sqrt(want) + 64)
            a = np.cumsum(rng.exponential(1.0 / rate, n))
            while a[-1] < seconds:
                a = np.concatenate([a, a[-1] + np.cumsum(
                    rng.exponential(1.0 / rate, n))])
            self.arrivals.append(a[a < seconds])

    def window(self, seconds: float) -> Window:
        bench, A, g = self.bench, self.arrivals, self.train
        bench.start_window()
        k = [0] * len(A)                   # trains injected per tenant
        sent = []                          # (inject, tenant, first train)
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now > seconds + self.drain_limit:
                break
            with jax.profiler.TraceAnnotation("chipbench.gen"):
                todo = []
                for t, a in enumerate(A):
                    due = int(np.searchsorted(a, now, side="right")) - k[t]
                    n = min(due, self.cap_trains, self._room(t))
                    if n > 0:
                        todo.append((t, k[t], n))
                        k[t] += n
            if todo or bench.pending_batches():
                for t, s, n in todo:
                    sent.append((self._inject(t, s * g, n * g), t, s))
                bench.run()
                bench.retire()
                continue
            left = [a[k[t]] for t, a in enumerate(A) if k[t] < len(a)]
            if not left:
                break
            time.sleep(max(0.0, min(left) - (time.perf_counter() - t0)))
        bench.drain(0.0)                   # what is pending now is failed
        lat, lag, ends = [], [], []
        for b, t, s in sent:
            if b.t_ready is None:
                continue
            due = np.repeat(A[t][s:s + len(b.headers) // g], g)
            lat.append((b.t_ready - t0 - due) * 1e6)
            lag.append((b.t_inject - t0 - due) * 1e3)
            ends.append(b.t_ready)
        return Window(t0, max(ends, default=t0), list(bench.delivered),
                      [len(a) * g for a in A],
                      latency_us=np.concatenate(lat) if lat else None,
                      gen_lag_ms=np.concatenate(lag) if lag else None,
                      step_ends=[t for t, _ in bench.marks[1:]])
