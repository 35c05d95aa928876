"""One cell as the window drives it: tenants made from the seed, deployed
through ``Platform``, fed, run and retired through public calls only.

A loop kind (``loops/<kind>.py``) decides *when* each tenant injects what;
:class:`Bench` does the rest the same way for every loop:

  inject   ``Deployment.inject`` of host-resident packet arrays, timed and
           wrapped in a ``chipbench.inject`` trace span.  A loop that reuses
           a pool of packets hands over the pool's rows as ``base``, and
           each packet is first stamped with its index in its tenant's
           stream (:func:`stamp`), so no packet of a run is sent twice;
  run      ``Platform.run()`` in a ``chipbench.run`` span, on the runtime
           the traffic mix names (:data:`RUNTIMES`);
  retire   ``Platform.report()``, a wait until every output is ready, and
           ``reset_window()`` on each ``ComputeBackend``, in a
           ``chipbench.retire`` span, so outputs never pile up on the device.
           Each tenant's outputs are matched, in order, to the oldest of its
           injects still pending; an inject whose output has not come back
           stays pending for a later retire.  A seeded sample of the matched
           outputs is kept, with a copy of its input, for the comparison
           with :mod:`chipbench.reference` once the window has closed;
  drain    after the window, ``run()`` and retire until nothing is pending
           or a time limit passes; what is still pending then never came
           back and counts as failed.
"""
from __future__ import annotations

import collections
import functools
import gc
import operator
import random
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference
from chipbench.work import chain_bytes

#: a traffic mix's ``runtime`` and the ``ComputeBackend(stream=...)`` it
#: builds: the batch engine (one sync a ``run()``) or the streaming engine
#: (a dispatch ring at ``ComputeBackend``'s own depth and in-flight limit)
RUNTIMES = {"batch": False, "stream": True}
#: the fields of an output compared with the reference
COMPARED = ("allow", "headers", "payload")
#: JAX's monitoring events that mark a new program: a trace, a compile
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


#: the word of a header and of a payload that carries a packet's stamp: the
#: destination address (the firewall's key, which NAT hashes too) and the
#: first payload word (which ChaCha20 encrypts)
STAMP_HEADER_WORD, STAMP_PAYLOAD_WORD = 1, 0
#: an odd multiplier: packet index times it, modulo 2**32, is one-to-one
STAMP_MUL = 0x9E3779B1


def seed_rng(seed: int, *words: int) -> np.random.Generator:
    """A generator for one purpose of one seed; any whole number is a seed."""
    return np.random.default_rng(
        np.random.SeedSequence([seed & (2 ** 64 - 1), *words]))


def zipf_weights(n: int, s: float) -> list[float]:
    """Zipf(s) weights normalised to a mean of 1, rank 1 first (the
    workload plane's ``zipf_weights``, kept here as part of the yardstick)."""
    raw = [1.0 / (i + 1) ** s for i in range(n)]
    mean = sum(raw) / n
    return [round(w / mean, 6) for w in raw]


def make_packets(rng: np.random.Generator, n: int):
    """``n`` 64-byte packets: random 5-tuple headers and payload words."""
    return (rng.integers(0, 2 ** 32, (n, 5), dtype=np.uint32),
            rng.integers(0, 2 ** 32, (n, 16), dtype=np.uint32))


def stamp(base, work, first: int) -> None:
    """Write the packets ``work`` (headers, payload) as ``base``'s, with
    packet ``first + i`` of its tenant's stream XORing ``(first + i) *
    STAMP_MUL mod 2**32`` into its stamped header and payload words.  A pool
    of packets reused over a run so never repeats a packet (for fewer than
    2**32 packets per tenant), and the stamped words stay uniformly random."""
    n = len(work[0])
    s = ((np.arange(first, first + n, dtype=np.uint64) * STAMP_MUL)
         & 0xFFFFFFFF).astype(np.uint32)
    for b, w, col in zip(base, work, (STAMP_HEADER_WORD, STAMP_PAYLOAD_WORD)):
        np.bitwise_xor(b[:, col], s, out=w[:, col])


@dataclass
class Tenant:
    """One tenant's deployment: its chain and the parameters its NTs use."""
    index: int
    name: str
    weight: float
    chain: tuple[str, ...]
    shard: int
    prefixes: np.ndarray
    masks: np.ndarray
    allow: np.ndarray
    key: np.ndarray
    nonce: np.ndarray
    nat_ip: int

    def params(self, stream_counters: bool) -> dict:
        p: dict = {}
        if "firewall" in self.chain:
            p["firewall"] = {"rules": (jnp.asarray(self.prefixes),
                                       jnp.asarray(self.masks),
                                       jnp.asarray(self.allow))}
        if "nat" in self.chain:
            p["nat"] = {"nat_ip": self.nat_ip}
        if "chacha20" in self.chain:
            p["chacha20"] = {"key": jnp.asarray(self.key),
                             "nonce": jnp.asarray(self.nonce)}
            if stream_counters:
                p["chacha20"].update(stream=True, counter0=1)
        return p


def weights_of(dep: dict) -> list[float]:
    w = dep["weights"]
    n = dep["tenants"]
    if "pattern" in w:
        return [float(w["pattern"][i % len(w["pattern"])]) for i in range(n)]
    if "zipf_s" in w:
        return zipf_weights(n, float(w["zipf_s"]))
    raise ValueError(f"unknown weights {w}")


def make_tenants(config: dict, seed: int) -> list[Tenant]:
    """The tenants of a configuration, made from the seed.  The chain mix
    has fixed counts; the seed shuffles which tenant runs which chain, and
    makes every rule table, key, nonce and packet."""
    dep = config["deployment"]
    n = dep["tenants"]
    chains = [tuple(c.split(">>")) for c, k in dep["chains"].items()
              for _ in range(k)]
    if len(chains) != n:
        raise ValueError(f"chain counts sum to {len(chains)}, not {n}")
    random.Random(f"chains:{seed}").shuffle(chains)
    lo, hi = dep["rule_prefix_len"]
    n_rules = dep["rules_per_tenant"]
    out = []
    for i, w in enumerate(weights_of(dep)):
        rng = seed_rng(seed, i, 1)
        prefixes = rng.integers(0, 2 ** 32, n_rules, dtype=np.uint32)
        mlen = rng.integers(lo, hi + 1, n_rules)
        masks = ((0xFFFFFFFF << (32 - mlen)) & 0xFFFFFFFF).astype(np.uint32)
        allow = rng.random(n_rules) < 0.5
        key = rng.integers(0, 2 ** 32, 8, dtype=np.uint32)
        nonce = rng.integers(0, 2 ** 32, 3, dtype=np.uint32)
        out.append(Tenant(i, f"t{i}", w, chains[i], i % dep["shards"],
                          prefixes & masks, masks, allow, key, nonce,
                          dep["nat_ip_base"] + i))
    return out


class CompileCounter:
    """Counts new programs (traces and compiles) while open."""

    def __init__(self):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.n += 1

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


class GcWatch:
    """The garbage collector's pauses while open, as (start, end,
    generation) on the host clock: a slow step is told apart by them."""

    def __init__(self):
        self.pauses: list[tuple[float, float, int]] = []
        self._t0: float | None = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.perf_counter(),
                                info["generation"]))
            self._t0 = None

    def close(self) -> None:
        gc.callbacks.remove(self._on)


@dataclass
class Sample:
    """One retired batch kept for the comparison: whose, what went in,
    the counter its keystream starts at, and the program's output."""
    tenant: int
    headers: np.ndarray
    payload: np.ndarray
    counter0: int
    out: dict


@dataclass
class Sent:
    """One injected batch, pending until its output is matched: whose, what
    went in (the loop's buffers, which it rewrites only once the batch is
    retired), the counter its keystream starts at, the host time after its
    inject, and the host time its output was ready."""
    tenant: int
    headers: np.ndarray
    payload: np.ndarray
    counter0: int
    t_inject: float
    t_ready: float | None = None


@dataclass
class Window:
    """What a loop's window measured, for the metric readers."""
    t_start: float
    t_end: float
    delivered: list[int]
    attempted: list[int]
    latency_us: np.ndarray | None = None
    gen_lag_ms: np.ndarray | None = None
    #: host time each step's outputs were ready
    step_ends: list[float] = field(default_factory=list)

    @property
    def steps(self) -> int:
        return len(self.step_ends)


class Bench:
    """A deployed cell and the public calls that drive it."""

    def __init__(self, config: dict, seed: int, *, devices=None,
                 backend_kw: dict | None = None, nts: dict | None = None,
                 check_per_tenant: int = 2, runtime: str = "batch"):
        from repro.api import (VPC_SPECS, ComputeBackend, Platform,
                               ShardedBackend, nt)
        self.config = config
        self.seed = seed
        dep = config["deployment"]
        self.stream_counters = bool(dep.get("stream_counters", False))
        self.tenants = make_tenants(config, seed)
        if runtime not in RUNTIMES:
            raise ValueError(f"unknown runtime {runtime!r} (have: "
                             f"{sorted(RUNTIMES)})")
        kw = dict(backend_kw or {}, stream=RUNTIMES[runtime])
        if nts:
            kw["nts"] = nts
        n_shards = dep["shards"]
        if n_shards == 1:
            self.computes = [ComputeBackend(**kw)]
            backend = self.computes[0]
        else:
            devices = list(devices if devices is not None else jax.devices())
            self.computes = [ComputeBackend(name=f"c{i}", device=devices[i],
                                            **kw) for i in range(n_shards)]
            backend = ShardedBackend(self.computes, auto_rebalance=False,
                                     health_threshold=1)
        self.plat = Platform(backend, specs=VPC_SPECS)
        self.deps = []
        for t in self.tenants:
            expr = functools.reduce(operator.rshift, [nt(n) for n in t.chain])
            deploy_kw = {"params": t.params(self.stream_counters)}
            if n_shards > 1:
                deploy_kw["shard"] = t.shard
            self.deps.append(self.plat.tenant(t.name, weight=t.weight)
                             .deploy(expr, **deploy_kw))
        n = len(self.tenants)
        self.fed = [0] * n                 # packets ever injected
        self.pending: list[collections.deque[Sent]] = [
            collections.deque() for _ in range(n)]
        self.check_per_tenant = check_per_tenant
        self._rand = [random.Random(f"sample:{seed}:{i}") for i in range(n)]
        self.samples: list[list[Sample]] = [[] for _ in range(n)]
        self.start_window(sampling=False)

    # --------------------------------------------------------- counters --
    def start_window(self, sampling: bool = True) -> None:
        n = len(self.tenants)
        self.sampling = sampling
        # what is still pending counts as attempted again
        self.attempted = [sum(len(s.headers) for s in q)
                          for q in self.pending]
        self.delivered = [0] * n
        self.bad_batches = 0
        self.inject_s = 0.0
        self.inject_pkts = 0
        self._offered = [0] * n            # batches offered to the sampler
        self.dispatches0 = self.dispatches()
        #: (host time, process CPU time) at the start and at every retire
        self.marks = [(time.perf_counter(), time.process_time())]

    def dispatches(self) -> int:
        return sum(c.stats["dispatches"] for c in self.computes)

    def pending_batches(self) -> int:
        return sum(len(q) for q in self.pending)

    # -------------------------------------------------------- the calls --
    def inject(self, t: int, headers: np.ndarray, payload: np.ndarray,
               base=None) -> Sent:
        """Inject one batch for tenant ``t``.  With ``base`` (the pool rows
        that ``headers`` and ``payload`` are buffers for) the batch is first
        stamped (:func:`stamp`) with its packets' indices."""
        n = len(headers)
        if base is not None:
            stamp(base, (headers, payload), self.fed[t])
        with jax.profiler.TraceAnnotation("chipbench.inject"):
            t0 = time.perf_counter()
            self.deps[t].inject(headers=headers, payload=payload)
            t1 = time.perf_counter()
        self.inject_s += t1 - t0
        self.inject_pkts += n
        # the keystream counter starts at 1 in each batch, or runs on
        # across the tenant's batches where the configuration says so
        c0 = self.fed[t] + 1 if self.stream_counters else 1
        sent = Sent(t, headers, payload, c0, t1)
        self.pending[t].append(sent)
        self.fed[t] += n
        self.attempted[t] += n
        return sent

    def run(self) -> None:
        with jax.profiler.TraceAnnotation("chipbench.run"):
            self.plat.run()

    def retire(self) -> tuple[float, list[Sent]]:
        """Collect every tenant's outputs, wait until they are ready, free
        them on the device.  Returns the host time they were ready and the
        injects they answered, each with its ``t_ready`` set."""
        with jax.profiler.TraceAnnotation("chipbench.retire"):
            rep = self.plat.report()
            ready, done = [], []
            for t, ten in enumerate(self.tenants):
                tr = rep.tenants.get(ten.name)
                queue = self.pending[t]
                for out in (tr.outputs if tr is not None else ()):
                    if not queue:              # an output nobody injected
                        self.bad_batches += 1
                        continue
                    sent = queue.popleft()
                    rows = _rows(out)
                    if rows != len(sent.headers):
                        self.bad_batches += 1
                    self.delivered[t] += min(rows, len(sent.headers))
                    ready.append(out)
                    done.append(sent)
                    if self.sampling:
                        self._offer(sent, out)
            jax.block_until_ready(ready)
            t_ready = time.perf_counter()
            for c in self.computes:
                c.reset_window()
        for sent in done:
            sent.t_ready = t_ready
        self.marks.append((t_ready, time.process_time()))
        return t_ready, done

    def drain(self, limit_s: float) -> None:
        """Run and retire until no inject is pending, for at most
        ``limit_s`` seconds; whatever is pending then never came back."""
        t_end = time.perf_counter() + limit_s
        while self.pending_batches() and time.perf_counter() < t_end:
            self.run()
            self.retire()
        self.bad_batches += self.pending_batches()

    def _offer(self, sent: Sent, out: dict) -> None:
        """Reservoir sampling: every retired batch of the tenant has the
        same chance to be among the ``check_per_tenant`` kept.  A kept
        batch's input is copied, since the loop reuses its buffers."""
        t = sent.tenant
        self._offered[t] += 1
        kept = self.samples[t]
        j = len(kept) if len(kept) < self.check_per_tenant else \
            self._rand[t].randrange(self._offered[t])
        if j >= self.check_per_tenant:
            return
        s = Sample(t, sent.headers.copy(), sent.payload.copy(),
                   sent.counter0, out)
        if j == len(kept):
            kept.append(s)
        else:
            kept[j] = s

    # ---------------------------------------------------------- results --
    def datapath_bytes(self) -> float:
        """Bytes the delivered packets' chains must move (``work``)."""
        return float(sum(d * chain_bytes(t.chain)
                         for d, t in zip(self.delivered, self.tenants)))

    def host_samples(self) -> None:
        """Copy the kept outputs to the host, freeing them on the device."""
        for kept in self.samples:
            for s in kept:
                s.out = {k: np.asarray(v) for k, v in s.out.items()
                         if k in COMPARED}

    def compare(self) -> tuple[int, int]:
        """Words that differ from the reference over the kept samples, and
        the packets checked."""
        bad = checked = 0
        for kept in self.samples:
            for s in kept:
                ten = self.tenants[s.tenant]
                want = reference.chain(ten.chain, s.headers, s.payload, ten,
                                       s.counter0)
                bad += mismatched_words(s.out, want)
                checked += len(s.headers)
        return bad, checked


def _rows(out: dict) -> int:
    for v in out.values():
        if getattr(v, "ndim", 0) >= 1:
            return int(v.shape[0])
    return 0


def mismatched_words(got: dict, want: dict) -> int:
    """u32 words (or verdicts) of ``want`` that ``got`` does not equal; a
    field that is missing or of another shape counts whole."""
    bad = 0
    for k, w in want.items():
        g = got.get(k)
        w = np.asarray(w)
        if g is None or np.shape(g) != w.shape:
            bad += w.size
            continue
        bad += int(np.count_nonzero(np.asarray(g) != w))
    return bad
