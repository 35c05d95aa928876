"""ComputeBackend: NT names bound to real batched JAX/Pallas kernels, with
an async zero-resync runtime.

The same builder DAG that drives the event simulator executes here as *one
fused program* — the generalization of the hardcoded
:func:`repro.serving.vpc.vpc_chain`.  Each compute NT is a pure function
over a *packet-batch state* (a dict of arrays: ``headers`` (N, 5) u32,
``payload`` (N, 16) u32, ``allow`` (N,) bool, ``ctr`` (N,) u32, ...);
chaining composes the functions inside one ``jax.jit``, so XLA fuses the
whole DAG exactly like placing an NT chain in a single region (no scheduler
round trips).

Runtime design (the paper's "schedule the chain once" insight, §4.2, applied
to the host runtime):

  - **Fused-kernel fast path.**  A linear chain whose stage names match a
    registered fused Pallas kernel (``firewall >> nat >> chacha20`` ->
    :func:`repro.kernels.vpc_datapath.vpc_datapath`) dispatches to it: one
    kernel launch for the whole chain, packet tiles resident in VMEM across
    all NTs.  Everything else falls back to the composed XLA path.
  - **Shape-bucketed compile cache.**  Batches are padded to power-of-two
    buckets, so the number of distinct shapes that can ever reach
    ``jax.jit`` — and therefore the number of compilations — is O(log N),
    not O(#batches).  Pad rows are safe for the built-in NTs because every
    one is row-wise (pad outputs are sliced off after the run); a custom
    ``ComputeNT`` that reduces *across* packets must mask with the
    ``state["valid"]`` row mask the runtime provides, or pad rows leak
    into its result.
  - **Scheduler-ordered batch composition.**  Pending injects live in
    per-tenant :class:`repro.core.sched.FairScheduler` queues; ``run()``
    drains them in weighted deficit-round-robin order (cost = wire bytes),
    so a heavy tenant's backlog can no longer starve a light tenant within
    a run — the light tenant's batches dispatch early in the device queue
    in proportion to its weight.  Injects for unregistered tenants are an
    error (a tenant's weight must exist before its traffic does).
  - **Batch coalescing.**  *Consecutive* same-DAG, same-signature entries
    of the fair drain order merge into one dispatch — a later batch may
    never jump the fair queue just because it coalesces, so a
    mixed-signature stream pays one dispatch per signature *run* (a
    single tenant with one signature still collapses to one dispatch per
    ``run()``).  The ChaCha keystream counter is per-packet *state*
    (``ctr``, synthesized at inject time), so merging or reordering
    batches never changes any packet's ciphertext.
  - **One device sync per run().**  Every pending batch is dispatched
    asynchronously; a single ``block_until_ready`` at the end is the only
    host<->device synchronization point, and the throughput window.
  - **Host staging.**  Every dispatch group is coalesced and padded in
    host buffers, with its ``valid`` row mask, and crosses to the device
    in one ``jax.device_put``: each array once, at bucket size, and no
    eager device op.  Both engines share the fill; batch mode stages in
    fresh buffers, the streaming engine in its ring slots.
  - **Buffer donation.**  Dispatch inputs are donated to XLA where the
    backend supports it.  Staging always materializes fresh buffers, so
    caller-owned arrays are never donated (inject the same arrays twice
    and both runs see identical bits).
  - **Streaming engine** (``run(stream=True)`` / :meth:`inject_stream` /
    ``ComputeBackend(stream=True)``): the pipelined alternative to the
    batch-synchronous drain.  Batches flow through a **dispatch ring** of
    pre-allocated, reusable staging slots per (bucket, signature) — steady
    state fills ring slots instead of materializing fresh bucket buffers —
    and each slot's ``jax.device_put`` (the async host->device transfer of
    the *next* group) overlaps the previous group's still-running kernel.
    The single end-of-run sync becomes a bounded in-flight window
    (``max_inflight``): a slot is drained with its own ``block_until_ready``
    only when the ring wraps, so transfer, compute, and result slicing
    pipeline instead of serializing.  With a device *list*, dispatch groups
    round-robin across the devices of one shard; stream-mode ChaCha stays
    bit-exact because per-packet counters are assigned when an item enters
    the ring (fair drain order — deterministic), never at completion time.
    The throughput window for a streaming run is first-dispatch ->
    last-drain.  ``inject_stream`` services a continuous inject source
    epoch-by-epoch through the scheduler's stream-credit window
    (:meth:`repro.core.sched.FairScheduler.stream_window`) instead of
    draining a static backlog — scheduler grants shape the stream
    in-flight, the Wave-style push-down.

Fork/join semantics mirror the sync buffer (§4.2): every branch of a stage
reads the stage's input state; the join merges each branch's declared
``writes``.  Two branches writing the same field is a build-time error — the
data model gives parallel branches no ordering to resolve it.

Egress applies the firewall verdict the way the fixed sNIC datapath does:
denied packets keep their original header and leave with a zeroed payload
(bit-exact with ``vpc_chain``).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.analysis import invariants as _sanitize
from repro.core.nt import GBPS, NTDag, NTSpec
from repro.core.sched import FairScheduler, SchedConfig
from repro.kernels.chacha20.ops import vmem_tile_bytes as _chacha_tile
from repro.kernels.vpc_datapath import vpc_datapath
from repro.kernels.vpc_datapath.ops import vmem_tile_bytes as _vpc_tile
from repro.serving.vpc import chacha20_xor_jnp, firewall, nat_rewrite

from .backend import PlatformReport, TenantReport
from .dag import DagError

#: fields that actually cross the wire; everything else (verdict bits,
#: counters, validity masks, scratch) is metadata and must not count
#: toward Gbps
WIRE_FIELDS = ("headers", "payload")

#: smallest pad bucket; buckets are _MIN_BUCKET * 2**k
_MIN_BUCKET = 8


def bucket_size(n: int) -> int:
    """Smallest power-of-two bucket (>= _MIN_BUCKET) holding ``n`` rows.

    Exact fits stay in their bucket (``bucket_size(2**k) == 2**k`` — the
    ring-wrap edge where an inject exactly fills the last ring slot must
    not spill into the next bucket and re-trace)."""
    if n < 0:
        raise ValueError(f"bucket_size needs n >= 0, got {n}")
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return b


@dataclass(frozen=True)
class ComputeNT:
    """One network task as real compute.

    ``fn(state, params) -> updates``: reads any state fields, returns the
    dict of fields it produces.  ``writes`` declares those fields so the
    fork/join merge can detect conflicts at build time.  ``prep(n, params)``
    optionally synthesizes per-packet state fields at inject time (e.g. the
    ChaCha keystream counter) so that batch coalescing and bucket padding
    cannot change the NT's output for any real packet; ``prep_fields``
    names them, so inject can skip ``prep`` when the caller already
    supplied every one.

    The remaining fields are admission-verifier metadata
    (:mod:`repro.analysis.verifier`), all optional: ``reads`` declares the
    state fields ``fn`` consumes so dataflow holes surface at deploy time;
    ``schema`` pins per-field trailing shape and dtype as
    ``((field, trailing_shape, dtype), ...)`` tuples (hashable, so the
    dataclass stays frozen-hashable) so shape breaks along an edge are
    static errors; ``tile_bytes`` is the NT kernel's worst-case VMEM tile
    residency, summed per fused branch against the per-core budget.
    """
    name: str
    fn: Callable[[dict, dict], dict]
    writes: tuple[str, ...]
    prep: Callable[[int, dict], dict] | None = None
    prep_fields: tuple[str, ...] = ()
    reads: tuple[str, ...] = ()
    schema: tuple[tuple[str, tuple[int, ...], str], ...] = ()
    tile_bytes: int = 0
    #: optional stream-state synthesizer, ``stream(n, params, state) ->
    #: (fields, new_state)``.  Activated per deployment with
    #: ``params[name]["stream"] = True``: instead of ``prep`` at inject
    #: time, the per-packet fields are assigned at *dispatch* time from a
    #: running per-deployment state (e.g. a continuing ChaCha ``ctr``
    #: across batches).  Because the state only ever advances when work is
    #: actually dispatched, a checkpoint taken between runs reflects
    #: exactly the completed stream — a failed-over deployment restored
    #: from it resumes bit-exact.
    stream: Callable[[int, dict, dict], tuple[dict, dict]] | None = None


# ------------------------------------------------------- built-in NT library --
def _fw_nt(state, params):
    allow = firewall(state["headers"], params["rules"])
    prev = state.get("allow")
    return {"allow": allow if prev is None else prev & allow}


def _nat_nt(state, params):
    return {"headers": nat_rewrite(state["headers"],
                                   params.get("nat_ip", 0x0A000001))}


def _chacha_nt(state, params):
    ctr = state.get("ctr")
    if ctr is None and "ctr0" in state:
        # per-slot counter base: a traced scalar expanded ON DEVICE inside
        # the jitted program, so a streaming ring slot carries one u32
        # instead of a bucket-sized counter array (pad rows get counters
        # past the batch; their output is sliced off like any pad row)
        ctr = jnp.asarray(state["ctr0"], jnp.uint32) + \
            jnp.arange(state["payload"].shape[0], dtype=jnp.uint32)
    return {"payload": chacha20_xor_jnp(state["payload"], params["key"],
                                        params["nonce"],
                                        params.get("counter0", 1),
                                        ctr=ctr)}


def _ctr_run(c0: int, n: int) -> np.ndarray:
    """Host-side counter run ``c0 + arange(n)`` (u32, wrapping): per-packet
    state stays with the packet data on the host until the dispatch ships
    it to the launch's own device."""
    return np.uint32(c0) + np.arange(n, dtype=np.uint32)


def _chacha_prep(n, params):
    return {"ctr": _ctr_run(params.get("counter0", 1), n)}


def _chacha_stream(n, params, state):
    """Stream-mode ``ctr``: a running keystream counter that continues
    across batches (and, via export/import_state + CheckpointManager,
    across a crash/recover cycle).  With ``params["scalar_ctr"]`` the
    per-packet array is replaced by a scalar ``ctr0`` base expanded inside
    the kernel — the per-slot counter plumbing the dispatch ring uses so a
    steady-state inject moves one u32, not an (N,) array.  Scalar-ctr
    batches never coalesce (a 0-d field is its own dispatch signature), so
    each keeps exactly its own counter run and the ciphertext stays
    bit-exact with the array path."""
    nxt = int(state.get("next_ctr", params.get("counter0", 1)))
    if params.get("scalar_ctr"):
        return ({"ctr0": jnp.uint32(nxt)}, {"next_ctr": nxt + n})
    return {"ctr": _ctr_run(nxt, n)}, {"next_ctr": nxt + n}


BUILTIN_COMPUTE_NTS: dict[str, ComputeNT] = {
    "firewall": ComputeNT(
        "firewall", _fw_nt, writes=("allow",), reads=("headers",),
        schema=(("headers", (5,), "uint32"), ("allow", (), "bool")),
        # fused-kernel share: packet tiles, the largest rule tile, the
        # running best and the scan (any table streams in such tiles)
        tile_bytes=_vpc_tile() - _chacha_tile(block_n=256)),
    "nat": ComputeNT(
        "nat", _nat_nt, writes=("headers",), reads=("headers",),
        schema=(("headers", (5,), "uint32"),),
        tile_bytes=4 * 256 * (5 + 5)),       # header tile in + out
    "chacha20": ComputeNT(
        "chacha20", _chacha_nt, writes=("payload",),
        reads=("payload", "ctr"),
        schema=(("payload", (16,), "uint32"), ("ctr", (), "uint32")),
        prep=_chacha_prep, prep_fields=("ctr",), stream=_chacha_stream,
        tile_bytes=_chacha_tile(block_n=256)),
}

# nominal service models for the same NT names on the sim substrate, so one
# spec registry can front both backends
VPC_SPECS: dict[str, NTSpec] = {
    "firewall": NTSpec("firewall", max_gbps=100.0, fixed_ns=300.0),
    "nat": NTSpec("nat", max_gbps=100.0, fixed_ns=300.0),
    "chacha20": NTSpec("chacha20", max_gbps=80.0, fixed_ns=500.0),
}


# --------------------------------------------------- fused kernel registry --
def _vpc_fused_factory(params: dict) -> Callable | None:
    """Fused launcher for the canonical VPC chain, or None if the deployment
    params cannot feed the megakernel (missing rules/key/nonce).  The
    deploy-time params are only a capability probe — every param is re-read
    from the runtime params argument, the same binding the composed path
    gives every NT."""
    try:
        params["firewall"]["rules"]
        params["chacha20"]["key"]
        params["chacha20"]["nonce"]
    except (KeyError, TypeError):
        return None

    def program(state: dict, params: dict) -> dict:
        ch = params["chacha20"]
        allow, hout, pout = vpc_datapath(
            state["headers"], state["payload"], params["firewall"]["rules"],
            ch["key"], ch["nonce"],
            nat_ip=params.get("nat", {}).get("nat_ip", 0x0A000001),
            # ctr0 is the streaming ring's per-slot counter base (a traced
            # scalar; the kernel wrapper expands it on device)
            counter0=state.get("ctr0", ch.get("counter0", 1)),
            ctr=state.get("ctr"))
        return {**state, "allow": allow, "headers": hout, "payload": pout}

    return program


#: exact linear-chain stage names -> fused program factory(params)
FUSED_KERNELS: dict[tuple[str, ...], Callable[[dict], Callable | None]] = {
    ("firewall", "nat", "chacha20"): _vpc_fused_factory,
}


def _linear_chain(dag: NTDag) -> tuple[str, ...] | None:
    """The dag's NT names if it is one linear chain, else None."""
    names: list[str] = []
    for stage in dag.stages:
        if len(stage) != 1:
            return None
        names.extend(stage[0])
    return tuple(names)


# ----------------------------------------------------------- runtime state --
@dataclass
class _Deployment:
    dag: NTDag
    params: dict
    fused: Callable | None                    # fused program or None
    composed: Callable                        # composed program (fallback)
    results: list = field(default_factory=list)
    # (bucket_rows, path) -> jitted program; one jit instance per bucket so
    # the compile cache is explicit and countable
    cache: dict[tuple[int, str], Callable] = field(default_factory=dict)
    #: per-NT running stream state (plain scalars, checkpointable); only
    #: advanced at dispatch time, so it always reflects completed work
    nt_state: dict[str, dict] = field(default_factory=dict)
    #: pinned device -> ``params`` with its arrays committed there, so a
    #: dispatch to a pinned device never copies rules and keys per launch
    device_params: dict = field(default_factory=dict)
    #: rules each packet is matched against (0 with no firewall)
    n_rules: int = 0


def _n_rules(dag: NTDag, params: dict) -> int:
    """Rules in the deployment's firewall table, 0 where it has none."""
    if "firewall" not in dag.all_nts():
        return 0
    return int(np.shape(params["firewall"]["rules"][0])[0])


def _commit(params: dict, dev) -> dict:
    """``params`` with every array leaf committed to ``dev``; plain Python
    values (flags, counters) stay host-side."""
    return jax.tree.map(
        lambda x: jax.device_put(x, dev) if hasattr(x, "shape") else x,
        params)


def _rows(batch: dict) -> int:
    for v in batch.values():
        if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1:
            return int(v.shape[0])
    return 0


# ------------------------------------------------------------ dispatch ring --
@dataclass
class _RingSlot:
    """One pre-allocated staging slot: host buffers sized to a bucket, one
    per array field of the dispatch signature (plus the ``valid`` row
    mask).  The slot is filled in place, shipped with one async
    ``jax.device_put`` of the whole dict (the device copy is what the
    jitted program donates), and returned to the ring's free list when its
    in-flight entry drains — so steady state allocates nothing."""
    key: tuple
    staging: dict[str, np.ndarray]


class DispatchRing:
    """Pool of reusable staging slots keyed by (bucket, array signature).

    ``allocs`` counts real slot materializations; once the pipeline warms
    up (at most ``max_inflight + 1`` slots per key are ever live) every
    acquire is a reuse — the zero-steady-state-allocation property the
    streaming tests assert."""

    def __init__(self, depth: int = 4):
        self.depth = int(depth)
        self._free: dict[tuple, list[_RingSlot]] = {}
        self.allocs = 0
        self.reuses = 0

    def acquire(self, bucket: int,
                fields: list[tuple[str, tuple[int, ...], np.dtype]],
                ) -> _RingSlot:
        key = (bucket, tuple((k, trail, str(dt)) for k, trail, dt in fields))
        free = self._free.get(key)
        if free:
            self.reuses += 1
            return free.pop()
        self.allocs += 1
        return _RingSlot(key, _staging_buffers(bucket, fields))

    def release(self, slot: _RingSlot) -> None:
        self._free.setdefault(slot.key, []).append(slot)

    def stats(self) -> dict:
        return {"allocs": self.allocs, "reuses": self.reuses,
                "depth": self.depth,
                "free_slots": sum(len(v) for v in self._free.values())}


@dataclass
class _InFlight:
    """A launched-but-undrained dispatch group: the ring entry the bounded
    in-flight window retires (per-slot sync) when the ring wraps."""
    dep: _Deployment
    orders: list[int]
    sizes: list[int]
    out: dict
    slot: _RingSlot | None
    enq: list[tuple[str, float]]          # (tenant, enqueued_at) per batch


def _signature(batch: dict):
    """Coalescing key: batches merge only when their field names, trailing
    shapes and dtypes agree (arrays concatenate along the packet axis)."""
    items = []
    for k in sorted(batch):
        v = batch[k]
        if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1:
            items.append((k, tuple(v.shape[1:]), str(v.dtype)))
        else:                      # non-array field: never coalesced
            items.append((k, "scalar", id(v)))
    return tuple(items)


def _array_fields(batch: dict) -> list[tuple[str, tuple[int, ...], np.dtype]]:
    """``(name, trailing shape, dtype)`` of each packet-axis field."""
    return [(k, tuple(v.shape[1:]), np.dtype(v.dtype))
            for k, v in batch.items()
            if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1]


def _staging_buffers(bucket: int, fields) -> dict[str, np.ndarray]:
    """Uninitialized host buffers for one dispatch group: one per field of
    ``fields`` and the ``valid`` row mask, ``bucket`` rows each."""
    staging = {k: np.empty((bucket,) + trail, dt) for k, trail, dt in fields}
    staging["valid"] = np.empty((bucket,), bool)
    return staging


def _fill_staging(batches: list[dict], sizes: list[int],
                  staging: dict[str, np.ndarray]) -> None:
    """Coalesce and pad a dispatch group into the host buffers ``staging``
    (from :func:`_staging_buffers` or a ring slot): each batch's rows at
    its offset, the pad rows zeroed, ``valid`` set on the real rows."""
    n = sum(sizes)
    off = 0
    for b, m in zip(batches, sizes):
        for k, dst in staging.items():
            if k != "valid":
                # host->host staging copy of the packet data (a batch
                # injected as a jax.Array is read back here, once)
                dst[off:off + m] = np.asarray(b[k])  # noqa: L-HOSTSYNC
        off += m
    for dst in staging.values():
        dst[n:] = 0                           # pad rows (exact fill: noop)
    staging["valid"][:n] = True


def _ship(template: dict, staging: dict[str, np.ndarray], bucket: int,
          dev) -> dict:
    """The filled ``staging`` as the group's input tree on ``dev`` (the
    default device when None), in one ``jax.device_put``; the 0-d and
    non-array fields of ``template``, the group's first batch, ride
    along."""
    state, rest = dict(staging), {}
    for k, v in template.items():             # 0-d / non-array fields
        if k not in state:
            if hasattr(v, "shape"):
                state[k] = _pad_to(v, bucket)
            else:
                rest[k] = v
    state = jax.device_put(state, dev)
    state.update(rest)
    return state


def _corrupt_batch(batch: dict, rng) -> dict:
    """Injected data fault: flip one payload bit (deterministic under the
    FaultState's seeded rng)."""
    pl = batch.get("payload")
    if pl is None or not hasattr(pl, "dtype") or getattr(pl, "size", 0) == 0:
        return batch
    a = jnp.asarray(pl)
    if not jnp.issubdtype(a.dtype, jnp.integer):
        return batch
    flat = a.reshape(-1)
    i = rng.randrange(flat.size)
    bit = jnp.asarray(1 << rng.randrange(8 * a.dtype.itemsize), a.dtype)
    flat = flat.at[i].set(flat[i] ^ bit)
    out = dict(batch)
    out["payload"] = flat.reshape(a.shape)
    return out


def _slice_result(out: dict, off: int, s: int) -> dict:
    """Un-coalesce one batch's rows out of a dispatched group's output,
    dropping the pad/validity scaffolding."""
    res = {}
    for k, v in out.items():
        if k == "valid":
            continue
        if hasattr(v, "shape") and getattr(v, "ndim", 0) >= 1:
            res[k] = v[off:off + s]
        else:
            res[k] = v
    return res


def _pad_to(x, b: int):
    """Pad the packet axis to ``b`` rows.  Always materializes a fresh
    buffer (even when no padding is needed, and for 0-d arrays) so the
    jitted program can donate its inputs without ever consuming a
    caller-owned array."""
    x = jnp.asarray(x)
    if x.ndim == 0:
        return x + jnp.zeros((), x.dtype)     # fresh 0-d buffer
    buf = jnp.zeros((b,) + x.shape[1:], x.dtype)
    return buf.at[: x.shape[0]].set(x)


class ComputeBackend:
    name = "compute"

    def __init__(self, nts: dict[str, ComputeNT] | None = None,
                 use_fused: bool | None = None, donate: bool = True,
                 quantum_bytes: float = 8 * 1500.0,
                 name: str | None = None, device=None,
                 capacity_gbps: float = 100.0, stream: bool = False,
                 ring_depth: int = 4, max_inflight: int | None = None):
        """``name`` and ``device`` give each instance an explicit shard
        identity: pass a ``jax.Device`` (or an index into
        ``jax.devices()``), or a *list* of devices, to pin dispatches there
        instead of inheriting the process-global default — a single device
        maps one shard per accelerator; a list round-robins this shard's
        dispatch groups across its devices.  ``capacity_gbps`` is the
        nominal wire capacity a placer provisions against.

        ``stream=True`` makes ``run()`` default to the pipelined streaming
        engine; ``ring_depth`` sizes the dispatch ring's staging pool and
        ``max_inflight`` (default: ``ring_depth``) bounds how many launched
        dispatch groups may be awaiting their per-slot drain at once."""
        if name is not None:
            self.name = name
        if device is None:
            self.devices = None
        else:
            devs = list(device) if isinstance(device, (list, tuple)) \
                else [device]
            self.devices = [d if hasattr(d, "platform")
                            else jax.devices()[int(d)] for d in devs]
        self.device = self.devices[0] if self.devices else None
        self._rr = 0                       # round-robin device cursor
        self.capacity_gbps = capacity_gbps
        self.stream = stream
        self.ring_depth = max(1, int(ring_depth))
        self.max_inflight = self.ring_depth if max_inflight is None \
            else max(1, int(max_inflight))
        self.ring = DispatchRing(depth=self.ring_depth)
        self._inflight: deque[_InFlight] = deque()
        #: batches dispatched into the ring but not yet drained (an I-BATCH
        #: conservation term: injected == completed + queued + shed +
        #: in_flight); nonzero only while the streaming engine is feeding
        self.inflight_batches = 0
        self._t_first: float | None = None   # streaming window: first launch
        self._t_last = 0.0                   # ... -> last drain
        self.nts = dict(BUILTIN_COMPUTE_NTS)
        self.nts.update(nts or {})
        # default: megakernels only where they compile (TPU).  Off-TPU the
        # fused path would run in Pallas interpret mode — a correctness
        # harness, not a datapath — so the composed XLA path is the default
        # there.  Pass use_fused=True to force (tests/benches do).
        self.use_fused = (jax.default_backend() == "tpu"
                          if use_fused is None else use_fused)
        # safe because staging always hands the program fresh buffers
        # (host staging buffers sent by _ship, and _pad_to's for 0-d
        # fields): caller-owned arrays are never donated
        self.donate = donate
        self.deployments: dict[int, _Deployment] = {}
        # fair time sharing of the dispatch stream: per-tenant queues served
        # in WDRR order, cost = wire bytes (strict tenancy: injects for
        # unregistered tenants raise)
        # WDRR granularity: wire bytes of deficit earned per round per unit
        # weight.  Default ~ one MTU-sized batch; set it near the typical
        # batch wire size for the tightest inter-tenant interleave.
        self.sched = FairScheduler(
            config=SchedConfig(quantum=float(quantum_bytes), strict=True),
            clock=time.perf_counter)
        self._order = 0                    # global inject sequence number
        #: (tenant, wire_bytes) per dispatched batch, in fair service order
        self.dispatch_log: list[tuple[str, float]] = []
        self._lat_s: dict[str, list[float]] = {}
        self._elapsed_s = 0.0
        self.stats = {"traces": 0, "dispatches": 0, "fused_dispatches": 0,
                      "batches": 0, "coalesced_batches": 0, "runs": 0,
                      "stream_batches": 0, "stream_epochs": 0,
                      # bucket rows, and pad rows among them, over launches
                      "rows_launched": 0, "pad_rows": 0,
                      # host bytes staging sent to the device
                      "h2d_bytes": 0,
                      # real rows times the firewall's rules, over launches
                      "rule_pairs": 0}
        #: host seconds inside each of the runtime's spans (``repro.obs``)
        self.span_s = dict.fromkeys(obs.PHASES, 0.0)
        #: batches fully dispatched + synced (I-BATCH conservation: this +
        #: sched.pending() + shed_batches == stats["batches"]); kept out of
        #: ``stats`` so report().extra is unchanged
        self.completed_batches = 0
        #: batches shed by backpressure or tenant churn (I-BATCH term)
        self.shed_batches = 0
        #: fault-injection switchboard (armed by a FaultInjector; None =
        #: zero-cost hooks)
        self.faults = None

    @property
    def tenants(self) -> dict[str, float]:
        return self.sched.weights

    def capacity(self) -> dict:
        """Capacity probe for a placer: nominal wire Gbps + device identity.
        Doubles as the health heartbeat — raises when crashed/hung, and a
        degraded shard reports its reduced rate."""
        if self.faults is not None:
            self.faults.check_probe()
        scale = self.faults.degrade if self.faults is not None else 1.0
        devs = self.devices if self.devices is not None else jax.devices()[:1]
        return {"gbps": scale * self.capacity_gbps, "device": str(devs[0]),
                "devices": [str(d) for d in devs]}

    # ----------------------------------------------------------- protocol --
    def register(self, spec: NTSpec) -> None:
        if spec.name not in self.nts:
            raise DagError(
                f"NT {spec.name!r} has no compute binding; register a "
                f"ComputeNT via register_nt() (have: {sorted(self.nts)})")

    def register_nt(self, nt: ComputeNT) -> None:
        self.nts[nt.name] = nt

    def add_tenant(self, tenant: str, weight: float) -> None:
        self.sched.add_tenant(tenant, weight)

    def remove_tenant(self, tenant: str) -> tuple[int, float]:
        """Tenant churn: drop the tenant's queue; shed batches are counted
        into the I-BATCH conservation term."""
        n, cost = self.sched.remove_tenant(tenant)
        self.shed_batches += n
        return n, cost

    def shed_backlog(self, tenant: str, cost_limit: float) -> tuple[int, float]:
        """Backpressure: cap one tenant's queued wire bytes (graceful
        degradation under fleet overload); counted, never silent."""
        n, cost = self.sched.shed_backlog(tenant, cost_limit)
        self.shed_batches += n
        return n, cost

    # ------------------------------------------------------------ compile --
    def _validate(self, dag: NTDag) -> None:
        for stage in dag.stages:
            writer: dict[str, tuple[int, str]] = {}
            for bi, branch in enumerate(stage):
                for name in branch:
                    if name not in self.nts:
                        raise DagError(f"NT {name!r} has no compute binding")
                    for fld in self.nts[name].writes:
                        prev = writer.get(fld)
                        if prev is not None and prev[0] != bi:
                            raise DagError(
                                f"parallel branches both write {fld!r} "
                                f"({prev[1]} and {name}); the join has no "
                                "ordering to merge them")
                        writer[fld] = (bi, name)

    def _composed_program(self, dag: NTDag) -> Callable:
        """Lower the DAG to one fused-by-XLA function (the fallback path for
        chains with no registered megakernel)."""
        def program(state: dict, params: dict) -> dict:
            state = dict(state)
            orig_headers = state.get("headers")
            for stage in dag.stages:
                if len(stage) == 1:
                    for name in stage[0]:
                        state.update(self.nts[name].fn(
                            state, params.get(name, {})))
                    continue
                joined: dict = {}
                for branch in stage:              # fork: same input state
                    bstate = dict(state)
                    for name in branch:
                        up = self.nts[name].fn(bstate, params.get(name, {}))
                        bstate.update(up)
                        joined.update(up)
                state.update(joined)              # join: merge branch writes
            allow = state.get("allow")
            if allow is not None:                 # egress verdict
                if orig_headers is not None and "headers" in state:
                    state["headers"] = jnp.where(
                        allow[:, None], state["headers"], orig_headers)
                if "payload" in state:
                    state["payload"] = jnp.where(
                        allow[:, None], state["payload"],
                        jnp.zeros_like(state["payload"]))
            return state

        return program

    def _jit(self, program: Callable) -> Callable:
        """One jit instance per (deployment, bucket, path) cache slot; the
        wrapper body runs exactly once per trace, so ``stats['traces']``
        counts real compilations."""
        def traced(state: dict, params: dict) -> dict:
            self.stats["traces"] += 1
            return program(state, params)

        if self.donate:
            return jax.jit(traced, donate_argnums=0)
        # donate=False is an explicit debugging escape hatch (keep inputs
        # alive to diff against outputs); not a dispatch-path oversight
        return jax.jit(traced)  # noqa: L-DONATE

    def _get_program(self, dep: _Deployment, bucket: int,
                     path: str) -> Callable:
        key = (bucket, path)
        prog = dep.cache.get(key)
        if prog is None:
            prog = self._jit(dep.fused if path == "fused" else dep.composed)
            dep.cache[key] = prog
        return prog

    # ------------------------------------------------------------- deploy --
    def deploy(self, dag: NTDag, params: dict | None = None, **_kw) -> None:
        params = params or {}
        self._validate(dag)
        fused = None
        if self.use_fused:
            chain = _linear_chain(dag)
            factory = FUSED_KERNELS.get(chain) if chain else None
            if factory is not None:
                fused = factory(params)
        self.deployments[dag.uid] = _Deployment(
            dag, params, fused, self._composed_program(dag),
            device_params={d: _commit(params, d)
                           for d in self.devices or ()},
            n_rules=_n_rules(dag, params))

    def inject(self, tenant: str, dag_uid: int, state: dict | None = None,
               **fields) -> None:
        """Queue one packet batch on the tenant's fair-scheduler queue.
        ``state`` (or keyword fields) holds the batch arrays, e.g.
        ``headers=(N, 5) u32, payload=(N, 16) u32``."""
        if dag_uid not in self.deployments:
            raise KeyError(f"DAG {dag_uid} not deployed")
        if tenant not in self.sched.queues:
            raise DagError(
                f"tenant {tenant!r} is not registered; call "
                "Platform.tenant(name, weight=...) (or add_tenant) before "
                "injecting — its weight decides its fair share")
        dep = self.deployments[dag_uid]
        if dep.dag.tenant != tenant:
            raise DagError(
                f"DAG {dag_uid} belongs to tenant {dep.dag.tenant!r}, not "
                f"{tenant!r}")
        batch = dict(state or {})
        batch.update(fields)
        if self.faults is not None:
            verdict = self.faults.gate_inject(tenant, dep.dag.all_nts())
            if verdict == "drop":
                return          # wire loss before the runtime; counted
            if verdict == "corrupt":
                batch = _corrupt_batch(batch, self.faults.rng)
        n = _rows(batch)
        for stage in dep.dag.stages:      # synthesize per-packet state (ctr)
            for branch in stage:
                for name in branch:
                    nt = self.nts.get(name)
                    if nt is None or nt.prep is None:
                        continue
                    if nt.stream is not None and \
                            dep.params.get(name, {}).get("stream"):
                        continue          # stream mode: assigned at dispatch
                    if nt.prep_fields and all(f in batch
                                              for f in nt.prep_fields):
                        continue          # caller supplied them all
                    for k, v in nt.prep(
                            n, dep.params.get(name, {})).items():
                        batch.setdefault(k, v)
        wire = sum(v.size * v.dtype.itemsize for k, v in batch.items()
                   if k in WIRE_FIELDS and hasattr(v, "dtype"))
        self._order += 1
        self.sched.submit(tenant, (self._order, dag_uid, batch),
                          cost=float(wire) if wire else float(max(n, 1)))
        self.stats["batches"] += 1

    def _stream_fields(self, dep: _Deployment, batch: dict) -> dict:
        """Dispatch-time synthesis for stream-mode NTs: advance the
        per-deployment running state and return the per-packet fields for
        this batch.  WDRR preserves per-tenant FIFO and a deployment
        belongs to one tenant, so dispatch order == inject order per
        stream."""
        out: dict = {}
        n = _rows(batch)
        for stage in dep.dag.stages:
            for branch in stage:
                for name in branch:
                    nt = self.nts.get(name)
                    if nt is None or nt.stream is None:
                        continue
                    p = dep.params.get(name, {})
                    if not p.get("stream"):
                        continue
                    if nt.prep_fields and all(f in batch
                                              for f in nt.prep_fields):
                        continue          # caller supplied them all
                    fields, dep.nt_state[name] = nt.stream(
                        n, p, dep.nt_state.get(name, {}))
                    out.update(fields)
        return out

    # ------------------------------------------------- failover state I/O --
    def export_state(self, dag_uid: int) -> dict | None:
        """Snapshot one deployment's stream state (plain scalars) for the
        coordinator's checkpoint; None when the deployment is stateless."""
        dep = self.deployments.get(dag_uid)
        if dep is None or not dep.nt_state:
            return None
        return {nt: dict(st) for nt, st in dep.nt_state.items()}

    def import_state(self, dag_uid: int, state: dict) -> None:
        """Restore stream state on a failover target so the recovered
        deployment resumes bit-exact.  Values may arrive as 0-d numpy
        arrays from a checkpoint restore; coerce back to plain ints."""
        def _scalar(v):
            try:
                return int(v)
            except (TypeError, ValueError):
                return v
        dep = self.deployments[dag_uid]
        dep.nt_state = {nt: {k: _scalar(v) for k, v in st.items()}
                        for nt, st in state.items()}

    def reset_window(self, keep_results: bool = False) -> None:
        """Start a fresh measurement window (the compute analogue of
        ``SimBackend.settle()``): clears the dispatch log and the latency
        monitors, and — unless ``keep_results`` — the accumulated
        per-deployment outputs together with the throughput window, so
        ``report()`` spans only subsequent ``run()`` calls (e.g. after a
        warmup pass that populated the jit caches).  With ``keep_results``
        the elapsed window is kept too: Gbps is bytes-over-window, and the
        two must cover the same runs."""
        self.dispatch_log.clear()
        self._lat_s.clear()
        if not keep_results:
            self._elapsed_s = 0.0
            for dep in self.deployments.values():
                dep.results.clear()

    # ---------------------------------------------------------------- run --
    def _next_device(self):
        """Round-robin device pin for the next dispatch group (None when the
        backend inherits the process default device)."""
        if self.devices is None:
            return None
        dev = self.devices[self._rr % len(self.devices)]
        self._rr += 1
        return dev

    def _fair_groups(self, entries: Iterable,
                     ) -> tuple[list, dict[int, tuple[str, float]]]:
        """Turn a fair service order into dispatch groups, coalescing
        *consecutive* same-DAG same-signature entries.  Stream-mode NT
        fields (the ChaCha ``ctr``) are assigned HERE — when the item
        enters the dispatch pipeline, in deterministic fair order — so
        multi-device round-robin and out-of-order drains can never change
        a packet's keystream counter."""
        groups: list[tuple[tuple, list]] = []
        enq_at: dict[int, tuple[str, float]] = {}
        for tenant, item in entries:
            order, dag_uid, batch = item.payload
            sf = self._stream_fields(self.deployments[dag_uid], batch)
            if sf:
                batch = {**batch, **sf}
            self.dispatch_log.append((tenant, item.cost))
            enq_at[order] = (tenant, item.enqueued_at)
            key = (dag_uid, _signature(batch))
            if not groups or groups[-1][0] != key:
                groups.append((key, []))
            groups[-1][1].append((order, batch))
        return groups, enq_at

    def _launch(self, dep: _Deployment, batches: list[dict], bucket: int,
                state: dict, dev) -> dict:
        """Common tail of both dispatch paths: the program call, on ``dev``
        (where ``state`` is already committed) or the default device."""
        n = sum(_rows(b) for b in batches)
        params = dep.device_params[dev] if dev is not None else dep.params
        path = ("fused" if dep.fused is not None
                and "allow" not in batches[0] else "composed")
        h2d = sum(v.nbytes for v in state.values()
                  if getattr(v, "ndim", 0) >= 1)   # the staged rows
        pairs = n * dep.n_rules                    # the firewall's scan
        with obs.span(obs.LAUNCH, self.span_s, rows=n, bucket=bucket,
                      h2d_bytes=h2d, rule_pairs=pairs):
            out = self._get_program(dep, bucket, path)(state, params)
        self.stats["dispatches"] += 1
        self.stats["rows_launched"] += bucket
        self.stats["pad_rows"] += bucket - n
        self.stats["h2d_bytes"] += h2d
        self.stats["rule_pairs"] += pairs
        if path == "fused":
            self.stats["fused_dispatches"] += 1
        return out

    def run(self, stream: bool | None = None, **_kw) -> None:
        """Service the tenant queues.  Batch mode (the default): drain in
        WDRR order, dispatch every batch asynchronously, synchronize with
        the device ONCE.  Stream mode (``stream=True``, or a backend built
        with ``stream=True``): the same fair order flows through the
        pipelined dispatch ring with a bounded in-flight window instead of
        a single end-of-run sync."""
        if stream is None:
            stream = self.stream
        if self.faults is not None and not self.faults.serving():
            return          # crashed/hung: queues keep their pending work
        if stream:
            self._run_stream()
            return
        t0 = time.perf_counter()
        # fair service order: the whole pending set, interleaved by weight
        with obs.span(obs.SCHED_ORDER, self.span_s):
            groups, enq_at = self._fair_groups(self.sched.drain())

        launched = []
        for (dag_uid, _sig), entries in groups:
            dep = self.deployments[dag_uid]
            orders = [order for order, _ in entries]
            batches = [batch for _, batch in entries]
            sizes = [_rows(b) for b in batches]
            n = sum(sizes)
            bucket = bucket_size(n)
            if len(batches) > 1:
                self.stats["coalesced_batches"] += len(batches)
            dev = self._next_device()
            with obs.span(obs.STAGE, self.span_s):
                staging = _staging_buffers(bucket,
                                           _array_fields(batches[0]))
                _fill_staging(batches, sizes, staging)
                state = _ship(batches[0], staging, bucket, dev)
            out = self._launch(dep, batches, bucket, state, dev)
            launched.append((dep, orders, sizes, out))

        with obs.span(obs.SYNC, self.span_s):
            jax.block_until_ready([o for *_, o in launched])  # the ONE sync
        t_done = time.perf_counter()
        self._elapsed_s += t_done - t0
        self.stats["runs"] += 1
        for tenant, t_enq in enq_at.values():   # inject -> sync completion
            self._lat_s.setdefault(tenant, []).append(t_done - t_enq)

        with obs.span(obs.SPLIT, self.span_s):
            split = []            # un-coalesce, drop pad rows
            for dep, orders, sizes, out in launched:
                off = 0
                for order, s in zip(orders, sizes):
                    split.append((order, dep, _slice_result(out, off, s)))
                    off += s
            for _, dep, res in sorted(split, key=lambda t: t[0]):
                dep.results.append(res)   # results stay in inject order
        self.completed_batches += len(enq_at)
        if _sanitize.enabled():           # end-of-drain conservation audit
            _sanitize.check_compute(self, self.name)

    # ---------------------------------------------------- streaming engine --
    def _stage_group(self, dep: _Deployment, orders: list[int],
                     batches: list[dict],
                     enq: list[tuple[str, float]]) -> _InFlight:
        """Fill one ring slot with a dispatch group and launch it: the
        staging write is host-side (reused numpy buffers — zero steady-state
        allocations), the ``device_put`` of the filled slot is the async
        host->device transfer that overlaps the previous group's kernel,
        and the jitted program donates the transferred buffers."""
        sizes = [_rows(b) for b in batches]
        n = sum(sizes)
        bucket = bucket_size(n)
        if len(batches) > 1:
            self.stats["coalesced_batches"] += len(batches)
        with obs.span(obs.STAGE, self.span_s):
            ring_slot = self.ring.acquire(bucket, _array_fields(batches[0]))
            _fill_staging(batches, sizes, ring_slot.staging)
            if self._t_first is None:
                self._t_first = time.perf_counter()   # window opens
            dev = self._next_device()
            state = _ship(batches[0], ring_slot.staging, bucket, dev)
        out = self._launch(dep, batches, bucket, state, dev)
        self.inflight_batches += len(orders)
        self.stats["stream_batches"] += len(orders)
        return _InFlight(dep, orders, sizes, out, ring_slot, enq)

    def _retire(self, slot_entry: _InFlight) -> None:
        """Drain one ring entry: the ONLY per-slot sync, taken when the
        bounded in-flight window wraps (or at the final flush)."""
        with obs.span(obs.SYNC, self.span_s):
            jax.block_until_ready(slot_entry.out)
        t_done = time.perf_counter()
        self._t_last = t_done
        with obs.span(obs.SPLIT, self.span_s):
            off = 0
            for order, s in zip(slot_entry.orders, slot_entry.sizes):
                # per-tenant FIFO + per-dep single tenant => retire order
                # is inject order for every deployment
                slot_entry.dep.results.append(
                    _slice_result(slot_entry.out, off, s))
                off += s
        for tenant, t_enq in slot_entry.enq:      # inject -> slot drain
            self._lat_s.setdefault(tenant, []).append(t_done - t_enq)
        if slot_entry.slot is not None:
            self.ring.release(slot_entry.slot)
        self.completed_batches += len(slot_entry.orders)
        self.inflight_batches -= len(slot_entry.orders)

    def _stream_feed(self, entries: Iterable) -> int:
        """Push one fair service window through the dispatch ring: launch
        each group, retiring the oldest in-flight entry whenever the
        window exceeds ``max_inflight`` — launches and drains interleave,
        so transfer and compute overlap across groups."""
        with obs.span(obs.SCHED_ORDER, self.span_s):
            groups, enq_at = self._fair_groups(entries)
        for (dag_uid, _sig), group in groups:
            dep = self.deployments[dag_uid]
            orders = [order for order, _ in group]
            batches = [batch for _, batch in group]
            slot_entry = self._stage_group(
                dep, orders, batches, [enq_at[o] for o in orders])
            self._inflight.append(slot_entry)
            while len(self._inflight) > self.max_inflight:  # ring wrap
                self._retire(self._inflight.popleft())
        return len(enq_at)

    def _stream_flush(self) -> None:
        """Drain every in-flight ring entry and close the streaming
        throughput window (first-dispatch -> last-drain)."""
        while self._inflight:
            self._retire(self._inflight.popleft())
        if self._t_first is not None:
            self._elapsed_s += self._t_last - self._t_first
            self._t_first = None

    def _run_stream(self) -> None:
        """One streaming run: the current backlog, pipelined."""
        self._stream_feed(self.sched.drain())
        self._stream_flush()
        self.stats["runs"] += 1
        if _sanitize.enabled():
            _sanitize.check_compute(self, self.name)

    def inject_stream(self, source: Iterable | Iterator, *,
                      epoch_cost: float | None = None,
                      epoch_batches: int | None = None) -> int:
        """Continuous-inject streaming: service a live inject ``source``
        epoch-by-epoch instead of draining a static backlog.

        ``source`` yields ``(tenant, dag_uid, state_dict)`` triples.  Each
        epoch ingests up to ``epoch_batches`` (default: the ring depth)
        fresh injects, asks the scheduler for one stream-credit window
        (:meth:`FairScheduler.stream_window` — WDRR order, at most
        ``epoch_cost`` wire bytes; ``None`` = the whole backlog), and feeds
        the granted work through the dispatch ring.  In-flight entries
        carry across epochs; the final flush drains them and closes the
        throughput window.  Returns the number of batches serviced."""
        per_epoch = self.ring_depth if epoch_batches is None \
            else max(1, int(epoch_batches))
        it = iter(source)
        exhausted = False
        served = 0
        while not exhausted or self.sched.pending():
            if self.faults is not None and not self.faults.gate_stream():
                break       # mid-stream fault: backlog stays queued/journaled
            for _ in range(per_epoch):
                try:
                    tenant, dag_uid, st = next(it)
                except StopIteration:
                    exhausted = True
                    break
                self.inject(tenant, dag_uid, state=st)
            served += self._stream_feed(self.sched.stream_window(epoch_cost))
            self.stats["stream_epochs"] += 1
        self._stream_flush()
        self.stats["runs"] += 1
        if _sanitize.enabled():
            _sanitize.check_compute(self, self.name)
        return served

    # ------------------------------------------------------------- report --
    def report(self) -> PlatformReport:
        rep = PlatformReport(backend=self.name,
                             duration_ns=self._elapsed_s * 1e9)
        rep.extra["compiles"] = self.stats["traces"]
        rep.extra.update(self.stats)
        rep.extra["ring"] = self.ring.stats()
        rep.extra["ring"]["max_inflight"] = self.max_inflight
        rep.extra["inflight_batches"] = self.inflight_batches
        sched_mon = self.sched.snapshot()
        for dep in self.deployments.values():
            tenant = dep.dag.tenant
            tr = rep.tenants.setdefault(
                tenant, TenantReport(tenant=tenant, backend=self.name))
            for out in dep.results:
                n = _rows(out)
                # throughput counts wire fields only: verdict bits, counters
                # and scratch fields are not packet bytes
                nbytes = sum(
                    v.size * v.dtype.itemsize
                    for k, v in out.items()
                    if k in WIRE_FIELDS and hasattr(v, "dtype"))
                tr.pkts_done += n
                tr.bytes_done += nbytes
                tr.outputs.append(out)
            if self._elapsed_s > 0:
                tr.gbps = tr.bytes_done * 8 / self._elapsed_s / 1e9
        # scheduler-side accounting: weight, fair-served wire bytes, and
        # inject->sync batch latencies
        for tenant, tr in rep.tenants.items():
            mon = sched_mon.get(tenant)
            if mon is not None:
                tr.extra["weight"] = mon["weight"]
                tr.extra["sched_served_bytes"] = mon["served_cost"]
            lats = sorted(self._lat_s.get(tenant, ()))
            if lats:
                tr.mean_latency_us = sum(lats) / len(lats) * 1e6
                tr.p99_latency_us = lats[
                    min(len(lats) - 1, int(0.99 * len(lats)))] * 1e6
        return rep


__all__ = ["BUILTIN_COMPUTE_NTS", "ComputeBackend", "ComputeNT",
           "DispatchRing", "FUSED_KERNELS", "VPC_SPECS", "WIRE_FIELDS",
           "bucket_size", "GBPS"]
