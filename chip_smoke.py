#!/usr/bin/env python3
"""Bring-up smoke run of the main path on a TPU, through the ``Platform`` API.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded compute fleet

One chip runs three phases:

  datapath-batch   8 tenants (weights 2:1:...:1) each deploy
                   ``firewall >> nat >> chacha20`` with their own 1024-rule
                   table, key, nonce and NAT IP, and inject 4 batches of
                   64 B packets (3 x 16384 + 12000) on the default
                   ``ComputeBackend``: a warm-up run, ``reset_window()``,
                   then the checked run.
  datapath-stream  the same traffic through the streaming runtime
                   (``ComputeBackend(stream=True)``, the dispatch ring).
  serving          full-width granite-moe-1b-a400m with random weights
                   answers 4 requests of 8 new tokens each.

``--chips 4`` runs only the fleet: 4 ``ComputeBackend(device=i)`` shards
behind a ``ShardedBackend`` carrying 8 stream-mode tenants, once without a
fault and once with one shard crashed mid-run.

Every datapath output must equal ``repro.serving.vpc.vpc_chain`` computed
on the host CPU, every dispatch must take the fused megakernel, and every
shard's outputs must live on its own chip.  A failed check raises and the
script exits non-zero.  Without a TPU it exits 1 before any phase.  Times
printed are smoke timings of one cold process, not benchmark numbers.
The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    sys.exit(f"chip_smoke.py: {ROOT / 'src' / 'repro'} not found; run it "
             "from a checkout of the repository")
sys.path.insert(0, str(ROOT / "src"))
# the bit-exact reference runs on the host CPU backend, so keep it loaded
# next to the TPU wherever the platform list is pinned
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.api import (VPC_SPECS, ComputeBackend, Platform,  # noqa: E402
                       ShardedBackend, nt)
from repro.api.serve_backend import SERVE_SPECS, ServeBackend  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.faults import FaultPlan  # noqa: E402
from repro.serving.vpc import make_packets, make_rules, vpc_chain  # noqa: E402

VPC = nt("firewall") >> nt("nat") >> nt("chacha20")
WEIGHTS = (2.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0, 1.0)
BATCHES = (16384, 16384, 16384, 12000)     # the last one takes the pad path
N_RULES = 1024
SERVE_MODEL = "granite-moe-1b-a400m"
FIELDS = ("allow", "headers", "payload")      # vpc_chain's result order


def say(phase: str, **facts) -> None:
    print(f"[smoke] {phase}: {json.dumps(facts, sort_keys=True)}",
          flush=True)


class Tenant:
    """One tenant's deployment parameters and traffic, made from its
    index: rules, key and nonce on the host, packets as numpy arrays."""

    def __init__(self, t: int, batches=BATCHES, n_rules: int = N_RULES):
        rng = np.random.default_rng(t)
        self.name = f"t{t}"
        self.weight = WEIGHTS[t % len(WEIGHTS)]
        self.rules = tuple(np.asarray(r) for r in make_rules(n_rules, seed=t))
        self.key = rng.integers(0, 2 ** 32, 8, dtype=np.uint32)
        self.nonce = rng.integers(0, 2 ** 32, 3, dtype=np.uint32)
        self.nat_ip = 0x0A000001 + t
        self.packets = [tuple(np.asarray(a) for a in
                              make_packets(n, seed=1000 * t + b))
                        for b, n in enumerate(batches)]

    def params(self, stream: bool = False) -> dict:
        chacha = {"key": jnp.asarray(self.key),
                  "nonce": jnp.asarray(self.nonce)}
        if stream:
            chacha.update(stream=True, counter0=1)
        return {"firewall": {"rules": tuple(jnp.asarray(r)
                                            for r in self.rules)},
                "nat": {"nat_ip": self.nat_ip},
                "chacha20": chacha}

    def reference(self, counter_runs: bool) -> list[tuple]:
        """``vpc_chain`` per batch on the host CPU.  ``counter_runs``: the
        keystream counter runs on across batches (stream-mode ChaCha)
        instead of restarting at 1 in each batch."""
        out, c0 = [], 1
        with jax.default_device(jax.devices("cpu")[0]):
            rules = tuple(jnp.asarray(r) for r in self.rules)
            for h, p in self.packets:
                res = vpc_chain(jnp.asarray(h), jnp.asarray(p), rules,
                                jnp.asarray(self.key),
                                jnp.asarray(self.nonce), nat_ip=self.nat_ip,
                                counter0=c0 if counter_runs else 1)
                out.append(tuple(np.asarray(x) for x in res))
                c0 += len(h)
        return out


def check_outputs(label: str, outputs: dict, refs: dict) -> None:
    """Every tenant's outputs, in inject order, equal its reference."""
    for name, ref in refs.items():
        got = outputs[name]
        if len(got) != len(ref):
            raise RuntimeError(f"{label}: tenant {name} has {len(got)} "
                               f"outputs, expected {len(ref)}")
        for b, (out, want) in enumerate(zip(got, ref)):
            for field, w in zip(FIELDS, want):
                np.testing.assert_array_equal(
                    np.asarray(out[field]), w,
                    err_msg=f"{label}: tenant {name} batch {b} {field}")


def as_reference(outputs: dict) -> dict:
    """One checked run's outputs in the reference's form, to compare the
    next run against."""
    return {name: [tuple(np.asarray(o[f]) for f in FIELDS) for o in outs]
            for name, outs in outputs.items()}


def check_fused(label: str, stats: dict) -> None:
    if not 0 < stats["fused_dispatches"] == stats["dispatches"]:
        raise RuntimeError(
            f"{label}: {stats['fused_dispatches']} of {stats['dispatches']} "
            "dispatches took the fused megakernel")


def outputs_of(plat: Platform) -> dict:
    return {name: rep.outputs for name, rep in plat.report().tenants.items()}


# ------------------------------------------------------------- one chip --
def datapath_phase(tenants: list[Tenant], refs: dict, stream: bool) -> dict:
    label = "datapath-stream" if stream else "datapath-batch"
    backend = ComputeBackend(stream=stream)
    plat = Platform(backend, specs=VPC_SPECS)
    deps = [plat.tenant(t.name, weight=t.weight).deploy(VPC,
                                                        params=t.params())
            for t in tenants]

    def inject_all():
        for t, dep in zip(tenants, deps):
            for h, p in t.packets:
                dep.inject(headers=h, payload=p)

    t0 = time.perf_counter()
    inject_all()
    plat.run()
    warm_s = time.perf_counter() - t0
    compiles = backend.stats["traces"]
    backend.reset_window()
    t0 = time.perf_counter()
    inject_all()
    plat.run()
    run_s = time.perf_counter() - t0
    outputs = outputs_of(plat)
    check_fused(label, backend.stats)
    if backend.stats["traces"] != compiles:
        raise RuntimeError(f"{label}: compiled inside the checked run")
    check_outputs(label, outputs, refs)
    say(label, warmup_with_compile_s=warm_s, run_s=run_s,
        packets=sum(len(h) for t in tenants for h, _ in t.packets),
        bit_exact_tenants=len(refs), stats=backend.stats)
    return outputs


def serving_phase(model: str = SERVE_MODEL, n_requests: int = 4,
                  max_new: int = 8, prompt_len: int = 16) -> None:
    cfg = configs.get_config(model)
    t0 = time.perf_counter()
    backend = ServeBackend(cfg, seed=0)
    jax.block_until_ready(backend.engine.params)
    init_s = time.perf_counter() - t0
    plat = Platform(backend, specs=SERVE_SPECS)
    chain = nt("cache") >> nt("prefill") >> nt("decode")
    deps = [plat.tenant(f"s{i}", weight=w).deploy(chain)
            for i, w in enumerate((2.0, 1.0))]
    rng = np.random.default_rng(0)
    for i in range(n_requests):
        prompt = rng.integers(0, cfg.vocab_size, prompt_len, dtype=np.int32)
        deps[i % len(deps)].inject(prompt, max_new=max_new)
    t0 = time.perf_counter()
    plat.run()
    run_s = time.perf_counter() - t0
    reqs = [r for rep in plat.report().tenants.values() for r in rep.outputs]
    if len(reqs) != n_requests:
        raise RuntimeError(f"serving: {len(reqs)} of {n_requests} answered")
    for r in reqs:
        if len(r.out) != max_new or not all(0 <= tok < cfg.vocab_size
                                            for tok in r.out):
            raise RuntimeError(f"serving: request {r.rid} returned {r.out}")
    n_params = sum(x.size for x in jax.tree.leaves(backend.engine.params))
    say("serving", model=model, params=n_params, init_s=init_s,
        run_with_compile_s=run_s, requests=len(reqs),
        tokens=[r.out for r in reqs],
        compiles=[(k, bs) for k, bs, _ in backend.engine.compile_log])


# ----------------------------------------------------------- four chips --
def fleet_run(tenants: list[Tenant], devices: list, plan: FaultPlan | None,
              ckpt: str | None) -> tuple[dict, ShardedBackend]:
    shards = [ComputeBackend(name=f"c{i}", device=d)
              for i, d in enumerate(devices)]
    sb = ShardedBackend(shards, auto_rebalance=False, fault_plan=plan,
                        health_threshold=1, checkpoint=ckpt)
    plat = Platform(sb, specs=VPC_SPECS)
    deps = [plat.tenant(t.name, weight=t.weight).deploy(
                VPC, shard=i % len(shards), params=t.params(stream=True))
            for i, t in enumerate(tenants)]
    for b in range(len(tenants[0].packets)):       # one epoch per batch
        for t, dep in zip(tenants, deps):
            h, p = t.packets[b]
            dep.inject(headers=h, payload=p)
        sb.run()
    for i, shard in enumerate(shards):
        check_fused(f"fleet shard c{i}", shard.stats)
        for dep in shard.deployments.values():
            for res in dep.results:
                if res["payload"].devices() != {devices[i]}:
                    raise RuntimeError(
                        f"fleet: shard c{i} output on "
                        f"{res['payload'].devices()}, not {devices[i]}")
    return outputs_of(plat), sb


def fleet_phase(devices: list, batches=BATCHES,
                n_rules: int = N_RULES) -> None:
    tenants = [Tenant(t, batches, n_rules) for t in range(8)]
    refs = {t.name: t.reference(counter_runs=True) for t in tenants}
    t0 = time.perf_counter()
    clean, _ = fleet_run(tenants, devices, None, None)
    clean_s = time.perf_counter() - t0
    check_outputs("fleet", clean, refs)
    say("fleet", devices=[str(d) for d in devices], run_with_compile_s=clean_s,
        bit_exact_tenants=len(refs),
        outputs_on_own_device=True)
    with tempfile.TemporaryDirectory(prefix=".chip_smoke_ckpt_",
                                     dir=ROOT) as ckpt:
        plan = FaultPlan(seed=3).crash(shard=1, epoch=2)
        t0 = time.perf_counter()
        crashed, sb = fleet_run(tenants, devices, plan, ckpt)
        crash_s = time.perf_counter() - t0
    (fo,) = sb.failovers
    if fo["shard"] != "c1" or fo["lost"] or not fo["moved"]:
        raise RuntimeError(f"fleet-crash: unexpected failover {fo}")
    check_outputs("fleet-crash vs crash-free", crashed, as_reference(clean))
    check_outputs("fleet-crash vs reference", crashed, refs)
    say("fleet-crash", run_with_compile_s=crash_s, failover=fo,
        replayed=sb.replayed, lost=sb.lost, bit_exact_tenants=len(refs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: datapath and serving; 4: the sharded fleet")
    args = ap.parse_args(argv)
    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py: no TPU (JAX found {devices[0].platform}); "
              "this smoke run needs the chip", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    say("device", platform=devices[0].platform,
        kind=devices[0].device_kind, count=len(devices))
    if args.chips == 4:
        fleet_phase(devices[:4])
    else:
        tenants = [Tenant(t) for t in range(len(WEIGHTS))]
        refs = {t.name: t.reference(counter_runs=False) for t in tenants}
        batch = datapath_phase(tenants, refs, stream=False)
        stream = datapath_phase(tenants, refs, stream=True)
        check_outputs("datapath-stream vs batch", stream,
                      as_reference(batch))
        del batch, stream
        serving_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
