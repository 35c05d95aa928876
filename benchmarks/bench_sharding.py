"""Sharded-platform scaling benchmark: {1,2,4}-shard fleets on the sim and
compute substrates behind one ``ShardedBackend``.

Per shard count and substrate:

  - **aggregate Gbps** — fleet throughput from the merged report (the sim
    rows should scale ~linearly with shard count: each shard is one 100G
    sNIC);
  - **per-shard Jain index** — Jain's fairness index over weight-normalized
    served bytes *within each shard* (1.0 = every shard split itself
    exactly by the tenant weights);
  - **global share error** — worst-case deviation of fleet-wide
    weight-normalized shares from their mean (the cross-shard epoch's
    convergence metric);
  - **consolidation savings** — sum of per-tenant offered peaks vs what the
    fleet actually provisions (sum of per-shard peak-of-aggregate), from
    the placer's arrival histories (§2 Figs 2-3 economics, measured not
    assumed).

The sim workload is the acceptance scenario: 4 tenants, weights 2:2:1:1,
each with a saturating base flood plus a phase-shifted on/off burst — so
every tenant always contends (weighted shares must converge globally)
while the offered-load *shapes* anti-correlate (the consolidation signal).
The compute workload drains 4 tenants' batch backlogs across the fleet
with WDRR inside every shard.

Writes ``BENCH_sharding.json`` at the repo root (alongside the compute and
fairness trajectory files) and returns a flat summary for
``benchmarks.run``.  The acceptance block asserts the ISSUE-4 bar: on the
2-shard sim fleet, global weighted shares within 5% and savings > 1.1x.

CLI:  PYTHONPATH=src python -m benchmarks.bench_sharding [--smoke|--full]
                                                         [--out PATH]
Exit codes: 0 ok, 1 schema/acceptance failure, 2 bad usage.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

from benchmarks.bench_fairness import jain

REPO_ROOT = Path(__file__).resolve().parents[1]
DEFAULT_OUT = REPO_ROOT / "BENCH_sharding.json"
WIRE_BYTES_PER_PKT = (5 + 16) * 4           # headers + payload, u32

WEIGHTS = {"t0": 2.0, "t1": 2.0, "t2": 1.0, "t3": 1.0}


def _share_err(bytes_by_tenant: dict[str, float]) -> float:
    """Worst deviation of weight-normalized shares from their mean."""
    shares = [bytes_by_tenant[t] / WEIGHTS[t] for t in WEIGHTS]
    mean = sum(shares) / len(shares)
    if mean <= 0:
        return 1.0
    return max(abs(s / mean - 1.0) for s in shares)


def _per_shard_jain(rep) -> dict[str, float]:
    """Jain over weight-normalized served bytes of each shard's tenants —
    zero shares INCLUDED: a shard starving a resident tenant must read as
    unfair, not be filtered into perfection."""
    out = {}
    for name, srep in rep.shards.items():
        shares = [tr.bytes_done / WEIGHTS.get(t, 1.0)
                  for t, tr in srep.tenants.items() if t in WEIGHTS]
        out[name] = round(jain(shares), 4)
    return out


# ================================================================== sim ====
def _sim_fleet(n_shards: int, dur_ms: float, period_ns: float) -> dict:
    from repro.api import Platform, ShardedBackend, SimBackend, VPC_SPECS, nt
    sb = ShardedBackend([SimBackend(name=f"sim{i}", seed=100 + i)
                         for i in range(n_shards)])
    plat = Platform(sb, specs=VPC_SPECS)
    chain = nt("firewall") >> nt("nat")
    deps = {}
    for t, w in WEIGHTS.items():
        ten = plat.tenant(t, weight=w)
        deps[t] = [ten.deploy(chain, shard=s) for s in range(n_shards)]
    sb.settle()
    for i, (t, ds) in enumerate(deps.items()):
        for j, d in enumerate(ds):
            # saturating base: every tenant contends every instant, so the
            # cross-shard epoch's weighted grants bind fleet-wide ...
            d.source("poisson", rate_gbps=150.0, mean_bytes=1500,
                     seed=100 + 10 * i + j, duration_ms=dur_ms)
            # ... while the offered-load *shape* stays bursty and
            # phase-shifted (the consolidation signal)
            d.source("onoff", peak_gbps=400.0, duty=0.5,
                     period_ns=period_ns, mean_bytes=1500, phase=i / 4.0,
                     seed=10 * i + j, duration_ms=dur_ms)
    plat.run(duration_ms=dur_ms)
    rep = plat.report()
    sav = rep.extra["consolidation"]
    return {
        "substrate": "sim", "n_shards": n_shards,
        "per_tenant": {t: {"gbps": round(rep[t].gbps, 2),
                           "weight": WEIGHTS[t],
                           "p99_us": round(rep[t].p99_latency_us, 1)}
                       for t in WEIGHTS},
        "aggregate_gbps": round(rep.total_gbps, 2),
        "per_shard_jain": _per_shard_jain(rep),
        "global_share_err": round(
            _share_err({t: rep[t].bytes_done for t in WEIGHTS}), 4),
        "consolidation": {
            "sum_of_peaks_gbps": round(sav["sum_of_peaks"], 1),
            "per_shard_peaks_gbps": [round(x, 1)
                                     for x in sav["per_shard_peaks"]],
            "savings": round(sav["savings"], 3),
        },
        "global_epochs": rep.extra["global_epochs"],
        "migrations": len(rep.extra["migrations"]),
    }


# ============================================================== compute ====
def _compute_fleet(n_shards: int, batch: int, batches_per_tenant: int) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.api import ComputeBackend, Platform, ShardedBackend, \
        VPC_SPECS, nt
    from repro.serving.vpc import make_packets, make_rules

    params = {"firewall": {"rules": make_rules(16, seed=2)},
              "nat": {"nat_ip": 0x0A000001},
              "chacha20": {"key": jnp.arange(8, dtype=jnp.uint32) * 3 + 1,
                           "nonce": jnp.arange(3, dtype=jnp.uint32) + 7}}
    sb = ShardedBackend(
        [ComputeBackend(use_fused=False, name=f"c{i}",
                        device=i % len(jax.devices()),
                        quantum_bytes=batch * WIRE_BYTES_PER_PKT)
         for i in range(n_shards)],
        auto_rebalance=False)
    plat = Platform(sb, specs=VPC_SPECS)
    chain = nt("firewall") >> nt("nat") >> nt("chacha20")
    deps = {t: plat.tenant(t, weight=w).deploy(chain, params=params)
            for t, w in WEIGHTS.items()}        # placement spreads tenants
    h, p = make_packets(batch, seed=1)

    def workload():
        for _ in range(batches_per_tenant):
            for d in deps.values():
                d.inject(headers=h, payload=p)
        plat.run()

    workload()                                  # warmup fills the jit caches
    for s in sb.shards:
        s.reset_window()
    workload()
    rep = plat.report()
    # a backlog drain runs to completion, so *totals* are demand-shaped —
    # fairness lives in the service ORDER.  Cut each shard's fair dispatch
    # log at the byte-half (batches_per_tenant is a multiple of 3, so with
    # weights 2:2:1:1 the half lands exactly on a WDRR round boundary) and
    # compare weight-normalized shares inside the prefix.
    shard_jain, worst_err = {}, 0.0
    for i, s in enumerate(sb.shards):
        half = sum(c for _, c in s.dispatch_log) / 2
        served: dict[str, float] = {}
        acc = 0.0
        for t, cost in s.dispatch_log:
            served[t] = served.get(t, 0.0) + cost
            acc += cost
            if acc >= half - 1e-9:
                break
        shares = [served[t] / WEIGHTS.get(t, 1.0) for t in served]
        shard_jain[sb.shard_names[i]] = round(jain(shares), 4)
        if len(shares) > 1:
            mean = sum(shares) / len(shares)
            worst_err = max(worst_err,
                            max(abs(x / mean - 1.0) for x in shares))
    return {
        "substrate": "compute", "n_shards": n_shards,
        "backend": jax.default_backend(),
        "per_tenant": {t: {"gbps": round(rep[t].gbps, 3),
                           "weight": WEIGHTS[t],
                           "pkts": rep[t].pkts_done}
                       for t in WEIGHTS},
        "aggregate_gbps": round(rep.total_gbps, 3),
        "aggregate_pkts": rep.total_pkts,
        "per_shard_jain": shard_jain,
        "global_share_err": round(worst_err, 4),
        "routes": rep.extra["routes"],
        "dispatches": sum(s.stats["dispatches"] for s in sb.shards),
    }


# ================================================================= bench ====
def bench_sharding(smoke: bool | None = None,
                   out_path: Path | str = DEFAULT_OUT) -> dict:
    import jax
    backend = jax.default_backend()
    if smoke is None:
        smoke = backend != "tpu"
    dur_ms = 1.6 if smoke else 3.2
    period_ns = 800_000.0
    batch = 32 if smoke else 1024
    # multiple of 3 so the compute half-cut is WDRR-round aligned
    per_tenant = 12 if smoke else 18

    configs = []
    for n in (1, 2, 4):
        configs.append(_sim_fleet(n, dur_ms, period_ns))
        configs.append(_compute_fleet(n, batch, per_tenant))

    # ISSUE-4 acceptance: the 2-shard sim row IS the 4-tenant bursty
    # workload — global weighted shares within 5%, savings > 1.1x
    two = next(c for c in configs
               if c["substrate"] == "sim" and c["n_shards"] == 2)
    acceptance = {
        "global_share_err": two["global_share_err"],
        "share_err_bound": 0.05,
        "savings": two["consolidation"]["savings"],
        "savings_bound": 1.1,
        "pass": (two["global_share_err"] <= 0.05
                 and two["consolidation"]["savings"] > 1.1),
    }
    res = {
        "bench": "bench_sharding",
        "mode": "smoke" if smoke else "full",
        "backend": backend,
        "weights": WEIGHTS,
        "configs": configs,
        "acceptance": acceptance,
        "note": ("4 tenants (weights 2:2:1:1) per fleet.  Sim rows: base "
                 "flood + phase-shifted on/off bursts; savings = sum of "
                 "per-tenant offered peaks / sum of per-shard "
                 "peak-of-aggregate (measured by the placer).  Compute "
                 "rows: WDRR backlog drain across the fleet; host-clock "
                 "Gbps are only meaningful on TPU — shares, Jain and "
                 "share_err are the binding signal everywhere."),
    }
    Path(out_path).write_text(json.dumps(res, indent=1))
    return res


def check_schema(res: dict) -> list[str]:
    """The contract CI enforces: {1,2,4}-shard coverage on both substrates,
    per-shard Jain sane, and the ISSUE-4 acceptance block passing."""
    errs = []
    for k in ("bench", "mode", "backend", "configs", "acceptance"):
        if k not in res:
            errs.append(f"missing key {k!r}")
    seen = {(c.get("substrate"), c.get("n_shards"))
            for c in res.get("configs", [])}
    for sub in ("sim", "compute"):
        for n in (1, 2, 4):
            if (sub, n) not in seen:
                errs.append(f"missing config {sub}/{n}-shard")
    for c in res.get("configs", []):
        need = {"per_tenant", "aggregate_gbps", "per_shard_jain",
                "global_share_err"}
        if not need <= set(c):
            errs.append(f"malformed config {c.get('substrate')}/"
                        f"{c.get('n_shards')}")
            continue
        if len(c["per_shard_jain"]) != c["n_shards"]:
            errs.append(f"{c['substrate']}/{c['n_shards']}: expected "
                        f"{c['n_shards']} per-shard Jain entries")
        for name, j in c["per_shard_jain"].items():
            if j < 0.85:
                errs.append(f"{c['substrate']}/{c['n_shards']} shard "
                            f"{name}: Jain {j} < 0.85")
        if c["substrate"] == "compute" and c["global_share_err"] > 0.05:
            errs.append(f"compute/{c['n_shards']}: WDRR order share err "
                        f"{c['global_share_err']} > 0.05")
    acc = res.get("acceptance", {})
    if not acc.get("pass"):
        errs.append(f"acceptance failed: share_err="
                    f"{acc.get('global_share_err')} (bound 0.05), savings="
                    f"{acc.get('savings')} (bound 1.1)")
    return errs


def bench_sharding_summary(out_dir: Path | str | None = None) -> dict:
    """Entry for benchmarks.run: flat keys only."""
    res = bench_sharding(out_path=Path(out_dir) / DEFAULT_OUT.name
                         if out_dir else DEFAULT_OUT)
    errs = check_schema(res)
    if errs:
        raise RuntimeError("; ".join(errs))
    flat = {k: v for k, v in res.items() if not isinstance(v, (list, dict))}
    for c in res["configs"]:
        key = f"{c['substrate']}_n{c['n_shards']}"
        flat[f"{key}_gbps"] = c["aggregate_gbps"]
        flat[f"{key}_share_err"] = c["global_share_err"]
        flat[f"{key}_jain_min"] = min(c["per_shard_jain"].values())
        if c["substrate"] == "sim":
            flat[f"{key}_savings"] = c["consolidation"]["savings"]
    flat["acceptance_pass"] = res["acceptance"]["pass"]
    return flat


def main(argv=None) -> int:
    args = list(argv if argv is not None else sys.argv[1:])
    smoke: bool | None = None
    out = DEFAULT_OUT
    while args:
        a = args.pop(0)
        if a == "--smoke":
            smoke = True
        elif a == "--full":
            smoke = False
        elif a == "--out":
            if not args:
                print("--out needs a path")
                return 2
            out = Path(args.pop(0))
        else:
            print(f"unknown flag {a!r}; known: --smoke --full --out PATH")
            return 2
    t0 = time.time()
    res = bench_sharding(smoke=smoke, out_path=out)
    for c in res["configs"]:
        key = f"{c['substrate']}_n{c['n_shards']}"
        print(f"bench_sharding,{key}_gbps,{c['aggregate_gbps']}")
        print(f"bench_sharding,{key}_share_err,{c['global_share_err']}")
        if c["substrate"] == "sim":
            print(f"bench_sharding,{key}_savings,"
                  f"{c['consolidation']['savings']}")
    acc = res["acceptance"]
    print(f"bench_sharding,acceptance_pass,{acc['pass']}")
    print(f"bench_sharding,seconds,{round(time.time() - t0, 1)}")
    print(f"bench_sharding,out,{out}")
    errs = check_schema(res)
    if errs:
        print("FAIL: " + "; ".join(errs))
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
