"""A benchmark root at a size a CPU test can hold.

It holds the real loop kinds, metric readers and peak table, and every
configuration that ``BENCHMARK.json`` lists and every traffic mix, with
their sizes cut: fewer tenants, short rule tables, small batches.  Every
key stays, so the harness reads them as it reads the real ones, and a
configuration or a mix is cut here by its keys, never by its name: one
added as files alone runs in the tests with no edit here.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax

from chipbench import harness, spec

#: tenants per configuration at the test size, unless it has more distinct
#: chains or shards than that: every chain and every shard keeps a tenant
TENANTS = 2
#: a four-shard fleet made here from ``vpc8-r1k``: four ``ComputeBackend``
#: shards behind ``ShardedBackend``, tenant i on shard i mod 4, keystream
#: counters running on across a tenant's batches.  The benchmark has no
#: four-chip cell yet; this drives the harness's path for one.
FLEET_SHARDS = 4
#: seconds a run waits after its window for what is pending
DRAIN_LIMIT_S = 10.0
#: batches kept per tenant for the comparison: more than a short window
#: at this size retires, so every batch is checked and a fault that spares
#: some batches (half of a coalesced launch) cannot pass by the draw
CHECK = 64
FLEET = {"name": "fleet.backlog", "config": "fleet", "traffic": "backlog-16k",
         "chips": 4}


def cut_deployment(dep: dict, rules: int) -> dict:
    """``dep`` at the test size: ``rules`` rules a tenant, and
    ``TENANTS`` tenants, or as many as it has distinct chains or shards
    where that is more, each distinct chain kept at least once (the
    commonest takes the rest) and the counts summing to the tenants."""
    chains = sorted(dep["chains"], key=lambda c: -dep["chains"][c])
    n = max(min(TENANTS, dep["tenants"]), len(chains), dep["shards"])
    counts = dict.fromkeys(chains, 1)
    counts[chains[0]] += n - len(chains)
    return {**dep, "tenants": n, "rules_per_tenant": rules,
            "chains": {c: counts[c] for c in dep["chains"]}}


def cut_traffic(tr: dict, batch: int) -> dict:
    """``tr`` at the test size: each size it has cut by ``batch``, a short
    warm-up, a short drain and every retired batch checked."""
    cuts = {"batch_pkts": batch, "warm_steps": 1, "rate_mpps": 0.002,
            "max_batch_pkts": 2 * batch, "train_pkts": batch,
            "pool_pkts": 4 * batch}
    return {**tr, **{k: v for k, v in cuts.items() if k in tr},
            "drain_limit_s": DRAIN_LIMIT_S, "check_per_tenant": CHECK}


def make_root(dst: Path, rules: int = 16, batch: int = 16,
              src: Path = spec.HERE) -> Path:
    """A benchmark root under ``dst`` made from the one at ``src``, whose
    checkout's ``BENCHMARK.json`` lists the configurations to cut."""
    dst, src = Path(dst), Path(src)
    for d in ("loops", "metrics"):
        shutil.copytree(src / d, dst / d)
    shutil.copy(src / "peaks.json", dst / "peaks.json")
    (dst / "configs").mkdir()
    (dst / "traffic").mkdir()
    for c in spec.load_benchmark(src.parent)["configs"]:
        cfg = spec.load_config(c["name"], src)
        cfg["deployment"] = cut_deployment(cfg["deployment"], rules)
        (dst / "configs" / f"{c['name']}.json").write_text(json.dumps(cfg))
    fleet = spec.load_config("vpc8-r1k", src)
    fleet["deployment"].update(
        rules_per_tenant=rules, tenants=FLEET_SHARDS, shards=FLEET_SHARDS,
        stream_counters=True,
        chains={"firewall>>nat>>chacha20": FLEET_SHARDS})
    (dst / "configs" / "fleet.json").write_text(json.dumps(fleet))
    for path in (src / "traffic").glob("*.json"):
        tr = cut_traffic(spec.load_traffic(path.stem, src), batch)
        (dst / "traffic" / path.name).write_text(json.dumps(tr))
    return dst


def run(cell: dict, root: Path, traced: bool, trace_dir: Path,
        seed: int = 2 ** 31 + 7, seconds: float = 0.3,
        bench: dict | None = None, **kw) -> dict:
    """One whole run of ``cell`` of ``bench`` (``BENCHMARK.json`` by
    default) at the test size, the look for a chip skipped; the fleet's
    four shards share the one CPU device."""
    kw.setdefault("backend_kw", {"use_fused": True})
    return harness.run_cell(cell, bench or spec.load_benchmark(), seed,
                            seconds, traced, 0.0,
                            peaks=spec.load_peaks("TPU v5 lite"), root=root,
                            devices=jax.devices()[:1] * 4,
                            trace_dir=trace_dir, **kw)
