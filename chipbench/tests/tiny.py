"""A benchmark root at a size a CPU test can hold.

It holds the real loop kinds, metric readers and peak table, and the real
configurations and traffic mixes with their sizes cut: fewer tenants,
short rule tables, small batches.  Every key stays, so the harness reads
them as it reads the real ones.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax

from chipbench import harness, spec

#: tenants per configuration at the test size
TENANTS = {"vpc8-r1k": 2}
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


def make_root(dst: Path, rules: int = 16, batch: int = 16) -> Path:
    dst = Path(dst)
    for d in ("loops", "metrics"):
        shutil.copytree(spec.HERE / d, dst / d)
    shutil.copy(spec.HERE / "peaks.json", dst / "peaks.json")
    (dst / "configs").mkdir()
    (dst / "traffic").mkdir()
    for name, n in TENANTS.items():
        cfg = spec.load_config(name)
        cfg["deployment"].update(rules_per_tenant=rules, tenants=n,
                                 chains={"firewall>>nat>>chacha20": n})
        (dst / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    fleet = spec.load_config("vpc8-r1k")
    fleet["deployment"].update(
        rules_per_tenant=rules, tenants=FLEET_SHARDS, shards=FLEET_SHARDS,
        stream_counters=True,
        chains={"firewall>>nat>>chacha20": FLEET_SHARDS})
    (dst / "configs" / "fleet.json").write_text(json.dumps(fleet))
    for path in (spec.HERE / "traffic").glob("*.json"):
        name = path.stem
        tr = spec.load_traffic(name)
        if tr["loop"] == "backlog":
            tr.update(batch_pkts=batch, warm_steps=1)
        else:
            tr.update(rate_mpps=0.002, max_batch_pkts=2 * batch,
                      train_pkts=batch, pool_pkts=4 * batch)
        tr.update(drain_limit_s=DRAIN_LIMIT_S, check_per_tenant=CHECK)
        (dst / "traffic" / f"{name}.json").write_text(json.dumps(tr))
    return dst


def run(cell: dict, root: Path, traced: bool, trace_dir: Path,
        seed: int = 2 ** 31 + 7, seconds: float = 0.3, **kw) -> dict:
    """One whole run of ``cell`` at the test size, the look for a chip
    skipped; the fleet's four shards share the one CPU device."""
    kw.setdefault("backend_kw", {"use_fused": True})
    return harness.run_cell(cell, spec.load_benchmark(), seed, seconds,
                            traced, 0.0, peaks=spec.load_peaks("TPU v5 lite"),
                            root=root, devices=jax.devices()[:1] * 4,
                            trace_dir=trace_dir, **kw)
