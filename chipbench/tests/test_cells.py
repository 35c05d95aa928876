"""Every cell of ``BENCHMARK.json``, run whole on the CPU at a small size
(``tiny.py``), through ``Platform`` with the megakernel forced on in
interpret mode: every loop kind, the stamping of reused packets, the
retire path with batches carried over, the comparison with the reference
and every end-to-end metric reader.  Times from here are not
speed."""
from __future__ import annotations

import json
import shutil

import numpy as np
import pytest

from chipbench import harness, spec
from chipbench.cell import (STAMP_HEADER_WORD, STAMP_PAYLOAD_WORD, Bench,
                            make_packets, seed_rng, stamp)
from chipbench.tests import tiny

BENCH = spec.load_benchmark()
CELLS = BENCH["workloads"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_cell_runs_correct_with_its_end_to_end_metrics(cell, root,
                                                       tmp_path):
    out = tiny.run(cell, root, False, tmp_path / "trace")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in spec.metrics_for(BENCH, cell["name"],
                                                "end_to_end")}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["checks"]["checked_pkts"]["value"] > 0
    dev = out["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)


def test_a_configuration_added_as_files_alone_runs_whole(tmp_path):
    """A checkout whose benchmark gains one configuration file, named in no
    table here, and its ``configs`` and ``workloads`` entries: the test
    root cuts it by its keys and the cell runs whole and correct."""
    src = tmp_path / "checkout" / "chipbench"
    for d in ("configs", "traffic", "loops", "metrics"):
        shutil.copytree(spec.HERE / d, src / d)
    shutil.copy(spec.HERE / "peaks.json", src / "peaks.json")
    cfg = spec.load_config("vpc8-r1k")
    cfg["deployment"].update(
        tenants=5, rules_per_tenant=10000, weights={"pattern": [3, 1]},
        chains={"firewall>>nat>>chacha20": 3, "firewall>>nat": 2})
    (src / "configs" / "novel5.json").write_text(json.dumps(cfg))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "novel5", "source": "a test",
                             "file": "chipbench/configs/novel5.json",
                             "reduced": [], "why": "a test"})
    cell = {"name": "novel5.backlog", "config": "novel5",
            "traffic": "backlog-16k", "chips": 1, "why": "a test"}
    bench["workloads"].append(cell)
    (src.parent / "BENCHMARK.json").write_text(json.dumps(bench))

    root = tiny.make_root(tmp_path / "tiny", src=src)
    cut = spec.load_config("novel5", root)
    dep = cut.pop("deployment")
    assert cut == {k: v for k, v in cfg.items() if k != "deployment"}
    assert set(dep) == set(cfg["deployment"])
    assert {k for k in dep if dep[k] != cfg["deployment"][k]} == \
        {"tenants", "rules_per_tenant", "chains"}
    assert (dep["tenants"], dep["rules_per_tenant"], dep["shards"]) == \
        (2, 16, 1)
    assert dep["chains"] == {"firewall>>nat>>chacha20": 1,
                             "firewall>>nat": 1}
    out = tiny.run(cell, root, False, tmp_path / "trace",
                   bench=spec.load_benchmark(src.parent))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in spec.metrics_for(
        bench, cell["name"], "end_to_end")}


@pytest.mark.parametrize("name", ["vpc8-r1k.backlog", "vpc8-r1k.stream"])
def test_the_traffic_names_the_runtime(name, root, tmp_path, monkeypatch):
    """A mix's ``runtime`` builds ``ComputeBackend(stream=...)``: the
    streaming engine fills its dispatch ring, and a mix without the key
    builds the batch engine, which never touches the ring."""
    benches = []

    class Recorded(harness.Bench):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            benches.append(self)
    monkeypatch.setattr(harness, "Bench", Recorded)
    cell = spec.find_cell(BENCH, name)
    stream = spec.load_traffic(cell["traffic"]).get("runtime") == "stream"
    out = tiny.run(cell, root, False, tmp_path / "trace")
    assert out["correct"], out["checks"]
    (compute,) = benches[0].computes
    assert compute.stream is stream
    assert (compute.stats["stream_batches"] > 0) is stream
    assert (compute.ring.allocs > 0) is stream
    if stream:
        assert compute.ring.reuses > 0


def test_an_unknown_runtime_is_an_error(root):
    config = spec.load_config("vpc8-r1k", root)
    with pytest.raises(ValueError, match="runtime"):
        Bench(config, 1, runtime="pipelined")


def test_fleet_cell_runs_correct_over_four_shards(root, tmp_path):
    """A four-shard fleet: four ``ComputeBackend`` shards behind
    ``ShardedBackend``, counters running on across batches."""
    out = tiny.run(tiny.FLEET, root, False, tmp_path / "trace")
    assert out["correct"], out["checks"]
    assert out["metrics"]["mpps"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS, ids=lambda c: c["name"])
def test_no_packet_repeats_in_a_run(cell, root, tmp_path, monkeypatch):
    """The loops reuse pools of packets; the stamp makes every packet a
    tenant sends in a run, warm-up included, differ from every other."""
    sent = {}
    real = Bench.inject

    def inject(self, t, headers, payload, base=None):
        b = real(self, t, headers, payload, base=base)
        sent.setdefault(t, []).append(np.hstack([b.headers, b.payload]))
        return b
    monkeypatch.setattr(Bench, "inject", inject)
    out = tiny.run(cell, root, False, tmp_path / "trace")
    assert out["correct"], out["checks"]
    for rows in sent.values():
        rows = np.concatenate(rows)
        assert len(rows) > 4 * 16          # the pools wrapped
        assert len(np.unique(rows, axis=0)) == len(rows)


def test_batches_left_queued_by_run_are_carried_over(root, tmp_path,
                                                     monkeypatch):
    """A runtime that serves nothing in every other ``run()`` (as a
    scheduler that leaves backlog queued would) is not at fault: its
    outputs are matched at a later retire, or in the drain after the
    window.  (The closed loop tops up only what was served, so the batches
    a ``run()`` coalesces keep the warmed sizes.)"""
    from repro.api.compute_backend import ComputeBackend
    real = ComputeBackend.run
    calls = [0]

    def every_other(self, *a, **kw):
        calls[0] += 1
        if calls[0] % 2 == 0:
            real(self, *a, **kw)
    monkeypatch.setattr(ComputeBackend, "run", every_other)
    out = tiny.run(CELLS[0], root, False, tmp_path / "trace")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["checks"]["bad_batches"]["value"] == 0


def test_poisson_trains_arrive_at_the_offered_rate_from_the_seed(root):
    cell = spec.find_cell(BENCH, "vpc8-r1k.poisson80")
    traffic = spec.load_traffic(cell["traffic"], root)
    config = spec.load_config(cell["config"], root)
    arrivals = []
    for seed in (2 ** 31 + 5, 2 ** 31 + 5, 6):
        loop = spec.load_loop("poisson", root).Loop(Bench(config, seed),
                                                    traffic)
        loop.prepare(200.0)
        arrivals.append(loop.arrivals)
    pkts = sum(len(a) for a in arrivals[0]) * traffic["train_pkts"]
    assert pkts / 200.0 == pytest.approx(traffic["rate_mpps"] * 1e6,
                                         rel=0.03)
    assert all((a == b).all() for a, b in zip(*arrivals[:2]))
    assert not all(len(a) == len(b) and (a == b).all()
                   for a, b in zip(arrivals[0], arrivals[2]))


def test_stamp_is_one_to_one_and_undone_by_a_second_stamp():
    base = make_packets(seed_rng(3, 0), 64)
    work = tuple(a.copy() for a in base)
    stamp(base, work, 0)
    again = tuple(a.copy() for a in base)
    stamp(base, again, 64)
    for b, w, a, col in zip(base, work, again,
                            (STAMP_HEADER_WORD, STAMP_PAYLOAD_WORD)):
        rest = np.arange(b.shape[1]) != col
        assert (w[:, rest] == b[:, rest]).all()
        assert (w[:, col] != a[:, col]).all()
        assert len(np.unique(w[:, col] ^ b[:, col])) == len(b)
    back = tuple(a.copy() for a in work)
    stamp(work, back, 0)
    assert all((x == y).all() for x, y in zip(back, base))


def test_host_notes_name_the_slowest_steps(root, tmp_path):
    out = tiny.run(CELLS[0], root, False, tmp_path / "trace")
    host = out["notes"]["host"]
    assert host["cpu_s"] > 0 and len(host["gc_pauses"]) == 3
    assert 1 <= len(host["slowest_steps"]) <= 3
    assert all(s["ms"] > 0 and s["gc_ms"] >= 0
               for s in host["slowest_steps"])
