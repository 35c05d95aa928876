"""Benchmark harness: one entry per paper figure/table.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --all      # same, explicit
  PYTHONPATH=src python -m benchmarks.run fig14 fig15
  PYTHONPATH=src python -m benchmarks.run --list     # names only
  PYTHONPATH=src python -m benchmarks.run bench --out /tmp/artifacts

Prints ``benchmark,key,value`` CSV.  Repo-root ``BENCH_*.json`` files
are the single source of truth for bench snapshots (``--out DIR``
redirects them); ``fig*`` JSON goes to ``experiments/bench/``.  Every
run writes a machine-readable manifest (``bench_manifest.json``: name
-> output path + status) next to the fig output.

Exit codes: 0 ok, 1 benchmark failure(s) or failed acceptance block,
2 unknown benchmark name/flag.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

from benchmarks import figures
from benchmarks.bench_compute import (bench_compute_stream_summary,
                                      bench_compute_summary)
from benchmarks.bench_fairness import bench_fairness_summary
from benchmarks.bench_resilience import bench_resilience_summary
from benchmarks.bench_scenarios import bench_scenarios_summary
from benchmarks.bench_sharding import bench_sharding_summary
from repro.compile_cache import enable_compile_cache

REPO_ROOT = Path(__file__).resolve().parents[1]
FIG_OUT = REPO_ROOT / "experiments" / "bench"

#: snapshot benches: the summary takes ``out_dir`` and the bench writes
#: its own canonical repo-root BENCH_<name>.json (single source of truth)
BENCHES = {
    "bench_compute": bench_compute_summary,
    "bench_compute_stream": bench_compute_stream_summary,
    "bench_fairness": bench_fairness_summary,
    "bench_resilience": bench_resilience_summary,
    "bench_scenarios": bench_scenarios_summary,
    "bench_sharding": bench_sharding_summary,
}
#: figure sweeps: plain ``f() -> dict``, written under experiments/bench/
FIGURES = {
    "fig2_consolidation_disagg": figures.fig2_consolidation_disagg,
    "fig3_consolidation_dc": figures.fig3_consolidation_dc,
    "fig7_resource_budget": figures.fig7_resource_budget,
    "fig8_9_ycsb": figures.fig8_9_ycsb,
    "fig10_replication": figures.fig10_replication,
    "fig11_vpc": figures.fig11_vpc,
    "fig12_13_fb_consolidation": figures.fig12_13_fb_consolidation,
    "fig14_credits": figures.fig14_credits,
    "fig15_chaining": figures.fig15_chaining,
    "fig16_parallelism": figures.fig16_parallelism,
    "fig17_drf_autoscale": figures.fig17_drf_autoscale,
    "sec714_distributed_offload": figures.sec714_distributed_offload,
}
ALL = {**BENCHES, **FIGURES}


def _acceptance_failed(res: dict) -> bool:
    """A summary that carries an acceptance verdict and says 'no'."""
    if res.get("acceptance_pass") is False:
        return True
    acc = res.get("acceptance")
    return isinstance(acc, dict) and acc.get("pass") is False


def main(argv=None) -> int:
    args = list(argv if argv is not None else sys.argv[1:])
    if "--list" in args or "-l" in args:
        for k in ALL:
            print(k)
        return 0
    out_dir: Path | None = None
    names: list[str] = []
    run_all = False
    while args:
        a = args.pop(0)
        if a == "--all":
            run_all = True
        elif a == "--out":
            if not args:
                print("--out needs a directory")
                return 2
            out_dir = Path(args.pop(0))
        elif a.startswith("-"):
            print(f"unknown flag {a!r}; known: --list --all --out DIR")
            return 2
        else:
            names.append(a)
    if run_all and names:
        print("--all takes no benchmark names")
        return 2
    if not names:
        names = list(ALL)

    enable_compile_cache()
    fig_out = out_dir if out_dir is not None else FIG_OUT
    fig_out.mkdir(parents=True, exist_ok=True)
    if out_dir is not None:
        out_dir.mkdir(parents=True, exist_ok=True)

    manifest: dict[str, dict] = {}
    failures = []
    for name in names:
        matches = [k for k in ALL if k.startswith(name)]
        if not matches:
            print(f"unknown benchmark {name!r}; known: {list(ALL)}")
            return 2
        for k in matches:
            if k in BENCHES:
                out_path = ((out_dir if out_dir is not None else REPO_ROOT)
                            / f"BENCH_{k.removeprefix('bench_')}.json")
            else:
                out_path = fig_out / f"{k}.json"
            t0 = time.time()
            try:
                res = ALL[k](out_dir=out_dir) if k in BENCHES else ALL[k]()
            except Exception as e:  # noqa: BLE001
                failures.append((k, repr(e)))
                print(f"{k},ERROR,{e!r}")
                manifest[k] = {"out": str(out_path), "status": "error",
                               "error": repr(e)}
                continue
            dt = time.time() - t0
            res["_seconds"] = round(dt, 1)
            for key, v in res.items():
                print(f"{k},{key},{v}")
            if k in FIGURES:
                out_path.write_text(json.dumps(res, indent=1))
            if _acceptance_failed(res):
                failures.append((k, "acceptance block failed"))
                manifest[k] = {"out": str(out_path),
                               "status": "acceptance_failed",
                               "seconds": round(dt, 1)}
            else:
                manifest[k] = {"out": str(out_path), "status": "ok",
                               "seconds": round(dt, 1)}

    manifest_path = fig_out / "bench_manifest.json"
    manifest_path.write_text(json.dumps(
        {"benches": manifest,
         "pass": not failures}, indent=1, sort_keys=True) + "\n")
    print(f"manifest,{manifest_path}")
    if failures:
        print(f"{len(failures)} benchmark(s) failed: {failures}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
