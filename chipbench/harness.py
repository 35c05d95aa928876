"""One run of one cell: set-up, the measured window, the comparison, the
result line.  :func:`run_cell` is everything after the look for a chip, so
the tests can drive a whole run on the CPU at a small size."""
from __future__ import annotations

import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import jax
import numpy as np

from chipbench import spec, trace
from chipbench.cell import Bench, CompileCounter, GcWatch, Window

#: profiler output of a traced run (inside the checkout; ignored by git)
TRACE_DIR = spec.ROOT / ".chipbench" / "trace"


@dataclass
class Record:
    """What a metric reader reads: the window, the cell's counters, the
    trace summary of a traced run, and the device's published peaks."""
    cell: str
    chips: int
    setup_s: float
    window: Window
    window_s: float
    datapath_bytes: float
    dispatches: int
    inject_s: float
    inject_pkts: int
    trace: trace.Summary | None
    peaks: dict


def memory(devices, key: str) -> int:
    vals = [(d.memory_stats() or {}).get(key, 0) for d in devices]
    return int(max(vals, default=0))


def run_cell(cell: dict, bench_spec: dict, seed: int, seconds: float,
             traced: bool, t_start: float, *, peaks: dict,
             root: Path = spec.HERE, devices=None,
             backend_kw: dict | None = None, nts: dict | None = None,
             trace_dir: Path = TRACE_DIR) -> dict:
    """Set up the cell, measure its window, check what the window produced
    against the reference, and return the result line as a dict."""
    t_cell = time.perf_counter()
    config = spec.load_config(cell["config"], root)
    traffic = spec.load_traffic(cell["traffic"], root)
    devices = list(devices if devices is not None else jax.devices())
    used = devices[:config["deployment"]["shards"]]
    bench = Bench(config, seed, devices=used, backend_kw=backend_kw,
                  nts=nts, check_per_tenant=int(traffic["check_per_tenant"]),
                  runtime=traffic.get("runtime", "batch"))
    loop = spec.load_loop(traffic["loop"], root).Loop(bench, traffic)
    t_deployed = time.perf_counter()
    loop.warm()
    t_warm = time.perf_counter()
    loop.prepare(seconds)
    traces0 = sum(c.stats["traces"] for c in bench.computes)
    mem0 = memory(used, "bytes_in_use")
    compiles = CompileCounter()
    gcw = GcWatch()
    cpu0 = time.process_time()
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            win = loop.window(seconds)
    finally:
        if traced:
            jax.profiler.stop_trace()
        compiles.close()
        gcw.close()
    cpu_s = time.process_time() - cpu0
    window_compiles = compiles.n + sum(c.stats["traces"]
                                       for c in bench.computes) - traces0
    mem1 = memory(used, "bytes_in_use")
    peak = memory(used, "peak_bytes_in_use")
    # the sampled outputs stay on the device until the comparison: the
    # window's growth in bytes in use should be no more than these
    held = sum(v.nbytes for kept in bench.samples for smp in kept
               for v in smp.out.values() if hasattr(v, "nbytes"))

    summary = None
    if traced:
        chip_ids = [getattr(d, "id", i) for i, d in enumerate(used)]
        summary = trace.reduce(trace.load(trace.find_xplane(trace_dir)),
                               chips=chip_ids)
    record = Record(
        cell=cell["name"], chips=len(used),
        setup_s=win.t_start - t_start, window=win,
        window_s=win.t_end - win.t_start,
        datapath_bytes=bench.datapath_bytes(),
        dispatches=bench.dispatches() - bench.dispatches0,
        inject_s=bench.inject_s, inject_pkts=bench.inject_pkts,
        trace=summary, peaks=peaks)

    # the comparison runs after the window and the memory reading, on the
    # host, with the program's outputs copied off the device
    t_check = time.perf_counter()
    bench.host_samples()
    mismatch, checked = bench.compare()
    check_s = time.perf_counter() - t_check

    # the loop has drained what was pending at the close: what never came
    # back is failed, what came back late only counts outside ``mpps``
    attempted = int(sum(win.attempted))
    failed = attempted - min(int(sum(bench.delivered)), attempted)
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(bench_spec, cell["name"], kind):
        value = spec.load_metric(m["name"], root).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    checks = {
        "mismatched_words": {"value": mismatch, "max": 0},
        "undelivered_pkts": {"value": failed, "max": 0},
        "bad_batches": {"value": bench.bad_batches, "max": 0},
        "window_compiles": {"value": window_compiles, "max": 0},
        "checked_pkts": {"value": checked, "min": 1},
    }
    correct = all(c["value"] <= c["max"] if "max" in c else
                  c["value"] >= c["min"] for c in checks.values())
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s()
        device["window_s"] = summary.window_s
        out["breakdown"] = {"device_ops": trace.top(summary.ops_s),
                            "idle_gaps": trace.top(summary.idle_by_span)}
    out["notes"] = _notes(win, {"start": t_cell - t_start,
                                "deploy": t_deployed - t_cell,
                                "warm": t_warm - t_deployed,
                                "prepare": win.t_start - t_warm},
                          host=_host(win, bench.marks, gcw.pauses, cpu_s),
                          check_s=check_s, device_bytes_in_use=[mem0, mem1],
                          sample_bytes_held=held)
    out["checks"] = checks
    return out


def _notes(win: Window, setup_phases: dict, **more) -> dict:
    """What no metric reports but a reader of a run wants: step times,
    where set-up went, the open loop's latency percentiles, the
    comparison's time and device memory around the window."""
    steps_ms = np.diff([win.t_start] + win.step_ends) * 1e3
    lat = win.latency_us
    return {"steps": win.steps, "window_s": win.t_end - win.t_start,
            "step_ms": {"p50": float(np.median(steps_ms)),
                        "max": float(steps_ms.max())} if win.steps else None,
            "setup_s": sum(setup_phases.values()),
            "setup_phases_s": setup_phases,
            "latency_us": {f"p{q:g}": float(np.percentile(lat, q))
                           for q in (50, 90, 99, 99.9)}
            if lat is not None else None, **more}


def _host(win: Window, marks, pauses, cpu_s: float) -> dict:
    """Where the host's time went in the window: its CPU time, the garbage
    collector's pauses, and the slowest steps with the CPU time and the
    pauses inside each, so a slow step is told apart from a slow chip."""
    inside = [(a, b, g) for a, b, g in pauses
              if win.t_start <= a and b <= win.t_end]
    steps = list(zip(marks, marks[1:]))
    slow = sorted(steps, key=lambda s: s[0][0] - s[1][0])[:3]
    return {"cpu_s": cpu_s,
            "gc_pauses": [sum(1 for *_, g in inside if g == k)
                          for k in range(3)],
            "gc_ms": sum(b - a for a, b, _ in inside) * 1e3,
            "gc_max_ms": max((b - a for a, b, _ in inside), default=0.0)
            * 1e3,
            "slowest_steps": [
                {"at_s": t0 - win.t_start, "ms": (t1 - t0) * 1e3,
                 "cpu_ms": (c1 - c0) * 1e3,
                 "gc_ms": sum(min(b, t1) - max(a, t0) for a, b, _ in inside
                              if b > t0 and a < t1) * 1e3}
                for (t0, c0), (t1, c1) in slow]}


def open_chip() -> list | None:
    """Turn on the persistent compilation cache and return JAX's devices,
    or None (with a message) where they are not TPUs."""
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to compile, goes to the cache, so a
    # second run of a cell compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX found {devices[0].platform}); the "
              "benchmark measures the chip only", file=sys.stderr)
        return None
    return devices


def main(args, t_start: float) -> int:
    """The command: look for the chips the cell needs, then run it."""
    devices = open_chip()
    if devices is None:
        return 1
    bench_spec = spec.load_benchmark()
    cell = spec.find_cell(bench_spec, args.workload)
    if len(devices) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, JAX "
              f"sees {len(devices)}", file=sys.stderr)
        return 1
    peaks = spec.load_peaks(devices[0].device_kind)
    out = run_cell(cell, bench_spec, args.seed, args.seconds,
                   bool(args.trace), t_start, peaks=peaks, devices=devices)
    for name, c in out["checks"].items():
        limit = f"<= {c['max']}" if "max" in c else f">= {c['min']}"
        print(f"check {name}: {c['value']} (limit {limit})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
