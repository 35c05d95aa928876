"""The knee sweep of an open-loop cell: the highest offered rate at which
the load generator keeps up.

    python3 chipbench/sweep.py --workload vpc8-r1k.poisson80 \\
        --rates 0.4 0.8 1.2 1.6 --seconds 8 --seed 5

One process sets the cell up once, then runs one window per rate, lowest
first, and prints one JSON line per rate: packets delivered per second,
the 50th and 99th percentiles of the generator's lag and of the latency,
and the lag's 99th percentile over the second and the last quarter of the
window's arrivals.  A rate is sustained where every due packet was
delivered and the last quarter's lag is at most twice the second
quarter's and under ``LAG_LIMIT_MS``: the lag does not grow over the
window.  The last line names the knee, the highest
sustained rate below the first that is not.  The cell's traffic file then
takes four fifths of it by hand; the benchmark never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
#: a lag past this in the last quarter of the window is not sustained
LAG_LIMIT_MS = 250.0


def quarter_p99(lag, q: int):
    import numpy as np
    n = len(lag)
    part = lag[q * n // 4:(q + 1) * n // 4]
    return float(np.quantile(part, 0.99)) if len(part) else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np

    from chipbench import harness, spec
    from chipbench.cell import Bench
    devices = harness.open_chip()
    if devices is None:
        return 1
    bench_spec = spec.load_benchmark()
    cell = spec.find_cell(bench_spec, args.workload)
    config = spec.load_config(cell["config"])
    traffic = spec.load_traffic(cell["traffic"])
    t0 = time.perf_counter()
    bench = Bench(config, args.seed, devices=devices,
                  check_per_tenant=0)
    loop = spec.load_loop(traffic["loop"]).Loop(bench, traffic)
    loop.warm()
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    knee = None
    for rate in sorted(args.rates):
        loop.rate = rate * 1e6
        loop.prepare(args.seconds)
        win = loop.window(args.seconds)
        # packets in inject order, so quarters are quarters of the window
        lag = win.gen_lag_ms
        q2, q4 = quarter_p99(lag, 1), quarter_p99(lag, 3)
        ok = q4 <= 2 * q2 and q4 < LAG_LIMIT_MS and \
            sum(win.delivered) == sum(win.attempted)
        row = {"rate_mpps": rate,
               "delivered_mpps": sum(win.delivered)
               / (win.t_end - win.t_start) / 1e6,
               "lag_p50_ms": float(np.quantile(lag, 0.5)),
               "lag_p99_ms": float(np.quantile(lag, 0.99)),
               "lag_p99_q2_ms": q2, "lag_p99_q4_ms": q4,
               "latency_p50_us": float(np.quantile(win.latency_us, 0.5)),
               "latency_p99_us": float(np.quantile(win.latency_us, 0.99)),
               "steps": win.steps, "sustained": ok}
        print(json.dumps(row), flush=True)
        if not ok:
            break
        knee = rate
    print(json.dumps({"knee_mpps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
