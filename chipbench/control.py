"""The control of the comparison that decides ``correct``.

The configurations state no numeric precision, so the control breaks one
guarantee they state: every packet is judged against its tenant's whole
rule table.  Here the firewall is skipped, every packet is allowed, and the
rest of the program runs as it does (the composed XLA path, since the
megakernel always scans).  Skipping the scan is the step that would tempt
a later change: the scan is most of the chain's work.  The comparison has
to read this as not correct.

    python3 chipbench/control.py --workload <cell> --seeds 21 22 23 \\
        --seconds 5

runs the cell with the control in the program's place, once per seed, on
the chips of this machine, and prints each run's checks.  The benchmark's
own runs never run it.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_nts() -> dict:
    """The program's NTs with the firewall's rule scan replaced by
    'allow every packet'."""
    import jax.numpy as jnp
    from repro.api.compute_backend import BUILTIN_COMPUTE_NTS

    def allow_all(state, params):
        return {"allow": jnp.ones(state["headers"].shape[:1], bool)}

    return {"firewall": dataclasses.replace(BUILTIN_COMPUTE_NTS["firewall"],
                                            fn=allow_all)}


#: the control runs the program's own composed path: a fused megakernel
#: would do the scan whatever the NT says
CONTROL_BACKEND = {"use_fused": False}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import harness, spec
    devices = harness.open_chip()
    if devices is None:
        return 1
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    peaks = spec.load_peaks(devices[0].device_kind)
    for seed in args.seeds:
        out = harness.run_cell(cell, bench, seed, args.seconds, False,
                               time.perf_counter(), peaks=peaks,
                               devices=devices, nts=control_nts(),
                               backend_kw=CONTROL_BACKEND)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
