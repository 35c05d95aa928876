"""The chip benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine: set-up
(tenants, rules, keys and packets from the seed, deployment through
``Platform``, a warm-up of every program shape the window uses), then the
measured window, then the comparison of a seeded sample of what the window
produced with the benchmark's own reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted`` and ``failed`` (in
packets), ``metrics`` (the cell's end-to-end metrics, or with ``--trace 1``
its per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``,
and last the ``checks``, each number compared beside its limit, which are
also the last lines on standard error.  Without a TPU, or with fewer chips
than the cell asks for, it exits 1 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: {ROOT / 'src' / 'repro'} not found; run from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime would otherwise write its logs to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import harness
    return harness.main(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
