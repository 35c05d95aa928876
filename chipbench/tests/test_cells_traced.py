"""Every cell of ``BENCHMARK.json`` traced, run whole on the CPU at a small
size (``tiny.py``) with the megakernel forced on in interpret mode: the
profiler window, the trace reduction and every per-layer reader.  Times
from here are not speed."""
from __future__ import annotations

import pytest

from chipbench import spec
from chipbench.tests import tiny

BENCH = spec.load_benchmark()
#: read from the host, so found on the CPU too; the device-trace readers
#: find no TPU plane here and leave their metric out
HOST_READERS = {"gen_lag_ms", "inject_us_per_kpkt", "launches_per_kpkt"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_traced_cell_reads_its_per_layer_metrics(cell, root, tmp_path):
    out = tiny.run(cell, root, True, tmp_path / "trace")
    assert out["correct"], out["checks"]
    names = {m["name"] for m in spec.metrics_for(BENCH, cell["name"],
                                                 "per_layer")}
    assert names & HOST_READERS <= set(out["metrics"]) <= names
    assert "datapath_roofline" not in out["metrics"]
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(out)[-1] == "checks"
