"""The window's ``h2d_bytes`` that ``phases`` sums and the reader
``h2d_bytes_per_pkt`` divides: on launch-span lists built here with known
sums, on a trace whose launches carry no ``h2d_bytes`` (a program from
before the argument), and in a traced run of every cell that lists the
reader at the test size (``tiny.py``)."""
from __future__ import annotations

import pytest
from jax.profiler import ProfileData

from chipbench import harness, phases, spec, trace
from chipbench.tests import test_phases, tiny

BENCH = spec.load_benchmark()
READER = "h2d_bytes_per_pkt"
LAUNCH = phases.LAUNCH_SPAN
#: bytes a staged row sends: headers, payload, keystream counter, valid
ROW_BYTES = 5 * 4 + 16 * 4 + 4 + 1


def window_bytes(lines):
    ph = phases.of_lines(lines)
    return None if ph is None else ph.h2d_bytes


def test_sums_the_launches_that_start_in_the_window():
    window_line = [
        (10, 100, trace.WINDOW_SPAN, None),
        (5, 12, LAUNCH, {"h2d_bytes": 1000}),        # starts before
        (20, 30, LAUNCH, {"h2d_bytes": 89 * 16, "rows": 16}),
        (40, 50, "repro.compute.stage", None),
        (60, 70, LAUNCH, {"h2d_bytes": 89 * 32}),
        (95, 105, LAUNCH, {"h2d_bytes": 7}),         # starts inside
        (100, 110, LAUNCH, {"h2d_bytes": 2000}),     # starts at the end
    ]
    other_line = [(20, 30, LAUNCH, {"h2d_bytes": 5000})]
    assert window_bytes([other_line, window_line]) == 89 * 48 + 7


@pytest.mark.parametrize("lines", [
    [[(0, 100, trace.WINDOW_SPAN, None), (20, 30, LAUNCH, {"rows": 16}),
      (40, 50, LAUNCH, {})]],                        # launches, no argument
    [[(0, 100, trace.WINDOW_SPAN, None)]],           # no launch
    [[(20, 30, LAUNCH, {"h2d_bytes": 89})]],         # no window
])
def test_nothing_to_read_is_none(lines):
    assert window_bytes(lines) is None


def test_a_profile_without_the_argument_reads_none():
    data = ProfileData.from_text_proto(test_phases.xspace())
    assert window_bytes(phases.host_lines(data)) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize(
    "cell", [c for c in BENCH["workloads"] if any(
        m["name"] == READER for m in spec.metrics_for(BENCH, c["name"],
                                                      "per_layer"))],
    ids=lambda c: c["name"])
def test_traced_cell_reads_the_bytes_sent(cell, root, tmp_path,
                                          monkeypatch):
    """Every packet the tiny cells send is host data: 89 bytes a row, and
    more with pad rows; the closed loop pads none."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    out = tiny.run(cell, root, True, tmp_path / "trace")
    assert out["correct"], out["checks"]
    got = out["metrics"][READER]
    assert got["unit"] == "B/pkt"
    if cell["traffic"] == "backlog-16k":
        assert got["value"] == pytest.approx(ROW_BYTES)
    else:
        assert got["value"] > 0
