"""The program's spans read with their nesting: on a trace built here with
known answers, on one the compute runtime records here, and in a traced
run of every cell at the test size (``tiny.py``).  Times from here are not
speed."""
from __future__ import annotations

import jax
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from chipbench import harness, phases, spec, trace
from chipbench.tests import test_trace, tiny

BENCH = spec.load_benchmark()
PROGRAM_READERS = {"order_us_per_kpkt", "stage_us_per_kpkt",
                   "launch_us_per_kpkt", "sync_us_per_kpkt",
                   "split_us_per_kpkt", "pad_row_pct"}
US = 1000


def xspace() -> str:
    """One chip and two host lines, in microseconds.  On the window's line
    the host injects until 20, runs until 80 and retires until 100; inside
    the run the program orders, stages and launches two groups, waits and
    splits, and runs no step of its own from 76 to 80.  The other line
    holds a span that must not be read.  The chip is busy 30..50 and
    90..95."""
    names: dict[str, int] = {}

    def meta(name: str) -> int:
        return names.setdefault(name, len(names) + 1)

    def event(s, e, name, args=None):
        stats = "".join(f"stats {{ metadata_id: {meta(k)} int64_value: {v} }}"
                        for k, v in (args or {}).items())
        return (f"events {{ metadata_id: {meta(name)} offset_ps: "
                f"{s * US * 1000} duration_ps: {(e - s) * US * 1000} "
                f"{stats} }}\n")

    def line(i, name, evs):
        body = "".join(event(*ev) for ev in evs)
        return f"lines {{ id: {i} name: \"{name}\" timestamp_ns: 0\n{body}}}\n"

    def plane(pid, name, lines, stat_names=()):
        body = "".join(line(i + 1, n, evs) for i, (n, evs) in
                       enumerate(lines))
        md = "".join(f"event_metadata {{ key: {names[n]} value {{ id: "
                     f"{names[n]} name: \"{n}\" }} }}\n" for n in names)
        sd = "".join(f"stat_metadata {{ key: {names[n]} value {{ id: "
                     f"{names[n]} name: \"{n}\" }} }}\n" for n in stat_names)
        names.clear()
        return f"planes {{ id: {pid} name: \"{name}\"\n{body}{md}{sd}}}\n"

    host = [(0, 100, "chipbench.window"), (0, 20, "chipbench.inject"),
            (20, 80, "chipbench.run"), (20, 24, "repro.sched.order"),
            (24, 34, "repro.compute.stage"),
            (34, 36, "repro.compute.launch", {"rows": 3072, "bucket": 4096}),
            (36, 40, "repro.compute.stage"),
            (40, 42, "repro.compute.launch",
             {"rows": 16384, "bucket": 16384}),
            (42, 70, "repro.compute.sync"), (70, 76, "repro.compute.split"),
            (80, 100, "chipbench.retire")]
    other = [(0, 100, "repro.compute.sync")]
    ops = [(30, 50, "custom-call"), (90, 95, "copy")]
    return (plane(1, "/device:TPU:0", [("XLA Ops", ops)])
            + plane(2, "/host:CPU", [("python", host), ("worker", other)],
                    stat_names=("rows", "bucket")))


@pytest.fixture(scope="module")
def data():
    return ProfileData.from_text_proto(xspace())


def test_idle_goes_to_the_innermost_span(data):
    s = phases.reduce(data)
    assert s.idle_by_span == pytest.approx(
        {"chipbench.inject": 20e-6, "repro.sched.order": 4e-6,
         "repro.compute.stage": 6e-6, "repro.compute.sync": 20e-6,
         "repro.compute.split": 6e-6, "chipbench.run": 4e-6,
         "chipbench.retire": 15e-6})
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s[0])
    # the flat reduction drops every span nested in chipbench.run
    flat = trace.reduce(trace.from_profile(data))
    assert flat.idle_by_span["chipbench.run"] == pytest.approx(40e-6)
    assert (s.busy_s, s.ops_s, s.window_s) == \
        (flat.busy_s, flat.ops_s, flat.window_s)


def test_phases_read_the_window_line_only(data):
    ph = phases.phases(data)
    assert ph.window_s == pytest.approx(100e-6)
    assert ph.span_s == pytest.approx(
        {"repro.sched.order": 4e-6, "repro.compute.stage": 14e-6,
         "repro.compute.launch": 4e-6, "repro.compute.sync": 28e-6,
         "repro.compute.split": 6e-6})
    assert (ph.rows, ph.bucket_rows) == (3072 + 16384, 4096 + 16384)


def test_without_program_spans_the_reduction_is_unchanged():
    data = ProfileData.from_text_proto(test_trace.xspace())
    for chips in (None, [0, 1, 2]):
        got = phases.reduce(data, chips)
        want = trace.reduce(trace.from_profile(data), chips)
        assert got == want


def test_flatten_names_each_segment_after_its_innermost_span():
    assert phases.flatten([(0, 10, "a"), (2, 4, "b"), (3, 4, "c"),
                           (6, 12, "d"), (20, 30, "e")]) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 6, "a"), (6, 10, "d"),
        (20, 30, "e")]
    flat = [(5, 6, "y"), (0, 2, "x")]
    assert phases.flatten(flat) == sorted(flat)


def test_span_totals_agree_with_the_recorded_trace(tmp_path):
    """The in-memory totals and the profiler's events time the same
    spans: within 2% in all, and for each span within 2% and 10 us per
    event (the profiler's own work on entry and exit, measured at up to
    8 us an event on the CPU)."""
    from repro.api import VPC_SPECS, ComputeBackend, Platform, nt
    from repro.serving.vpc import make_packets, make_rules

    be = ComputeBackend(use_fused=False)
    plat = Platform(be, specs=VPC_SPECS)
    dep = plat.tenant("t").deploy(
        nt("firewall") >> nt("nat"),
        params={"firewall": {"rules": make_rules(32, seed=1)},
                "nat": {"nat_ip": 0x0A000001}})
    h, p = make_packets(4096, seed=3)

    def step():
        for _ in range(8):
            dep.inject(headers=h, payload=p)
        with TraceAnnotation("chipbench.run"):
            plat.run()
    step()                                   # compile outside the window
    before = dict(be.span_s)
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(4):
            step()
    jax.profiler.stop_trace()
    ph = phases.phases(phases.load(trace.find_xplane(tmp_path)))
    mem = {k: v - before[k] for k, v in be.span_s.items()}
    assert set(ph.span_s) == set(mem)
    assert sum(ph.span_s.values()) == pytest.approx(sum(mem.values()),
                                                    rel=0.02)
    for name, secs in mem.items():
        events = sum(1 for *_, n in ph.spans if n == name)
        assert ph.span_s[name] == pytest.approx(
            secs, rel=0.02, abs=10e-6 * events), name
    assert (ph.rows, ph.bucket_rows) == (4 * 8 * 4096, 4 * 8 * 4096)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_traced_cell_reads_the_program_spans(cell, root, tmp_path,
                                             monkeypatch):
    """A traced run prints every program reader its cell lists, and the
    nested reduction of its trace names the program's steps."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    out = tiny.run(cell, root, True, tmp_path / "trace")
    assert out["correct"], out["checks"]
    listed = {m["name"] for m in spec.metrics_for(BENCH, cell["name"],
                                                  "per_layer")}
    assert listed & PROGRAM_READERS <= set(out["metrics"])
    assert all(out["metrics"][m]["value"] >= 0
               for m in listed & PROGRAM_READERS)
    s = phases.reduce(phases.load(trace.find_xplane(tmp_path / "trace")),
                      chips=[0])
    assert any(k.startswith(phases.PROGRAM_PREFIX) for k in s.idle_by_span)
    assert sum(s.idle_by_span.values()) == pytest.approx(s.window_s)
    assert s.window_s == pytest.approx(out["device"]["window_s"])


@pytest.mark.parametrize("stale", [False, True])
def test_a_trace_of_another_run_is_not_read(stale, root, tmp_path,
                                            monkeypatch):
    """The readers read the harness's trace only where its window is the
    run's: a stale trace, or none, leaves the program metrics out."""
    elsewhere = tmp_path / "elsewhere"
    if stale:
        elsewhere.mkdir()
        (elsewhere / "old.xplane.pb").write_bytes(
            ProfileData.text_proto_to_serialized_xspace(xspace()))
    monkeypatch.setattr(harness, "TRACE_DIR", elsewhere)
    cell = spec.find_cell(BENCH, "vpc8-r1k.backlog")
    out = tiny.run(cell, root, True, tmp_path / "trace")
    assert out["correct"], out["checks"]
    assert not PROGRAM_READERS & set(out["metrics"])
