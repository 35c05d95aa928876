"""The reader ``fw_gpairs_per_s``: the window's launch ``rule_pairs`` over
the device time of the datapath programs, on launch-span lists built here,
on a profile built here with a known answer, on one whose launches carry
no ``rule_pairs`` (a program from before the argument), and on the trace
a traced run of every cell that lists the reader records at the test size
(``tiny.py``), where each launch's pairs are its rows times the rules."""
from __future__ import annotations

from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

from chipbench import harness, phases, spec, trace
from chipbench.tests import test_phases, tiny

BENCH = spec.load_benchmark()
READER = "fw_gpairs_per_s"
LAUNCH = phases.LAUNCH_SPAN
US = 1000                                  # ns in a microsecond


def reader():
    return spec.load_metric(READER)


def test_sums_the_launches_that_start_in_the_window():
    window_line = [
        (10, 110, trace.WINDOW_SPAN, None),
        (5, 12, LAUNCH, {"rule_pairs": 1000}),       # starts before
        (20, 30, LAUNCH, {"rule_pairs": 16 * 100, "rows": 16}),
        (40, 50, "repro.compute.stage", None),
        (60, 70, LAUNCH, {"rule_pairs": 32 * 100}),
        (105, 115, LAUNCH, {"rule_pairs": 7}),       # starts inside
        (110, 120, LAUNCH, {"rule_pairs": 2000}),    # starts at the end
    ]
    other_line = [(20, 30, LAUNCH, {"rule_pairs": 5000})]
    got = reader().window_pairs([other_line, window_line])
    assert got == (pytest.approx(100e-9), 48 * 100 + 7)


@pytest.mark.parametrize("lines, want", [
    ([[(0, 100, trace.WINDOW_SPAN, None), (20, 30, LAUNCH, {"rows": 16}),
       (40, 50, LAUNCH, {})]], None),              # launches, no argument
    ([[(0, 100, trace.WINDOW_SPAN, None)]], None),  # no launch
    ([[(20, 30, LAUNCH, {"rule_pairs": 89})]], "no window"),
])
def test_nothing_to_read(lines, want):
    got = reader().window_pairs(lines)
    assert got is None if want else got[1] is None


def xspace(launches) -> str:
    """One chip busy in two ``jit_traced`` programs (10..40 and 60..70 us)
    and one other program (80..90), and a host line whose window spans
    0..100 us and holds the launch spans ``launches``, (start, end,
    rule_pairs) in microseconds."""
    names: dict[str, int] = {}

    def meta(name):
        return names.setdefault(name, len(names) + 1)

    def event(s, e, name, args=()):
        stats = "".join(f"stats {{ metadata_id: {meta(k)} int64_value: {v} }}"
                        for k, v in args)
        return (f"events {{ metadata_id: {meta(name)} offset_ps: "
                f"{s * US * 1000} duration_ps: {(e - s) * US * 1000} "
                f"{stats} }}\n")

    def plane(pid, name, lines, stat_names=()):
        body = "".join(
            f"lines {{ id: {i + 1} name: \"{ln}\" timestamp_ns: 0\n"
            + "".join(event(*ev) for ev in evs) + "}\n"
            for i, (ln, evs) in enumerate(lines))
        md = "".join(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                     f"\"{n}\" }} }}\n" for n, i in names.items()
                     if n not in stat_names)
        sd = "".join(f"stat_metadata {{ key: {names[n]} value {{ id: "
                     f"{names[n]} name: \"{n}\" }} }}\n" for n in stat_names)
        names.clear()
        return f"planes {{ id: {pid} name: \"{name}\"\n{body}{md}{sd}}}\n"

    mods = [(10, 40, "jit_traced(1)"), (60, 70, "jit_traced(1)"),
            (80, 90, "jit_other(2)")]
    ops = [(s, e, "custom-call") for s, e, _ in mods]
    host = [(0, 100, "chipbench.window"), (0, 100, "chipbench.run")] + [
        (s, e, LAUNCH, (("rule_pairs", n),)) for s, e, n in launches]
    return (plane(1, "/device:TPU:0", [("XLA Ops", ops),
                                       ("XLA Modules", mods)])
            + plane(2, "/host:CPU", [("python", host)],
                    stat_names=("rule_pairs",) if launches else ()))


def read_profile(text, tmp_path, monkeypatch):
    """The reader's value on the profile ``text``, written where a traced
    run writes its own."""
    (tmp_path / "p.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    summary = trace.reduce(trace.load(tmp_path / "p.xplane.pb"))
    return reader().read(SimpleNamespace(trace=summary))


def test_a_recorded_profile_reads_pairs_over_program_time(tmp_path,
                                                           monkeypatch):
    """5e6 + 3e6 pairs in the window over 40 us of ``jit_traced`` time:
    200 Gpair/s; the launch at 95 us ends outside the window and counts,
    the other program's time does not."""
    got = read_profile(xspace([(5, 8, 5_000_000), (50, 52, 2_000_000),
                               (95, 101, 1_000_000)]), tmp_path,
                       monkeypatch)
    assert got == pytest.approx(8e6 / 40e-6 / 1e9)


def test_a_profile_without_the_argument_reads_none(tmp_path, monkeypatch):
    assert read_profile(xspace([]), tmp_path, monkeypatch) is None
    assert reader().window_pairs(phases.host_lines(
        ProfileData.from_text_proto(test_phases.xspace())))[1] is None


def test_an_untraced_run_reads_none():
    assert reader().read(SimpleNamespace(trace=None)) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize(
    "cell", [c for c in BENCH["workloads"] if any(
        m["name"] == READER for m in spec.metrics_for(BENCH, c["name"],
                                                      "per_layer"))],
    ids=lambda c: c["name"])
def test_traced_cell_records_rows_times_rules(cell, root, tmp_path,
                                              monkeypatch):
    """Each launch of a traced run carries its rows times its tenant's
    rules; on the CPU no TPU plane gives program time, so the metric is
    left out."""
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    out = tiny.run(cell, root, True, tmp_path / "trace")
    assert out["correct"], out["checks"]
    assert READER not in out["metrics"]
    lines = phases.host_lines(phases.load(trace.find_xplane(
        tmp_path / "trace")))
    rules = spec.load_config(cell["config"], root)["deployment"][
        "rules_per_tenant"]
    ph = phases.of_lines(lines)
    assert ph.rows > 0
    assert reader().window_pairs(lines)[1] == ph.rows * rules
