"""The reduction from a profiler trace to busy, idle and program
time, on a trace built here with known answers and on one recorded here."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace


def xspace() -> str:
    """Two chips and the host, in nanoseconds: the window spans 0..100 us;
    the host injects until 20, runs until 80 and retires until 100."""
    def events(line_id, name, evs, meta):
        body = "".join(
            f"events {{ metadata_id: {meta[n]} offset_ps: {s * 1000} "
            f"duration_ps: {(e - s) * 1000} }}\n" for s, e, n in evs)
        return (f"lines {{ id: {line_id} name: \"{name}\" timestamp_ns: 0\n"
                f"{body}}}\n")

    def plane(pid, name, lines):
        meta = {}
        for _, evs in lines:
            for _, _, n in evs:
                meta.setdefault(n, len(meta) + 1)
        body = "".join(events(i + 1, ln, evs, meta)
                       for i, (ln, evs) in enumerate(lines))
        md = "".join(f"event_metadata {{ key: {i} value {{ id: {i} "
                     f"name: \"{n}\" }} }}\n" for n, i in meta.items())
        return f"planes {{ id: {pid} name: \"{name}\"\n{body}{md}}}\n"

    us = 1000
    chip0_ops = [(10 * us, 30 * us, "fusion.a"), (25 * us, 40 * us, "fusion.b"),
                 (60 * us, 70 * us, "custom-call")]
    chip0_mods = [(10 * us, 40 * us, "jit_traced(1)"),
                  (60 * us, 70 * us, "jit_other(2)")]
    chip1_ops = [(35 * us, 65 * us, "fusion.a")]
    chip1_mods = [(35 * us, 65 * us, "jit_traced(1)")]
    host = [(0, 100 * us, "chipbench.window"),
            (0, 20 * us, "chipbench.inject"),
            (20 * us, 80 * us, "chipbench.run"),
            (80 * us, 100 * us, "chipbench.retire"),
            (30 * us, 31 * us, "PjitFunction(traced)")]
    return (plane(1, "/device:TPU:0", [("XLA Ops", chip0_ops),
                                       ("XLA Modules", chip0_mods)])
            + plane(2, "/device:TPU:1", [("XLA Ops", chip1_ops),
                                         ("XLA Modules", chip1_mods)])
            + plane(3, "/host:CPU", [("python", host)]))


@pytest.fixture(scope="module")
def summary():
    data = jax.profiler.ProfileData.from_text_proto(xspace())
    return trace.reduce(trace.from_profile(data))


def test_window_and_busy(summary):
    assert summary.window_s == pytest.approx(100e-6)
    assert summary.busy_s == pytest.approx({0: 40e-6, 1: 30e-6})
    assert summary.mean_busy_s() == pytest.approx(35e-6)
    assert summary.chips == 2


def test_idle_split_over_host_spans(summary):
    assert summary.idle_by_span == pytest.approx(
        {"chipbench.inject": 30e-6, "chipbench.run": 60e-6,
         "chipbench.retire": 40e-6})


def test_ops_and_program_time(summary):
    assert summary.ops_s == pytest.approx(
        {"fusion.a": 50e-6, "fusion.b": 15e-6, "custom-call": 10e-6})
    assert summary.module_busy_s(r"^jit_traced") == pytest.approx(60e-6)
    assert summary.module_busy_s(r"^jit_nothing") == 0.0
    assert trace.top(summary.ops_s, 2) == [["fusion.a", pytest.approx(50e-6)],
                                           ["fusion.b", pytest.approx(15e-6)]]


def test_a_chip_that_ran_nothing_is_idle_the_whole_window():
    data = jax.profiler.ProfileData.from_text_proto(xspace())
    s = trace.reduce(trace.from_profile(data), chips=[0, 1, 2])
    assert s.busy_s[2] == 0.0
    assert s.idle_by_span["chipbench.run"] == pytest.approx(120e-6)


def test_interval_helpers():
    assert trace.merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert trace.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert trace.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]
    assert trace.attribute([(0, 10)], [(2, 4, "a"), (6, 7, "b")]) == \
        {"a": 2, "b": 1, "other": 7}


def test_op_label_is_opcode_and_shape():
    assert trace.op_label(
        "%copy.1 = u32[16384,5]{0,1:T(8,128)} copy(u32[16384,5]{0,1:T(8,128)}"
        " %a.1)") == "copy u32[16384,5]"
    assert trace.op_label(
        "%traced.1 = (u32[1024,1]{1,0:T(8,128)S(1)}, u32[1024,5]{1,0}) "
        "custom-call(u32[1,1024]{1,0} %b), custom_call_target=\"x\"") == \
        "custom-call (u32[1024,1], u32[1024,5])"
    assert trace.op_label("fusion.a") == "fusion.a"


def test_a_recorded_trace_is_read(tmp_path):
    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("chipbench.run"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(tmp_path))
    names = {n for _, _, n in tr.spans}
    assert {trace.WINDOW_SPAN, "chipbench.run"} <= names
    s = trace.reduce(tr, chips=[0])
    assert s.window_s > 0 and s.busy_s == {0: 0.0}
    assert sum(s.idle_by_span.values()) == pytest.approx(s.window_s)
