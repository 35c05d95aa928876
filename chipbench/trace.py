"""From a profiler trace (``.xplane.pb``) to device busy and idle time.

The JAX profiler writes one plane per device (``/device:TPU:<i>``) whose
``XLA Ops`` line holds every operation the device ran and whose
``XLA Modules`` line holds every program launch, and one host plane
(``/host:CPU``) that holds the benchmark's own spans
(``jax.profiler.TraceAnnotation`` named ``chipbench.<what>``).  This module
reduces them, inside the span ``chipbench.window``, to:

  busy       per chip, the union of the intervals in which an op ran;
  modules    per chip, each program launch as an interval, so a reader can
             take the device time of the programs it names;
  ops        device seconds by op (opcode and result shape), summed over
             chips;
  idle       per chip, the gaps between busy intervals, each split over
             the host spans it falls in and summed by span name.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
#: idle time in no benchmark span
NO_SPAN = "other"
#: an HLO op's opcode, the first lower-case word followed by "(" after "="
_OPCODE = re.compile(r" ([a-z][\w-]*)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")


@dataclass
class Trace:
    """The events the reduction reads, in nanoseconds on the trace's clock."""
    ops: dict[int, list[tuple[float, float, str]]] = field(default_factory=dict)
    modules: dict[int, list[tuple[float, float, str]]] = \
        field(default_factory=dict)
    spans: list[tuple[float, float, str]] = field(default_factory=list)


@dataclass
class Summary:
    window_s: float
    busy_s: dict[int, float]
    ops_s: dict[str, float]
    idle_by_span: dict[str, float]
    #: per chip, program launches clipped to the window: (start, end, name)
    modules: dict[int, list[tuple[float, float, str]]]

    @property
    def chips(self) -> int:
        return len(self.busy_s)

    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def module_busy_s(self, pattern: str) -> float:
        """Device seconds, summed over chips, in which a program whose name
        matches ``pattern`` (a regular expression) ran."""
        rx = re.compile(pattern)
        total = 0.0
        for mods in self.modules.values():
            iv = merge([(s, e) for s, e, name in mods if rx.search(name)])
            total += sum(e - s for s, e in iv)
        return total * 1e-9


def find_xplane(logdir: str | Path) -> Path:
    found = sorted(Path(logdir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def load(path: str | Path) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(path)))


def from_profile(data) -> Trace:
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dst = tr.ops.setdefault(chip, [])
                elif line.name == MODULES_LINE:
                    dst = tr.modules.setdefault(chip, [])
                else:
                    continue
                dst.extend((e.start_ns, e.end_ns, e.name) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                tr.spans.extend((e.start_ns, e.end_ns, e.name)
                                for e in line.events
                                if e.name.startswith(SPAN_PREFIX))
    return tr


def op_label(hlo: str) -> str:
    """A short name for an op event, whose name is its HLO text: the
    opcode and the result's shape without layouts, so that
    ``%copy.1 = u32[16384,5]{0,1:T(8,128)} copy(...)`` reads
    ``copy u32[16384,5]``.  A name that is not HLO text is kept."""
    if " = " not in hlo:
        return hlo
    rest = hlo.split(" = ", 1)[1]
    m = _OPCODE.search(rest)
    if m is None:
        return hlo
    return f"{m.group(1)} {_LAYOUT.sub('', rest[:m.start()]).strip()}"


def merge(intervals) -> list[tuple[float, float]]:
    """Union of intervals, sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of merged ``busy`` intervals within ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def attribute(idle, spans) -> dict[str, float]:
    """Split each idle interval over the host spans it overlaps (spans are
    disjoint and sorted) and sum nanoseconds by span name; idle time in no
    span goes to ``other``."""
    out: dict[str, float] = {}
    starts = [s for s, _, _ in spans]
    for lo, hi in idle:
        covered = 0.0
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(spans) and spans[i][0] < hi:
            s, e, name = spans[i]
            ov = min(e, hi) - max(s, lo)
            if ov > 0:
                out[name] = out.get(name, 0.0) + ov
                covered += ov
            i += 1
        if hi - lo - covered > 0:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (hi - lo - covered)
    return out


def _disjoint(spans) -> list[tuple[float, float, str]]:
    """The benchmark's spans never nest: sort them, and drop any that
    starts inside the one before."""
    out: list[tuple[float, float, str]] = []
    for s, e, name in sorted(spans):
        if out and s < out[-1][1]:
            continue
        out.append((s, e, name))
    return out


def reduce(tr: Trace, chips: list[int] | None = None) -> Summary:
    """Reduce a trace inside its ``chipbench.window`` span (or, without
    one, from the first to the last device event).  ``chips`` limits the
    reduction to the devices the cell uses; a used chip that ran nothing
    counts as idle for the whole window."""
    win = [(s, e) for s, e, name in tr.spans if name == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:
        ends = [x for ev in tr.ops.values() for x in ev]
        if not ends:
            raise ValueError("trace has no device ops and no window span")
        lo, hi = min(s for s, _, _ in ends), max(e for _, e, _ in ends)
    if chips is None:
        chips = sorted(tr.ops)
    spans = _disjoint([x for x in tr.spans if x[2] != WINDOW_SPAN])
    busy_s, idle_ns, ops_s = {}, {}, {}
    for c in chips:
        ev = tr.ops.get(c, [])
        busy = merge(clip([(s, e) for s, e, _ in ev], lo, hi))
        busy_s[c] = sum(e - s for s, e in busy) * 1e-9
        for name, ns in attribute(gaps(busy, lo, hi), spans).items():
            idle_ns[name] = idle_ns.get(name, 0.0) + ns
        for s, e, name in ev:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = op_label(name)
                ops_s[key] = ops_s.get(key, 0.0) + d * 1e-9
    modules = {c: [(max(s, lo), min(e, hi), name)
                   for s, e, name in tr.modules.get(c, [])
                   if e > lo and s < hi] for c in chips}
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_s,
                   ops_s=ops_s,
                   idle_by_span={k: v * 1e-9 for k, v in idle_ns.items()},
                   modules=modules)


def top(d: dict[str, float], n: int = 10) -> list[list]:
    """The ``n`` largest entries as ``[name, seconds]`` pairs."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
