"""The program's own spans in a profiler trace, read with their nesting.

The compute runtime opens spans named ``repro.<layer>.<step>`` (in
``src/repro/obs.py``) inside the harness's ``chipbench.run``.  ``trace.py``
reads only spans that never nest; this module reads the host line (thread)
that holds ``chipbench.window`` again, nested spans and all:

  phases   host seconds inside each ``repro.`` span within the window, and
           the real and bucket rows and the host bytes sent of every
           ``repro.compute.launch`` that starts in it (its ``rows``,
           ``bucket`` and ``h2d_bytes`` arguments);
  flatten  the line's spans as disjoint segments, each named after the
           innermost span that covers it;
  reduce   ``trace.reduce`` with every idle interval split over those
           segments, so idle time inside ``Platform.run()`` goes to the
           program's step that was running, and only what no step covers
           stays under ``chipbench.run``.  On a trace with no ``repro.``
           span it returns what ``trace.reduce`` returns.

The metric readers find the trace their run wrote with :func:`of_record`;
a run of a program without these spans has nothing to read.

    python3 -m chipbench.phases [trace_dir]

prints, as one JSON line, the phases and the nested idle attribution of the
trace last written under ``trace_dir`` (the harness's, by default).
"""
from __future__ import annotations

import json
import math
import sys
from dataclasses import dataclass
from pathlib import Path

from chipbench import trace

PROGRAM_PREFIX = "repro."
LAUNCH_SPAN = "repro.compute.launch"
#: the prefixes of the host spans read here: the harness's and the program's
PREFIXES = (trace.SPAN_PREFIX, PROGRAM_PREFIX)


@dataclass
class Phases:
    """The window line of one trace, in nanoseconds on the trace's clock."""
    window: tuple[float, float]
    #: the line's spans other than the window, as (start, end, name)
    spans: list[tuple[float, float, str]]
    #: host seconds inside each ``repro.`` span, clipped to the window
    span_s: dict[str, float]
    #: real rows and bucket rows over the launches that start in the window
    rows: int
    bucket_rows: int
    #: host bytes those launches sent to the device, or None where none of
    #: them carries the ``h2d_bytes`` argument (a program without it)
    h2d_bytes: int | None = None

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def host_lines(data) -> list[list[tuple[float, float, str, dict | None]]]:
    """Per line of the host plane, its harness and program spans as
    (start, end, name, arguments); arguments are read for launches only."""
    for plane in data.planes:
        if plane.name == trace.HOST_PLANE:
            return [[(e.start_ns, e.end_ns, e.name,
                      dict(e.stats) if e.name == LAUNCH_SPAN else None)
                     for e in line.events if e.name.startswith(PREFIXES)]
                    for line in plane.lines]
    return []


def phases(data) -> Phases | None:
    """The spans of the line that holds ``chipbench.window``, or None where
    no line does.  Spans on other lines are not read."""
    return of_lines(host_lines(data))


def of_lines(lines) -> Phases | None:
    """:func:`phases` of host lines as :func:`host_lines` gives them."""
    for line in lines:
        win = [(s, e) for s, e, name, _ in line if name == trace.WINDOW_SPAN]
        if win:
            break
    else:
        return None
    lo, hi = win[0]
    spans, span_s, rows, bucket_rows, h2d = [], {}, 0, 0, None
    for s, e, name, args in line:
        if name == trace.WINDOW_SPAN:
            continue
        spans.append((s, e, name))
        d = min(e, hi) - max(s, lo)
        if name.startswith(PROGRAM_PREFIX) and d > 0:
            span_s[name] = span_s.get(name, 0.0) + d * 1e-9
        if args is not None and lo <= s < hi:
            rows += int(args.get("rows", 0))
            bucket_rows += int(args.get("bucket", 0))
            if "h2d_bytes" in args:
                h2d = (h2d or 0) + int(args["h2d_bytes"])
    return Phases((lo, hi), spans, span_s, rows, bucket_rows, h2d)


def flatten(spans) -> list[tuple[float, float, str]]:
    """Spans of one thread, which nest, as sorted disjoint segments, each
    named after the innermost span covering it; a span that outlasts its
    parent is cut at the parent's end, and time in no span has no segment.
    Spans that do not nest come back as they are, sorted."""
    out: list[tuple[float, float, str]] = []

    def emit(lo: float, hi: float, name: str) -> None:
        if hi <= lo:
            return
        if out and out[-1][1] == lo and out[-1][2] == name:
            out[-1] = (out[-1][0], hi, name)
        else:
            out.append((lo, hi, name))

    stack: list[tuple[float, str]] = []        # (end, name), outer first
    t = 0.0                                    # the last boundary emitted
    for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:     # close what ended before s
            end, outer = stack.pop()
            emit(t, end, outer)
            t = end
        if stack:
            emit(t, s, stack[-1][1])
            e = min(e, stack[-1][0])
        stack.append((e, name))
        t = s
    while stack:
        end, outer = stack.pop()
        emit(t, end, outer)
        t = end
    return out


def reduce(data, chips: list[int] | None = None) -> trace.Summary:
    """``trace.reduce`` of the profile ``data``, with idle time attributed
    to the innermost span on the window's line."""
    tr = trace.from_profile(data)
    ph = phases(data)
    if ph is not None:
        tr.spans = [(*ph.window, trace.WINDOW_SPAN)] + flatten(ph.spans)
    return trace.reduce(tr, chips)


def load(path: str | Path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


#: the one trace last read by :func:`of_record`: (path, mtime, size) and
#: its phases, so the readers of one run parse it once
_last: tuple[tuple, Phases | None] | None = None


def of_record(r) -> Phases | None:
    """The phases of the trace the harness wrote for the traced run ``r``
    (under ``harness.TRACE_DIR``), or None: an untraced run, no trace, or a
    trace whose window is not ``r``'s."""
    global _last
    if r.trace is None:
        return None
    from chipbench import harness
    try:
        path = trace.find_xplane(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size)
    if _last is None or _last[0] != key:
        _last = (key, phases(load(path)))
    ph = _last[1]
    if ph is None or not math.isclose(ph.window_s, r.trace.window_s,
                                      rel_tol=1e-9):
        return None
    return ph


def us_per_kpkt(r, name: str) -> float | None:
    """Host microseconds inside span ``name`` in ``r``'s window, per
    thousand packets delivered in it; None where the span never ran."""
    ph = of_record(r)
    n = sum(r.window.delivered)
    if ph is None or name not in ph.span_s or n <= 0:
        return None
    return ph.span_s[name] * 1e6 / (n / 1000.0)


def main(argv: list[str]) -> int:
    from chipbench import harness
    path = trace.find_xplane(argv[0] if argv else harness.TRACE_DIR)
    data = load(path)
    ph = phases(data)
    s = reduce(data)
    idle = s.idle_by_span
    program = sum(v for k, v in idle.items() if k.startswith(PROGRAM_PREFIX))
    in_run = program + idle.get("chipbench.run", 0.0)
    print(json.dumps({
        "window_s": s.window_s, "busy_s": s.busy_s,
        "idle_gaps": trace.top(idle, 20),
        "program_share_of_run_idle": program / in_run if in_run else None,
        "span_s": ph.span_s if ph else None,
        "rows": ph.rows if ph else None,
        "bucket_rows": ph.bucket_rows if ph else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
