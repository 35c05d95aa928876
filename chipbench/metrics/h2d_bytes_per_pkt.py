"""Host bytes the compute runtime sent to the device, per packet delivered
in the window: the ``h2d_bytes`` argument of every ``repro.compute.launch``
span that starts in it (the same count as
``ComputeBackend.stats["h2d_bytes"]``), summed, over packets delivered.
Read from the traced run's profile, once a run; a program whose launch
spans carry no such argument has nothing to read."""
from chipbench import harness, phases, trace

ARG = "h2d_bytes"

#: (path, mtime, size) of the trace last read, and its window's bytes
_last: tuple[tuple, int | None] | None = None


def window_bytes(lines) -> int | None:
    """``ARG`` summed over the launch spans that start inside
    ``chipbench.window``, on the window's line of ``lines`` (as
    ``phases.host_lines`` gives them); None where no line holds the window
    or no launch in it carries the argument."""
    for line in lines:
        win = [(s, e) for s, e, name, _ in line if name == trace.WINDOW_SPAN]
        if win:
            break
    else:
        return None
    lo, hi = win[0]
    sent = [int(args[ARG]) for s, _e, name, args in line
            if name == phases.LAUNCH_SPAN and args and ARG in args
            and lo <= s < hi]
    return sum(sent) if sent else None


def read(r):
    global _last
    n = sum(r.window.delivered)
    if phases.of_record(r) is None or n <= 0:    # not this run's trace
        return None
    path = trace.find_xplane(harness.TRACE_DIR)
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size)
    if _last is None or _last[0] != key:
        _last = (key, window_bytes(phases.host_lines(phases.load(path))))
    sent = _last[1]
    return None if sent is None else sent / n
