"""Firewall (packet, rule) pairs the datapath programs matched per second
of their device time, in Gpair/s: the ``rule_pairs`` argument (a launch's
real rows times its deployment's rules, the same count as
``ComputeBackend.stats["rule_pairs"]``) of every ``repro.compute.launch``
span that starts in the window, summed, over the device time of the
``jit_traced`` programs (the compute runtime's dispatch programs).

An achieved rate, not a roofline share: no integer vector peak is
published for the v5e (``peaks.json``), and the firewall's scan is integer
compare-and-select work.  The reader scans the traced run's profile
itself; an untraced run, a trace of another window, or a program whose
launch spans carry no ``rule_pairs`` has nothing to read."""
import math

from chipbench import phases, trace

#: the trace's names of the compute runtime's dispatch programs
DATAPATH_PROGRAMS = r"^jit_traced"


def window_pairs(lines):
    """``(window_s, pairs)`` of host lines as ``phases.host_lines`` gives
    them: the window's length, and the ``rule_pairs`` of the launches that
    start in it, or None where none carries the argument.  None where no
    line holds the window."""
    for line in lines:
        win = [(s, e) for s, e, name, _ in line if name == trace.WINDOW_SPAN]
        if win:
            break
    else:
        return None
    lo, hi = win[0]
    pairs = None
    for s, _, name, args in line:
        if name == phases.LAUNCH_SPAN and args and "rule_pairs" in args \
                and lo <= s < hi:
            pairs = (pairs or 0) + int(args["rule_pairs"])
    return (hi - lo) * 1e-9, pairs


def read(r):
    if r.trace is None:
        return None
    busy = r.trace.module_busy_s(DATAPATH_PROGRAMS)
    if busy <= 0:
        return None
    from chipbench import harness
    try:
        path = trace.find_xplane(harness.TRACE_DIR)
    except FileNotFoundError:
        return None
    got = window_pairs(phases.host_lines(phases.load(path)))
    if got is None or not got[1] or not math.isclose(
            got[0], r.trace.window_s, rel_tol=1e-9):
        return None
    return got[1] / busy / 1e9
