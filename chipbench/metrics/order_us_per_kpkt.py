"""Host time inside the scheduler's span ``repro.sched.order`` (the fair
drain and the grouping of batches for coalescing, in ``Platform.run()``),
in microseconds per thousand packets delivered in the window.  Read from
the traced run's profile; a program without the span has nothing to read."""
from chipbench import phases


def read(r):
    return phases.us_per_kpkt(r, "repro.sched.order")
