"""Host time inside the compute runtime's span ``repro.compute.sync`` (the
host blocked on the device: the kernels and any transfer still in flight),
in microseconds per thousand packets delivered in the window.  Read from
the traced run's profile; a program without the span has nothing to
read."""
from chipbench import phases


def read(r):
    return phases.us_per_kpkt(r, "repro.compute.sync")
