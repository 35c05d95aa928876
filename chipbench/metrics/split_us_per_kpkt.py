"""Host time inside the compute runtime's span ``repro.compute.split``
(each launch's output sliced back into its batches), in microseconds per
thousand packets delivered in the window.  Read from the traced run's
profile; a program without the span has nothing to read."""
from chipbench import phases


def read(r):
    return phases.us_per_kpkt(r, "repro.compute.split")
