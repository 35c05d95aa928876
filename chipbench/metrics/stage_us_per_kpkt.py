"""Host time inside the compute runtime's span ``repro.compute.stage``
(coalescing, padding to the bucket, the ``valid`` mask and the copy to the
device), in microseconds per thousand packets delivered in the window.
Read from the traced run's profile; a program without the span has nothing
to read."""
from chipbench import phases


def read(r):
    return phases.us_per_kpkt(r, "repro.compute.stage")
