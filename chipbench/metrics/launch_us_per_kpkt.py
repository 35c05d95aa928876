"""Host time inside the compute runtime's span ``repro.compute.launch``
(the jitted program call), in microseconds per thousand packets delivered
in the window; over ``launches_per_kpkt`` it is the host cost of one
launch.  Read from the traced run's profile; a program without the span
has nothing to read."""
from chipbench import phases


def read(r):
    return phases.us_per_kpkt(r, "repro.compute.launch")
