"""Host bytes the compute runtime sent to the device, per packet delivered
in the window: the ``h2d_bytes`` argument of every ``repro.compute.launch``
span that starts in it (the same count as
``ComputeBackend.stats["h2d_bytes"]``), summed by ``phases.phases``, over
packets delivered.  Read from the traced run's profile; a program whose
launch spans carry no such argument has nothing to read."""
from chipbench import phases


def read(r):
    ph = phases.of_record(r)
    n = sum(r.window.delivered)
    if ph is None or ph.h2d_bytes is None or n <= 0:
        return None
    return ph.h2d_bytes / n
