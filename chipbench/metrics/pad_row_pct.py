"""Share, in percent, of the rows launched in the window that are padding:
over every ``repro.compute.launch`` span that starts in it, bucket rows
less real rows (the span's ``bucket`` and ``rows`` arguments, the same
counts as ``ComputeBackend.stats["pad_rows"]`` and ``["rows_launched"]``)
over bucket rows.  Read from the traced run's profile; a program without
the span has nothing to read."""
from chipbench import phases


def read(r):
    ph = phases.of_record(r)
    if ph is None or ph.bucket_rows <= 0:
        return None
    return 100.0 * (ph.bucket_rows - ph.rows) / ph.bucket_rows
