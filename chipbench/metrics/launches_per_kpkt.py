"""Program launches of the compute runtime (the change in
``ComputeBackend.stats["dispatches"]`` over the window, summed over
shards) per thousand packets delivered.  A count, not a time."""


def read(r):
    n = sum(r.window.delivered)
    if n <= 0:
        return None
    return r.dispatches / (n / 1000.0)
