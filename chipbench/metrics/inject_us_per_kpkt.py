"""Host time spent inside ``Deployment.inject`` (the tenant API down to
``ComputeBackend.inject``), in microseconds per thousand packets injected
in the window."""


def read(r):
    if r.inject_pkts <= 0:
        return None
    return r.inject_s * 1e6 / (r.inject_pkts / 1000.0)
