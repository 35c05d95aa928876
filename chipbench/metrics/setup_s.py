"""Seconds from the process's start to the window's start: imports, the
tenants' data, deployment, and the warm-up of every program shape the
window uses (compilation, or loading it from the persistent cache)."""


def read(r):
    return r.setup_s
