"""Named host spans at the program's layer boundaries.

A :class:`span` is two things at once: a profiler trace annotation, which
costs next to nothing while no profiler runs, and a running total of the
host seconds spent inside it, kept in a dict its caller owns (for the
compute runtime, ``ComputeBackend.span_s``).  Names are fixed strings under
``repro.`` named after the layer; per-call details such as row counts go in
as keyword arguments, which the profiler stores beside the event, so the
event's name stays the same on every call.
"""
from __future__ import annotations

from time import perf_counter

from jax.profiler import TraceAnnotation

#: the scheduler's fair drain and the grouping of batches for coalescing
SCHED_ORDER = "repro.sched.order"
#: coalescing, padding to the bucket, the ``valid`` mask, host to device
STAGE = "repro.compute.stage"
#: the jitted program call
LAUNCH = "repro.compute.launch"
#: the host blocked on the device: kernels and transfers still in flight
SYNC = "repro.compute.sync"
#: slicing each launch's output back into its batches
SPLIT = "repro.compute.split"
#: every span the compute runtime opens
PHASES = (SCHED_ORDER, STAGE, LAUNCH, SYNC, SPLIT)


class span:
    """``with span(name, totals, **args):`` opens a trace annotation
    ``name`` (with ``args`` as its arguments) and adds the block's host
    seconds to ``totals[name]``, which must exist."""

    __slots__ = ("_name", "_totals", "_annotation", "_t0")

    def __init__(self, name: str, totals: dict[str, float], **args):
        self._name = name
        self._totals = totals
        self._annotation = TraceAnnotation(name, **args)

    def __enter__(self) -> None:
        self._t0 = perf_counter()
        self._annotation.__enter__()

    def __exit__(self, *exc) -> None:
        self._annotation.__exit__(*exc)
        self._totals[self._name] += perf_counter() - self._t0
