"""Share, in percent, of the datapath programs' device time that the
bytes their chains must move (``chipbench.work.chain_bytes`` per delivered
packet) would take at the chip's HBM bandwidth (``peaks.json``).  A
bandwidth roofline only: no integer vector peak is published for the v5e.

The datapath programs are the runtime's jitted dispatch programs, which the
trace names ``jit_traced`` (the fused megakernel and the composed XLA path
alike).  A traced run in which no such program ran has nothing to read."""

#: the trace's names of the compute runtime's dispatch programs
DATAPATH_PROGRAMS = r"^jit_traced"


def read(r):
    if r.trace is None:
        return None
    busy = r.trace.module_busy_s(DATAPATH_PROGRAMS)
    if busy <= 0 or r.datapath_bytes <= 0:
        return None
    return 100.0 * (r.datapath_bytes / r.peaks["hbm_bytes_per_s"]) / busy
