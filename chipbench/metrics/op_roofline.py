"""The worker operators' share of the memory roofline, in percent: the
least time their bytes need at the chip's HBM bandwidth, over the device
time of their programs in the trace.

Bytes needed are every operand and build column read once at its true
row count plus the output written once, counted from the tables passed
into and out of ``device_ops.run`` (``probes.py``), so they do not
depend on how the operators are implemented. The operators do almost no
arithmetic, so memory bounds them."""

PROGRAMS = ("jit__program", "jit__head")


def read(run):
    if run.trace is None or run.probes is None or not run.probes.op_bytes:
        return None
    device_s = sum(run.trace["program_s"].get(p, 0.0) for p in PROGRAMS)
    if device_s <= 0:
        return None
    least_s = run.probes.op_bytes / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
