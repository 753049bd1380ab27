"""Share of the traced span in which the level kernel ran, in percent, the
mean over the cell's chips. Nothing is read where the kernel never ran."""

#: the level kernel of kernels/histogram.py: a Pallas custom call named in
#: the trace after the function that calls it, ``fused_level_split_tpu.<n>``
KERNEL = r"^fused_level_split_tpu"


def read(run):
    if run.trace is None or not run.device_planes:
        return None
    span = run.span_s()
    kernel = run.op_s(KERNEL)
    if span <= 0 or kernel <= 0:
        return None
    return 100.0 * kernel / span
