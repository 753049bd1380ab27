"""The level kernel's share of its roofline, in percent: the least time the
chip needs for the level work of the trees the traced span trained (the
larger of their histogram adds over peak FLOP/s and their bytes over peak
HBM bytes/s, ``counts.py``), over the kernel's device time there. The bytes
count one read of the bin ids and of g and h per tree, a floor for any
implementation, so this share is a lower bound."""

from bench import counts
#: the level kernel of kernels/histogram.py: a Pallas custom call named in
#: the trace after the function that calls it, ``fused_level_split_tpu.<n>``
KERNEL = r"^fused_level_split_tpu"


def read(run):
    if run.trace is None or not run.device_planes or run.peak is None:
        return None
    kernel = run.op_s(KERNEL) * len(run.device_planes)
    done = [r for r in run.scored() if r.task.estimator in ("gbdt", "forest")]
    if kernel <= 0 or not done:
        return None
    ops = sum(counts.level_kernel_ops(r.task.estimator, r.task.params,
                                      run.train_rows, run.features) for r in done)
    nbytes = sum(counts.level_kernel_bytes(r.task.estimator, r.task.params,
                                           run.train_rows, run.features)
                 for r in done)
    least, _bound = counts.least_seconds(ops, nbytes, run.peak)
    return 100.0 * least / kernel
