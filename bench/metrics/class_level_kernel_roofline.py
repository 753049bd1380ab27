"""The level kernel's share of its roofline, in percent, where each launch
carries all K trees of a round's level: the same device operation and the
same counts (``counts.py``, at the configuration's outputs per row) as
``level_kernel_roofline``, read in the cells of a K-class configuration.
A lower bound, as that metric is."""
from bench.cells import BENCH_DIR, load_module

_LEVEL = load_module(BENCH_DIR / "metrics" / "level_kernel_roofline.py")


def read(run):
    if run.outputs < 2:
        return None
    return _LEVEL.read(run)
