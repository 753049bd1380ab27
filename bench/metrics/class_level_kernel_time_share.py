"""Share of the traced span in which the level kernel ran, in percent, where
each launch carries all K trees of a round's level: the same device operation
as ``level_kernel_time_share``, read in the cells of a K-class
configuration."""
from bench.cells import BENCH_DIR, load_module

_LEVEL = load_module(BENCH_DIR / "metrics" / "level_kernel_time_share.py")


def read(run):
    if run.outputs < 2:
        return None
    return _LEVEL.read(run)
