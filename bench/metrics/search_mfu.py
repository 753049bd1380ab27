"""The whole search's share of the chips' bf16 peak, in percent: the
operations the algorithms require for the configurations scored in the
span (``counts.py``, for the configuration's outputs per row) over span x
chips x peak."""
from bench import counts


def read(run):
    if run.peak is None:
        return None
    span = run.span_s()
    done = run.scored()
    if span <= 0 or not done:
        return None
    ops = sum(counts.config_ops(r.task.estimator, r.task.params,
                                run.train_rows, run.features,
                                run.outputs) for r in done)
    return 100.0 * ops / (span * run.chips * float(run.peak["bf16_flops_per_s"]))
