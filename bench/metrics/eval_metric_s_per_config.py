"""Seconds of host metric per scored configuration: the program's
``repro.eval.metric`` spans (the reduction of a unit's probabilities to
scores, the AUC) summed inside the traced span, over the configurations
scored there. Nothing is read where no such span lies in the traced span."""
from bench import trace_reduce as tr
from bench.metrics.train_idle_share import spans_in


def read(run):
    metric = spans_in(run, "repro.eval.metric")
    done = run.scored()
    if not metric or not done:
        return None
    return tr.op_ns(metric, run.lo, run.hi) / 1e9 / len(done)
