"""Share of the traced span in which the device sat idle while an executor
scored its models, inside a ``repro.eval`` span of the program and outside
every ``repro.train`` span, in percent, the mean over the cell's chips.
Nothing is read where no ``repro.eval`` span lies in the traced span."""
from bench.metrics.train_idle_share import idle_ns, spans_in


def read(run):
    scoring = spans_in(run, "repro.eval")
    if not scoring:
        return None
    train = spans_in(run, "repro.train")
    idle = idle_ns(run, scoring + train) - idle_ns(run, train)
    return 100.0 * idle / (run.hi - run.lo)
