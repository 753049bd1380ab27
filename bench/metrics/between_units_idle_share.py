"""Share of the traced span in which the device sat idle while no
``repro.unit`` span of the program was open on any thread, in percent, the
mean over the cell's chips: the session's planning, dispatch and result
handling, and the loop between searches. Nothing is read where no
``repro.unit`` span lies in the traced span."""
from bench import trace_reduce as tr
from bench.metrics.train_idle_share import idle_ns, spans_in


def read(run):
    units = spans_in(run, "repro.unit")
    if not units:
        return None
    whole = [tr.Event(run.lo, run.hi, "")]
    return 100.0 * (idle_ns(run, whole) - idle_ns(run, units)) / (run.hi - run.lo)
