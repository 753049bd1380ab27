"""Share of the traced span in which the device sat idle while an executor
trained, inside a ``repro.train`` span of the program, in percent, the mean
over the cell's chips. Nothing is read where no such span lies in the
traced span.

With ``eval_idle_share``, ``between_units_idle_share`` and the idle time
inside ``repro.unit`` spans outside training and scoring (conversion, the
executor's own work) it splits ``device_idle_share`` by layer. Where spans
of two executors overlap, the idle time counts once: to training before
scoring, and to a unit before the time between units."""
from bench import trace_reduce as tr


def _ns(intervals) -> int:
    return sum(b - a for a, b in intervals)


def idle_ns(run, spans) -> float:
    """Nanoseconds the devices sat idle inside the union of ``spans``,
    clipped to the traced span, the mean over the chips."""
    inside = tr.union(spans, run.lo, run.hi)
    per = []
    for plane in run.device_planes:
        idle = tr.gaps(tr.union(run.device_events(plane), run.lo, run.hi),
                       run.lo, run.hi)
        both = tr.union([tr.Event(a, b, "") for a, b in idle + inside],
                        run.lo, run.hi)
        per.append(_ns(idle) + _ns(inside) - _ns(both))
    return sum(per) / len(per)


def spans_in(run, name: str) -> list:
    """The program's ``name`` spans that overlap the traced span; none where
    the run was not traced or its span is empty."""
    if run.trace is None or not run.device_planes or run.hi <= run.lo:
        return []
    return [ev for ev in run.trace.spans(name)
            if tr.clip(ev, run.lo, run.hi) > 0]


def read(run):
    train = spans_in(run, "repro.train")
    if not train:
        return None
    return 100.0 * idle_ns(run, train) / (run.hi - run.lo)
