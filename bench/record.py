"""What one run measured, as the metric readers (``metrics/*.py``) read it.

Every rate and share is taken over whole searches: the span runs from the
window's start to the end of the last search that finished inside it, and
the work counted is that of the searches in it, all of it. The search that
the window's end cancels is left out, work and time: a search streams its
long configurations first and its short ones last, so a cut inside one
would make the rate swing with where the cut falls.
"""
from __future__ import annotations

import dataclasses
import math

from bench import trace_reduce


@dataclasses.dataclass
class RunRecord:
    cell: object
    landed: list                 # drive.Landed, in the order they landed
    t0: float                    # window start, host clock
    ends: list                   # host clock at the end of each whole search
    n_executors: int
    chips: int
    setup: dict                  # convert_s, compile_s of the set-up
    train_rows: int
    features: int
    peak: dict | None            # the chip's row of peaks.json
    trace: trace_reduce.Trace | None = None
    #: the traced span [lo, hi) in the trace's clock, and the devices in it
    lo: int = 0
    hi: int = 0
    device_planes: tuple[str, ...] = ()

    # -- host clock ---------------------------------------------------------
    def done(self) -> list:
        """The results of the whole searches that trained."""
        return [lr.result for lr in self.landed
                if lr.search < len(self.ends) and lr.result.ok]

    def scored(self) -> list:
        """The results of the whole searches that trained and were scored."""
        return [r for r in self.done()
                if r.score is not None and math.isfinite(r.score)]

    def span_s(self) -> float:
        """Window start to the end of the last whole search."""
        if self.trace is not None:
            return (self.hi - self.lo) / 1e9
        return max(self.ends, default=self.t0) - self.t0

    def configs_per_s(self) -> float | None:
        span = self.span_s()
        n = len(self.scored())
        return n / span if n and span > 0 else None

    # -- device trace -------------------------------------------------------
    def attach_trace(self, trace: trace_reduce.Trace, devices) -> None:
        """Keep the trace and find the traced span: the benchmark's
        ``bench.window`` span, cut at the last ``bench.search_end`` inside
        it (an empty span where no search finished)."""
        self.trace = trace
        win = trace.span("bench.window")
        if win is None:
            raise RuntimeError("the trace holds no bench.window span")
        marks = [ev.start for ev in trace.spans("bench.search_end")
                 if win.start <= ev.start <= win.end]
        self.lo, self.hi = win.start, (max(marks) if marks else win.start)
        names = sorted(trace.devices)
        ids = {f"TPU:{d.id}" for d in devices}
        used = [n for n in names if n.rsplit("/device:", 1)[-1] in ids]
        self.device_planes = tuple(used or names[:len(devices)])

    def device_events(self, plane: str) -> list:
        return self.trace.devices.get(plane, [])

    def busy_s(self) -> float:
        """Seconds some operation ran on the device, mean over the chips."""
        per = [trace_reduce.busy_ns(self.device_events(p), self.lo, self.hi)
               for p in self.device_planes]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def op_s(self, pattern: str) -> float:
        """Device seconds of the operations matching ``pattern``, mean over
        the chips."""
        per = [trace_reduce.op_ns(trace_reduce.matching(self.device_events(p),
                                                        pattern),
                                  self.lo, self.hi)
               for p in self.device_planes]
        return sum(per) / len(per) / 1e9 if per else 0.0

    def breakdown(self) -> dict:
        """The ten device operations that took most time and the ten host
        activities under which the devices sat idle longest, summed over
        the chips."""
        ops: dict[str, int] = {}
        idle: dict[str, int] = {}
        for p in self.device_planes:
            evs = self.device_events(p)
            for name, ns in trace_reduce.top_ops(evs, self.lo, self.hi, k=10**6):
                ops[name] = ops.get(name, 0) + ns
            gaps = trace_reduce.gaps(trace_reduce.union(evs, self.lo, self.hi),
                                     self.lo, self.hi)
            for name, ns in trace_reduce.attribute_gaps(gaps, self.trace.host,
                                                        k=10**6):
                idle[name] = idle.get(name, 0) + ns

        def top(d):
            return [[k, v / 1e9] for k, v in
                    sorted(d.items(), key=lambda kv: (-kv[1], kv[0]))[:10]]

        return {"device_ops": top(ops), "idle_gaps": top(idle)}
