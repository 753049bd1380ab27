"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is reduced to plain intervals first (:func:`load`): for each device,
the operations that ran on it; for the host, every named event of every
thread. The functions below work on those intervals alone, so they are
tested on synthetic traces without a chip.

- busy time: the union of a device's operation intervals inside a window;
- an operation class's time: the summed durations of its events, clipped
  to the window;
- idle gaps: the stretches inside the window where no operation ran on a
  device, each named by what the host was doing then.

Times are in nanoseconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq
import re
from pathlib import Path
from typing import Callable, Iterable

#: host spans the benchmark itself writes around its calls into the program
BENCH_SPAN_PREFIX = "bench."
#: device gaps shorter than this are counted together, unnamed: the time
#: between back-to-back operations of one program
SHORT_GAP_NS = 50_000
SHORT_GAP_NAME = "between ops (<50us)"


@dataclasses.dataclass(frozen=True)
class Event:
    start: int
    end: int
    #: a device operation's HLO instruction name (``fusion.443``), a host
    #: event's name
    name: str
    #: a device operation's result type (``s32[300000,28]``), where known
    shape: str = ""

    @property
    def duration(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    #: device name -> its operations, sorted by start
    devices: dict[str, list[Event]]
    #: every host event, sorted by start
    host: list[Event]

    def span(self, name: str) -> Event | None:
        """The first host event of this name (the benchmark's own spans)."""
        for ev in self.host:
            if ev.name == name:
                return ev
        return None

    def spans(self, name: str) -> list[Event]:
        return [ev for ev in self.host if ev.name == name]


def clip(ev: Event, lo: int, hi: int) -> int:
    return max(0, min(ev.end, hi) - max(ev.start, lo))


def union(events: Iterable[Event], lo: int, hi: int) -> list[tuple[int, int]]:
    """The union of the events' intervals clipped to ``[lo, hi)``, as sorted
    disjoint intervals."""
    out: list[tuple[int, int]] = []
    for ev in sorted(events, key=lambda e: e.start):
        a, b = max(ev.start, lo), min(ev.end, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def busy_ns(events: Iterable[Event], lo: int, hi: int) -> int:
    return sum(b - a for a, b in union(events, lo, hi))


def matching(events: Iterable[Event], pattern: str) -> list[Event]:
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev.name)]


def op_ns(events: Iterable[Event], lo: int, hi: int) -> int:
    """Summed durations of the events, clipped to ``[lo, hi)``."""
    return sum(clip(ev, lo, hi) for ev in events)


def self_ns(events: Iterable[Event], lo: int, hi: int) -> list[tuple[Event, int]]:
    """Each event's own time in ``[lo, hi)``: its clipped duration less what
    the events nested inside it cover. A ``while`` loop's operation spans
    the operations of its body, and counting both would count twice."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    own = [clip(ev, lo, hi) for ev in evs]
    stack: list[int] = []
    for i, ev in enumerate(evs):
        while stack and evs[stack[-1]].end <= ev.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= clip(ev, lo, hi)
        stack.append(i)
    return [(ev, max(0, d)) for ev, d in zip(evs, own)]


def top_ops(events: Iterable[Event], lo: int, hi: int, k: int = 10,
            key: Callable[[Event], str] | None = None) -> list[tuple[str, int]]:
    """The ``k`` operation classes with the most own device time in the
    window."""
    key = key or op_class
    tot: dict[str, int] = {}
    for ev, d in self_ns(events, lo, hi):
        if d:
            name = key(ev)
            tot[name] = tot.get(name, 0) + d
    return sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:k]


_NUMBERED = re.compile(r"[._-]?\d+$")


def op_class(ev: Event) -> str:
    """An operation's name without the number XLA appends to each instance,
    with its result type: ``fusion.123`` and ``fusion.7`` are one class
    where both make an ``s32[300000,28]``."""
    name = _NUMBERED.sub("", ev.name) or ev.name
    return f"{name} {ev.shape}" if ev.shape else name


def gaps(busy: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    """The stretches of ``[lo, hi)`` outside the busy intervals."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return out


def attribute_gaps(idle: list[tuple[int, int]], host: list[Event],
                   k: int = 10) -> list[tuple[str, int]]:
    """Name each idle gap by what the host was doing in it, and sum by name.

    Of the host events that overlap a gap, the one that overlaps it most
    names it (on a tie, the shorter, more specific one). The benchmark's
    own spans (``bench.*``) name a gap only where no event of the program
    or the runtime overlaps it. Gaps under ``SHORT_GAP_NS`` are summed
    under one name. Returns the ``k`` names with the most idle time.
    """
    host = sorted(host, key=lambda e: e.start)
    starts = [e.start for e in host]
    tot: dict[str, int] = {}
    active: list[tuple[int, int, Event]] = []   # heap by end
    j = 0
    for a, b in sorted(idle):
        d = b - a
        if d < SHORT_GAP_NS:
            tot[SHORT_GAP_NAME] = tot.get(SHORT_GAP_NAME, 0) + d
            continue
        stop = bisect.bisect_left(starts, b)
        while j < stop:
            ev = host[j]
            heapq.heappush(active, (ev.end, j, ev))
            j += 1
        while active and active[0][0] <= a:
            heapq.heappop(active)
        best, best_key = "no host event", None
        for end, _, ev in active:
            if end <= a:
                continue
            ov = min(ev.end, b) - max(ev.start, a)
            if ov <= 0:
                continue
            own = ev.name.startswith(BENCH_SPAN_PREFIX)
            kk = (own, -ov, ev.duration)
            if best_key is None or kk < best_key:
                best, best_key = ev.name, kk
        tot[best] = tot.get(best, 0) + d
    return sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:k]


# ---------------------------------------------------------------------------
# reading the profiler's files
# ---------------------------------------------------------------------------

def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


_HLO = re.compile(r"^%?([\w.\-]+) = (\(|[a-z0-9]+\[[\d,]*\])?")


def hlo_name(text: str) -> tuple[str, str]:
    """(``fusion.443``, ``s32[300000,28]``) from the HLO instruction
    ``%fusion.443 = s32[300000,28]{0,1} fusion(...)`` that a TPU trace gives
    as an operation's name; a tuple-valued result reads ``tuple``."""
    m = _HLO.match(text)
    if not m:
        return text, ""
    shape = m.group(2) or ""
    return m.group(1), "tuple" if shape == "(" else shape


def load(log_dir: str | Path, ops_line: str = "XLA Ops") -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``: each device plane's
    ``ops_line`` (every line of it where it has none) and every host line."""
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        lines = list(plane.lines)
        if _is_device_plane(plane.name):
            chosen = [ln for ln in lines if ln.name == ops_line] or lines
            evs = [Event(int(e.start_ns), int(e.start_ns + e.duration_ns),
                         *hlo_name(e.name))
                   for ln in chosen for e in ln.events]
            devices[plane.name] = sorted(evs, key=lambda e: e.start)
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for e in ln.events:
                    if e.duration_ns > 0:
                        host.append(Event(int(e.start_ns),
                                          int(e.start_ns + e.duration_ns),
                                          e.name))
    host.sort(key=lambda e: e.start)
    return Trace(devices=devices, host=host)
