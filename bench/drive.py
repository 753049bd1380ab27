"""Set-up and the measured window of one cell, through the program's own
entry points: ``SearchSpec`` -> ``Session.results`` on one backend that
lives for the whole run.

The traffic is a closed loop of searches, one in flight: the next search is
submitted when the last has streamed all its scored results. Searches are
taken from the traffic file in order, from the first, round and round. The
search in flight when the window closes is cancelled; the host clock at the
end of every search that finished inside the window is kept, since rates
and shares are taken over whole searches.
"""
from __future__ import annotations

import collections
import dataclasses
import sys
import time
from typing import Callable

import numpy as np

from bench.cells import ROOT, Cell


@dataclasses.dataclass
class Landed:
    """One result as the window saw it."""
    t: float                 # host clock when it reached the benchmark
    result: object           # the program's TaskResult
    search: int = 0          # the search of the window it belongs to


class Monitor:
    """JAX's compile events, split by phase (set-up, window, check)."""

    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.phase = "setup"
        self.seconds: dict[str, collections.Counter] = collections.defaultdict(
            collections.Counter)
        self.counts: dict[str, collections.Counter] = collections.defaultdict(
            collections.Counter)

    def install(self) -> None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        self.seconds[self.phase][name] += secs
        self.counts[self.phase][name] += 1

    def _event(self, name, **_):
        self.counts[self.phase][name] += 1

    def programs(self, phase: str) -> int:
        """Programs obtained (compiled or loaded from the cache)."""
        return self.counts[phase][self.BACKEND_COMPILE]

    def compiled(self, phase: str) -> int:
        return self.counts[phase][self.CACHE_MISS]

    def compile_seconds(self, phase: str) -> float:
        return self.seconds[phase][self.BACKEND_COMPILE]


def split_standardize(x: np.ndarray, y: np.ndarray, fractions, seed: int):
    """Shuffle rows by the seed, cut them by ``fractions`` and standardise
    every part with the first part's mean and deviation. Returns the parts
    as (x, y) pairs."""
    n = x.shape[0]
    idx = np.random.default_rng(seed).permutation(n)
    total = float(sum(fractions))
    parts, start = [], 0
    for i, f in enumerate(fractions):
        stop = n if i == len(fractions) - 1 else start + int(n * f / total)
        part = idx[start:stop]
        parts.append((x[part], y[part]))
        start = stop
    mean = parts[0][0].mean(axis=0)
    std = parts[0][0].std(axis=0)
    std = np.where(std < 1e-12, 1.0, std)
    return [(((px - mean) / std).astype(np.float32), py) for px, py in parts]


def make_data(cell: Cell, seed: int):
    """The configuration's rows from the seed: (train (x, y), valid (x, y))."""
    x, y = cell.generator().generate(cell.config, seed)
    train, valid, _test = split_standardize(x, y, cell.config["split"], seed)
    return train, valid


def _spaces(search: list[dict]):
    from repro.core.grid import SearchSpace

    groups: dict[str, list[dict]] = {}
    for c in search:
        groups.setdefault(c["estimator"], []).append(dict(c["params"]))
    return [SearchSpace(est, tuple(cfgs)) for est, cfgs in groups.items()]


class Driver:
    """One backend, one dataset, searches submitted one after another."""

    def __init__(self, cell: Cell, seed: int, train, valid, devices):
        import repro.tabular  # noqa: F401  (registers the four estimators)
        from repro.core.data_format import DenseMatrix

        self.cell, self.seed = cell, seed
        self.traffic = cell.traffic
        self.train = DenseMatrix(*train)
        self.valid = DenseMatrix(*valid)
        self.devices = devices
        self.backend = self._backend()

    def _backend(self):
        t = self.traffic
        if t["backend"] == "local":
            from repro.core import LocalExecutorPool

            return LocalExecutorPool(int(t["n_executors"]))
        if t["backend"] == "mesh_slices":
            import jax

            from repro.core import MeshSliceExecutorPool

            mesh = jax.sharding.Mesh(np.asarray(self.devices), ("data",))
            return MeshSliceExecutorPool(mesh, n_slices=int(t["n_executors"]))
        raise ValueError(f"unknown backend {t['backend']!r}")

    def spec(self, search: list[dict]):
        from repro.core import AnalyticProfiler, SearchSpec

        t = self.traffic
        return SearchSpec(spaces=_spaces(search), n_executors=int(t["n_executors"]),
                          policy=t["policy"], profiler=AnalyticProfiler(),
                          metric=self.cell.config["metric"], seed=self.seed,
                          fuse=bool(t["fuse"]),
                          max_fuse=int(t["max_fuse"]))

    def session(self, search: list[dict]):
        """A new Session on the run's backend. The backend's journal is
        replaced first: every search numbers its tasks from 0, and a journal
        that holds an earlier search's tasks would skip them."""
        from repro.core import Session
        from repro.core.fault import SearchWAL

        self.backend.wal = SearchWAL(None)
        return Session(self.spec(search), backend=self.backend)

    def run_search(self, i: int, deadline: float | None = None,
                   on_result: Callable[[Landed], None] | None = None
                   ) -> tuple[list[Landed], bool]:
        """Search ``i`` of the loop, and whether it finished: it runs to its
        end, or until a result lands after ``deadline`` (the search is then
        cancelled; the task in flight finishes before this returns)."""
        searches = self.traffic["searches"]
        session = self.session(searches[i % len(searches)])
        out: list[Landed] = []
        stream = session.results(self.train, self.valid)
        finished = True
        try:
            for res in stream:
                t = time.perf_counter()
                if deadline is not None and t > deadline:
                    finished = False
                    break
                landed = Landed(t, res, i)
                out.append(landed)
                if on_result is not None:
                    on_result(landed)
        finally:
            stream.close()
        return out, finished

    def warmup(self) -> tuple[float, int]:
        """Every search the traffic names for warming up, once: fills the
        prepared-data cache and loads every program the window runs.
        Returns (conversion seconds, results)."""
        convert, n = 0.0, 0
        for i in self.traffic["warmup"]:
            for landed in self.run_search(int(i))[0]:
                convert += landed.result.convert_seconds
                n += 1
                if not landed.result.ok:
                    print(f"warm-up: {landed.result.task.key()} failed: "
                          f"{landed.result.error}", file=sys.stderr)
        return convert, n

    def window(self, seconds: float, annotate: Callable | None = None):
        """The closed loop for ``seconds``. Returns (start, landed results,
        the host clock at the end of each search that finished inside the
        window); with ``annotate``, each such end is marked
        ``bench.search_end`` in the trace."""
        t0 = time.perf_counter()
        deadline = t0 + seconds
        landed: list[Landed] = []
        ends: list[float] = []
        i = 0
        while time.perf_counter() < deadline:
            if annotate is not None:
                with annotate(f"bench.search.{i}"):
                    got, finished = self.run_search(i, deadline,
                                                    self._mark(annotate))
            else:
                got, finished = self.run_search(i, deadline)
            t = time.perf_counter()
            if finished and t <= deadline:
                ends.append(t)
                if annotate is not None:
                    with annotate("bench.search_end"):
                        pass
            landed.extend(got)
            i += 1
        return t0, landed, ends

    @staticmethod
    def _mark(annotate):
        def mark(_landed):
            with annotate("bench.result"):
                pass
        return mark

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        self.backend.prepared_cache.clear()
        self.backend = None


def src_path() -> str:
    return str(ROOT / "src")
