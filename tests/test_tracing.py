"""Spans inside the search program (``repro.core.tracing``): a tiny
two-family search captured with ``jax.profiler.trace`` holds every span at
its layer boundary, joined by ``search``/``unit``, and the seconds the
results report are the spans' own intervals."""
import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.tabular  # noqa: F401  (registers the estimators)
from bench import trace_reduce
from repro.core import DenseMatrix, GridBuilder, SearchSpec, Session, tracing
from repro.core.tracing import span
from repro.kernels import ops

NAMES = ("repro.session.plan", "repro.unit", "repro.convert", "repro.train",
         "repro.eval", "repro.eval.metric")
#: spans that run inside one unit, on the unit's thread
IN_UNIT = ("repro.train", "repro.eval", "repro.eval.metric")


def _host_spans(log_dir):
    """Every ``repro.*`` host event as (line, name, start, end, stats); the
    line stands for the thread that wrote it."""
    files = sorted(log_dir.rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(files[-1]))
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, ln in enumerate(plane.lines):
            for e in ln.events:
                if e.name.startswith("repro."):
                    out.append(((plane.name, i), e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    rng = np.random.default_rng(20261018)
    x = rng.normal(size=(300, 6)).astype(np.float32)
    y = (x[:, 0] + 0.3 * rng.normal(size=300) > 0).astype(np.float32)
    train, valid = DenseMatrix(x, y).split((0.7, 0.3), seed=5)
    spec = SearchSpec(
        spaces=[GridBuilder("gbdt").add_grid("eta", [0.1, 0.3])
                .add_grid("round", [2]).add_grid("max_depth", [2]).build(),
                GridBuilder("logreg").add_grid("c", [0.5]).build()],
        n_executors=1, fuse=True, max_fuse=4)
    session = Session(spec)
    log_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(log_dir)):
        results = list(session.results(train, valid))
    return session, results, log_dir, _host_spans(log_dir)


def test_every_span_is_in_the_trace(traced):
    _session, results, log_dir, spans = traced
    assert results and all(r.ok and r.score is not None for r in results)
    names = {ev.name for ev in trace_reduce.load(log_dir).host}
    assert set(NAMES) <= names
    assert set(NAMES) == {name for _, name, *_ in spans}
    # one fused GBDT unit and one solo logistic regression
    units = [st for _, name, _, _, st in spans if name == "repro.unit"]
    assert sorted((u["family"], u["size"]) for u in units) == [("gbdt", 2),
                                                             ("logreg", 1)]


def test_spans_carry_the_search_and_the_unit(traced):
    session, _results, _log_dir, spans = traced
    for _, name, _, _, st in spans:
        assert st["search"] == session.search_id, name
        if name == "repro.session.plan":
            assert st["round"] == 0 and st["n_units"] == 2
        else:
            assert "unit" in st, name
    convert = [st for _, name, _, _, st in spans if name == "repro.convert"]
    assert all(st["bytes"] > 0 and st["format"] for st in convert)


def test_layer_spans_lie_inside_a_unit_on_the_same_thread(traced):
    spans = traced[3]
    units = [s for s in spans if s[1] == "repro.unit"]
    for line, name, a, b, st in spans:
        if name not in IN_UNIT:
            continue
        outer = [u for u in units if u[0] == line and u[2] <= a and b <= u[3]]
        assert len(outer) == 1, name
        assert outer[0][4]["unit"] == st["unit"]


def test_result_seconds_are_the_span_durations(traced):
    _session, results, _log_dir, spans = traced

    def span_s(name, unit):
        hit = [(b - a) / 1e9 for _, n, a, b, st in spans
               if n == name and st["unit"] == unit]
        assert len(hit) == 1, (name, unit)
        return hit[0]

    for r in results:
        members = [m for m in results if m.batch_size == r.batch_size]
        unit = (r.task.task_id if r.batch_size == 1
                else -1 - min(m.task.task_id for m in members))
        assert r.train_seconds * r.batch_size == pytest.approx(
            span_s("repro.train", unit), abs=1e-3)
        assert r.eval_seconds * r.batch_size == pytest.approx(
            span_s("repro.eval", unit), abs=1e-3)
    # a build's seconds go to one member of the unit that built it
    builds = sorted((b - a) / 1e9 for _, n, a, b, st in spans
                    if n == "repro.convert" and "eval" not in st["format"])
    paid = sorted(r.convert_seconds for r in results if r.convert_seconds > 0)
    assert len(builds) == len(paid) == 2
    assert paid == pytest.approx(builds, abs=1e-3)


def test_tree_fits_name_the_rows_their_levels_read(traced):
    """A GBDT fit's ``repro.train`` span says which rows its levels below
    the root read (``ops.level_rows`` of the fit's width); a dense fit's
    span says nothing of it."""
    spans = traced[3]
    train = {st["family"]: st for _, name, _, _, st in spans
             if name == "repro.train"}
    assert train["gbdt"]["level_rows"] == ops.level_rows(6, 64)
    assert "level_rows" not in train["logreg"]


def test_a_span_passes_its_join_keys_to_the_spans_inside_it():
    with span("repro.unit", search=4, unit=-9, family="gbdt") as outer:
        with span("repro.train", family="gbdt", size=1) as inner:
            assert tracing._TL.join == {"search": 4, "unit": -9}
    assert tracing._TL.join == {}
    assert outer.seconds >= inner.seconds >= 0.0
    tracing.annotate(level_rows="all")      # no span open: nothing to mark
