"""The per-layer metrics that read the program's spans, on synthetic
traces: idle time split by layer, clipped to the traced span, the mean over
the chips, and nothing read where the program wrote no spans."""
import types

import pytest

from bench import cells, trace_reduce as tr
from bench.record import RunRecord

MS = 1_000_000
SPLIT = ("train_idle_share", "eval_idle_share", "between_units_idle_share")


def ev(a, b, name):
    return tr.Event(int(a * MS), int(b * MS), name)


def reader(name):
    return cells.load_module(cells.BENCH_DIR / "metrics" / f"{name}.py")


def record(devices, host, n_scored=4):
    res = types.SimpleNamespace(
        ok=True, score=0.9, train_seconds=1.0, eval_seconds=0.5,
        convert_seconds=0.0,
        task=types.SimpleNamespace(estimator="gbdt", params={"round": 3}))
    landed = [types.SimpleNamespace(result=res, search=0)] * n_scored
    rec = RunRecord(cell=types.SimpleNamespace(name="t"), landed=landed,
                    t0=0.0, ends=[1.0], n_executors=1, chips=len(devices),
                    setup={}, train_rows=1, features=1, outputs=1,
                    peak=None)
    rec.attach_trace(tr.Trace(devices=devices, host=host),
                     [types.SimpleNamespace(id=i) for i in range(len(devices))])
    return rec


#: the window is [0, 100); the last search ends at 90, so the traced span
#: is [0, 90). Two units lie inside it; a third runs past its end.
HOST = [
    ev(0, 100, "bench.window"), ev(90, 90, "bench.search_end"),
    ev(0, 10, "repro.session.plan"), ev(40, 50, "repro.session.plan"),
    ev(10, 40, "repro.unit"), ev(10, 12, "repro.convert"),
    ev(12, 30, "repro.train"), ev(30, 38, "repro.eval"),
    ev(35, 37, "repro.eval.metric"),
    ev(50, 80, "repro.unit"), ev(50, 70, "repro.train"),
    ev(70, 78, "repro.eval"), ev(76, 78, "repro.eval.metric"),
    ev(85, 95, "repro.unit"), ev(85, 93, "repro.train"),
    ev(92, 93, "repro.eval.metric"),
    ev(5, 60, "np.asarray(jax.Array)"),
]
#: chip 0 idles 49 ms of 90: [0,5) [7,14) [28,31) [34,52) [68,71) [75,86)
#: [88,90); chip 1 never idles
CHIP0 = [ev(5, 7, "fusion.1"), ev(14, 28, "fusion.2"), ev(31, 34, "fusion.3"),
         ev(52, 68, "fusion.4"), ev(71, 75, "fusion.5"), ev(86, 88, "fusion.6")]
CHIP1 = [ev(0, 90, "while.1")]


def test_idle_time_is_split_by_the_span_it_falls_in():
    rec = record({"/device:TPU:0": CHIP0}, HOST)
    got = {name: reader(name).read(rec) for name in SPLIT}
    # training: [12,14) [28,30) [50,52) [68,70) and, clipped at the span's
    # end, [85,86) [88,90); scoring: [30,31) [34,38) [70,71) [75,78)
    assert got["train_idle_share"] == pytest.approx(100 * 11 / 90)
    assert got["eval_idle_share"] == pytest.approx(100 * 9 / 90)
    # outside every unit: [0,5) [7,10) [40,50) [80,86)
    assert got["between_units_idle_share"] == pytest.approx(100 * 23 / 90)


def test_the_split_adds_up_to_the_device_idle_share():
    rec = record({"/device:TPU:0": CHIP0}, HOST)
    idle = reader("device_idle_share").read(rec)
    # inside a unit but outside training and scoring: the conversion
    # [10,12), then [38,40) and [78,80)
    in_unit = 100 * 6 / 90
    assert sum(reader(n).read(rec) for n in SPLIT) + in_unit == pytest.approx(
        idle)


def test_shares_are_the_mean_over_the_chips():
    one = record({"/device:TPU:0": CHIP0}, HOST)
    two = record({"/device:TPU:0": CHIP0, "/device:TPU:1": CHIP1}, HOST)
    assert two.device_planes == ("/device:TPU:0", "/device:TPU:1")
    for name in SPLIT + ("device_idle_share",):
        assert reader(name).read(two) == pytest.approx(
            reader(name).read(one) / 2)


def test_metric_seconds_are_clipped_to_the_span_and_shared_by_the_configs():
    rec = record({"/device:TPU:0": CHIP0}, HOST, n_scored=4)
    # [35,37) and [76,78); the span at 92 lies past the span's end
    assert reader("eval_metric_s_per_config").read(rec) == pytest.approx(
        0.004 / 4)


def test_nothing_is_read_where_the_program_wrote_no_spans():
    host = [h for h in HOST if not h.name.startswith("repro.")]
    rec = record({"/device:TPU:0": CHIP0}, host)
    for name in SPLIT + ("eval_metric_s_per_config",):
        assert reader(name).read(rec) is None
    # spans that lie wholly past the traced span are not read either
    late = host + [ev(91, 95, "repro.unit"), ev(91, 93, "repro.train"),
                   ev(93, 95, "repro.eval"), ev(94, 95, "repro.eval.metric")]
    rec = record({"/device:TPU:0": CHIP0}, late)
    for name in SPLIT + ("eval_metric_s_per_config",):
        assert reader(name).read(rec) is None


def test_idle_gaps_are_named_by_the_program_span_that_overlaps_most():
    # the runtime's np.asarray event spans [5,60); in the gap [34,52) it
    # overlaps 18 ms, more than any span of the program, so it names it; in
    # [88,90) only the program's unit and training spans overlap it, and
    # the shorter, more specific training span names it
    idle = [(34 * MS, 52 * MS), (88 * MS, 90 * MS)]
    got = dict(tr.attribute_gaps(idle, HOST))
    assert got["np.asarray(jax.Array)"] == 18 * MS
    assert got["repro.train"] == 2 * MS
