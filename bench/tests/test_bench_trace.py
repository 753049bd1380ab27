"""The reduction from a trace to device numbers, on synthetic traces."""
import types

import pytest

from bench import cells, trace_reduce as tr
from bench.record import RunRecord

MS = 1_000_000


def ev(a, b, name):
    return tr.Event(int(a * MS), int(b * MS), name)


def test_union_merges_overlaps_and_clips_to_the_window():
    evs = [ev(0, 2, "a"), ev(1, 3, "b"), ev(5, 6, "c"), ev(9, 12, "d")]
    assert tr.union(evs, 1 * MS, 10 * MS) == [(1 * MS, 3 * MS), (5 * MS, 6 * MS),
                                              (9 * MS, 10 * MS)]
    assert tr.busy_ns(evs, 1 * MS, 10 * MS) == 4 * MS
    assert tr.gaps(tr.union(evs, 1 * MS, 10 * MS), 1 * MS, 10 * MS) == [
        (3 * MS, 5 * MS), (6 * MS, 9 * MS)]


KERNEL = cells.load_module(
    cells.BENCH_DIR / "metrics/level_kernel_time_share.py").KERNEL


def test_kernel_time_matches_the_kernel_by_name_and_is_clipped():
    evs = [ev(0, 4, "fused_level_split_tpu.109"), ev(5, 6, "fusion.3"),
           ev(7, 9, "fused_level_split_tpu.7"), ev(9, 10, "copy.fused_level_split_tpu")]
    hits = tr.matching(evs, KERNEL)
    assert [e.name for e in hits] == ["fused_level_split_tpu.109",
                                      "fused_level_split_tpu.7"]
    assert tr.op_ns(hits, 2 * MS, 8 * MS) == 3 * MS


def test_hlo_names_are_cut_from_the_instruction_text():
    text = ('%fused_level_split_tpu.109 = (f32[4,512,1]{2,1,0}) custom-call('
            'bf16[4,512,640] %pad.1336), custom_call_target="tpu_custom_call"')
    assert tr.hlo_name(text) == ("fused_level_split_tpu.109", "tuple")
    assert tr.hlo_name("%fusion.443 = s32[300000,28]{0,1:T(8,128)} fusion("
                       "s32[600000,28]{0,1} %gte)") == ("fusion.443",
                                                        "s32[300000,28]")
    assert tr.hlo_name("%iota.2 = s32[] iota()") == ("iota.2", "s32[]")
    assert tr.hlo_name("PjitFunction(f)") == ("PjitFunction(f)", "")


def test_top_ops_merge_numbered_instances_and_count_own_time():
    # a while loop spans the operations of its body: its own time is what
    # they leave over
    evs = [ev(0, 10, "while.5"), ev(1, 2, "fusion.1"), ev(2, 4, "fusion.22"),
           ev(4, 5, "copy.5"), ev(4.2, 4.5, "copy-start.2"),
           tr.Event(5 * MS, 6 * MS, "fusion.3", "s32[300000,28]")]
    assert tr.top_ops(evs, 0, 10 * MS) == [
        ("while", 5 * MS), ("fusion", 3 * MS), ("fusion s32[300000,28]", MS),
        ("copy", int(0.7 * MS)), ("copy-start", int(0.3 * MS))]


def test_idle_gaps_are_named_by_the_host():
    idle = [(10 * MS, 20 * MS), (30 * MS, 31 * MS), (40 * MS, 40 * MS + 1000)]
    host = [ev(0, 100, "bench.window"),
            ev(9, 18, "PjitFunction(_fit_gbdt_core)"),
            ev(11, 12, "np.asarray"),
            ev(29, 32, "bench.result")]
    got = dict(tr.attribute_gaps(idle, host))
    # the runtime's event overlapping the gap most names it, not the
    # benchmark's own span; where only the benchmark's spans overlap, the
    # shortest of them names it; very short gaps are summed apart
    assert got["PjitFunction(_fit_gbdt_core)"] == 10 * MS
    assert got["bench.result"] == 1 * MS
    assert got[tr.SHORT_GAP_NAME] == 1000


def _record(trace):
    cell = types.SimpleNamespace(name="t")
    res = types.SimpleNamespace(
        ok=True, score=0.9, train_seconds=1.0, eval_seconds=0.5,
        convert_seconds=0.0,
        task=types.SimpleNamespace(estimator="gbdt",
                                   params={"round": 3, "max_depth": 4}))
    landed = [types.SimpleNamespace(t=1.0, result=res, search=0),
              types.SimpleNamespace(t=3.0, result=res, search=1)]
    rec = RunRecord(cell=cell, landed=landed, t0=0.0, ends=[1.5],
                    n_executors=1, chips=1,
                    setup={"convert_s": 1.0, "compile_s": 2.0},
                    train_rows=600_000, features=28, outputs=1,
                    peak=cells.peaks_for("TPU v5 lite"))
    rec.attach_trace(trace, [types.SimpleNamespace(id=0)])
    return rec


def test_record_cuts_the_span_at_the_last_result_and_averages_devices():
    trace = tr.Trace(
        devices={"/device:TPU:0": [ev(1, 3, "fused_level_split_tpu.1"),
                                   ev(4, 5, "fusion.2")],
                 "/device:TPU:1": [ev(0, 10, "fusion.1")]},
        host=[ev(1, 20, "bench.window"), ev(6, 6.5, "bench.result"),
              ev(15, 15.1, "bench.result"), ev(15.2, 15.2, "bench.search_end"),
              ev(17, 17.1, "bench.result"), ev(25, 26, "bench.result"),
              ev(25, 25, "bench.search_end"), ev(5, 6, "Thunk")])
    rec = _record(trace)
    # the span ends where the last whole search inside the window ended,
    # and only that search's result is counted
    assert len(rec.scored()) == 1
    assert rec.device_planes == ("/device:TPU:0",)
    assert rec.lo == 1 * MS and rec.hi == 15.2 * MS
    assert rec.span_s() == pytest.approx(0.0142)
    assert rec.busy_s() == pytest.approx(0.003)
    b = rec.breakdown()
    assert b["device_ops"][0] == ["fused_level_split_tpu", pytest.approx(0.002)]
    assert len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(0.0112)


def test_kernel_metrics_read_nothing_where_the_kernel_never_ran():
    trace = tr.Trace(devices={"/device:TPU:0": [ev(1, 3, "fusion.1")]},
                     host=[ev(0, 10, "bench.window"), ev(9, 9.1, "bench.result"),
                           ev(9.1, 9.1, "bench.search_end")])
    rec = _record(trace)
    share = cells.load_module(cells.BENCH_DIR / "metrics/level_kernel_time_share.py")
    roof = cells.load_module(cells.BENCH_DIR / "metrics/level_kernel_roofline.py")
    assert share.read(rec) is None
    assert roof.read(rec) is None


def test_roofline_and_mfu_stay_under_100_percent_of_what_they_count():
    trace = tr.Trace(
        devices={"/device:TPU:0": [ev(0, 8, "fused_level_split_tpu.1")]},
        host=[ev(0, 10, "bench.window"), ev(9, 9.1, "bench.result"),
              ev(9.1, 9.1, "bench.search_end")])
    rec = _record(trace)
    roof = cells.load_module(cells.BENCH_DIR / "metrics/level_kernel_roofline.py")
    mfu = cells.load_module(cells.BENCH_DIR / "metrics/search_mfu.py")
    # 3 trees of 600k x 36 bytes take 79 us at 819 GB/s; the kernel ran 8 ms
    assert roof.read(rec) == pytest.approx(100 * 3 * 600_000 * 36 / 819e9 / 0.008)
    assert 0 < mfu.read(rec) < 100
