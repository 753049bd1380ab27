"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``): JAX start-up, the configuration's rows from
the seed, and the traffic's warm-up searches, which convert the data to
every format the window uses and load every program it runs. Then the
closed loop of searches runs for ``--seconds``. With ``--trace 1`` the
window runs under the profiler and the cell's per-layer metrics are
reported; otherwise its end-to-end metrics. Last, the configurations the
window scored are checked against the plain reference (``check.py``).

The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``, each compared number with its limit.
Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import cells, check, drive, trace_reduce  # noqa: E402
from bench.record import RunRecord  # noqa: E402

#: JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def chips_or_none(jax, want: int):
    """The first ``want`` TPU devices, or None where JAX finds fewer."""
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < want:
        return None
    return devs[:want]


def use_compile_cache(jax) -> None:
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def answers_of(landed) -> list[check.Answer]:
    return [check.Answer(family=lr.result.task.estimator,
                         params=dict(lr.result.task.params),
                         model=check.program_model(lr.result.task.estimator,
                                                   lr.result.model),
                         score=lr.result.score,
                         train_seconds=lr.result.train_seconds,
                         executor=lr.result.executor_id)
            for lr in landed if lr.result.ok]


def correctness(cell, seed, train, valid, landed, failed):
    """(correct, checks) of the window's answers against the reference."""
    from bench import reference

    answers = answers_of(landed)
    picked = check.sample(answers, cell.traffic["check_sample"], seed,
                          int(cell.traffic["n_executors"]))
    prep = reference.Prepared(*train)
    per = [check.numbers(prep, valid[0], valid[1], answers[i], reference)
           for i in picked]
    ok, checks = check.judge(check.worst(per), cell.config["correct_limits"])
    log(f"checked {len(picked)} of {len(answers)} scored configurations")
    return ok and failed == 0 and bool(answers), checks


def main(argv=None, require_chip: bool = True) -> int:
    args = parse(argv)
    cell = cells.load_cell(args.workload)
    import jax

    devices = chips_or_none(jax, cell.chips)
    if devices is None:
        if require_chip:
            log(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                f"finds {len(jax.devices())} {jax.devices()[0].platform} "
                "device(s). No device number is taken off the chip.")
            return 2
        devices = jax.devices()[:cell.chips]
    use_compile_cache(jax)
    monitor = drive.Monitor()
    monitor.install()
    sys.path.insert(0, drive.src_path())

    train, valid = drive.make_data(cell, args.seed)
    log(f"data: train {train[0].shape} valid {valid[0].shape}, "
        f"{train[0].nbytes + valid[0].nbytes} bytes of float32 rows")
    driver = drive.Driver(cell, args.seed, train, valid, devices)
    convert_s, n_warm = driver.warmup()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f}s: {n_warm} warm-up results, conversion "
        f"{convert_s:.3f}s, {monitor.programs('setup')} programs obtained "
        f"({monitor.compiled('setup')} compiled), backend compile "
        f"{monitor.compile_seconds('setup'):.3f}s")

    monitor.phase = "window"
    trace_dir = TRACE_DIR / cell.name
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    else:
        annotate = None
    with (annotate("bench.window") if annotate else contextlib.nullcontext()):
        t0, landed, ends = driver.window(args.seconds, annotate)
    if args.trace:
        jax.profiler.stop_trace()
    monitor.phase = "check"
    log(f"window: {monitor.programs('window')} programs obtained, "
        f"{monitor.compiled('window')} compiled inside the window")
    peak_bytes = memory_peak(devices)

    failed = sum(1 for lr in landed
                 if not lr.result.ok or lr.result.score is None
                 or not math.isfinite(lr.result.score))
    record = RunRecord(
        cell=cell, landed=landed, t0=t0, ends=ends,
        n_executors=int(cell.traffic["n_executors"]), chips=cell.chips,
        setup={"convert_s": convert_s,
               "compile_s": monitor.compile_seconds("setup")},
        train_rows=train[0].shape[0], features=train[0].shape[1],
        peak=cells.peaks_for(devices[0].device_kind) if require_chip else None)
    breakdown = None
    if args.trace:
        record.attach_trace(trace_reduce.load(trace_dir), devices)
        shutil.rmtree(trace_dir, ignore_errors=True)
        breakdown = record.breakdown()
    driver.free()

    if args.trace:
        wanted = cell.per_layer
    else:
        wanted = cell.end_to_end
    metrics = {}
    for m in wanted:
        if m["name"] == "setup_s":
            value = setup_s
        elif m["name"] == "configs_per_s":
            value = record.configs_per_s()
        else:
            value = cell.metric_reader(m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    correct, checks = correctness(cell, args.seed, train, valid, landed, failed)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    if args.trace and record.trace is not None:
        device["busy_s"] = record.busy_s()
        device["window_s"] = record.span_s()
    out = {"correct": bool(correct), "attempted": len(landed), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
