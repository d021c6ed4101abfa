#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload logreg.fleet --seed 7 --seconds 10 --trace 0

Everything a cell needs is found by name: its entry in ``BENCHMARK.json``,
the configuration file it names with the plain reference beside it
(``bench/configs/<config>_ref.py``), the traffic mix
``bench/traffic/<mix>.json`` (whose ``kind`` picks the driver in
``bench/harness/``), the limits of its correctness check
``bench/limits/<cell>.json``, one reader per metric
``bench/metrics/<metric>.py``, the kernel counts ``bench/kernels/`` and the
peaks ``bench/peaks.json``.

A run: refuse to go on without the cell's TPU chips; build the model and
warm up with one whole call of the cell's own shapes (set-up); loop whole
calls for ``--seconds`` and finish the one in flight; with ``--trace 1``
also trace a short steady window of ``trace_calls`` calls; read the peak
device memory; read back from the program what the check compares that
the calls do not return (``read_back``); free the program and check the
window's outputs against the plain reference;
print the numbers compared with their limits on standard error, then the
result line on standard output. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from harness import core  # noqa: E402
from harness.parity import Checks  # noqa: E402


def _log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def _calls(driver, first: int, annotate, *, seconds=None, count=None):
    """Loop whole calls from job ``first`` (a closed loop): until
    ``seconds`` have passed, then finish the call in flight, or ``count``
    calls. Returns (calls, failed, elapsed_s)."""
    t0 = time.perf_counter()
    job, failed = first, 0
    while True:
        try:
            with annotate(f"bench.{driver.span}"):
                out = driver.call(job)
        except Exception:  # a failed call is counted, and the run goes on
            traceback.print_exc()
            failed += 1
            out = None
        end = time.perf_counter()
        if out is not None:
            with annotate("bench.host"):
                driver.keep(job, out)
        job += 1
        if count is not None and job - first >= count:
            break
        if seconds is not None and end - t0 >= seconds:
            break
    return job - first, failed, end - t0


def main(argv=None, require_chip: bool = True, shrink=None) -> int:
    """``require_chip`` and ``shrink`` (a function that cuts the cell's
    configuration and traffic down in place) are for the benchmark's own
    tests on the CPU; a run on the chip uses neither."""
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    spec = core.load_json(core.ROOT / "BENCHMARK.json")
    cell = core.Cell(args.workload, spec)
    if shrink is not None:
        shrink(cell)
    import jax
    from jax.profiler import TraceAnnotation

    devs = (core.require_devices(cell.chips) if require_chip
            else jax.devices()[:cell.chips])
    cache_dir = core.enable_cache()
    clock = core.SetupClock()
    limits = core.load_json(BENCH / "limits" / f"{cell.name}.json")

    driver = importlib.import_module(
        f"harness.{cell.traffic['kind']}").Driver(cell, args.seed)
    trace_calls = int(cell.traffic["trace_calls"]) if args.trace else 0
    with TraceAnnotation(f"bench.{driver.span}"):
        driver.call(0)
    setup_s = time.perf_counter() - T_START
    snap = clock.snapshot()
    _log(f"setup_s={setup_s!r} backend_compile_s={snap['backend_compile_s']!r}"
         f" compiles={snap['compiles']} persistent_cache_hits="
         f"{snap['persistent_cache_hits']} persistent_cache_misses="
         f"{snap['persistent_cache_misses']} missed={snap['missed']} "
         f"cache_dir={cache_dir}")

    calls, failed, elapsed = _calls(driver, 1, TraceAnnotation,
                                    seconds=args.seconds)
    in_window = clock.compiles - snap["compiles"]
    _log(f"window: {calls} calls, {failed} failed, {elapsed!r} s, "
         f"{in_window} compiles inside the window")
    rec = {"setup_s": setup_s, "window_s": elapsed, "calls": calls,
           "driver": driver.record(), "work": driver.work(calls),
           "notes": [], "trace": None}

    if args.trace:
        from harness import trace
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            trace.start_trace(log_dir)
            _calls(driver, 1 + calls, TraceAnnotation, count=trace_calls)
            jax.profiler.stop_trace()
            events = trace.events_from_xspace(trace.find_xspace(log_dir))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        rec["trace"] = trace.reduce_events(events)
        _log(f"trace: {len(events)} events, window "
             f"{rec['trace']['window_s']!r} s, busy "
             f"{rec['trace']['busy_s']!r} s, breakdown "
             f"{json.dumps(rec['trace']['breakdown'])}")

    device = core.device_info(devs)
    driver.read_back()
    rec["peaks"] = core.peaks_for(device["kind"] if require_chip
                                  else "TPU v5 lite")
    metrics = core.read_metrics(
        cell.per_layer if args.trace else cell.end_to_end, rec)
    for note in rec["notes"]:
        _log(note)

    # the reference runs on the host once the program's state is gone
    driver.free()
    jax.clear_caches()
    checks = Checks()
    driver.check(checks, limits)
    checks.add("failed_calls", failed, 0)
    result = {"correct": checks.ok, "attempted": calls, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        result["device"]["busy_s"] = rec["trace"]["busy_s"]
        result["device"]["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = rec["trace"]["breakdown"]
    result["checks"] = checks.as_dict()
    checks.print_last_lines()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
