"""What every cell shares: the cell's entries in ``BENCHMARK.json``, the
device check, set-up accounting, metric readers and the result line."""
from __future__ import annotations

import importlib.util
import json
import logging
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class NoDevice(SystemExit):
    """The cell's chips are not there; no result is printed."""


def load_module(path: Path):
    """Import a reader or reference file by path (its name may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix and
    the metrics it reports, all found by name."""

    def __init__(self, name: str, spec: dict):
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                             f"{sorted(cells)}")
        self.workload = cells[name]
        self.name = name
        configs = {c["name"]: c for c in spec["configs"]}
        self.config_entry = configs[self.workload["config"]]
        self.config = load_json(ROOT / self.config_entry["file"])
        cfg_file = ROOT / self.config_entry["file"]
        self.reference = load_module(
            cfg_file.with_name(cfg_file.stem + "_ref.py"))
        self.traffic = load_json(
            BENCH / "traffic" / f"{self.workload['traffic']}.json")
        self.chips = int(self.workload["chips"])

        def mine(m):
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end = [m for m in spec["end_to_end"] if mine(m)]
        self.per_layer = [m for m in spec["per_layer"] if mine(m)]


def require_devices(chips: int):
    """The accelerator the cell asks for, or exit without a result."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX reports platform "
              f"{dev.platform!r}, device_kind {dev.device_kind!r}, "
              f"{len(devs)} device(s). No result.", file=sys.stderr)
        raise NoDevice(3)
    return devs[:chips]


def enable_cache() -> str:
    """JAX's persistent compilation cache at the checkout's fixed path.

    Every program is written there, however fast it compiled, so that a
    second run in the same checkout loads all of them."""
    import jax

    from repro.runtime.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class SetupClock:
    """Backend-compile seconds, persistent-cache hits and misses (copied
    from ``chip_smoke.SetupClock``), with the name of each program that
    missed the persistent cache (from JAX's compiler log)."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        self.compiles = 0
        self.missed = []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        clock = self

        class _Misses(logging.Handler):
            def emit(self, record):
                if record.msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
                    clock.missed.append(str(record.args[0]))

        # the compiler logs misses at DEBUG; keep its own output at WARNING
        log = logging.getLogger("jax._src.compiler")
        log.setLevel(logging.DEBUG)
        log.propagate = False
        log.addHandler(_Misses(logging.DEBUG))
        warn = logging.StreamHandler(sys.stderr)
        warn.setLevel(logging.WARNING)
        log.addHandler(warn)

    def _on_duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _on_event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"backend_compile_s": self.compile_s,
                "compiles": self.compiles,
                "persistent_cache_hits": self.cache_hits,
                "persistent_cache_misses": len(self.missed),
                "missed": sorted(set(self.missed))}


def device_info(devs) -> dict:
    import jax
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def read_metrics(metrics, rec: dict) -> dict:
    """Run each metric's reader on the run record; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(rec)
        if value is not None and not math.isfinite(value):
            print(f"bench: metric {m['name']} read {value!r}; left out",
                  file=sys.stderr)
        elif value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table["devices"]:
        raise SystemExit(f"bench: no peaks for device_kind {kind!r} in "
                         "bench/peaks.json")
    return table["devices"][kind]


def kernel_cost(kernel: str):
    """The ``cost(**shapes) -> (flops, bytes)`` function of one kernel."""
    return load_module(BENCH / "kernels" / f"{kernel}.py").cost
