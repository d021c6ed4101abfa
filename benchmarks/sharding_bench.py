"""Sharded-inference bench: chain scaling + tall-data weak scaling.

Every cell runs in this process on ``jax.devices()[:D]`` for D = 1 and
4, and the cells are aggregated into one schema-valid
``BENCH_sharding.json`` report. The process needs four devices: a
four-chip host, or on the CPU
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` set before JAX
starts (there "devices" are host threads of ONE machine — a
correctness and compilation story, not a hardware-speed one).

Two stories:

* ``chains`` — chain-throughput scaling. Forced CPU devices share the
  physical cores, so the honest headline is the PER-DEVICE projection:
  ``scaling = T(C chains, 1 device) / T(C/D chains per device)`` — the
  wall-clock a D-device fleet would see if each device were a real
  core, with the measured D-device mesh wall-clock recorded alongside
  (``method`` field says which is which; on a 1-core container the
  mesh wall-clock is host-serialized and NOT a speedup claim).
* ``weakdata`` — tall-data weak scaling of the psum density: time of
  the full-data density/grad at rows R on one device vs rows R/D per
  shard, plus the sharded-vs-unsharded density parity.

``python -m benchmarks.sharding_bench [--fast] [--json PATH]``
"""
from __future__ import annotations

import argparse
import sys
from typing import Dict, List

SEED = 0
WARMUP = 1
REPEATS = 3


def _devices(num_devices: int) -> List:
    import jax
    devs = jax.devices()[:num_devices]
    if len(devs) < num_devices:
        raise RuntimeError(
            f"the sharding bench needs {num_devices} devices and JAX has "
            f"{len(devs)}; on the CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={num_devices} before "
            "JAX starts")
    return devs


# ---------------------------------------------------------------------------
# cells (each on the first ``num_devices`` devices)
# ---------------------------------------------------------------------------
def _time(fn, repeats: int = REPEATS, warmup: int = WARMUP) -> float:
    import time
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _chains_cell(num_devices: int, fast: bool) -> Dict:
    import jax

    from repro.core.program import clear_cache
    from repro.infer import HMC, run_chains
    from repro.models import paper_suite
    from repro.sharding import ShardedRun

    n = 2_000
    chains_total = 8
    num_samples = 50 if fast else 200
    num_warmup = num_samples // 2
    pm = paper_suite.build("gauss_unknown", n=n)
    kernel = HMC(step_size=pm.step_size, n_leapfrog=4, adapt_step_size=True)
    key = jax.random.PRNGKey(SEED)

    devs = _devices(num_devices)
    out = {"devices": num_devices, "chains_total": chains_total,
           "num_samples": num_samples, "num_warmup": num_warmup, "n_rows": n}

    def run(nc, mesh=None):
        return run_chains(key, pm.model, kernel, num_samples,
                          num_warmup=num_warmup, num_chains=nc, mesh=mesh)

    # full fleet on one device (the single-device baseline program)
    out["wall_full_s"] = _time(lambda: run(chains_total))
    # the per-device slice: what ONE device of a D-device fleet executes
    per_dev = max(1, chains_total // num_devices)
    clear_cache()
    out["wall_perdev_s"] = _time(lambda: run(per_dev))
    if num_devices > 1:
        plan = ShardedRun.plan(devices=devs)
        clear_cache()
        out["wall_mesh_s"] = _time(lambda: run(chains_total, mesh=plan))
        ch = run(chains_total, mesh=plan)
        out["mesh_cache_misses"] = int(ch.health.cache_misses)
        out["mesh_cache_hits"] = int(ch.health.cache_hits)
    return out


def _weakdata_cell(num_devices: int, fast: bool) -> Dict:
    import jax
    import numpy as np

    from repro.infer import HMC
    from repro.infer.chains import setup_chain_driver
    from repro.models import paper_suite
    from repro.sharding import ShardedRun, make_sharded_logdensity

    rows = 40_000 if fast else 200_000
    pm = paper_suite.build("gauss_unknown", n=rows)
    kernel = HMC()
    tvi, _, dim, q0s, _ = setup_chain_driver(
        jax.random.PRNGKey(SEED), pm.model, kernel, num_chains=1,
        init_jitter=0.0)
    q = q0s[0]
    devs = _devices(num_devices)
    out = {"devices": num_devices, "rows": rows, "dim": dim}

    ld_full = pm.model.make_logdensity_fn(tvi)
    vg_full = jax.jit(jax.value_and_grad(ld_full))
    out["wall_full_s"] = _time(lambda: jax.block_until_ready(vg_full(q)))

    # the per-shard program: the SAME density over rows/D observations —
    # what one device of the sharded evaluation executes between psums
    pm_shard = paper_suite.build("gauss_unknown", n=rows // num_devices)
    tvi_s, *_ = setup_chain_driver(
        jax.random.PRNGKey(SEED), pm_shard.model, kernel, num_chains=1,
        init_jitter=0.0)
    vg_shard = jax.jit(jax.value_and_grad(
        pm_shard.model.make_logdensity_fn(tvi_s)))
    out["wall_pershard_s"] = _time(
        lambda: jax.block_until_ready(vg_shard(q)))

    if num_devices > 1:
        plan = ShardedRun.plan(data_shards=num_devices, devices=devs,
                               shard_sites=("y",))
        ld_mesh = make_sharded_logdensity(pm.model, tvi, plan)
        v_mesh = float(ld_mesh(q))
        v_full = float(ld_full(q))
        out["parity_rel_err"] = abs(v_mesh - v_full) / max(abs(v_full), 1.0)
        vg_mesh = jax.jit(jax.value_and_grad(ld_mesh.raw))
        out["wall_mesh_s"] = _time(
            lambda: jax.block_until_ready(vg_mesh(q)))
        g_mesh = np.asarray(vg_mesh(q)[1])
        g_full = np.asarray(vg_full(q)[1])
        denom = max(float(np.max(np.abs(g_full))), 1.0)
        out["grad_rel_err"] = float(np.max(np.abs(g_mesh - g_full)) / denom)
    return out


# ---------------------------------------------------------------------------
# aggregate cells into the report
# ---------------------------------------------------------------------------
def report(fast: bool = False) -> Dict:
    from benchmarks.bench_io import entry, make_report

    entries: List[Dict] = []

    c1 = _chains_cell(1, fast)
    c4 = _chains_cell(4, fast)
    # per-device projection: T(all chains, 1 dev) / T(per-device slice)
    scaling = c1["wall_full_s"] / max(c4["wall_perdev_s"], 1e-9)
    draws = c1["chains_total"] * c1["num_samples"]
    entries.append(entry(
        "sharding/chains_x8_dev1",
        c1["wall_full_s"] / draws * 1e6,
        wall_s=round(c1["wall_full_s"], 4), **{k: c1[k] for k in
        ("chains_total", "num_samples", "num_warmup", "n_rows")}))
    entries.append(entry(
        "sharding/chains_throughput_scaling",
        c4["wall_perdev_s"] / draws * 1e6,
        scaling=round(scaling, 3), devices=4,
        method="projected_per_device",
        note=("T(8 chains on 1 device) / T(2-chain per-device program); "
              "forced CPU devices share one physical core, so the mesh "
              "wall-clock below is host-serialized, not a speedup"),
        wall_full_dev1_s=round(c1["wall_full_s"], 4),
        wall_perdev_s=round(c4["wall_perdev_s"], 4),
        wall_mesh_measured_s=round(c4.get("wall_mesh_s", 0.0), 4),
        mesh_cache_misses=c4.get("mesh_cache_misses", 0)))

    w1 = _weakdata_cell(1, fast)
    w4 = _weakdata_cell(4, fast)
    weak = w1["wall_full_s"] / max(w4["wall_pershard_s"], 1e-9)
    entries.append(entry(
        "sharding/weakdata_density_grad",
        w1["wall_full_s"] * 1e6,
        rows=w1["rows"], devices=4,
        weak_scaling=round(weak, 3),
        method="projected_per_shard",
        wall_full_dev1_s=round(w1["wall_full_s"], 6),
        wall_pershard_s=round(w4["wall_pershard_s"], 6),
        wall_mesh_measured_s=round(w4.get("wall_mesh_s", 0.0), 6),
        parity_rel_err=w4.get("parity_rel_err", 0.0),
        grad_rel_err=w4.get("grad_rel_err", 0.0)))

    return make_report("sharding", entries, seed=SEED, warmup=WARMUP,
                       repeats=REPEATS)


def run(fast: bool = False):
    """Text-mode section for ``benchmarks.run``."""
    rep = report(fast=fast)
    for e in rep["entries"]:
        x = e["extra"]
        tail = ";".join(f"{k}={v}" for k, v in sorted(x.items())
                        if not isinstance(v, str))
        yield f"{e['name']},{e['us_per_call']:.1f},{tail}"


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--fast", action="store_true")
    p.add_argument("--json", default=None, metavar="PATH")
    args = p.parse_args(argv)

    rep = report(fast=args.fast)
    for e in rep["entries"]:
        print(f"{e['name']}: {e['us_per_call']:.1f} us/call "
              f"{e['extra'].get('scaling', e['extra'].get('weak_scaling', ''))}")
    if args.json:
        from benchmarks.bench_io import write_report
        write_report(rep, args.json)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
