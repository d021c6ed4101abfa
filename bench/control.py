#!/usr/bin/env python3
"""Readings that the limits of a cell's check are set from.

    python3 bench/control.py --workload logreg.fleet --seeds 11,12,13 \\
        --calls 8

In one process, for each seed: drive the cell's timed entry for ``--calls``
whole calls with that seed's inputs (as a run's window does, at the
cell's own sizes), then read every number of the check as a run would
(the program's readings, the lower ones); and read the control: the
plain reference put in the program's place, computed in ``jax.numpy`` on
the chip at each precision that the configuration's reference names
(``CONTROLS``: the stated one, then the steps below it), at the same
draws and in the same batch shapes (the upper readings). The control
gives ``logp_gap`` and ``grad_gap``; the program's draws and moments stay
the program's. Prints one JSON line per seed. The benchmark's own runs
never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import numpy as np  # noqa: E402

from harness import core  # noqa: E402
from harness.parity import Checks  # noqa: E402


def _chains_control(driver, precision: str) -> dict:
    """The reference in the program's place at ``precision``: its log
    density and gradient at the window's sampled draws, the fleet's
    ``num_chains`` lanes at a time, read as the run reads the program's."""
    import jax
    import jax.numpy as jnp

    from harness.chains import grad_gap
    ref = driver.ref
    f = jax.jit(ref.logp_grad_jax(precision))
    q = np.concatenate([j["q"] for j in driver.jobs])
    c, lps, grads = driver.chains, [], []
    for i in range(0, len(q), c):
        k = len(q[i:i + c])
        lp, g = f(jnp.asarray(np.resize(q[i:i + c], (c, q.shape[1])),
                              jnp.float32))
        lps.append(np.asarray(lp, np.float64)[:k])
        grads.append({n: np.asarray(v, np.float64)[:k]
                      for n, v in g.items()})
    got = np.concatenate(lps)
    want = ref.logp(q)
    return {"logp_gap": float(np.max(np.abs(got - want)
                                     / np.maximum(1.0, np.abs(want)))),
            "grad_gap": grad_gap({n: np.concatenate([g[n] for g in grads])
                                  for n in grads[0]}, ref.grad_leaves(q))}


def main(argv=None, require_chip: bool = True, shrink=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--calls", type=int, required=True)
    args = p.parse_args(argv)

    spec = core.load_json(core.ROOT / "BENCHMARK.json")
    cell = core.Cell(args.workload, spec)
    if shrink is not None:
        shrink(cell)
    import importlib

    import jax
    if require_chip:
        core.require_devices(cell.chips)
    core.enable_cache()
    limits = core.load_json(BENCH / "limits" / f"{cell.name}.json")
    mod = importlib.import_module(f"harness.{cell.traffic['kind']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        driver = mod.Driver(cell, seed)
        for job in range(1, args.calls + 1):
            driver.keep(job, driver.call(job))
        driver.read_back()
        checks = Checks()
        driver.check(checks, limits)
        row = {"seed": seed, "program": {n: v for n, v, _ in checks.items},
               "control": {pr: _chains_control(driver, pr)
                           for pr in cell.reference.CONTROLS},
               "seconds": time.perf_counter() - t0,
               "device": jax.devices()[0].device_kind}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
