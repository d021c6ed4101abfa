"""Device time of one fused log-density block on each path, on a TPU.

For each family, chain count and block length, two programs compute the
vmapped value and gradient of ``site_block_sum``: one forces the Pallas
kernel (``use_pallas=True``), one XLA's reduction of the ``ref.py``
oracle (``use_pallas=False``). Both run ``--calls`` times under the
profiler; the result is each program's device time per call (its
``XLA Modules`` events) and, on the Pallas side, the kernel's own op time.
This is the evidence behind ``ops.pallas_pays``'s one-tile limit.

    python3 -m benchmarks.block_path_sweep --out build/block_path_sweep.json

Needs a TPU; the Bernoulli block's ``y`` is shared by the chains, as the
logreg likelihood's data is.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import tempfile
from collections import defaultdict

import jax
import jax.numpy as jnp

from repro.kernels.fused_logpdf import ops

FAMILIES = ("std_normal", "bernoulli_logits")
CHAINS = (4, 64)
LENGTHS = (101, 1_024, 4_096, 10_000, 40_000)


def _program(family, chains, n, pallas):
    y = (jax.random.uniform(jax.random.PRNGKey(1), (n,)) < 0.4
         ).astype(jnp.float32)

    def one(a):
        seg = (a,) if family == "std_normal" else (a, y)
        return ops.site_block_sum(family, [seg], use_pallas=pallas,
                                  interpret=False)

    def vg(a):
        return jax.vmap(jax.value_and_grad(one))(a)

    path = "pallas" if pallas else "xla"
    vg.__name__ = f"sweep_{family}_{chains}_{n}_{path}"
    a = jax.random.normal(jax.random.PRNGKey(0), (chains, n))
    return vg.__name__, jax.jit(vg), a


def _device_events(log_dir):
    """(line, name, start_ns, dur_ns) of every event on the TPU planes."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                yield (line.name, e.name, float(e.start_ns),
                       float(e.duration_ns))


def _per_program(events):
    """Module time and Pallas kernel op time by sweep program, in ns; an
    op belongs to the module whose interval holds its start."""
    modules, ops_ = [], []
    for line, name, t0, dur in events:
        if line == "XLA Modules":
            m = re.match(r"jit_(sweep_\w+?)(\(|$|\.)", name)
            if m:
                modules.append((t0, t0 + dur, m.group(1)))
        elif line == "XLA Ops" and name.lstrip("%").startswith("fused_"):
            ops_.append((t0, dur))
    modules.sort()
    starts = [m[0] for m in modules]
    module_ns, kernel_ns = defaultdict(float), defaultdict(float)
    for t0, t1, label in modules:
        module_ns[label] += t1 - t0
    for t0, dur in ops_:
        i = bisect.bisect_right(starts, t0) - 1
        if i >= 0 and t0 < modules[i][1]:
            kernel_ns[modules[i][2]] += dur
    return module_ns, kernel_ns


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=50)
    ap.add_argument("--out", default="build/block_path_sweep.json")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        raise SystemExit("block_path_sweep measures a TPU; none is here")
    progs = [_program(f, c, n, p) for f in FAMILIES for c in CHAINS
             for n in LENGTHS for p in (True, False)]
    for _, fn, a in progs:
        jax.block_until_ready(fn(a))
    log_dir = tempfile.mkdtemp(prefix="block_path_sweep")
    jax.profiler.start_trace(log_dir)
    for _, fn, a in progs:
        for _ in range(args.calls):
            out = fn(a)
        jax.block_until_ready(out)
    jax.profiler.stop_trace()

    module_ns, kernel_ns = _per_program(_device_events(log_dir))
    rows = [{"program": label,
             "us_per_call": module_ns[label] / args.calls / 1e3,
             "kernel_us_per_call": kernel_ns[label] / args.calls / 1e3}
            for label, _, _ in progs]
    for r in rows:
        print(json.dumps(r), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": jax.devices()[0].device_kind,
                   "calls": args.calls, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
