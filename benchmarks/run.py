"""Benchmark aggregator: one section per paper table / deliverable.

  table1          — paper Table 1: static HMC (4 leapfrog, 2000 iters) on
                    the 8 benchmark models; typed vs handwritten vs untyped
  typed_ablation  — §2.2 claim isolated: per-call log-density cost
  kernels         — per-kernel allclose + HBM-traffic accounting, plus
                    fused vs per-site log-joint wall clock
  roofline        — 3-term roofline per dry-run cell (needs dryrun JSONL)
  multichain      — the vmapped ``run_chains`` driver: N chains of static
                    HMC as one jit(vmap(...)) program (enabled by
                    ``--chains N``; also runnable via --only multichain)
  resume          — segmented (checkpointable) driver vs the single-scan
                    driver: end-to-end overhead per run_chains call
  queries         — compiled (cached-program) vs eager probability
                    queries; posterior predictive as one jit(vmap) vs
                    the per-draw loop
  sharding        — mesh-dispatched chains (chain-throughput scaling on
                    1 and 4 devices of this process) + tall-data weak
                    scaling of the psum density

``python -m benchmarks.run [--fast] [--only SECTION] [--chains N]
[--json-dir DIR]`` (--fast cuts table1 to 200 iterations for quick
regression runs; --json-dir additionally writes the schema-valid
``BENCH_*.json`` reports — logjoint, leapfrog, roofline — into DIR).
A failing section or report does not stop the others, but the command
then exits 1.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback


def run_multichain(num_chains: int, fast: bool = False):
    """Exercise ``repro.infer.run_chains``: N-chain static HMC, one vmap."""
    import jax

    from repro.infer import HMC, run_chains, split_rhat
    from repro.models import paper_suite

    pm = paper_suite.build("gauss_unknown")
    num_samples = 200 if fast else 1000
    kernel = HMC(step_size=pm.step_size, n_leapfrog=pm.n_leapfrog,
                 adapt_step_size=True)
    t0 = time.perf_counter()
    ch = run_chains(jax.random.PRNGKey(0), pm.model, kernel,
                    num_samples=num_samples, num_warmup=num_samples // 2,
                    num_chains=num_chains)
    wall = time.perf_counter() - t0
    per_draw_us = wall / (num_chains * num_samples) * 1e6
    rhat = split_rhat(ch["m"])
    yield (f"multichain/gauss_unknown/hmc_x{num_chains},{per_draw_us:.1f},"
           f"draws={ch['m'].shape};wall_s={wall:.2f};rhat_m={rhat:.3f}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--fast", action="store_true")
    p.add_argument("--only", default=None,
                   choices=("table1", "typed_ablation", "kernels",
                            "leapfrog", "roofline", "multichain", "resume",
                            "queries", "sharding"))
    p.add_argument("--json-dir", default=None, metavar="DIR",
                   help="also write BENCH_*.json reports into DIR")
    p.add_argument("--chains", type=int, default=None, metavar="N",
                   help="run the vmapped multi-chain driver with N chains "
                        "(adds the 'multichain' section)")
    args = p.parse_args(argv)

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    sections = []
    if args.only in (None, "typed_ablation"):
        from benchmarks import typed_ablation
        sections.append(("typed_ablation", typed_ablation.run))
    if args.only in (None, "kernels"):
        from benchmarks import kernels_bench
        sections.append(("kernels", kernels_bench.run))
    if args.only in (None, "leapfrog"):
        from benchmarks import leapfrog_bench
        sections.append(("leapfrog", leapfrog_bench.run))
    if args.only in (None, "roofline"):
        from benchmarks import roofline
        sections.append(("roofline", roofline.run))
    if args.only in (None, "resume"):
        from benchmarks import resume_bench
        sections.append(
            ("resume", lambda: resume_bench.run(fast=args.fast)))
    if args.only in (None, "queries"):
        from benchmarks import queries_bench
        sections.append(("queries", queries_bench.run))
    if args.only in (None, "sharding"):
        from benchmarks import sharding_bench
        sections.append(
            ("sharding", lambda: sharding_bench.run(fast=args.fast)))
    if args.only == "multichain" or args.chains is not None:
        n = args.chains if args.chains is not None else 4
        sections.append(
            ("multichain", lambda: run_multichain(n, fast=args.fast)))
    if args.only in (None, "table1"):
        from benchmarks import table1
        iters = 200 if args.fast else 2000
        sections.append(("table1", lambda: table1.run(iters=iters)))

    failed = []
    for name, fn in sections:
        print(f"==== {name} ====", flush=True)
        t0 = time.time()
        try:
            for line in fn():
                print(line, flush=True)
        except Exception as e:  # keep the suite going; record the failure
            traceback.print_exc()
            print(f"{name}/ERROR,0,{e!r}", flush=True)
            failed.append(name)
        print(f"==== {name} done in {time.time() - t0:.0f}s ====", flush=True)

    if args.json_dir:
        from benchmarks.bench_io import write_report
        os.makedirs(args.json_dir, exist_ok=True)
        reporters = []
        if args.only in (None, "kernels"):
            from benchmarks import kernels_bench
            reporters.append(("BENCH_logjoint.json", kernels_bench.report))
        if args.only in (None, "leapfrog"):
            from benchmarks import leapfrog_bench
            reporters.append(("BENCH_leapfrog.json", leapfrog_bench.report))
        if args.only in (None, "roofline"):
            from benchmarks import roofline
            reporters.append(("BENCH_roofline.json", roofline.report))
        if args.only in (None, "resume"):
            from benchmarks import resume_bench
            reporters.append(
                ("BENCH_resume.json",
                 lambda: resume_bench.report(fast=args.fast)))
        if args.only in (None, "queries"):
            from benchmarks import queries_bench
            reporters.append(("BENCH_queries.json", queries_bench.report))
        if args.only in (None, "sharding"):
            from benchmarks import sharding_bench
            reporters.append(
                ("BENCH_sharding.json",
                 lambda: sharding_bench.report(fast=args.fast)))
        for fname, reporter in reporters:
            path = os.path.join(args.json_dir, fname)
            try:
                write_report(reporter(), path)
                print(f"wrote {path}", flush=True)
            except Exception as e:
                traceback.print_exc()
                print(f"JSON {fname} FAILED: {e!r}", flush=True)
                failed.append(fname)
    if failed:
        print(f"FAILED: {', '.join(failed)}", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
