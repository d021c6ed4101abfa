#!/usr/bin/env python3
"""Drive the sampler path once on a TPU and check what comes out.

    python3 chip_smoke.py               # one chip: phases 1-6 below
    python3 chip_smoke.py --four-chips  # only the chains x data mesh, 4 chips

One process, no fallback: the first failed check raises, and the command
exits non-zero without printing the result line. On one chip it runs, at
the paper's Table-1 widths and through the entry points a user calls:

1. device   — JAX must report a TPU;
2. cache    — JAX's persistent compilation cache (``.jax_cache/`` in this
              checkout unless ``JAX_COMPILATION_CACHE_DIR`` is set);
3. leapfrog — ``gaussian_10k`` (dim 10,000), static HMC through
              ``run_chains`` with the fused Pallas leapfrog;
4. logjoint — ``logreg`` (10,000 x 100), NUTS through ``run_chains`` on the
              fused log-joint (Pallas ``site_block_sum``);
5. cond     — ``eight_schools``, NUTS on the conditional potential spec;
6. queries  — ``prob`` of all four kinds and a ``QueryServer`` batch.

Every reference is computed on the CPU device (or in NumPy float64) of the
same process. Compile seconds are printed per phase as set-up time; they
are not a metric. The last line of standard output is one JSON object
naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Parity tolerances, fixed before any chip run. An error is
# max|got - want| / max(1, max|want|) over the compared array.
TOL_DENSITY = 1e-5      # log-density values and gradients, float32
TOL_QUERY = 1e-5        # prob / QueryServer answers against NumPy float64
TOL_DRAW_MEAN = 0.05    # gaussian_10k: |pooled mean| of the draws
TOL_DRAW_SD = 0.05      # gaussian_10k: |pooled sd - 1|
TOL_MESH_MEAN = 0.5     # mesh vs one chip: |mean diff| / posterior sd
TOL_MESH_SD = 0.3       # mesh vs one chip: |sd ratio - 1|


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def parity(name: str, got, want, tol: float) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    require(got.shape == want.shape,
            f"{name}: shape {got.shape} != reference {want.shape}")
    err = float(np.max(np.abs(got - want), initial=0.0)
                / max(1.0, float(np.max(np.abs(want), initial=0.0))))
    print(f"parity {name}: err={err!r} tol={tol!r}", flush=True)
    require(bool(np.isfinite(got).all()) and err <= tol,
            f"{name}: error {err!r} above tolerance {tol!r}")
    return err


def kernel_in_hlo(hlo_text: str, kernel_name: str) -> bool:
    """Whether the compiled HLO runs ``kernel_name`` as a Mosaic kernel."""
    return any(f"%{kernel_name}" in line
               and 'custom_call_target="tpu_custom_call"' in line
               for line in hlo_text.splitlines())


class SetupClock:
    """Sums JAX's backend-compile durations and persistent-cache hits per
    phase. (Trace durations are left out: a jit traced inside another's
    trace would be counted twice.)"""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _on_event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def phase(self, name: str):
        clock = self

        class _Phase:
            def __enter__(self):
                print(f"== {name}", flush=True)
                self.c0, self.h0 = clock.compile_s, clock.cache_hits
                self.t0 = time.perf_counter()

            def __exit__(self, exc_type, exc, tb):
                if exc_type is None:
                    print(f"[set-up, not a metric] {name}: backend compile "
                          f"{clock.compile_s - self.c0!r} s, persistent-cache "
                          f"hits {clock.cache_hits - self.h0}, phase wall "
                          f"{time.perf_counter() - self.t0!r} s", flush=True)
                return False

        return _Phase()


def require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found — JAX's first device is "
            f"{dev.platform!r} ({dev.device_kind}). This check runs only on "
            "a TPU and has no CPU fallback.")
    return dev


def _cpu():
    import jax
    return jax.devices("cpu")[0]


def _on_cpu(tree):
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    require(all(d.platform == "cpu" for x in leaves for d in x.devices()),
            "a reference result was not computed on the CPU device")
    return tree


def _linked_trace(model, seed: int = 0):
    import jax
    return model.typed_varinfo(jax.random.PRNGKey(seed)).link()


def _chain_health(name: str, ch) -> None:
    import numpy as np
    print(ch.health.report(), flush=True)
    require(bool(np.isfinite(ch.stats["logp"]).all()),
            f"{name}: non-finite log-density in the draws")
    for site in ch.names():
        require(bool(np.isfinite(ch[site]).all()),
                f"{name}: non-finite draws of '{site}'")
    require(int(np.sum(ch.health.nonfinite)) == 0,
            f"{name}: non-finite kernel state")
    require(ch.health.fallback_segments == 0,
            f"{name}: {ch.health.fallback_segments} segment(s) fell back "
            "to the reference path")


# ---------------------------------------------------------------------------
# phase 3 — fused leapfrog (Pallas fused_leapfrog / fused_potential_vg)
# ---------------------------------------------------------------------------
def phase_fused_leapfrog(dim: int = 10_000, chains: int = 16,
                         warmup: int = 100, samples: int = 100) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.program import cached_potential, program_cache
    from repro.infer import HMC, run_chains
    from repro.kernels.fused_leapfrog import potential_value_and_grad
    from repro.kernels.fused_leapfrog import ref as leapfrog_ref
    from repro.models import paper_suite

    pm = paper_suite.gaussian_10k(dim)
    res = cached_potential(pm.model, _linked_trace(pm.model))
    print(f"gaussian_10k spec kind: {res.kind} ({res.reason})", flush=True)
    require(res.kind == "separable",
            f"gaussian_10k compiled to {res.kind!r}, not 'separable'")

    u = np.asarray(0.5 * np.random.default_rng(1).normal(size=dim),
                   np.float32)
    vg = jax.jit(lambda x: potential_value_and_grad(res.spec, x))
    lp, g = vg(jnp.asarray(u))
    hlo = vg.lower(jnp.asarray(u)).compile().as_text()
    require(kernel_in_hlo(hlo, "fused_potential_vg"),
            "potential_value_and_grad did not compile to the Pallas kernel")

    with jax.default_device(_cpu()):
        pm_cpu = paper_suite.gaussian_10k(dim)
        lp_ref, g_ref = _on_cpu(jax.jit(
            lambda x: leapfrog_ref.potential_value_and_grad_ref(res.spec, x)
        )(u))
        lp_hw, g_hw = _on_cpu(jax.jit(jax.value_and_grad(pm_cpu.handwritten))(u))
    parity("gaussian_10k potential value vs ref (cpu)", lp, lp_ref,
           TOL_DENSITY)
    parity("gaussian_10k potential grad vs ref (cpu)", g, g_ref, TOL_DENSITY)
    parity("gaussian_10k potential value vs handwritten (cpu)", lp, lp_hw,
           TOL_DENSITY)
    parity("gaussian_10k potential grad vs handwritten (cpu)", g, g_hw,
           TOL_DENSITY)

    kernel = HMC(step_size=0.1, n_leapfrog=4, leapfrog="fused")
    ch = run_chains(jax.random.PRNGKey(2), pm.model, kernel, samples,
                    num_warmup=warmup, num_chains=chains)
    _chain_health("gaussian_10k", ch)
    progs = [program_cache().get(k) for k in program_cache().keys()
             if k.kind == "chain"]
    require(len(progs) == 1, f"expected one chain program, found {progs}")
    hlo = jax.jit(progs[0].raw).lower(
        jax.ShapeDtypeStruct((chains, 2), jnp.uint32),
        jax.ShapeDtypeStruct((chains, dim), jnp.float32)).compile().as_text()
    require(kernel_in_hlo(hlo, "fused_leapfrog"),
            "the gaussian_10k chain program does not run the Pallas "
            "fused_leapfrog kernel")
    x = ch["x"]
    mean, sd = float(x.mean()), float(x.std())
    print(f"gaussian_10k draws {x.shape}: pooled mean {mean!r}, pooled sd "
          f"{sd!r}, accept {float(ch.stats['accept_prob'].mean())!r}",
          flush=True)
    require(abs(mean) <= TOL_DRAW_MEAN,
            f"gaussian_10k pooled mean {mean!r} beyond {TOL_DRAW_MEAN}")
    require(abs(sd - 1.0) <= TOL_DRAW_SD,
            f"gaussian_10k pooled sd {sd!r} beyond 1 +- {TOL_DRAW_SD}")


# ---------------------------------------------------------------------------
# phase 4 — fused log-joint (Pallas site_block_sum) under NUTS
# ---------------------------------------------------------------------------
def _logreg_point(dim: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return np.asarray(0.1 * rng.normal(size=dim + 1), np.float32)


def phase_fused_logjoint(n: int = 10_000, dim: int = 100, chains: int = 8,
                         warmup: int = 100, samples: int = 100):
    import jax
    import jax.numpy as jnp

    from repro.core.program import cached_potential, density_program
    from repro.infer import NUTS, run_chains
    from repro.models import paper_suite

    pm = paper_suite.logreg(n=n, dim=dim)
    tvi = _linked_trace(pm.model)
    res = cached_potential(pm.model, tvi)
    print(f"logreg spec kind: {res.kind} ({res.reason}); NUTS integrates "
          "the fused log-joint with autodiff gradients", flush=True)

    f_vg = jax.jit(jax.value_and_grad(
        density_program(pm.model, tvi, backend="fused").raw))
    r_vg = jax.jit(jax.value_and_grad(
        pm.model.make_logdensity_fn(tvi, backend="reference")))
    hlo = f_vg.lower(jnp.asarray(_logreg_point(dim, 0))).compile().as_text()
    require(kernel_in_hlo(hlo, "fused_bernoulli_logpdf"),
            "the logreg density program does not run the Pallas "
            "fused_bernoulli_logpdf kernel")
    require(not kernel_in_hlo(hlo, "fused_std_normal_logpdf"),
            "the logreg prior's 101-coordinate block launches the Pallas "
            "fused_std_normal_logpdf kernel; one (8, 128) tile is XLA's")
    print("logreg density program: fused_bernoulli_logpdf is a "
          "tpu_custom_call, the prior is XLA's", flush=True)

    with jax.default_device(_cpu()):
        pm_cpu = paper_suite.logreg(n=n, dim=dim)
        hw_vg = jax.jit(jax.value_and_grad(pm_cpu.handwritten))
    for seed in (0, 1):
        q = _logreg_point(dim, seed)
        vf, gf = f_vg(jnp.asarray(q))
        vr, gr = r_vg(jnp.asarray(q))
        with jax.default_device(_cpu()):
            vh, gh = _on_cpu(hw_vg(q))
        parity(f"logreg[{seed}] value fused vs reference (tpu)", vf, vr,
               TOL_DENSITY)
        parity(f"logreg[{seed}] grad fused vs reference (tpu)", gf, gr,
               TOL_DENSITY)
        parity(f"logreg[{seed}] value fused (tpu) vs handwritten (cpu)",
               vf, vh, TOL_DENSITY)
        parity(f"logreg[{seed}] grad fused (tpu) vs handwritten (cpu)",
               gf, gh, TOL_DENSITY)

    ch = run_chains(jax.random.PRNGKey(3), pm.model, NUTS(), samples,
                    num_warmup=warmup, num_chains=chains, backend="fused")
    _chain_health("logreg", ch)
    print(f"logreg NUTS: mean tree depth "
          f"{float(ch.stats['tree_depth'].mean())!r}, accept "
          f"{float(ch.stats['accept_prob'].mean())!r}", flush=True)
    return pm, ch


# ---------------------------------------------------------------------------
# phase 5 — conditional potential spec (eight_schools)
# ---------------------------------------------------------------------------
def phase_conditional(chains: int = 8, warmup: int = 100,
                      samples: int = 100) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.program import cached_potential, density_program
    from repro.infer import NUTS, run_chains
    from repro.kernels.fused_leapfrog import potential_value_and_grad
    from repro.models import paper_suite

    pm = paper_suite.eight_schools()
    tvi = _linked_trace(pm.model)
    res = cached_potential(pm.model, tvi)
    print(f"eight_schools spec kind: {res.kind} ({res.reason})", flush=True)
    require(res.kind == "conditional",
            f"eight_schools compiled to {res.kind!r}, not 'conditional'")

    with jax.default_device(_cpu()):
        hw_vg = jax.jit(jax.value_and_grad(paper_suite.eight_schools()
                                           .handwritten))
    d_vg = jax.jit(jax.value_and_grad(
        density_program(pm.model, tvi, backend="fused").raw))
    s_vg = jax.jit(lambda x: potential_value_and_grad(res.spec, x))
    rng = np.random.default_rng(4)
    for k in range(2):
        q = np.asarray(rng.normal(size=tvi.num_flat), np.float32)
        vd, gd = d_vg(jnp.asarray(q))
        vs, gs = s_vg(jnp.asarray(q))
        with jax.default_device(_cpu()):
            vh, gh = _on_cpu(hw_vg(q))
        parity(f"eight_schools[{k}] density value (tpu) vs handwritten "
               "(cpu)", vd, vh, TOL_DENSITY)
        parity(f"eight_schools[{k}] density grad (tpu) vs handwritten "
               "(cpu)", gd, gh, TOL_DENSITY)
        parity(f"eight_schools[{k}] cond spec value (tpu) vs handwritten "
               "(cpu)", vs, vh, TOL_DENSITY)
        parity(f"eight_schools[{k}] cond spec grad (tpu) vs handwritten "
               "(cpu)", gs, gh, TOL_DENSITY)

    ch = run_chains(jax.random.PRNGKey(5), pm.model, NUTS(), samples,
                    num_warmup=warmup, num_chains=chains)
    _chain_health("eight_schools", ch)
    print(f"eight_schools NUTS: mu mean {float(ch.mean('mu'))!r}, tau mean "
          f"{float(ch.mean('tau'))!r}, divergences "
          f"{int(np.sum(ch.health.divergences))}", flush=True)


# ---------------------------------------------------------------------------
# phase 6 — prob queries and the QueryServer against NumPy float64
# ---------------------------------------------------------------------------
def _logreg_reference(X, y, w, b):
    """Plain float64 log prior / log likelihood of logreg."""
    import numpy as np
    X, y = np.asarray(X, np.float64), np.asarray(y, np.float64)
    w, b = np.asarray(w, np.float64), float(b)
    c = 0.5 * np.log(2.0 * np.pi)
    prior = (np.sum(-0.5 * w * w - c)
             + (-0.5 * (b / 3.0) ** 2 - np.log(3.0) - c))
    logit = X @ w + b
    lik = np.sum(y * logit - np.logaddexp(0.0, logit))
    return float(prior), float(lik)


def _ppd_reference(X, y, ws, bs):
    import numpy as np
    lls = np.array([_logreg_reference(X, y, w, b)[1]
                    for w, b in zip(ws, bs)])
    top = lls.max()
    return float(top + np.log(np.mean(np.exp(lls - top))))


def phase_queries(pm, chain, num_draws: int = 16) -> None:
    import numpy as np

    from repro.core.program import program_cache
    from repro.core.queries import prob
    from repro.launch.serve import QueryServer

    X, y = pm.data["X"], pm.data["y"]
    dim = X.shape[1]
    ws = chain.flat("w")[:num_draws]
    bs = chain.flat("b")[:num_draws]
    rng = np.random.default_rng(6)

    def point(k):
        return (np.asarray(0.1 * rng.normal(size=dim), np.float32),
                np.float32(0.1 * k))

    def request(kind, w0, b0):
        binds = {"m": pm.model, "y0": y, "w0": w0, "b0": b0}
        if kind == "prior":
            return "w = w0, b = b0 | model = m", binds
        if kind == "likelihood":
            return "y = y0 | w = w0, b = b0, model = m", binds
        if kind == "joint":
            return "y = y0, w = w0, b = b0 | model = m", binds
        binds["c"] = {"w": ws, "b": bs}
        return "y = y0 | chain = c, model = m", binds

    def reference(kind, w0, b0):
        if kind == "posterior_predictive":
            return _ppd_reference(X, y, ws, bs)
        prior, lik = _logreg_reference(X, y, w0, b0)
        return {"prior": prior, "likelihood": lik,
                "joint": prior + lik}[kind]

    kinds = ("prior", "likelihood", "joint", "posterior_predictive")
    for kind in kinds:
        w0, b0 = point(0)
        spec, binds = request(kind, w0, b0)
        got = prob(spec, **binds)
        parity(f"prob {kind} (tpu) vs numpy float64", got,
               reference(kind, w0, b0), TOL_QUERY)

    cache = program_cache()
    for kind in kinds:
        progs = [cache.get(k) for k in cache.keys()
                 if k.kind == f"query/{kind}"]
        require(len(progs) == 1 and progs[0].calls >= 1
                and progs[0].retraces >= 1,
                f"prob {kind} did not run as one compiled cached program")
    print("prob: each kind ran as one compiled program of the cache",
          flush=True)

    server = QueryServer()
    reqs, refs = [], []
    for i in range(8):
        kind = kinds[i % 4]
        w0, b0 = point(i + 1)
        reqs.append(request(kind, w0, b0))
        refs.append(reference(kind, w0, b0))
    answers = server.serve(reqs)
    for i, (got, want) in enumerate(zip(answers, refs)):
        parity(f"QueryServer request {i} ({kinds[i % 4]}) vs numpy float64",
               got, want, TOL_QUERY)
    stats = server.stats.as_dict()
    print(f"QueryServer: {stats}", flush=True)
    batched = [cache.get(k) for k in cache.keys()
               if k.kind.startswith("query/") and k.kind.endswith("/batched")]
    require(stats["groups"] == 4 and len(batched) == 4
            and all(p.calls >= 1 and p.retraces >= 1 for p in batched),
            "QueryServer did not evaluate its 4 groups as compiled programs")


# ---------------------------------------------------------------------------
# --four-chips — logreg on a 2 x 2 chains x data mesh vs one chip
# ---------------------------------------------------------------------------
def phase_four_chips(chains: int = 8, warmup: int = 100,
                     samples: int = 100) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.infer import NUTS, run_chains
    from repro.models import paper_suite
    from repro.sharding import (ShardedRun, make_sharded_logdensity,
                                sharded_arrays)

    devices = jax.devices()
    require(len(devices) == 4, f"--four-chips needs 4 devices, JAX has "
            f"{len(devices)}")
    pm = paper_suite.logreg()
    dim = pm.data["X"].shape[1]
    # X and y share the observation axis, so both are cut into data shards
    plan = ShardedRun.plan(data_shards=2, shard_sites=("X", "y"))
    print(f"mesh plan: {plan}", flush=True)

    for site, arr in zip(plan.shard_sites, sharded_arrays(pm.model, plan)):
        held = {s.device for s in arr.addressable_shards}
        rows = {s.data.shape[0] for s in arr.addressable_shards}
        print(f"shard site '{site}' {arr.shape}: rows per device {rows} on "
              f"{sorted(d.id for d in held)}", flush=True)
        require(held == set(devices) and rows == {arr.shape[0] // 2},
                f"'{site}' is not split over the data axis of all 4 devices")

    tvi = _linked_trace(pm.model)
    ld_mesh = make_sharded_logdensity(pm.model, tvi, plan)
    mesh_vg = jax.jit(jax.value_and_grad(ld_mesh.raw))
    one_vg = jax.jit(jax.value_and_grad(pm.model.make_logdensity_fn(tvi)))
    q = jnp.asarray(_logreg_point(dim, 0))
    hlo = mesh_vg.lower(q).compile().as_text()
    require("all-reduce" in hlo, "the sharded density has no all-reduce")
    for seed in (0, 1):
        q = jnp.asarray(_logreg_point(dim, seed))
        vm, gm = mesh_vg(q)
        vo, go = one_vg(q)
        parity(f"logreg[{seed}] value mesh vs one chip", vm, vo, TOL_DENSITY)
        parity(f"logreg[{seed}] grad mesh vs one chip", gm, go, TOL_DENSITY)

    key = jax.random.PRNGKey(7)
    ch_mesh = run_chains(key, pm.model, NUTS(), samples, num_warmup=warmup,
                         num_chains=chains, mesh=plan)
    _chain_health("logreg mesh", ch_mesh)
    ch_one = run_chains(key, pm.model, NUTS(), samples, num_warmup=warmup,
                        num_chains=chains)
    _chain_health("logreg one chip", ch_one)
    qm = np.concatenate([ch_mesh.flat("w"), ch_mesh.flat("b")[:, None]], 1)
    qo = np.concatenate([ch_one.flat("w"), ch_one.flat("b")[:, None]], 1)
    same = float(np.mean(np.all(np.abs(qm - qo) <= 1e-4, axis=1)))
    sd = qo.std(axis=0)
    mean_gap = float(np.max(np.abs(qm.mean(axis=0) - qo.mean(axis=0)) / sd))
    sd_gap = float(np.max(np.abs(qm.std(axis=0) / sd - 1.0)))
    print(f"mesh vs one chip draws: share equal to 1e-4 {same!r}; max "
          f"|mean diff|/sd {mean_gap!r} (tol {TOL_MESH_MEAN}); max "
          f"|sd ratio - 1| {sd_gap!r} (tol {TOL_MESH_SD})", flush=True)
    require(mean_gap <= TOL_MESH_MEAN and sd_gap <= TOL_MESH_SD,
            "the mesh run's posterior does not match the one-chip run's")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the 2 x 2 chains x data mesh check")
    args = p.parse_args(argv)

    # the references run on the CPU device of this same process
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    dev = require_tpu()
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:  # the version string is informational only
        libtpu = "unknown"
    print(f"device: {dev.device_kind} x {len(jax.devices())} "
          f"(platform {dev.platform}); jax {jax.__version__}, "
          f"libtpu {libtpu}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = SetupClock()

    if args.four_chips:
        with clock.phase("four chips: logreg on a 2 x 2 chains x data mesh"):
            phase_four_chips()
    else:
        with clock.phase("fused leapfrog: gaussian_10k, static HMC"):
            phase_fused_leapfrog()
        with clock.phase("fused log-joint: logreg, NUTS"):
            pm, chain = phase_fused_logjoint()
        with clock.phase("conditional spec: eight_schools, NUTS"):
            phase_conditional()
        with clock.phase("queries: prob and QueryServer on logreg"):
            phase_queries(pm, chain)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
