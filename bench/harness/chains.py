"""Traffic of kind ``chains``: whole ``run_chains`` jobs in a closed loop.

A job is what a user submits: ``num_chains`` chains of ``num_warmup``
adaptation and ``num_samples`` kept draws, one call, with a key drawn from
the seed. The window loops jobs; after each, the benchmark keeps what the
check and the metrics need (every kept draw of every parameter, a seeded
sample of whole draws with the log density the program recorded for
them, and the sampler's counters) and drops the rest.

After the window, ``read_back`` evaluates the value and gradient that the
sampler's own kernel uses (``init`` of the kernel the chain program runs,
built from the same cached density programs) at the sampled draws, in
blocks of the fleet's ``num_chains`` lanes; ``check`` compares them with
the plain reference once the program is freed.
"""
from __future__ import annotations

import hashlib

import numpy as np

from harness import traffic as gen


class Driver:
    span = "run_chains"

    def __init__(self, cell, seed: int):
        import repro.infer
        from repro.models import paper_suite

        cfg, tr = cell.config, cell.traffic
        self.cell, self.seed = cell, int(seed)
        self.pm = paper_suite.build(cfg["model"], **cfg["sizes"])
        sampler = cfg["sampler"]
        self.kernel = getattr(repro.infer, sampler["class"])(
            **sampler["params"])
        self.backend = cfg["backend"]
        self.chains = int(tr["num_chains"])
        self.warmup = int(tr["num_warmup"])
        self.samples = int(tr["num_samples"])
        self.ref = cell.reference.Reference(cfg)
        self.per_job = int(tr["check_draws_per_job"])
        self.jobs = []
        self.program_grads = None

    def _key(self, job: int):
        import jax
        return jax.random.PRNGKey(gen.job_seed(self.seed, job))

    def call(self, job: int):
        from repro.infer import run_chains
        return run_chains(self._key(job), self.pm.model, self.kernel,
                          self.samples, num_warmup=self.warmup,
                          num_chains=self.chains, backend=self.backend)

    def keep(self, job: int, chain) -> None:
        q = np.asarray(self.ref.param_vector(chain.draws), np.float32)
        rng = gen.stream(self.seed, 5, job)
        c = rng.integers(0, self.chains, self.per_job)
        s = rng.integers(0, self.samples, self.per_job)
        stats = chain.stats
        self.jobs.append({
            "draws": q,
            "q": np.array(q[c, s], np.float64),
            "sites": {k: np.array(v[c, s]) for k, v in chain.draws.items()},
            "logp": np.array(stats["logp"][c, s], np.float64),
            "tree_depth": (np.array(stats["tree_depth"])
                           if "tree_depth" in stats else None),
            "nonfinite": int(np.size(stats["logp"])
                             - np.isfinite(stats["logp"]).sum()),
        })

    def record(self) -> dict:
        """What the metric readers read of the jobs kept so far."""
        return {"kind": "chains", "chains": self.chains,
                "num_warmup": self.warmup, "num_samples": self.samples,
                "jobs": list(self.jobs), "config": self.cell.config,
                "num_params": self.ref.num_params,
                "traffic": self.cell.traffic}

    def sampled_flat(self):
        """The sampled draws in the program's flat unconstrained layout,
        with the layout's site slices."""
        from repro.infer.chains import setup_chain_driver
        tvi, kern, _, _, _ = setup_chain_driver(
            self._key(0), self.pm.model, self.kernel,
            num_chains=self.chains, backend=self.backend)
        layout = tvi.layout
        flat = np.zeros((sum(len(j["q"]) for j in self.jobs),
                         layout.unc_size), np.float32)
        row = 0
        for j in self.jobs:
            k = len(j["q"])
            for site in layout.sites:
                if site.support != "real":
                    raise ValueError(f"site {site.name!r} is not on the real "
                                     "line; its draws are not its flat value")
                flat[row:row + k, site.unc_offset:
                     site.unc_offset + site.unc_size] = \
                    j["sites"][site.name].reshape(k, -1)
            row += k
        return flat, layout, kern

    def read_back(self) -> None:
        """The sampler's value and gradient at the sampled draws, from the
        kernel the window's chain program was built with, ``num_chains``
        lanes at a time (the fleet's own batch)."""
        import jax
        import jax.numpy as jnp
        flat, layout, kern = self.sampled_flat()
        init = jax.jit(jax.vmap(lambda q: kern.init(q)[1:3]))
        c, grads = self.chains, []
        for i in range(0, len(flat), c):
            block = np.resize(flat[i:i + c], (c, flat.shape[1]))
            _, g = init(jnp.asarray(block))
            grads.append(np.asarray(g, np.float64)[:len(flat[i:i + c])])
        g = np.concatenate(grads)
        self.program_grads = {s.name: g[:, s.unc_offset:
                                        s.unc_offset + s.unc_size]
                              for s in layout.sites}

    def free(self) -> None:
        from repro.core.program import clear_cache
        self.pm = self.kernel = None
        clear_cache()

    def work(self, calls: int) -> dict:
        return {"draws": calls * self.chains * self.samples}

    def check(self, checks, limits: dict) -> None:
        """Compare what the window produced with the plain reference."""
        q = np.concatenate([j["q"] for j in self.jobs])
        logp = np.concatenate([j["logp"] for j in self.jobs])
        want = self.ref.logp(q)
        gap = np.abs(logp - want) / np.maximum(1.0, np.abs(want))
        checks.add("logp_gap", float(np.max(gap)) if np.isfinite(gap).all()
                   else float("inf"), limits["logp_gap"])
        checks.add("grad_gap", grad_gap(self.program_grads,
                                        self.ref.grad_leaves(q)),
                   limits["grad_gap"])
        n = sum(j["draws"].shape[0] * j["draws"].shape[1] for j in self.jobs)
        mean = sum(j["draws"].sum(axis=(0, 1), dtype=np.float64)
                   for j in self.jobs) / n
        sd = np.sqrt(sum(np.square(j["draws"] - mean).sum(axis=(0, 1))
                         for j in self.jobs) / n)
        mean_ref, sd_ref = self.ref.posterior_moments()
        checks.add("mean_gap", float(np.max(np.abs(mean - mean_ref) / sd_ref)),
                   limits["mean_gap"])
        checks.add("sd_gap", float(np.max(np.abs(sd / sd_ref - 1.0))),
                   limits["sd_gap"])
        ids = [hashlib.blake2b(chain.tobytes(), digest_size=16).digest()
               for j in self.jobs for chain in j["draws"]]
        checks.add("dup_chains", len(ids) - len(set(ids)),
                   limits["dup_chains"])
        checks.add("nonfinite", sum(j["nonfinite"] for j in self.jobs)
                   + sum(int(np.size(j["draws"])
                             - np.isfinite(j["draws"]).sum())
                         for j in self.jobs),
                   limits["nonfinite"])


def grad_gap(got: dict, want: dict) -> float:
    """Largest gradient gap over draws and leaves: per draw and leaf the
    norm of the difference over the reference leaf's norm, or the median
    leaf's norm at that draw where that is larger (a leaf whose gradient
    is near nought is not divided by nought)."""
    if got is None:
        return float("inf")
    names = sorted(want)
    ref_norm = np.stack([np.linalg.norm(want[k], axis=-1) for k in names])
    floor = np.median(ref_norm, axis=0)
    worst = 0.0
    for k, norm in zip(names, ref_norm):
        g = np.asarray(got[k], np.float64)
        if not np.isfinite(g).all():
            return float("inf")
        diff = np.linalg.norm(g - want[k], axis=-1)
        worst = max(worst, float(np.max(diff / np.maximum(norm, floor))))
    return worst
