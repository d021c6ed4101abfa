"""The program's own spans, counters and named scopes.

``run_chains`` writes host spans into the profiler's trace (root, set-up,
dispatch, collect, and the program cache's fingerprint and build), all
tagged with one ``call`` number; NUTS counts the leapfrog steps of each
tree (``n_leapfrog``); the cache counts the bytes it hashes to key
programs (``fingerprint_bytes``); the log density and the integrator run
under ``jax.named_scope``.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import cache_stats, model, observe, sample
from repro.ckpt.checkpoint import read_meta
from repro.dists import HalfNormal, Normal
from repro.infer import HMC, NUTS, run_chains

SPANS = ("repro.run_chains", "repro.run_chains.setup",
         "repro.run_chains.dispatch", "repro.run_chains.collect",
         "repro.program.fingerprint", "repro.program.build")


def _model(n=100):
    y = np.random.default_rng(7).normal(2.0, 1.0, n).astype(np.float32)

    # a fresh generator per call, so the program cache starts cold for it
    @model
    def g(y):
        mu = sample("mu", Normal(0.0, 10.0))
        s = sample("s", HalfNormal(2.0))
        observe("y", Normal(mu, s), y)

    return g(jnp.asarray(y)), y


def _program_spans(log_dir):
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("repro.", "bench.")):
                    out.append((line.name, e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return out


def test_run_chains_spans_share_one_call_and_nest(tmp_path):
    m, _ = _model()
    kern = NUTS(step_size=0.1, max_depth=4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # Python function events would slow it
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(2):
            run_chains(jax.random.PRNGKey(0), m, kern, 10, num_warmup=10,
                       num_chains=2)
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(str(tmp_path))
    # the benchmark counts each ``bench.`` span as a user call
    assert not [s for s in spans if s[1].startswith("bench.")]
    roots = [s for s in spans if s[1] == "repro.run_chains"]
    assert len(roots) == 2
    first, second = (r[4]["call"] for r in roots)
    assert second == first + 1
    assert roots[0][4]["num_chains"] == 2
    by_call = {}
    for line, name, start, end, stats in spans:
        root = next(r for r in roots if r[4]["call"] == stats["call"])
        assert line == root[0] and root[2] <= start <= end <= root[3]
        by_call.setdefault(stats["call"], set()).add(name)
    assert by_call[first] == set(SPANS)
    # the second identical call builds and retraces nothing
    assert by_call[second] == set(SPANS) - {"repro.program.build"}
    fp = [s[4] for s in spans if s[1] == "repro.program.fingerprint"]
    assert all(s["bytes"] == 400 for s in fp)


@pytest.mark.parametrize("leapfrog", ["auto", "reference"])
def test_nuts_n_leapfrog_lies_within_its_tree(leapfrog):
    m, _ = _model()
    ch = run_chains(jax.random.PRNGKey(1), m,
                    NUTS(step_size=0.3, max_depth=6, leapfrog=leapfrog),
                    40, num_warmup=20, num_chains=3)
    n, depth = ch.stats["n_leapfrog"], ch.stats["tree_depth"]
    assert n.shape == depth.shape == (3, 40) and n.dtype == np.int32
    assert (depth >= 1).all()
    assert ((2 ** (depth - 1) <= n) & (n <= 2 ** depth - 1)).all()
    assert n.sum() <= (2 ** depth - 1).sum()


def test_n_leapfrog_flows_through_the_segmented_driver(tmp_path):
    m, _ = _model()
    d = str(tmp_path / "ckpt")
    ch = run_chains(jax.random.PRNGKey(2), m, NUTS(max_depth=5), 12,
                    num_warmup=6, num_chains=2, checkpoint_dir=d,
                    checkpoint_every=5)
    assert read_meta(d)["format"] == "run_chains/3"
    n, depth = ch.stats["n_leapfrog"], ch.stats["tree_depth"]
    assert n.shape == (2, 12)
    assert ((2 ** (depth - 1) <= n) & (n <= 2 ** depth - 1)).all()


def test_fingerprint_bytes_per_call():
    m, y = _model()
    kern = NUTS(max_depth=4)
    run_chains(jax.random.PRNGKey(0), m, kern, 5, num_chains=2)
    before = cache_stats()["fingerprint_bytes"]
    ch = run_chains(jax.random.PRNGKey(0), m, kern, 5, num_chains=2)
    # the density, the potential and the chain program each key on y
    assert ch.health.fingerprint_bytes == 3 * y.nbytes
    assert cache_stats()["fingerprint_bytes"] - before == 3 * y.nbytes
    assert f"{3 * y.nbytes} byte(s) fingerprinted" in ch.health.report()


@pytest.mark.parametrize("sampler", [NUTS(max_depth=3),
                                     HMC(n_leapfrog=3, leapfrog="reference")],
                         ids=["nuts", "hmc"])
def test_named_scopes_in_the_chain_program(sampler):
    m, _ = _model(16)
    tvi = m.typed_varinfo(jax.random.PRNGKey(0)).link()
    ld = m.make_logdensity_fn(tvi)
    kern = sampler.make_kernel(ld, int(tvi.num_flat))
    state = kern.init(tvi.flat())
    text = jax.jit(kern.step).lower(state, jax.random.PRNGKey(1)) \
        .as_text(debug_info=True)
    assert "repro.logdensity" in text
    if isinstance(sampler, NUTS):
        assert "repro.integrator" in text
