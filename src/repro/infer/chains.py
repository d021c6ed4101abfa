"""Chain container, MCMC diagnostics, and the vmapped multi-chain driver.

Three layers:

* ``Chain`` + ``effective_sample_size`` / ``split_rhat`` — posterior draw
  storage with a leading chain axis and the standard mixing diagnostics.
* ``TransitionKernel`` — the protocol every MCMC sampler exposes through
  ``make_kernel(logdensity, dim)``: pure ``init``/``warm``/``finalize``/
  ``step`` functions over a flat unconstrained state, with no Python state,
  so a whole chain is one ``lax.scan`` and MANY chains are one ``vmap``.
* ``run_chains`` — the many-chains-on-one-device driver (GenJAX-style):
  builds the model's fused flat log-density ONCE, vmaps the transition
  kernel over a leading chain axis with per-chain PRNG keys and jittered
  inits, and packages the stacked draws back through the typed trace.
"""
from __future__ import annotations

import warnings
from typing import Any, Callable, Dict, NamedTuple, Optional

import numpy as np

__all__ = ["Chain", "TransitionKernel", "effective_sample_size",
           "package_draws", "run_chains", "split_rhat"]


def _fmt(v, width: int, prec: int) -> str:
    """Fixed-width float cell; non-finite renders as an explicit marker
    (``n/a``) instead of a bare ``nan`` so degenerate diagnostics are
    visible at a glance."""
    v = float(v)
    if np.isnan(v):
        return f"{'n/a':>{width}}"
    return f"{v:>{width}.{prec}f}"


class Chain:
    """Posterior draws: dict name -> (num_chains, num_samples, ...) arrays.

    Single-chain results are stored with a leading chain axis of 1.
    ``health`` (optional) is the :class:`~repro.infer.driver.ChainHealth`
    report the driver produced; ``summary()`` appends it when present.
    """

    def __init__(self, draws: Dict[str, Any],
                 stats: Optional[Dict[str, Any]] = None, health=None):
        self.draws = {k: np.asarray(v) for k, v in draws.items()}
        self.stats = {k: np.asarray(v) for k, v in (stats or {}).items()}
        self.health = health
        first = next(iter(self.draws.values()))
        self.num_chains, self.num_samples = first.shape[0], first.shape[1]

    def __getitem__(self, name: str) -> np.ndarray:
        return self.draws[name]

    def names(self):
        return list(self.draws)

    def flat(self, name: str) -> np.ndarray:
        """(num_chains*num_samples, ...) view of a variable."""
        v = self.draws[name]
        return v.reshape((-1,) + v.shape[2:])

    def mean(self, name: str):
        return self.flat(name).mean(axis=0)

    def std(self, name: str):
        return self.flat(name).std(axis=0)

    def to_dict_of_flat(self) -> Dict[str, np.ndarray]:
        return {n: self.flat(n) for n in self.names()}

    def summary(self) -> str:
        has_div = "diverging" in self.stats
        n_div = int(np.sum(self.stats["diverging"])) if has_div else 0
        header = f"{'param':<18}{'mean':>12}{'std':>12}{'ess':>10}{'rhat':>8}"
        if has_div:
            header += f"{'div':>6}"
        lines = [header]
        for n in self.names():
            v = self.draws[n]
            scalar = v.reshape(v.shape[0], v.shape[1], -1)[..., 0]
            ess = effective_sample_size(scalar)
            rhat = split_rhat(scalar)
            row = (f"{n:<18}{_fmt(self.mean(n).ravel()[0], 12, 4)}"
                   f"{_fmt(self.std(n).ravel()[0], 12, 4)}"
                   f"{_fmt(ess, 10, 1)}{_fmt(rhat, 8, 3)}")
            if has_div:
                row += f"{n_div:>6d}"
            lines.append(row)
        if self.health is not None:
            lines += ["", self.health.report()]
        return "\n".join(lines)

    def __repr__(self):
        return (f"Chain(chains={self.num_chains}, samples={self.num_samples}, "
                f"vars={self.names()})")


def _autocov(x: np.ndarray) -> np.ndarray:
    n = x.shape[-1]
    x = x - x.mean(axis=-1, keepdims=True)
    nfft = int(2 ** np.ceil(np.log2(2 * n)))
    f = np.fft.rfft(x, nfft, axis=-1)
    acov = np.fft.irfft(f * np.conj(f), nfft, axis=-1)[..., :n].real
    return acov / n


def effective_sample_size(x: np.ndarray) -> float:
    """Geyer initial-monotone ESS for (chains, samples) scalar draws.

    Degenerate inputs — fewer than 4 draws per chain, or zero variance
    (a constant / fully stuck chain) — have no defined ESS; those cases
    return ``nan`` WITH an explicit ``RuntimeWarning`` naming the cause
    rather than silently propagating ``nan`` arithmetic."""
    x = np.atleast_2d(np.asarray(x, dtype=np.float64))
    m, n = x.shape
    if n < 4:
        warnings.warn(
            f"effective_sample_size is undefined for {n} draws per chain "
            "(need >= 4); returning nan", RuntimeWarning, stacklevel=2)
        return float("nan")
    acov = _autocov(x)
    mean_var = acov[:, 0].mean() * n / (n - 1.0)
    var_plus = mean_var * (n - 1.0) / n
    if m > 1:
        var_plus += x.mean(axis=1).var(ddof=1)
    if not np.isfinite(var_plus) or var_plus <= 1e-300:
        warnings.warn(
            "effective_sample_size is undefined for zero-variance or "
            "non-finite draws (constant / stuck chain?); returning nan",
            RuntimeWarning, stacklevel=2)
        return float("nan")
    rho = 1.0 - (mean_var - acov.mean(axis=0)) / var_plus
    # Geyer initial-positive-monotone sequence over lag pairs
    prev_pair = np.inf
    tau = 1.0
    t = 1
    while t + 1 < n:
        pair = rho[t] + rho[t + 1]
        if pair < 0:
            break
        pair = min(pair, prev_pair)  # initial monotone
        prev_pair = pair
        tau += 2.0 * pair
        t += 2
    return float(m * n / max(tau, 1e-12))


def split_rhat(x: np.ndarray) -> float:
    """Split-chain potential scale reduction factor.

    Degenerate inputs warn explicitly instead of silently returning a
    bare ``nan``: fewer than 4 draws per chain -> ``nan``; zero variance
    everywhere (all chains constant at one point) -> ``nan``; zero
    within-chain variance but distinct chain means (chains stuck at
    DIFFERENT points — the worst possible mixing) -> ``inf``."""
    x = np.atleast_2d(np.asarray(x, dtype=np.float64))
    m, n = x.shape
    half = n // 2
    if half < 2:
        warnings.warn(
            f"split_rhat is undefined for {n} draws per chain (need >= 4 "
            "to split); returning nan", RuntimeWarning, stacklevel=2)
        return float("nan")
    halves = np.concatenate([x[:, :half], x[:, half:2 * half]], axis=0)
    m2, n2 = halves.shape
    chain_means = halves.mean(axis=1)
    chain_vars = halves.var(axis=1, ddof=1)
    w = chain_vars.mean()
    b = n2 * chain_means.var(ddof=1)
    if not np.isfinite(w) or w <= 1e-300:
        if not np.isfinite(b) or b <= 1e-300:
            warnings.warn(
                "split_rhat is undefined for zero-variance draws (all "
                "chains constant); returning nan",
                RuntimeWarning, stacklevel=2)
            return float("nan")
        warnings.warn(
            "split_rhat: zero within-chain variance with distinct chain "
            "means (chains stuck at different points); returning inf",
            RuntimeWarning, stacklevel=2)
        return float("inf")
    var_plus = (n2 - 1.0) / n2 * w + b / n2
    return float(np.sqrt(var_plus / w))


# ---------------------------------------------------------------------------
# vmapped multi-chain driver
# ---------------------------------------------------------------------------
class TransitionKernel(NamedTuple):
    """Pure-function MCMC transition kernel over a flat unconstrained state.

    Samplers build one via ``make_kernel(logdensity, dim)``. All four
    fields are jit/vmap-compatible closures:

    Attributes
    ----------
    init : callable
        ``q0 (dim,) -> state``; evaluates whatever the sampler caches
        (log-density, gradient, adaptation state) at the initial position.
    warm : callable
        ``(state, t, key) -> state``; one warmup transition at iteration
        ``t`` (a float scalar), including any step-size adaptation.
    finalize : callable
        ``state -> state``; freezes adapted quantities (e.g. the
        dual-averaged step size) before sampling starts. ``run_chains``
        calls it only after a non-empty warmup — with ``num_warmup=0``
        the configured (unadapted) settings are kept.
    step : callable
        ``(state, key) -> (state, out)`` with ``out`` a dict of per-draw
        arrays that MUST contain ``"q"`` (the flat position, shape
        ``(dim,)``) and ``"logp"``; extra keys become ``Chain.stats``.
    spec_reason : str, optional
        Why the fused-integrator PotentialSpec could NOT be compiled for
        this kernel (``None`` when a spec is in use or was never
        wanted) — the diagnosis from ``repro.core.potential``, surfaced
        so ``leapfrog="auto"`` fallbacks are explainable instead of
        silent.
    """

    init: Callable
    warm: Callable
    finalize: Callable
    step: Callable
    spec_reason: Optional[str] = None


def package_draws(tvi_linked, qs, stats: Optional[Dict[str, Any]] = None) -> Chain:
    """Map flat unconstrained draws back to constrained named arrays.

    Parameters
    ----------
    tvi_linked : TypedVarInfo
        Linked typed trace fixing the flat layout of ``qs``.
    qs : array, shape ``(num_chains, num_samples, num_flat)``
        Unconstrained draws.
    stats : dict of arrays, optional
        Per-draw sampler statistics, each ``(num_chains, num_samples, ...)``.

    Returns
    -------
    Chain
        Draws keyed by site symbol, each
        ``(num_chains, num_samples) + site.shape`` on the constrained
        support (one jitted double-vmap of ``replace_flat().invlink()``).
    """
    import jax

    from repro.core.program import (CompiledProgram, ProgramKey,
                                    program_cache, trace_fingerprint)

    # cached on the trace FINGERPRINT (layout + dist-leaf content): the
    # invlink bakes the stored dists' parameters (e.g. Uniform bounds),
    # so equal-layout traces with different dist params compile apart
    key = ProgramKey(trace_fingerprint(tvi_linked), "package",
                     tvi_linked.layout, (), "fused", ())

    def build():
        def to_constrained(q):
            return tvi_linked.replace_flat(q).invlink().as_dict()

        return CompiledProgram(
            key, lambda q: jax.vmap(jax.vmap(to_constrained))(q))

    prog = program_cache().get_or_build(key, build)
    draws = prog(qs)
    return Chain({k: np.asarray(v) for k, v in draws.items()},
                 stats={k: np.asarray(v) for k, v in (stats or {}).items()})


def setup_chain_driver(key, model, kernel, *, num_chains: int,
                       init_varinfo=None, init_jitter: float = 1.0,
                       backend: str = "fused"):
    """Shared preamble of the single-scan and segmented drivers.

    Builds the linked trace, the fused log-density, the sampler's
    :class:`TransitionKernel` (with a compiled PotentialSpec when the
    sampler wants one), jittered per-chain initial positions, and the
    per-chain PRNG keys. Key derivation here is THE contract both
    drivers share — it is what makes a segmented run draw-for-draw
    identical to a single-scan run under the same master key.

    Returns ``(tvi_linked, kern, dim, q0s, chain_keys)``.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.varinfo import assert_continuous_supports

    k_init, k_run = jax.random.split(key)
    tvi = (init_varinfo if init_varinfo is not None
           else model.typed_varinfo(k_init))
    assert_continuous_supports(tvi, type(kernel).__name__)
    tvi = tvi.link()
    # density + PotentialSpec come from the ProgramCache: repeated
    # run_chains / driver-segment calls on the same (model, layout,
    # backend) reuse one compiled program instead of re-tracing
    from repro.core.program import cached_potential, density_program
    logdensity = density_program(model, tvi, backend=backend)
    dim = int(tvi.num_flat)
    spec, spec_reason = None, None
    if getattr(kernel, "uses_potential_spec", False):
        res = cached_potential(model, tvi, backend=backend)
        spec, spec_reason = res.spec, res.reason
    kern = (kernel.make_kernel(logdensity, dim, spec=spec)
            if spec is not None else kernel.make_kernel(logdensity, dim))
    if spec_reason is not None and getattr(kern, "spec_reason", None) is None:
        kern = kern._replace(spec_reason=spec_reason)

    q0 = tvi.flat()
    q0s = jnp.broadcast_to(q0, (num_chains, dim))
    if init_jitter:
        q0s = q0s + jax.random.uniform(
            jax.random.fold_in(k_init, 7), (num_chains, dim),
            minval=-init_jitter, maxval=init_jitter)
    chain_keys = jax.random.split(k_run, num_chains)
    return tvi, kern, dim, q0s, chain_keys


def _chain_body(kern, num_warmup: int, num_samples: int):
    """The per-chain warmup+sampling scan both drivers vmap.

    Key derivation (``fold_in(chain_key, 1|2)`` then ``split``) is THE
    shared contract with the segmented driver's presplit key blocks — do
    not change one without the other.
    """
    import jax
    import jax.numpy as jnp

    def one_chain(ckey, q0):
        state = kern.init(q0)
        if num_warmup > 0:
            wkeys = jax.random.split(jax.random.fold_in(ckey, 1), num_warmup)
            ts = jnp.arange(num_warmup, dtype=jnp.float32)

            def warm_body(s, inp):
                t, k = inp
                return kern.warm(s, t, k), None

            state, _ = jax.lax.scan(warm_body, state, (ts, wkeys))
            # freeze adapted quantities only when adaptation actually ran:
            # dual-averaging's smoothed iterate starts at exp(0)=1.0, which
            # would silently replace the configured step size otherwise
            state = kern.finalize(state)
        skeys = jax.random.split(jax.random.fold_in(ckey, 2), num_samples)
        _, outs = jax.lax.scan(kern.step, state, skeys)
        return outs

    return one_chain


def _sharded_chain_outs(plan, model, tvi, kernel, dim: int, num_warmup: int,
                        num_samples: int, backend: str, chain_keys, q0s,
                        cache):
    """chains × data mesh program: shard_map(vmap(chain)) with the
    likelihood psum folded into the per-device fused log-joint.

    The transition kernel is REBUILT inside the mapped function from a
    density that binds this device's data shard — so each device runs
    one compiled per-shard program, and the only collective per leapfrog
    step is the scalar likelihood all-reduce (plus its transpose in the
    gradient). The fused-integrator PotentialSpec path is skipped here:
    a spec is compiled against the full-data density and cannot absorb
    the collective, so the mesh path uses the autodiff integrator over
    the fused density backend.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core.contexts import LikelihoodContext, PriorContext
    from repro.core.program import (CompiledProgram, ProgramKey,
                                    kernel_fingerprint, model_fingerprint)
    from repro.kernels.fused_logpdf.ops import all_reduce_block_sum
    from repro.sharding.data_parallel import sharded_arrays

    sites = plan.shard_sites
    shards = sharded_arrays(model, plan)

    def local_run(ckeys, local_q0s, *local_data):
        mm = model.bind(**dict(zip(sites, local_data)))

        def _prior(flat_u):
            return mm.logp_with_context(tvi.replace_flat(flat_u),
                                        PriorContext(), backend=backend)

        def _lik(flat_u):
            return mm.logp_with_context(tvi.replace_flat(flat_u),
                                        LikelihoodContext(), backend=backend)

        # The gradient must be taken INSIDE the mesh program (the kernel
        # differentiates the density per leapfrog step), and there the
        # naive grad of ``prior + psum(lik)`` is WRONG: psum's transpose
        # hands each device its own cotangent without re-summing, so a
        # chain would move along only its local shard's likelihood
        # gradient. custom_vjp restores the math — the backward pass
        # all-reduces the likelihood gradient exactly like the forward
        # all-reduces the likelihood value. (grad taken OUTSIDE a
        # shard_map — e.g. make_sharded_logdensity().raw — doesn't need
        # this: the shard_map boundary transposes replicated inputs
        # correctly.)
        @jax.custom_vjp
        def logdensity(flat_u):
            return _prior(flat_u) + all_reduce_block_sum(
                _lik(flat_u), plan.data_axis)

        def _ld_fwd(flat_u):
            val = _prior(flat_u) + all_reduce_block_sum(
                _lik(flat_u), plan.data_axis)
            return val, flat_u

        def _ld_bwd(flat_u, g):
            gp = jax.grad(_prior)(flat_u)
            gl = all_reduce_block_sum(jax.grad(_lik)(flat_u),
                                      plan.data_axis)
            return (g * (gp + gl),)

        logdensity.defvjp(_ld_fwd, _ld_bwd)

        kern = kernel.make_kernel(logdensity, dim)
        body = _chain_body(kern, num_warmup, num_samples)
        return jax.vmap(body)(ckeys, local_q0s)

    mapped = jax.shard_map(
        local_run, mesh=plan.mesh,
        in_specs=(P(plan.chain_axis), P(plan.chain_axis))
        + (P(plan.data_axis),) * len(sites),
        out_specs=P(plan.chain_axis), check_vma=False)

    csh = plan.chain_sharding()
    chain_keys = jax.device_put(chain_keys, csh)
    q0s = jax.device_put(q0s, csh)

    kfp = kernel_fingerprint(kernel)
    if kfp is None:
        return jax.jit(mapped)(chain_keys, q0s, *shards)
    num_chains = int(q0s.shape[0])
    pkey = ProgramKey(
        model_fingerprint(model), "chain", tvi.layout,
        (num_chains, num_warmup, num_samples), backend,
        (kfp,), plan.fingerprint())
    prog = cache.get_or_build(
        pkey, lambda: CompiledProgram(
            pkey, lambda ks, qs, *sh: mapped(ks, qs, *sh)))
    return prog(chain_keys, q0s, *shards)


def run_chains(key, model, kernel, num_samples: int, *, num_warmup: int = 0,
               num_chains: int = 4, init_varinfo=None, init_jitter: float = 1.0,
               backend: str = "fused", mesh=None,
               checkpoint_dir: Optional[str] = None,
               checkpoint_every: Optional[int] = None, checkpoint_keep: int = 3,
               preemption=None, fallback: bool = True) -> Chain:
    """Run ``num_chains`` MCMC chains as ONE vmap-compiled program.

    The model's log-density is built once from the typed trace (fused
    flat-buffer backend by default) and shared by every chain; the whole
    warmup+sampling loop of all chains is a single ``jit(vmap(...))`` —
    chains advance in lockstep on one device instead of running serially.

    Parameters
    ----------
    key : jax PRNG key
        Master key; split into one independent key per chain (plus one for
        trace discovery and init jitter).
    model : repro.core.model.Model
        Bound model to sample from.
    kernel : HMC | NUTS | RWMH
        Any sampler exposing ``make_kernel(logdensity, dim)``.
    num_samples : int
        Post-warmup draws per chain.
    num_warmup : int
        Warmup (adaptation) iterations per chain, discarded.
    num_chains : int
        Number of parallel chains (the leading axis of every result).
    init_varinfo : TypedVarInfo, optional
        Typed trace to initialise from; discovered from the prior if absent.
    init_jitter : float
        Half-width of the per-chain Uniform jitter around the discovery
        draw in UNCONSTRAINED space (overdispersed inits make split-R-hat
        meaningful). ``0.0`` starts every chain at the same point.
    backend : {"fused", "reference"}
        Log-density backend (see ``Model.make_logdensity_fn``).
    mesh : ShardedRun or jax.sharding.Mesh, optional
        Device-mesh placement plan (``repro.sharding.ShardedRun``). With
        a non-trivial chains axis the fleet is partitioned across the
        mesh's ``chains`` devices; with ``data`` shards > 1 the plan's
        ``shard_sites`` arrays are partitioned along their leading axis
        and the likelihood is all-reduced with one ``psum`` inside the
        fused log-joint (the PotentialSpec fused integrator is skipped
        on that path). A trivial (one-device) plan or ``None`` keeps the
        single-device vmap path byte-for-byte. ``num_chains`` must be
        divisible by the chains-axis size. Composes with checkpointing
        for chains-only plans; data sharding + checkpointing is not
        supported.
    checkpoint_dir : str, optional
        Directory for atomic keep-N ``RunState`` snapshots. Setting it
        (or ``checkpoint_every`` / ``preemption``) switches to the
        SEGMENTED driver (``repro.infer.driver``): the loop runs in
        ``checkpoint_every``-sized compiled segments, snapshots between
        them, and RESUMES bit-exactly from the latest committed snapshot
        when one exists (same master key required).
    checkpoint_every : int, optional
        Segment length in transitions (warmup + sampling). Defaults to
        a tenth of the total when only ``checkpoint_dir`` is given.
    checkpoint_keep : int
        Keep-N retention for committed snapshots.
    preemption : PreemptionHandler, optional
        Polled between segments; on preemption the driver writes a final
        synchronous checkpoint and returns the partial chain cleanly.
        When ``checkpoint_dir`` is set and this is ``None``, the driver
        installs its own SIGTERM/SIGINT handler for the duration.
    fallback : bool
        Segmented driver only: retry a segment whose state went
        non-finite on the reference backend (fused -> reference graceful
        degradation), recording the event in ``Chain.health``.

    Returns
    -------
    Chain
        Draws of shape ``(num_chains, num_samples) + site.shape`` per site;
        ``stats`` holds ``logp`` and the kernel's extras (accept_prob,
        diverging, ...); ``health`` carries the ``ChainHealth`` report.
    """
    from repro.core.program import call_span, program_cache, span
    from repro.sharding.mesh import ShardedRun
    with call_span("repro.run_chains", num_chains=num_chains,
                   num_warmup=num_warmup, num_samples=num_samples):
        plan = ShardedRun.normalize(mesh)
        if plan is not None and plan.is_trivial:
            plan = None  # graceful degradation: one device == no mesh
        if plan is not None:
            plan.validate_chains(num_chains)

        if (checkpoint_dir is not None or checkpoint_every is not None
                or preemption is not None):
            from repro.infer.driver import run_segmented
            return run_segmented(
                key, model, kernel, num_samples, num_warmup=num_warmup,
                num_chains=num_chains, init_varinfo=init_varinfo,
                init_jitter=init_jitter, backend=backend, mesh=plan,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
                checkpoint_keep=checkpoint_keep, preemption=preemption,
                fallback=fallback)

        cache = program_cache()
        stats0 = cache.stats()
        with span("repro.run_chains.setup"):
            tvi, kern, dim, q0s, chain_keys = setup_chain_driver(
                key, model, kernel, num_chains=num_chains,
                init_varinfo=init_varinfo, init_jitter=init_jitter,
                backend=backend)
        with span("repro.run_chains.dispatch"):
            outs = _dispatch(plan, model, tvi, kern, kernel, dim,
                             num_chains, num_warmup, num_samples,
                             init_jitter, backend, chain_keys, q0s, cache)
        with span("repro.run_chains.collect"):
            qs = outs.pop("q")
            chain = package_draws(tvi, qs, stats=outs)
            from repro.infer.driver import health_from_stats
            chain.health = health_from_stats(
                chain.stats, num_warmup=num_warmup, num_samples=num_samples,
                num_chains=num_chains)
            s1 = cache.stats()
            h = chain.health
            h.cache_hits = max(0, s1["hits"] - stats0["hits"])
            h.cache_misses = max(0, s1["misses"] - stats0["misses"])
            h.cache_retraces = max(0, s1["retraces"] - stats0["retraces"])
            h.fingerprint_bytes = (s1["fingerprint_bytes"]
                                   - stats0["fingerprint_bytes"])
        return chain


def _dispatch(plan, model, tvi, kern, kernel, dim: int, num_chains: int,
              num_warmup: int, num_samples: int, init_jitter: float,
              backend: str, chain_keys, q0s, cache):
    """Look up (or build) the chain program and call it; returns its
    outputs as the device produced them (dispatch is asynchronous)."""
    import jax

    from repro.core.program import (CompiledProgram, ProgramKey,
                                    kernel_fingerprint, model_fingerprint)
    if plan is not None and plan.num_data_shards > 1:
        # chains x data mesh program (likelihood psum inside the density)
        outs = _sharded_chain_outs(
            plan, model, tvi, kernel, dim, num_warmup, num_samples,
            backend, chain_keys, q0s, cache)
    else:
        if plan is not None:
            # chains-only placement: the SAME per-chain math, with the
            # fleet inputs laid over the mesh's chain devices — input
            # shardings propagate through jit(vmap), so each device runs
            # its block of chains and nothing crosses devices
            csh = plan.chain_sharding()
            chain_keys = jax.device_put(chain_keys, csh)
            q0s = jax.device_put(q0s, csh)
        one_chain = _chain_body(kern, num_warmup, num_samples)

        # the WHOLE vmapped chain program is cached — jit keys on function
        # identity, so without this every run_chains call would re-trace
        # even though density/spec were reused. Keyed on the sampler's full
        # config fingerprint (+ the mesh placement fingerprint: a sharded
        # executable must never be served unsharded); a non-dataclass
        # kernel cannot be fingerprinted safely and bypasses the cache.
        kfp = kernel_fingerprint(kernel)
        if kfp is not None:
            ckey_prog = ProgramKey(
                model_fingerprint(model), "chain", tvi.layout,
                (num_chains, num_warmup, num_samples), backend,
                (kfp, float(init_jitter)),
                plan.fingerprint() if plan is not None else ())
            prog = cache.get_or_build(
                ckey_prog,
                lambda: CompiledProgram(
                    ckey_prog, lambda ks, qs: jax.vmap(one_chain)(ks, qs)))
            outs = prog(chain_keys, q0s)
        else:
            outs = jax.jit(jax.vmap(one_chain))(chain_keys, q0s)
    return outs
