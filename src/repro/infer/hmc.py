"""Static HMC — the paper's benchmark algorithm (§4: 4 leapfrog steps).

Two execution paths, mirroring the paper's central comparison:

* ``run``          — TYPED path: the log-density is specialised on the
  TypedVarInfo structure and the whole chain runs inside one
  ``jax.lax.scan`` under ``jit`` (the Stan-like compiled path).
* ``run_untyped``  — UNTYPED path: every iteration re-executes the model
  eagerly through the dynamic dict trace (Python dispatch per op, fresh
  trace per call) — the honest analogue of ``Vector{Real}`` + dynamic
  dispatch that the paper's typed traces eliminate.

Both draw identical chains given the same key (same algorithm, same
arithmetic), which is asserted in tests.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.contexts import Context
from repro.core.model import Model
from repro.core.program import cached_potential, density_program
from repro.core.varinfo import TypedVarInfo, assert_continuous_supports
from repro.infer.chains import Chain, TransitionKernel, package_draws
from repro.kernels.fused_leapfrog import (fused_leapfrog,
                                          potential_value_and_grad)

__all__ = ["HMC", "DualAveraging"]


@dataclasses.dataclass(frozen=True)
class DualAveraging:
    """Nesterov dual-averaging step-size adaptation (Stan warmup)."""

    target_accept: float = 0.8
    gamma: float = 0.05
    t0: float = 10.0
    kappa: float = 0.75

    def init(self, step_size):
        mu = jnp.log(10.0 * step_size)
        return (jnp.log(step_size), jnp.zeros(()), jnp.zeros(()), mu)

    def update(self, state, accept_prob, t):
        log_eps, log_eps_bar, h_bar, mu = state
        t = t + 1.0
        eta = 1.0 / (t + self.t0)
        h_bar = (1.0 - eta) * h_bar + eta * (self.target_accept - accept_prob)
        log_eps = mu - jnp.sqrt(t) / self.gamma * h_bar
        w = jnp.power(t, -self.kappa)
        log_eps_bar = w * log_eps + (1.0 - w) * log_eps_bar
        return (log_eps, log_eps_bar, h_bar, mu)


def _leapfrog(logdensity_and_grad: Callable, q, p, grad, step_size,
              n_steps: int, inv_mass=None):
    """n_steps leapfrog updates. Returns (q, p, logp, grad).

    ``inv_mass`` is an optional DIAGONAL inverse mass (a flat vector);
    ``None`` keeps the unit metric. The velocity is ``inv_mass * p``.
    """

    def body(carry, _):
        q, p, grad = carry
        p_half = p + 0.5 * step_size * grad
        vel = p_half if inv_mass is None else inv_mass * p_half
        q_new = q + step_size * vel
        logp_new, grad_new = logdensity_and_grad(q_new)
        p_new = p_half + 0.5 * step_size * grad_new
        return (q_new, p_new, grad_new), logp_new

    (q, p, grad), logps = jax.lax.scan(body, (q, p, grad), None, length=n_steps)
    return q, p, logps[-1], grad


def hmc_transition(ld_and_grad: Callable, q, logp, grad, step_size,
                   key, n_leapfrog: int, *, inv_mass=None,
                   leapfrog_fn: Optional[Callable] = None):
    """One Metropolis-corrected HMC transition.

    Returns ``(q, logp, grad, accept_prob, accepted, diverging)``; shared
    by ``HMC.run`` and the ``TransitionKernel`` built by
    ``HMC.make_kernel`` so both paths run the exact same arithmetic.
    ``diverging`` is the Stan criterion: the proposed trajectory's energy
    error exceeds 1000 (or is NaN) — the transition is still valid (the
    proposal is simply rejected) but a high divergence count signals the
    integrator is unstable at the current step size.

    ``inv_mass`` (diagonal, flat vector or None) shapes BOTH the momentum
    draw (``p ~ N(0, M)``) and the kinetic energy — the single source of
    metric truth for the fused and reference integrators alike.
    ``leapfrog_fn(q, p, grad, step_size, n_steps)`` swaps in a fused
    integrator (which must already close over the same ``inv_mass``);
    ``None`` runs the reference ``_leapfrog``. The MH correction is
    identical either way.
    """
    k_mom, k_acc = jax.random.split(key)
    noise = jax.random.normal(k_mom, q.shape)
    p0 = noise if inv_mass is None else noise / jnp.sqrt(inv_mass)
    if leapfrog_fn is None:
        q_new, p_new, logp_new, grad_new = _leapfrog(
            ld_and_grad, q, p0, grad, step_size, n_leapfrog,
            inv_mass=inv_mass)
    else:
        q_new, p_new, logp_new, grad_new = leapfrog_fn(
            q, p0, grad, step_size, n_leapfrog)

    def kinetic(p):
        if inv_mass is None:
            return 0.5 * jnp.sum(p * p)
        return 0.5 * jnp.sum(p * p * inv_mass)

    h0 = -logp + kinetic(p0)
    h1 = -logp_new + kinetic(p_new)
    delta = h0 - h1
    diverging = jnp.isnan(delta) | (-delta > 1000.0)
    log_accept = jnp.minimum(0.0, delta)
    log_accept = jnp.where(jnp.isnan(log_accept), -jnp.inf, log_accept)
    accept = jnp.log(jax.random.uniform(k_acc, ())) < log_accept
    q = jnp.where(accept, q_new, q)
    logp = jnp.where(accept, logp_new, logp)
    grad = jnp.where(accept, grad_new, grad)
    return q, logp, grad, jnp.exp(log_accept), accept, diverging


def make_chain_fn(logdensity: Callable, num_samples: int, step_size: float,
                  n_leapfrog: int, collect: bool = True) -> Callable:
    """Build ``f(key, q0) -> (qs, logps, accept_probs)`` for a RAW flat
    log-density. Used by the Table-1 harness so the typed-DSL path and the
    hand-written "Stan-analogue" path run the EXACT same HMC program and
    differ only in where the log-density came from."""

    def ld_and_grad(q):
        return jax.value_and_grad(logdensity)(q)

    def hmc_step(carry, key):
        q, logp, grad = carry
        k_mom, k_acc = jax.random.split(key)
        p0 = jax.random.normal(k_mom, q.shape)
        q_new, p_new, logp_new, grad_new = _leapfrog(
            ld_and_grad, q, p0, grad, step_size, n_leapfrog)
        h0 = -logp + 0.5 * jnp.sum(p0 * p0)
        h1 = -logp_new + 0.5 * jnp.sum(p_new * p_new)
        log_accept = jnp.minimum(0.0, h0 - h1)
        log_accept = jnp.where(jnp.isnan(log_accept), -jnp.inf, log_accept)
        accept = jnp.log(jax.random.uniform(k_acc, ())) < log_accept
        q = jnp.where(accept, q_new, q)
        logp = jnp.where(accept, logp_new, logp)
        grad = jnp.where(accept, grad_new, grad)
        out = (q, logp, jnp.exp(log_accept)) if collect \
            else (logp, jnp.exp(log_accept))
        return (q, logp, grad), out

    def chain(key, q0):
        logp0, grad0 = ld_and_grad(q0)
        keys = jax.random.split(key, num_samples)
        (qf, _, _), outs = jax.lax.scan(hmc_step, (q0, logp0, grad0), keys)
        if collect:
            return outs
        return (qf,) + outs

    return chain


@dataclasses.dataclass
class HMC:
    """Static HMC with a fixed number of leapfrog steps (paper setup).

    ``leapfrog`` selects the integrator:

    * ``"auto"``      — compile the model to a separable
      :class:`~repro.kernels.fused_leapfrog.PotentialSpec` when possible
      and run the fused n-step integrator (one Pallas launch on TPU,
      analytic-gradient scan elsewhere); fall back to the reference
      autodiff leapfrog otherwise.
    * ``"fused"``     — require the fused integrator (raise if the model
      is not separable).
    * ``"reference"`` — always use the autodiff leapfrog.

    ``inv_mass`` is an optional DIAGONAL inverse mass-matrix (flat
    vector over the unconstrained state). Momentum sampling, kinetic
    energy and the velocity update all read it through ONE code path
    (``hmc_transition``), shared by both integrators.
    """

    step_size: float = 0.1
    n_leapfrog: int = 4
    adapt_step_size: bool = False
    target_accept: float = 0.8
    backend: str = "fused"  # log-density backend (see make_logdensity_fn)
    leapfrog: str = "auto"  # "auto" | "fused" | "reference"
    inv_mass: Optional[Any] = None  # diagonal inverse mass (flat vector)

    @property
    def uses_potential_spec(self) -> bool:
        """Whether drivers should try to compile a PotentialSpec for this
        sampler (``run_chains`` checks this before ``make_kernel``)."""
        return self.leapfrog != "reference"

    # -- typed, fully-compiled path ------------------------------------------
    def run(self, key, m: Model, num_samples: int,
            num_warmup: int = 0,
            init_varinfo: Optional[TypedVarInfo] = None,
            ctx: Optional[Context] = None,
            num_chains: int = 1,
            collect: bool = True) -> Chain:
        k_init, k_run = jax.random.split(jax.random.PRNGKey(0) if key is None else key)
        tvi = (init_varinfo if init_varinfo is not None
               else m.typed_varinfo(k_init))
        assert_continuous_supports(tvi, "HMC")
        tvi = tvi.link()
        logdensity = density_program(m, tvi, ctx=ctx, backend=self.backend)
        spec, spec_reason = None, None
        if self.uses_potential_spec:
            res = cached_potential(m, tvi, ctx=ctx, backend=self.backend)
            spec, spec_reason = res.spec, res.reason
        # ONE adaptation/transition code path for fused and reference
        # integrators: everything below routes through the TransitionKernel
        kern = self.make_kernel(logdensity, int(tvi.flat().shape[0]),
                                spec=spec, spec_reason=spec_reason)

        def one_chain(key, q0):
            state = kern.init(q0)
            if num_warmup > 0:
                keys = jax.random.split(jax.random.fold_in(key, 1), num_warmup)
                ts = jnp.arange(num_warmup, dtype=jnp.float32)

                def warm_body(s, inp):
                    t, k = inp
                    return kern.warm(s, t, k), None

                state, _ = jax.lax.scan(warm_body, state, (ts, keys))
                # freeze the dual-averaged step only if adaptation actually
                # ran: the smoothed iterate starts at exp(0)=1.0
                state = kern.finalize(state)

            def body(s, key):
                s, o = kern.step(s, key)
                out = ((o["q"], o["logp"], o["accept_prob"], o["diverging"])
                       if collect else (o["logp"], o["accept_prob"]))
                return s, out

            keys = jax.random.split(jax.random.fold_in(key, 2), num_samples)
            state, outs = jax.lax.scan(body, state, keys)
            if collect:
                return outs  # (qs, logps, accs, divs)
            return (state[0], *outs)

        if num_chains == 1:
            chain_fn = jax.jit(lambda k: one_chain(k, tvi.flat()))
            outs = chain_fn(k_run)
            qs, logps, accs, divs = (o[None] for o in outs)  # add chain axis
        else:
            keys = jax.random.split(k_run, num_chains)
            # overdispersed inits: Uniform(-1, 1) jitter around the
            # discovery draw in unconstrained space — distinct starts
            # (split-R-hat certifies mixing) without the pathological
            # curvature extremes a fixed step size cannot escape
            n_flat = tvi.flat().shape[0]
            q0s = tvi.flat()[None] + jax.random.uniform(
                jax.random.fold_in(k_init, 7), (num_chains, n_flat),
                minval=-1.0, maxval=1.0)
            chain_fn = jax.jit(jax.vmap(one_chain))
            qs, logps, accs, divs = chain_fn(keys, q0s)

        return self._package(m, tvi, qs, logps, accs, divs)

    def _package(self, m: Model, tvi_linked: TypedVarInfo, qs, logps, accs,
                 divs=None) -> Chain:
        """Map flat unconstrained draws back to constrained named arrays."""
        stats = {"logp": logps, "accept_prob": accs}
        if divs is not None:
            stats["diverging"] = divs
        return package_draws(tvi_linked, qs, stats=stats)

    # -- TransitionKernel protocol (run_chains driver) -------------------------
    def make_kernel(self, logdensity: Callable, dim: int,
                    spec=None, spec_reason: Optional[str] = None
                    ) -> TransitionKernel:
        """Build the pure HMC :class:`TransitionKernel` for ``run_chains``.

        Parameters
        ----------
        logdensity : callable
            Flat unconstrained log-density ``(dim,) -> scalar`` (usually
            ``Model.make_logdensity_fn`` output — the fused hot path).
        dim : int
            Length of the flat unconstrained state.
        spec : PotentialSpec or CondPotentialSpec, optional
            Compiled (conditionally-)separable potential
            (``repro.core.potential``). When given (and ``leapfrog !=
            "reference"``) the kernel uses the fused integrator: analytic
            gradients and the whole n-step leapfrog as one unit, no
            autodiff over the full state in the hot loop.
        spec_reason : str, optional
            Compiler diagnosis when ``spec`` is ``None`` — carried on the
            returned kernel (``TransitionKernel.spec_reason``) and quoted
            by the ``leapfrog="fused"`` error.

        Returns
        -------
        TransitionKernel
            State ``(q, logp, grad, da_state, eps)``; ``step`` emits
            ``{"q", "logp", "accept_prob"}`` per draw. Warmup runs
            dual-averaging adaptation when ``adapt_step_size``.
        """
        del dim  # the state shape is carried by q itself
        if self.leapfrog not in ("auto", "fused", "reference"):
            raise ValueError(f"unknown leapfrog mode {self.leapfrog!r}")
        if self.leapfrog == "fused" and spec is None:
            why = f": {spec_reason}" if spec_reason else \
                " (PotentialSpec compilation failed or was not attempted)"
            raise ValueError(
                "leapfrog='fused' requires a (conditionally-)separable "
                f"model{why}; use leapfrog='auto' to fall back to the "
                "reference integrator")
        use_fused = spec is not None and self.leapfrog != "reference"
        inv_mass = None if self.inv_mass is None \
            else jnp.asarray(self.inv_mass, jnp.float32)

        if use_fused:
            def ld_and_grad(q):
                with jax.named_scope("repro.logdensity"):
                    return potential_value_and_grad(spec, q)

            def leapfrog_fn(q, p, grad, eps, n):
                return fused_leapfrog(spec, q, p, grad, eps, n,
                                      inv_mass=inv_mass)
        else:
            def ld_and_grad(q):
                with jax.named_scope("repro.logdensity"):
                    return jax.value_and_grad(logdensity)(q)

            leapfrog_fn = None

        da = DualAveraging(target_accept=self.target_accept)

        def init(q0):
            logp0, grad0 = ld_and_grad(q0)
            eps = jnp.asarray(self.step_size)
            return (q0, logp0, grad0, da.init(eps), eps)

        def warm(state, t, key):
            q, logp, grad, da_state, eps = state
            cur = jnp.exp(da_state[0]) if self.adapt_step_size else eps
            q, logp, grad, acc, _, _ = hmc_transition(
                ld_and_grad, q, logp, grad, cur, key, self.n_leapfrog,
                inv_mass=inv_mass, leapfrog_fn=leapfrog_fn)
            if self.adapt_step_size:
                da_state = da.update(da_state, acc, t)
            return (q, logp, grad, da_state, eps)

        def finalize(state):
            q, logp, grad, da_state, eps = state
            if self.adapt_step_size:
                eps = jnp.exp(da_state[1])
            return (q, logp, grad, da_state, eps)

        def step(state, key):
            q, logp, grad, da_state, eps = state
            q, logp, grad, acc, _, div = hmc_transition(
                ld_and_grad, q, logp, grad, eps, key, self.n_leapfrog,
                inv_mass=inv_mass, leapfrog_fn=leapfrog_fn)
            out = {"q": q, "logp": logp, "accept_prob": acc,
                   "diverging": div}
            return (q, logp, grad, da_state, eps), out

        return TransitionKernel(init, warm, finalize, step,
                                spec_reason=None if use_fused
                                else spec_reason)

    # -- untyped eager path (the paper's slow general mode) -------------------
    def run_untyped(self, key, m: Model, num_samples: int,
                    init_varinfo: Optional[TypedVarInfo] = None) -> Chain:
        """Same algorithm, executed through the dynamic untyped trace.

        No jit anywhere: every log-density (and its gradient) re-traces the
        Python model, dispatching dynamically — the UntypedVarInfo mode.
        """
        k_init, k_run = jax.random.split(key)
        tvi = (init_varinfo if init_varinfo is not None
               else m.typed_varinfo(k_init))
        assert_continuous_supports(tvi, "HMC")
        tvi = tvi.link()
        logdensity = m.make_logdensity_fn(tvi)  # NOT jitted

        rng = np.random.default_rng(np.asarray(jax.random.key_data(k_run))[-1])
        q = np.asarray(tvi.flat())
        logp = float(logdensity(jnp.asarray(q)))
        grad = np.asarray(jax.grad(logdensity)(jnp.asarray(q)))

        qs, logps, accs = [], [], []
        for _ in range(num_samples):
            p0 = rng.standard_normal(q.shape).astype(q.dtype)
            qn, pn, gn = q.copy(), p0.copy(), grad.copy()
            for _ in range(self.n_leapfrog):
                pn = pn + 0.5 * self.step_size * gn
                qn = qn + self.step_size * pn
                # fresh eager evaluation each call — dynamic path
                lpn = float(logdensity(jnp.asarray(qn)))
                gn = np.asarray(jax.grad(logdensity)(jnp.asarray(qn)))
                pn = pn + 0.5 * self.step_size * gn
            h0 = -logp + 0.5 * float(p0 @ p0)
            h1 = -lpn + 0.5 * float(pn @ pn)
            log_acc = min(0.0, h0 - h1)
            if np.isnan(log_acc):
                log_acc = -np.inf
            if np.log(rng.uniform()) < log_acc:
                q, logp, grad = qn, lpn, gn
            qs.append(q.copy())
            logps.append(logp)
            accs.append(np.exp(log_acc))

        qs = jnp.asarray(np.stack(qs))[None]
        return self._package(m, tvi, qs, np.asarray(logps)[None],
                             np.asarray(accs)[None])
