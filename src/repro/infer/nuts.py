"""NUTS — iterative No-U-Turn sampler (multinomial variant), jit-compiled.

Beyond-paper feature: the paper benchmarks static HMC; a production PPL
needs adaptive path lengths. This is the checkpoint-stack iterative
formulation (Phan & Pradhan style): a doubling tree of depth ``max_depth``
is built with ``lax.while_loop``; u-turn checks against power-of-two
subtree boundaries use a checkpoint array indexed by the binary structure
of the leaf counter. Works on the flat unconstrained space produced by a
linked TypedVarInfo, so the whole chain is one compiled program.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.model import Model
from repro.core.program import cached_potential, density_program
from repro.core.varinfo import TypedVarInfo, assert_continuous_supports
from repro.infer.chains import Chain, TransitionKernel
from repro.infer.hmc import DualAveraging, HMC
from repro.kernels.fused_leapfrog import potential_value_and_grad

__all__ = ["NUTS"]


def _is_turning(q_l, p_l, q_r, p_r):
    dq = q_r - q_l
    return (jnp.dot(dq, p_l) <= 0.0) | (jnp.dot(dq, p_r) <= 0.0)


def _leaf_to_ckpt(n, max_depth):
    """leaf counter -> (idx_min, idx_max) of checkpoints to u-turn-check."""

    def count_bits(c):  # number of set bits in n >> 1
        def body(s):
            x, acc = s
            return (x >> 1, acc + (x & 1))
        x, acc = jax.lax.while_loop(lambda s: s[0] > 0, body, (n >> 1, 0))
        return acc

    def trailing_ones(c):
        def body(s):
            x, acc = s
            return (x >> 1, acc + 1)
        x, acc = jax.lax.while_loop(lambda s: (s[0] & 1) != 0, body, (n, 0))
        return acc

    idx_max = count_bits(n)
    num_sub = trailing_ones(n)
    idx_min = idx_max - num_sub + 1
    return idx_min, idx_max


@dataclasses.dataclass
class NUTS:
    step_size: float = 0.1
    max_depth: int = 10
    adapt_step_size: bool = True
    target_accept: float = 0.8
    backend: str = "fused"  # log-density backend (see make_logdensity_fn)
    leapfrog: str = "auto"  # "auto" | "fused" | "reference"

    @property
    def uses_potential_spec(self) -> bool:
        """Whether drivers should try to compile a PotentialSpec for this
        sampler (``run_chains`` checks this before ``make_kernel``)."""
        return self.leapfrog != "reference"

    def _make_ld_grad(self, logdensity, spec, spec_reason=None):
        """(logp, grad) evaluator for tree leaves.

        With a compiled PotentialSpec the gradient is the analytic opcode
        table (fused value+grad, zero autodiff); otherwise
        ``jax.value_and_grad`` on the reference log-density.
        """
        if self.leapfrog not in ("auto", "fused", "reference"):
            raise ValueError(f"unknown leapfrog mode {self.leapfrog!r}")
        if self.leapfrog == "fused" and spec is None:
            why = f": {spec_reason}" if spec_reason else \
                " (PotentialSpec compilation failed or was not attempted)"
            raise ValueError(
                "leapfrog='fused' requires a (conditionally-)separable "
                f"model{why}; use leapfrog='auto' to fall back to autodiff "
                "gradients")
        if spec is not None and self.leapfrog != "reference":
            def value_and_grad(q):
                return potential_value_and_grad(spec, q)
        else:
            value_and_grad = jax.value_and_grad(logdensity)

        def ld_grad(q):
            with jax.named_scope("repro.logdensity"):
                return value_and_grad(q)

        return ld_grad

    def _build_step(self, ld_grad, dim: int):
        """Build the single compiled NUTS transition.

        Returns ``nuts_step(q0, logp0, grad0, eps, key) -> (q, logp, grad,
        accept_prob, tree_depth, diverging, n_leapfrog)`` — shared by
        :meth:`run` and :meth:`make_kernel` so both drivers run identical
        tree code. ``n_leapfrog`` is the number of leapfrog steps the
        tree took: the last subtree may stop early, so it lies between
        ``2**(tree_depth - 1)`` and ``2**tree_depth - 1``.
        """

        def one_leapfrog(q, p, grad, eps, direction):
            with jax.named_scope("repro.integrator"):
                e = eps * direction
                p = p + 0.5 * e * grad
                q = q + e * p
                logp, grad = ld_grad(q)
                p = p + 0.5 * e * grad
                return q, p, logp, grad

        def nuts_step(q0, logp0, grad0, eps, key):
            k_mom, k_dir, k_mult = jax.random.split(key, 3)
            p0 = jax.random.normal(k_mom, (dim,))
            h0 = -logp0 + 0.5 * jnp.dot(p0, p0)

            # tree state
            # checkpoints for u-turn tests (one per depth level)
            ck_q = jnp.zeros((self.max_depth + 1, dim))
            ck_p = jnp.zeros((self.max_depth + 1, dim))

            init = dict(
                q_l=q0, p_l=p0, grad_l=grad0,
                q_r=q0, p_r=p0, grad_r=grad0,
                q_prop=q0, logp_prop=logp0, grad_prop=grad0,
                log_weight=jnp.zeros(()),          # log sum of exp(-H) seen
                depth=jnp.zeros((), jnp.int32),
                turning=jnp.zeros((), bool),
                diverging=jnp.zeros((), bool),
                sum_acc=jnp.zeros(()), n_acc=jnp.zeros(()),
                key=k_mult,
            )

            def expand_cond(s):
                return (~s["turning"] & ~s["diverging"]
                        & (s["depth"] < self.max_depth))

            def expand_body(s):
                key, k_dir, k_leaf = jax.random.split(s["key"], 3)
                go_right = jax.random.bernoulli(k_dir)
                n_leaf = jnp.asarray(1, jnp.int32) << s["depth"]  # 2^depth steps

                # build subtree of size 2^depth in chosen direction
                def leaf_body(ls):
                    (i, q, p, grad, logp, ck_q_, ck_p_, log_w, turning,
                     diverging, q_prop, logp_prop, grad_prop, sum_acc, n_acc,
                     lkey) = ls
                    direction = jnp.where(go_right, 1.0, -1.0)
                    q, p, logp, grad = one_leapfrog(q, p, grad, eps, direction)
                    h = -logp + 0.5 * jnp.dot(p, p)
                    diverging = diverging | (h - h0 > 1000.0) | jnp.isnan(h)
                    lw = jnp.where(diverging, -jnp.inf, h0 - h)
                    # multinomial progressive sampling within the new subtree
                    lkey, k_acc = jax.random.split(lkey)
                    new_total = jnp.logaddexp(log_w, lw)
                    take = (jnp.log(jax.random.uniform(k_acc, ()))
                            < lw - new_total)
                    q_prop = jnp.where(take, q, q_prop)
                    logp_prop = jnp.where(take, logp, logp_prop)
                    grad_prop = jnp.where(take, grad, grad_prop)
                    sum_acc = sum_acc + jnp.minimum(1.0, jnp.exp(h0 - h))
                    n_acc = n_acc + 1.0
                    # u-turn checks via checkpoint stack
                    idx_min, idx_max = _leaf_to_ckpt(i, self.max_depth)
                    is_even = (i & 1) == 0
                    ck_q_ = jnp.where(is_even,
                                      ck_q_.at[idx_max].set(q), ck_q_)
                    ck_p_ = jnp.where(is_even,
                                      ck_p_.at[idx_max].set(p), ck_p_)

                    def check_turn(_):
                        def chk(j, t):
                            ql, pl = ck_q_[j], ck_p_[j]
                            qr, pr = q, p
                            ql, qr = jnp.where(go_right, ql, qr), jnp.where(go_right, qr, ql)
                            pl, pr = jnp.where(go_right, pl, pr), jnp.where(go_right, pr, pl)
                            return t | _is_turning(ql, pl, qr, pr)
                        return jax.lax.fori_loop(idx_min, idx_max + 1, chk,
                                                 jnp.zeros((), bool))

                    turning = turning | jnp.where(is_even, False, check_turn(None))
                    return (i + 1, q, p, grad, logp, ck_q_, ck_p_, new_total,
                            turning, diverging, q_prop, logp_prop, grad_prop,
                            sum_acc, n_acc, lkey)

                def leaf_cond(ls):
                    i = ls[0]
                    turning, diverging = ls[8], ls[9]
                    return (i < n_leaf) & ~turning & ~diverging

                # start subtree from the boundary in the chosen direction
                q_s = jnp.where(go_right, s["q_r"], s["q_l"])
                p_s = jnp.where(go_right, s["p_r"], s["p_l"])
                g_s = jnp.where(go_right, s["grad_r"], s["grad_l"])

                # subtree proposal accumulates separately, then merges
                sub = (jnp.zeros((), jnp.int32), q_s, p_s, g_s,
                       jnp.zeros(()), ck_q, ck_p, -jnp.inf,
                       jnp.zeros((), bool), jnp.zeros((), bool),
                       q_s, jnp.zeros(()), g_s, s["sum_acc"], s["n_acc"],
                       k_leaf)
                sub = jax.lax.while_loop(leaf_cond, leaf_body, sub)
                (_, q_e, p_e, g_e, logp_e, _, _, sub_log_w, sub_turning,
                 sub_diverging, sub_q_prop, sub_logp_prop, sub_grad_prop,
                 sum_acc, n_acc, _) = sub

                # merge subtree proposal with the main proposal (biased
                # progressive sampling toward the new subtree)
                key, k_swap = jax.random.split(key)
                take_new = (jnp.log(jax.random.uniform(k_swap, ()))
                            < sub_log_w - s["log_weight"])
                take_new = take_new & ~sub_turning & ~sub_diverging
                q_prop = jnp.where(take_new, sub_q_prop, s["q_prop"])
                logp_prop = jnp.where(take_new, sub_logp_prop, s["logp_prop"])
                grad_prop = jnp.where(take_new, sub_grad_prop, s["grad_prop"])
                log_weight = jnp.logaddexp(s["log_weight"], sub_log_w)

                # update boundary in the direction we grew
                q_l = jnp.where(go_right, s["q_l"], q_e)
                p_l = jnp.where(go_right, s["p_l"], p_e)
                g_l = jnp.where(go_right, s["grad_l"], g_e)
                q_r = jnp.where(go_right, q_e, s["q_r"])
                p_r = jnp.where(go_right, p_e, s["p_r"])
                g_r = jnp.where(go_right, g_e, s["grad_r"])

                turning = sub_turning | _is_turning(q_l, p_l, q_r, p_r)
                return dict(
                    q_l=q_l, p_l=p_l, grad_l=g_l, q_r=q_r, p_r=p_r, grad_r=g_r,
                    q_prop=q_prop, logp_prop=logp_prop, grad_prop=grad_prop,
                    log_weight=log_weight, depth=s["depth"] + 1,
                    turning=turning, diverging=s["diverging"] | sub_diverging,
                    sum_acc=sum_acc, n_acc=n_acc, key=key,
                )

            out = jax.lax.while_loop(expand_cond, expand_body, init)
            acc_prob = out["sum_acc"] / jnp.maximum(out["n_acc"], 1.0)
            return (out["q_prop"], out["logp_prop"], out["grad_prop"],
                    acc_prob, out["depth"], out["diverging"],
                    out["n_acc"].astype(jnp.int32))

        return nuts_step

    # -- TransitionKernel protocol (run_chains driver) -------------------------
    def make_kernel(self, logdensity, dim: int, spec=None,
                    spec_reason: Optional[str] = None) -> TransitionKernel:
        """Build the pure NUTS :class:`TransitionKernel` for ``run_chains``.

        State is ``(q, logp, grad, da_state, eps)``; ``step`` emits
        ``{"q", "logp", "accept_prob", "tree_depth", "diverging",
        "n_leapfrog"}`` per draw (``diverging`` = the doubling tree hit an
        energy error > 1000 or NaN and was truncated; ``n_leapfrog`` = the
        leapfrog steps the tree took). Warmup runs dual-averaging on
        the mean subtree acceptance statistic.
        ``spec`` (an optional compiled PotentialSpec) swaps the tree-leaf
        gradient for the fused analytic evaluator; ``spec_reason`` (the
        compiler diagnosis when ``spec`` is None) rides on the returned
        kernel so the fallback is explainable.
        """
        ld_grad = self._make_ld_grad(logdensity, spec, spec_reason)
        nuts_step = self._build_step(ld_grad, dim)
        da = DualAveraging(target_accept=self.target_accept)

        def init(q0):
            logp0, grad0 = ld_grad(q0)
            eps = jnp.asarray(self.step_size)
            return (q0, logp0, grad0, da.init(eps), eps)

        def warm(state, t, key):
            q, logp, grad, da_state, eps = state
            cur = jnp.exp(da_state[0]) if self.adapt_step_size else eps
            q, logp, grad, acc, _, _, _ = nuts_step(q, logp, grad, cur,
                                                    key)
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
            q, logp, grad, acc, depth, div, n_leapfrog = nuts_step(
                q, logp, grad, eps, key)
            out = {"q": q, "logp": logp, "accept_prob": acc,
                   "tree_depth": depth, "diverging": div,
                   "n_leapfrog": n_leapfrog}
            return (q, logp, grad, da_state, eps), out

        use_fused = spec is not None and self.leapfrog != "reference"
        return TransitionKernel(init, warm, finalize, step,
                                spec_reason=None if use_fused
                                else spec_reason)

    def run(self, key, m: Model, num_samples: int, num_warmup: int = 500,
            init_varinfo: Optional[TypedVarInfo] = None,
            num_chains: int = 1) -> Chain:
        k_init, k_run = jax.random.split(key)
        tvi = (init_varinfo if init_varinfo is not None
               else m.typed_varinfo(k_init))
        assert_continuous_supports(tvi, "NUTS")
        tvi = tvi.link()
        logdensity = density_program(m, tvi, backend=self.backend)
        spec, spec_reason = None, None
        if self.uses_potential_spec:
            res = cached_potential(m, tvi, backend=self.backend)
            spec, spec_reason = res.spec, res.reason
        ld_grad = self._make_ld_grad(logdensity, spec, spec_reason)
        dim = int(tvi.flat().shape[0])
        da = DualAveraging(target_accept=self.target_accept)
        nuts_step = self._build_step(ld_grad, dim)

        def one_chain(key, q0):
            logp0, grad0 = ld_grad(q0)
            da_state = da.init(jnp.asarray(self.step_size))

            def warm_body(carry, inp):
                q, logp, grad, da_state = carry
                t, k = inp
                eps = jnp.exp(da_state[0]) if self.adapt_step_size \
                    else jnp.asarray(self.step_size)
                q, logp, grad, acc, _, _, _ = nuts_step(q, logp, grad, eps, k)
                if self.adapt_step_size:
                    da_state = da.update(da_state, acc, t)
                return (q, logp, grad, da_state), None

            if num_warmup > 0:
                keys = jax.random.split(jax.random.fold_in(key, 1), num_warmup)
                ts = jnp.arange(num_warmup, dtype=jnp.float32)
                (q0, logp0, grad0, da_state), _ = jax.lax.scan(
                    warm_body, (q0, logp0, grad0, da_state), (ts, keys))
            # dual-averaged step only if adaptation actually ran: the
            # smoothed iterate starts at exp(0)=1.0, not step_size
            eps = jnp.exp(da_state[1]) \
                if (self.adapt_step_size and num_warmup > 0) \
                else jnp.asarray(self.step_size)

            def body(carry, k):
                q, logp, grad = carry
                q, logp, grad, acc, depth, div, _ = nuts_step(q, logp, grad,
                                                              eps, k)
                return (q, logp, grad), (q, logp, acc, depth, div)

            keys = jax.random.split(jax.random.fold_in(key, 2), num_samples)
            _, outs = jax.lax.scan(body, (q0, logp0, grad0), keys)
            return outs

        if num_chains == 1:
            outs = jax.jit(lambda k: one_chain(k, tvi.flat()))(k_run)
            qs, logps, accs, depths, divs = (o[None] for o in outs)
        else:
            keys = jax.random.split(k_run, num_chains)
            q0s = jnp.broadcast_to(tvi.flat(), (num_chains, dim))
            qs, logps, accs, depths, divs = jax.jit(jax.vmap(one_chain))(
                keys, q0s)

        packer = HMC()
        chain = packer._package(m, tvi, qs, logps, accs, divs)
        chain.stats["tree_depth"] = np.asarray(depths)
        return chain
