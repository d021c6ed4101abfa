"""The paper's 8 benchmark models (Table 1) + hand-written Stan analogues.

Each builder returns a ``PaperModel`` with:
* ``model``        — the DSL version (typed-trace path),
* ``handwritten``  — a hand-coded log-density over the SAME flat
  unconstrained layout (the operational Stan analogue: a statically-typed,
  compiled log-density with no PPL machinery),
* deterministic synthetic data at the paper's stated sizes,
* the static-HMC settings (4 leapfrog steps per the paper; per-model
  step sizes tuned like the paper's "step size varies for different
  models").

Table 1 sizes:
  gaussian_10k   : 10,000-D standard normal
  gauss_unknown  : 10,000 1-D observations, unknown mean+variance
  naive_bayes    : 1,000 obs of MNIST->PCA-40 (synthetic stand-in), 10 classes
  logreg         : 10,000 obs x 100 dims
  hier_poisson   : 50 obs
  sto_volatility : 500 obs
  hmm_semisup    : K=5 latent, V=20 symbols, T=300 (200 unsupervised)
  lda            : V=100, K=5, D=10 docs, ~1,000 words each
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.bijectors import Sigmoid, StickBreaking
from repro.core import factor, model, observe, sample
from repro.dists import (Bernoulli, BernoulliLogits, Categorical, Dirichlet,
                         Exponential, Gamma, HalfCauchy, HalfNormal,
                         InverseGamma, MvNormalDiag, Normal, Poisson, Uniform)

__all__ = ["PaperModel", "build", "MODEL_NAMES"]

_LOG_2PI = math.log(2.0 * math.pi)
# A float32 matmul on a TPU defaults to one bfloat16 pass, which moves a
# 10,000-row log-likelihood by far more than float32 rounding. A single
# ``X @ w`` may run in float32 anyway, but under ``vmap`` over draws or
# chains it becomes a matrix product on the MXU; the densities below state
# full float32 precision.
_F32_MATMUL = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class PaperModel:
    name: str
    model: object                     # bound Model (DSL/typed path)
    handwritten: Optional[Callable]   # flat unconstrained -> log density
    step_size: float
    n_leapfrog: int = 4               # paper: static HMC, 4 leapfrog steps
    data: Optional[Dict] = None


def _norm_lp(x, loc, scale):
    z = (x - loc) / scale
    return -0.5 * z * z - jnp.log(scale) - 0.5 * _LOG_2PI


# ---------------------------------------------------------------------------
# 1. 10,000-D Gaussian
# ---------------------------------------------------------------------------
def gaussian_10k(dim: int = 10_000) -> PaperModel:
    @model
    def gauss10k():
        sample("x", MvNormalDiag(jnp.zeros(dim), jnp.ones(dim)))

    def handwritten(q):  # x: (dim,), identity transform
        return jnp.sum(-0.5 * q * q - 0.5 * _LOG_2PI)

    return PaperModel("gaussian_10k", gauss10k(), handwritten, step_size=0.1)


# ---------------------------------------------------------------------------
# 2. Gaussian with unknown mean and variance, 10,000 observations
# ---------------------------------------------------------------------------
def gauss_unknown(n: int = 10_000, seed: int = 0) -> PaperModel:
    rng = np.random.default_rng(seed)
    y = rng.normal(1.5, 0.7, size=n).astype(np.float32)

    @model
    def gdemo(y):
        s = sample("s", InverseGamma(2.0, 3.0))
        m = sample("m", Normal(0.0, jnp.sqrt(s)))
        observe("y", Normal(m, jnp.sqrt(s)), y)

    yj = jnp.asarray(y)

    def handwritten(q):
        u_s, m = q[0], q[1]
        s = jnp.exp(u_s)
        a, b = 2.0, 3.0
        lp = (a * jnp.log(b) - (a + 1.0) * jnp.log(s) - b / s
              - jax.scipy.special.gammaln(a)) + u_s  # + log|d s/d u|
        sd = jnp.sqrt(s)
        lp += _norm_lp(m, 0.0, sd)
        lp += jnp.sum(_norm_lp(yj, m, sd))
        return lp

    return PaperModel("gauss_unknown", gdemo(yj), handwritten, step_size=0.01,
                      data={"y": y})


# ---------------------------------------------------------------------------
# 3. Naive Bayes — 1,000 obs, 10 classes, 40 PCA dims (synthetic MNIST-PCA)
# ---------------------------------------------------------------------------
def naive_bayes(n: int = 1_000, n_classes: int = 10, dim: int = 40,
                seed: int = 1) -> PaperModel:
    rng = np.random.default_rng(seed)
    true_means = rng.normal(0.0, 3.0, size=(n_classes, dim))
    labels = rng.integers(0, n_classes, size=n)
    x = (true_means[labels] + rng.normal(0.0, 1.0, (n, dim))).astype(np.float32)
    labels = labels.astype(np.int32)

    @model
    def nb(x, labels):
        mu = sample("mu", MvNormalDiag(jnp.zeros((n_classes, dim)),
                                       10.0 * jnp.ones((n_classes, dim))))
        observe("x", Normal(mu[labels], 1.0), x)

    xj, lj = jnp.asarray(x), jnp.asarray(labels)

    def handwritten(q):
        mu = q.reshape(n_classes, dim)
        lp = jnp.sum(_norm_lp(mu, 0.0, 10.0))
        lp += jnp.sum(_norm_lp(xj, mu[lj], 1.0))
        return lp

    return PaperModel("naive_bayes", nb(xj, lj), handwritten, step_size=0.01,
                      data={"x": x, "labels": labels})


# ---------------------------------------------------------------------------
# 4. Logistic Regression — 10,000 obs x 100 dims
# ---------------------------------------------------------------------------
def logreg(n: int = 10_000, dim: int = 100, seed: int = 2) -> PaperModel:
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, dim)).astype(np.float32)
    w_true = rng.normal(size=dim) * (rng.random(dim) < 0.3)
    logits = X @ w_true
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.int32)

    @model
    def lr(X, y):
        w = sample("w", MvNormalDiag(jnp.zeros(dim), jnp.ones(dim)))
        b = sample("b", Normal(0.0, 3.0))
        observe("y", BernoulliLogits(
            jnp.matmul(X, w, precision=_F32_MATMUL) + b), y)

    Xj, yj = jnp.asarray(X), jnp.asarray(y)

    def handwritten(q):
        w, b = q[:dim], q[dim]
        lp = jnp.sum(_norm_lp(w, 0.0, 1.0)) + _norm_lp(b, 0.0, 3.0)
        logit = jnp.matmul(Xj, w, precision=_F32_MATMUL) + b
        lp += jnp.sum(yj * logit - jax.nn.softplus(logit))
        return lp

    return PaperModel("logreg", lr(Xj, yj), handwritten, step_size=0.002,
                      data={"X": X, "y": y})


# ---------------------------------------------------------------------------
# 5. Hierarchical Poisson — 50 obs, 10 groups
# ---------------------------------------------------------------------------
def hier_poisson(n: int = 50, n_groups: int = 10, seed: int = 3) -> PaperModel:
    rng = np.random.default_rng(seed)
    groups = rng.integers(0, n_groups, size=n).astype(np.int32)
    a0_true, a1_true = 1.0, rng.normal(0.0, 0.4, size=n_groups)
    log_exposure = np.log(rng.uniform(0.5, 2.0, size=n)).astype(np.float32)
    y = rng.poisson(np.exp(a0_true + a1_true[groups] + log_exposure)).astype(np.int32)

    @model
    def hp(y, groups, log_exposure):
        a0 = sample("a0", Normal(0.0, 10.0))
        sigma = sample("sigma", Gamma(1.0, 1.0))
        a1_std = sample("a1_std", MvNormalDiag(jnp.zeros(n_groups),
                                               jnp.ones(n_groups)))
        a1 = a1_std * sigma  # non-centred
        observe("y", Poisson(jnp.exp(a0 + a1[groups] + log_exposure)), y)

    yj, gj, lej = jnp.asarray(y), jnp.asarray(groups), jnp.asarray(log_exposure)

    def handwritten(q):
        a0, u_sig = q[0], q[1]
        a1_std = q[2:]
        sigma = jnp.exp(u_sig)
        lp = _norm_lp(a0, 0.0, 10.0)
        lp += (-sigma) + u_sig  # Gamma(1,1) logpdf + jacobian
        lp += jnp.sum(_norm_lp(a1_std, 0.0, 1.0))
        lam = jnp.exp(a0 + (a1_std * sigma)[gj] + lej)
        yf = yj.astype(lam.dtype)
        lp += jnp.sum(jax.scipy.special.xlogy(yf, lam) - lam
                      - jax.scipy.special.gammaln(yf + 1.0))
        return lp

    return PaperModel("hier_poisson", hp(yj, gj, lej), handwritten,
                      step_size=0.02, data={"y": y, "groups": groups})


# ---------------------------------------------------------------------------
# 6. Stochastic Volatility — 500 obs (non-centred AR(1) latent log-vol)
# ---------------------------------------------------------------------------
def sto_volatility(T: int = 500, seed: int = 4) -> PaperModel:
    rng = np.random.default_rng(seed)
    phi_t, sig_t, mu_t = 0.95, 0.25, -1.0
    h = np.empty(T)
    h[0] = rng.normal(mu_t, sig_t / np.sqrt(1 - phi_t ** 2))
    for t in range(1, T):
        h[t] = mu_t + phi_t * (h[t - 1] - mu_t) + rng.normal(0, sig_t)
    y = (rng.normal(size=T) * np.exp(h / 2)).astype(np.float32)

    @model
    def sv(y):
        T_ = y.shape[0]
        phi = sample("phi", Uniform(-1.0, 1.0))
        sigma = sample("sigma", HalfCauchy(1.0))
        mu = sample("mu", Normal(-1.0, 1.0))
        h_std = sample("h_std", MvNormalDiag(jnp.zeros(T_), jnp.ones(T_)))
        # non-centred AR(1) reconstruction: linear recurrence via scan
        h0 = mu + sigma / jnp.sqrt(1.0 - phi * phi) * h_std[0]

        def step(h_prev, eps):
            h_t = mu + phi * (h_prev - mu) + sigma * eps
            return h_t, h_t

        _, h_rest = jax.lax.scan(step, h0, h_std[1:])
        h = jnp.concatenate([h0[None], h_rest])
        observe("y", Normal(0.0, jnp.exp(h / 2.0)), y)

    yj = jnp.asarray(y)

    def handwritten(q):
        u_phi, u_sig, mu = q[0], q[1], q[2]
        h_std = q[3:]
        # phi: sigmoid to (-1,1) + jacobian
        phi = -1.0 + 2.0 * jax.nn.sigmoid(u_phi)
        lp = -jnp.log(2.0)  # Uniform(-1,1) density
        lp += (jnp.log(2.0) - jax.nn.softplus(u_phi) - jax.nn.softplus(-u_phi))
        sigma = jnp.exp(u_sig)
        lp += (jnp.log(2.0) - jnp.log(jnp.pi) - jnp.log1p(sigma ** 2)) + u_sig
        lp += _norm_lp(mu, -1.0, 1.0)
        lp += jnp.sum(_norm_lp(h_std, 0.0, 1.0))
        h0 = mu + sigma / jnp.sqrt(1.0 - phi * phi) * h_std[0]

        def step(h_prev, eps):
            h_t = mu + phi * (h_prev - mu) + sigma * eps
            return h_t, h_t

        _, h_rest = jax.lax.scan(step, h0, h_std[1:])
        h = jnp.concatenate([h0[None], h_rest])
        lp += jnp.sum(_norm_lp(yj, 0.0, jnp.exp(h / 2.0)))
        return lp

    return PaperModel("sto_volatility", sv(yj), handwritten, step_size=0.01,
                      data={"y": y})


# ---------------------------------------------------------------------------
# 7. Semi-supervised HMM — K=5, V=20, T=300 (first 100 supervised)
# ---------------------------------------------------------------------------
def hmm_semisup(K: int = 5, V: int = 20, T: int = 300, T_sup: int = 100,
                seed: int = 5) -> PaperModel:
    rng = np.random.default_rng(seed)
    theta_t = rng.dirichlet(np.full(K, 2.0), size=K)   # transitions
    phi_t = rng.dirichlet(np.full(V, 0.5), size=K)     # emissions
    z = np.empty(T, dtype=np.int64)
    w = np.empty(T, dtype=np.int64)
    z[0] = rng.integers(K)
    w[0] = rng.choice(V, p=phi_t[z[0]])
    for t in range(1, T):
        z[t] = rng.choice(K, p=theta_t[z[t - 1]])
        w[t] = rng.choice(V, p=phi_t[z[t]])
    w_sup, z_sup = w[:T_sup].astype(np.int32), z[:T_sup].astype(np.int32)
    w_unsup = w[T_sup:].astype(np.int32)

    alpha = jnp.full((K, K), 2.0)
    beta = jnp.full((K, V), 0.5)

    @model
    def hmm(w_sup, z_sup, w_unsup):
        theta = sample("theta", Dirichlet(alpha))  # (K,K) rows
        phi = sample("phi", Dirichlet(beta))       # (K,V) rows
        log_theta, log_phi = jnp.log(theta), jnp.log(phi)
        # supervised segment: categorical transitions + emissions
        observe("z_sup", Categorical(log_theta[z_sup[:-1]]), z_sup[1:])
        observe("w_sup", Categorical(log_phi[z_sup]), w_sup)
        # unsupervised segment: forward algorithm marginalising z
        alpha0 = log_theta[z_sup[-1]] + log_phi[:, w_unsup[0]]

        def fwd(prev, w_t):
            nxt = jax.scipy.special.logsumexp(
                prev[:, None] + log_theta, axis=0) + log_phi[:, w_t]
            return nxt, None

        alphaT, _ = jax.lax.scan(fwd, alpha0, w_unsup[1:])
        factor("w_unsup", jax.scipy.special.logsumexp(alphaT))

    def handwritten(q):
        sb = StickBreaking()
        off = 0
        u_theta = q[off:off + K * (K - 1)].reshape(K, K - 1); off += K * (K - 1)
        u_phi = q[off:off + K * (V - 1)].reshape(K, V - 1); off += K * (V - 1)
        theta = sb.forward(u_theta)
        phi = sb.forward(u_phi)
        lp = sb.forward_log_det_jacobian(u_theta) + sb.forward_log_det_jacobian(u_phi)
        # dirichlet priors
        def dir_lp(x, conc):
            return (jnp.sum(jax.scipy.special.xlogy(conc - 1.0, x))
                    - jnp.sum(jax.scipy.special.gammaln(conc))
                    + jnp.sum(jax.scipy.special.gammaln(jnp.sum(conc, -1))))
        lp += dir_lp(theta, alpha) + dir_lp(phi, beta)
        log_theta, log_phi = jnp.log(theta), jnp.log(phi)
        zs, ws = jnp.asarray(z_sup), jnp.asarray(w_sup)
        wu = jnp.asarray(w_unsup)
        lp += jnp.sum(jnp.take_along_axis(
            jax.nn.log_softmax(log_theta[zs[:-1]], -1), zs[1:, None], -1))
        lp += jnp.sum(jnp.take_along_axis(
            jax.nn.log_softmax(log_phi[zs], -1), ws[:, None], -1))
        alpha0 = log_theta[zs[-1]] + log_phi[:, wu[0]]

        def fwd(prev, w_t):
            nxt = jax.scipy.special.logsumexp(
                prev[:, None] + log_theta, axis=0) + log_phi[:, w_t]
            return nxt, None

        alphaT, _ = jax.lax.scan(fwd, alpha0, wu[1:])
        lp += jax.scipy.special.logsumexp(alphaT)
        return lp

    return PaperModel(
        "hmm_semisup",
        hmm(jnp.asarray(w_sup), jnp.asarray(z_sup), jnp.asarray(w_unsup)),
        handwritten, step_size=0.01,
        data={"w_sup": w_sup, "z_sup": z_sup, "w_unsup": w_unsup})


# ---------------------------------------------------------------------------
# 8. LDA — V=100, K=5, D=10, ~1,000 words per doc (collapsed z)
# ---------------------------------------------------------------------------
def lda(V: int = 100, K: int = 5, D: int = 10, avg_len: int = 1_000,
        seed: int = 6) -> PaperModel:
    rng = np.random.default_rng(seed)
    phi_t = rng.dirichlet(np.full(V, 0.1), size=K)
    theta_t = rng.dirichlet(np.full(K, 0.5), size=D)
    doc_ids, words = [], []
    for d in range(D):
        n_d = int(rng.poisson(avg_len))
        zs = rng.choice(K, size=n_d, p=theta_t[d])
        ws = np.array([rng.choice(V, p=phi_t[z]) for z in zs])
        doc_ids.append(np.full(n_d, d)); words.append(ws)
    doc_ids = np.concatenate(doc_ids).astype(np.int32)
    words = np.concatenate(words).astype(np.int32)

    alpha = jnp.full((D, K), 1.0)
    beta = jnp.full((K, V), 0.5)

    @model
    def lda_m(doc_ids, words):
        theta = sample("theta", Dirichlet(alpha))  # (D,K)
        phi = sample("phi", Dirichlet(beta))       # (K,V)
        # collapsed topic assignment: word ~ Categorical(theta[d] @ phi)
        word_probs = jnp.matmul(theta[doc_ids], phi,
                                precision=_F32_MATMUL)  # (N,V)
        observe("w", Categorical(jnp.log(word_probs)), words)

    dj, wj = jnp.asarray(doc_ids), jnp.asarray(words)

    def handwritten(q):
        sb = StickBreaking()
        off = 0
        u_theta = q[off:off + D * (K - 1)].reshape(D, K - 1); off += D * (K - 1)
        u_phi = q[off:off + K * (V - 1)].reshape(K, V - 1)
        theta = sb.forward(u_theta)
        phi = sb.forward(u_phi)
        lp = (sb.forward_log_det_jacobian(u_theta)
              + sb.forward_log_det_jacobian(u_phi))

        def dir_lp(x, conc):
            return (jnp.sum(jax.scipy.special.xlogy(conc - 1.0, x))
                    - jnp.sum(jax.scipy.special.gammaln(conc))
                    + jnp.sum(jax.scipy.special.gammaln(jnp.sum(conc, -1))))
        lp += dir_lp(theta, alpha) + dir_lp(phi, beta)
        word_probs = jnp.matmul(theta[dj], phi, precision=_F32_MATMUL)
        lp += jnp.sum(jnp.log(word_probs[jnp.arange(wj.shape[0]), wj]))
        return lp

    return PaperModel("lda", lda_m(dj, wj), handwritten, step_size=0.005,
                      data={"doc_ids": doc_ids, "words": words})


# ---------------------------------------------------------------------------
# Eight schools (Rubin 1981) — the canonical conditionally-separable
# hierarchy: (mu, tau) couple every theta_i, but GIVEN (mu, tau) the
# thetas are independent Normals with Normal likelihood attached. Not a
# Table-1 model; it exercises the conditional potential-spec path.
# ---------------------------------------------------------------------------
def eight_schools() -> PaperModel:
    y = np.asarray([28., 8., -3., 7., -1., 1., 18., 12.], dtype=np.float32)
    sigma = np.asarray([15., 10., 16., 11., 9., 11., 10., 18.],
                       dtype=np.float32)

    @model
    def schools(y, sigma):
        mu = sample("mu", Normal(0.0, 5.0))
        tau = sample("tau", HalfNormal(5.0))
        theta = sample("theta", Normal(mu * jnp.ones(8), tau))
        observe("y", Normal(theta, sigma), y)

    yj, sj = jnp.asarray(y), jnp.asarray(sigma)

    def handwritten(q):  # layout: mu, u_tau = log tau, theta[0:8]
        mu, u_tau, theta = q[0], q[1], q[2:10]
        tau = jnp.exp(u_tau)
        lp = _norm_lp(mu, 0.0, 5.0)
        lp += (0.5 * math.log(2.0 / math.pi) - math.log(5.0)
               - 0.5 * (tau / 5.0) ** 2 + u_tau)
        lp += jnp.sum(_norm_lp(theta, mu, tau))
        lp += jnp.sum(_norm_lp(yj, theta, sj))
        return lp

    return PaperModel("eight_schools", schools(yj, sj), handwritten,
                      step_size=0.1, data={"y": y, "sigma": sigma})


MODEL_NAMES = ("gaussian_10k", "gauss_unknown", "naive_bayes", "logreg",
               "hier_poisson", "sto_volatility", "hmm_semisup", "lda")

_BUILDERS = {
    "eight_schools": eight_schools,
    "gaussian_10k": gaussian_10k,
    "gauss_unknown": gauss_unknown,
    "naive_bayes": naive_bayes,
    "logreg": logreg,
    "hier_poisson": hier_poisson,
    "sto_volatility": sto_volatility,
    "hmm_semisup": hmm_semisup,
    "lda": lda,
}


def build(name: str, **overrides) -> PaperModel:
    return _BUILDERS[name](**overrides)
