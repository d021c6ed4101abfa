"""Plain reference of ``logreg``: w ~ Normal(0, I_dim), b ~ Normal(0, 3),
y_i ~ Bernoulli(sigmoid(x_i . w + b)), written in NumPy float64 from the
model's definition. It imports nothing of the program: the data set is
drawn again here with the recipe that the configuration names (NumPy's
``default_rng(seed)``: X, then w_true with 30% non-zero, then y).

Besides the log density it gives the posterior's mean and sd by the
Laplace approximation (Newton's method to the mode in float64; the sd is
the root of the diagonal of the inverse negative Hessian). Its mean is the
mode: for the largest coefficient the posterior mean lies 0.84 posterior
sd from it (importance sampling on the host), so sound runs read a
``mean_gap`` near 0.85, and the limit sits above that. It gives the
gradient by leaf (``w`` and ``b``, the model's sites), and a
``jax.numpy`` version of the log density and its gradient at a stated
precision for the control.
"""
from __future__ import annotations

import math

import numpy as np

_HALF_LOG_2PI = 0.5 * math.log(2.0 * math.pi)
PRIOR_SD_B = 3.0


def make_data(n: int, dim: int, seed: int):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, dim)).astype(np.float32)
    w_true = rng.normal(size=dim) * (rng.random(dim) < 0.3)
    logits = X @ w_true
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.int32)
    return X, y


def log_prior(w, b):
    w = np.asarray(w, np.float64)
    b = np.asarray(b, np.float64)
    return (np.sum(-0.5 * w * w - _HALF_LOG_2PI, axis=-1)
            + (-0.5 * (b / PRIOR_SD_B) ** 2 - math.log(PRIOR_SD_B)
               - _HALF_LOG_2PI))


def log_lik(X, y, w, b):
    """Log likelihood of rows (X, y) at w (..., dim) and b (...)."""
    X = np.asarray(X, np.float64)
    y = np.asarray(y, np.float64)
    w = np.asarray(w, np.float64)
    b = np.asarray(b, np.float64)
    logit = w @ X.T + b[..., None]
    return np.sum(y * logit - np.logaddexp(0.0, logit), axis=-1)


# the precisions the control runs at: the configuration states float32
# at HIGHEST for X @ w, read to show where the control itself stands; the
# steps below it are HIGH (three bfloat16 passes) and one bfloat16 pass
CONTROLS = ("highest", "high", "bfloat16")


def _matvec(X, w, precision: str):
    """X @ w over the last axis of w at ``precision``. One bfloat16 pass is
    written out (both operands rounded to bfloat16, products accumulated
    in float32), so it computes the same on any backend; HIGH is the
    backend's own (on a CPU it is float32)."""
    import jax
    import jax.numpy as jnp
    if precision == "bfloat16":
        return jnp.einsum("nd,...d->...n", X.astype(jnp.bfloat16),
                          w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    prec = {"highest": jax.lax.Precision.HIGHEST,
            "high": jax.lax.Precision.HIGH}[precision]
    return jnp.einsum("nd,...d->...n", X, w, precision=prec)


def _jax_lik(X, y, w, b, precision: str):
    import jax.numpy as jnp
    logit = _matvec(X, w, precision) + b[..., None]
    y = y.astype(jnp.float32)
    return jnp.sum(y * logit - jnp.logaddexp(0.0, logit), axis=-1)


def _jax_prior(w, b):
    import jax.numpy as jnp
    return (jnp.sum(-0.5 * w * w - _HALF_LOG_2PI, axis=-1)
            + (-0.5 * (b / PRIOR_SD_B) ** 2 - math.log(PRIOR_SD_B)
               - _HALF_LOG_2PI))


class Reference:
    def __init__(self, config: dict):
        s = config["sizes"]
        self.n, self.dim = int(s["n"]), int(s["dim"])
        self.X, self.y = make_data(self.n, self.dim, int(s["seed"]))
        self.num_params = self.dim + 1
        self._moments = None

    @staticmethod
    def param_vector(draws: dict) -> np.ndarray:
        """(chains, samples, dim + 1) flat parameters: w, then b."""
        return np.concatenate([draws["w"], draws["b"][..., None]], axis=-1)

    def logp(self, q) -> np.ndarray:
        q = np.asarray(q, np.float64)
        w, b = q[..., :self.dim], q[..., self.dim]
        return log_prior(w, b) + log_lik(self.X, self.y, w, b)

    def grad_leaves(self, q) -> dict:
        """The gradient of the log density at ``q`` (..., dim + 1), by
        leaf: ``w`` (..., dim) and ``b`` (..., 1)."""
        q = np.asarray(q, np.float64)
        w, b = q[..., :self.dim], q[..., self.dim]
        X = self.X.astype(np.float64)
        logit = w @ X.T + b[..., None]
        r = self.y - 1.0 / (1.0 + np.exp(-logit))
        return {"w": r @ X - w,
                "b": (r.sum(axis=-1) - b / PRIOR_SD_B ** 2)[..., None]}

    def posterior_moments(self):
        """Laplace mean (the mode) and sd of every parameter."""
        if self._moments is None:
            Xt = np.concatenate([self.X.astype(np.float64),
                                 np.ones((self.n, 1))], axis=1)
            y = self.y.astype(np.float64)
            prec0 = np.ones(self.num_params)
            prec0[-1] = 1.0 / PRIOR_SD_B ** 2

            def grad_hess(theta):
                s = 1.0 / (1.0 + np.exp(-(Xt @ theta)))
                grad = Xt.T @ (y - s) - prec0 * theta
                hess = (Xt * (s * (1.0 - s))[:, None]).T @ Xt \
                    + np.diag(prec0)
                return grad, hess

            mode = np.zeros(self.num_params)
            for _ in range(100):
                grad, hess = grad_hess(mode)
                step = np.linalg.solve(hess, grad)
                mode = mode + step
                if np.max(np.abs(step)) < 1e-12:
                    break
            sd = np.sqrt(np.diag(np.linalg.inv(grad_hess(mode)[1])))
            self._moments = (mode, sd)
        return self._moments

    def logp_jax(self, q, precision: str):
        """The log density in ``jax.numpy``, X @ w at ``precision``
        ("highest", "high" or "bfloat16")."""
        import jax.numpy as jnp
        q = jnp.asarray(q, jnp.float32)
        w, b = q[..., :self.dim], q[..., self.dim]
        return _jax_prior(w, b) + _jax_lik(
            jnp.asarray(self.X), jnp.asarray(self.y), w, b, precision)

    def logp_grad_jax(self, precision: str):
        """A function of a batch of ``q`` (batch, dim + 1) giving the
        ``jax.numpy`` log density and its gradient by leaf, every matmul
        (X @ w and its transpose) at ``precision``."""
        import jax

        def one(q):
            return self.logp_jax(q, precision)

        def f(q):
            lp, g = jax.vmap(jax.value_and_grad(one))(q)
            return lp, {"w": g[:, :self.dim], "b": g[:, self.dim:]}
        return f
