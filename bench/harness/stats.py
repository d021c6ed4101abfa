"""Mixing statistics, kept with the benchmark so that no change
to the program can change the yardstick.

``effective_sample_size`` is a copy of the Geyer initial-monotone estimator
of ``repro.infer.chains`` (warnings dropped: a degenerate input returns nan,
which the caller treats as a failed reading).
"""
from __future__ import annotations

import numpy as np


def _autocov(x: np.ndarray) -> np.ndarray:
    n = x.shape[-1]
    x = x - x.mean(axis=-1, keepdims=True)
    nfft = int(2 ** np.ceil(np.log2(2 * n)))
    f = np.fft.rfft(x, nfft, axis=-1)
    acov = np.fft.irfft(f * np.conj(f), nfft, axis=-1)[..., :n].real
    return acov / n


def effective_sample_size(x: np.ndarray) -> float:
    """Geyer initial-monotone ESS of (chains, samples) scalar draws."""
    x = np.atleast_2d(np.asarray(x, dtype=np.float64))
    m, n = x.shape
    if n < 4:
        return float("nan")
    acov = _autocov(x)
    mean_var = acov[:, 0].mean() * n / (n - 1.0)
    var_plus = mean_var * (n - 1.0) / n
    if m > 1:
        var_plus += x.mean(axis=1).var(ddof=1)
    if not np.isfinite(var_plus) or var_plus <= 1e-300:
        return float("nan")
    rho = 1.0 - (mean_var - acov.mean(axis=0)) / var_plus
    prev_pair = np.inf
    tau = 1.0
    t = 1
    while t + 1 < n:
        pair = rho[t] + rho[t + 1]
        if pair < 0:
            break
        pair = min(pair, prev_pair)
        prev_pair = pair
        tau += 2.0 * pair
        t += 2
    return float(m * n / max(tau, 1e-12))


def ess_per_param(draws: np.ndarray) -> np.ndarray:
    """ESS of each parameter of (chains, samples, params) draws."""
    return np.array([effective_sample_size(draws[:, :, j])
                     for j in range(draws.shape[2])])


def ess_sums(rec: dict) -> np.ndarray:
    """Per parameter, the ESS of each job summed over the window's
    jobs (computed once per run record)."""
    if "_ess_sums" not in rec:
        jobs = rec["driver"]["jobs"]
        rec["_ess_sums"] = np.sum([ess_per_param(j["draws"]) for j in jobs],
                                  axis=0)
    return rec["_ess_sums"]
