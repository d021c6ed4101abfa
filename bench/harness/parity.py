"""The comparison that decides ``correct``.

``rel_gap`` is the parity arithmetic of ``chip_smoke.parity``: the largest
absolute difference over the compared array, over max(1, max |reference|).
Every number compared is printed beside its limit, and ``Checks.line``
gives them in the order they were added.
"""
from __future__ import annotations

import sys

import numpy as np


def rel_gap(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} != reference {want.shape}")
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want), initial=0.0)
                 / max(1.0, float(np.max(np.abs(want), initial=0.0))))


def rowwise_rel_gap(got, want) -> float:
    """Largest per-element gap, each over max(1, |its reference|)."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise ValueError(f"shape {got.shape} != reference {want.shape}")
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want)
                        / np.maximum(1.0, np.abs(want)), initial=0.0))


class Checks:
    """Numbers compared with their limits; a number passes at or below."""

    def __init__(self):
        self.items = []

    def add(self, name: str, value: float, limit: float) -> None:
        self.items.append((name, float(value), float(limit)))

    @property
    def ok(self) -> bool:
        return bool(self.items) and all(
            np.isfinite(v) and v <= lim for _, v, lim in self.items)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.items}

    def print_last_lines(self) -> None:
        for n, v, lim in self.items:
            verdict = "ok" if np.isfinite(v) and v <= lim else "FAIL"
            print(f"check {n}: {v!r} limit {lim!r} {verdict}",
                  file=sys.stderr, flush=True)
