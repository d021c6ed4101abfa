"""Useful share of the vmapped NUTS tree loop, in %: the vmapped loop runs
until the deepest chain of a transition is done, so per kept transition
the fleet pays chains x the deepest chain's leapfrog steps. A tree of
depth k takes 2**k - 1 steps (the last subtree may stop early, so this
counts an upper bound of the steps on both sides of the ratio)."""
import numpy as np


def read(rec):
    jobs = rec["driver"].get("jobs") or []
    depths = [j["tree_depth"] for j in jobs if j["tree_depth"] is not None]
    if not depths:
        return None
    steps = np.concatenate([(2.0 ** d - 1.0) for d in depths], axis=1)
    useful = steps.sum()
    paid = steps.shape[0] * steps.max(axis=0).sum()
    return 100.0 * useful / paid
