"""The whole sampling step's share of the chip's peak, in %: the least
time that the window's useful chain leapfrog steps need (the larger of
their operations over peak FLOP/s and their bytes over peak HBM bandwidth,
counted per step by ``kernels/<config>_step.py``) over the window's wall
time. Useful steps: a NUTS tree of depth k takes 2**k - 1 (an upper
bound), counted on the kept draws and scaled by (warmup + samples) /
samples for the warm-up transitions, whose depths the sampler does not
record."""
import numpy as np

from harness.core import BENCH, load_module


def read(rec):
    d = rec["driver"]
    if d["kind"] != "chains" or not d["jobs"] \
            or d["jobs"][0]["tree_depth"] is None:
        return None
    cfg = d["config"]
    step = load_module(BENCH / "kernels" / f"{cfg['name']}_step.py")
    flops, bytes_ = step.cost(cfg["sizes"], d["chains"])
    kept = sum(float(np.sum(2.0 ** j["tree_depth"] - 1.0)) for j in d["jobs"])
    steps = kept * (d["num_warmup"] + d["num_samples"]) / d["num_samples"]
    pk = rec["peaks"]
    t_flops = steps * flops / pk["flops_per_s"]
    t_bytes = steps * bytes_ / pk["hbm_bytes_per_s"]
    rec["notes"].append(
        f"mfu.sample: {steps!r} useful chain steps; bound by "
        f"{'bytes' if t_bytes >= t_flops else 'flops'}")
    return 100.0 * max(t_flops, t_bytes) / rec["window_s"]
