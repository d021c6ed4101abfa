"""The numerator of ``min_ess_per_s`` over the kept draws it came from:
the sampler's mixing per draw, apart from its speed."""
from harness.stats import ess_sums


def read(rec):
    if rec["driver"]["kind"] != "chains":
        return None
    return float(ess_sums(rec).min()) / rec["work"]["draws"]
