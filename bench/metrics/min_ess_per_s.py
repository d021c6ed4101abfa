"""Per parameter, the Geyer ESS of each job's kept draws summed over the
window's independent jobs; the smallest over the parameters, over the
window's wall time."""
from harness.stats import ess_sums


def read(rec):
    if rec["driver"]["kind"] != "chains":
        return None
    return float(ess_sums(rec).min()) / rec["window_s"]
