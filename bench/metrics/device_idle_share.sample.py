"""Share of the traced window (whole ``run_chains`` jobs back to back) in
which no operation ran on the device, in %."""


def read(rec):
    t = rec.get("trace")
    if t is None or rec["driver"]["kind"] != "chains":
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
