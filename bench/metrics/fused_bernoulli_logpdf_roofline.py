"""Share of its roofline that the Pallas ``fused_bernoulli_logpdf`` kernel
reaches in the traced window, in %: the least time its launches need (the
larger of operations over peak FLOP/s and bytes over peak HBM bandwidth,
counted by ``kernels/fused_bernoulli_logpdf.py`` for the configuration's
``n`` observations) over its device time in the trace."""
from harness.core import kernel_cost
from harness.trace import kernel_time


def read(rec):
    t = rec.get("trace")
    d = rec["driver"]
    if t is None or d["kind"] != "chains":
        return None
    secs, launches = kernel_time(t, "fused_bernoulli_logpdf")
    if launches == 0 or secs <= 0:
        return None
    flops, bytes_ = kernel_cost("fused_bernoulli_logpdf")(
        n=int(d["config"]["sizes"]["n"]), batch=d["chains"])
    pk = rec["peaks"]
    t_flops = launches * flops / pk["flops_per_s"]
    t_bytes = launches * bytes_ / pk["hbm_bytes_per_s"]
    rec["notes"].append(
        f"fused_bernoulli_logpdf: {launches} launches, {secs!r} s on the "
        f"device; bound by {'bytes' if t_bytes >= t_flops else 'flops'}")
    return 100.0 * max(t_flops, t_bytes) / secs
