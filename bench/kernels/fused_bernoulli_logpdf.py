"""Operations and HBM bytes of one ``fused_bernoulli_logpdf`` launch.

In the style of ``benchmarks/roofline.py::_kernel_cells`` (elementwise
log-density reductions: the inputs stream once, one scalar comes out):
per observation ``-logaddexp(0, -l) - (1 - y) * l`` and the accumulate,
10 flops with each transcendental counted as one; bytes are the logits
and the labels, 4 bytes each, and the scalar out. Under ``vmap`` one
launch carries every chain of the fleet. ``n`` is the number of
observations, not the padded tile size.
"""


def cost(n: int, batch: int):
    flops = 10 * n
    bytes_ = 4.0 * 2 * n + 4
    return batch * flops, batch * bytes_
