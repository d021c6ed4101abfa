"""Operations and HBM bytes that one useful leapfrog step of one ``logreg``
chain requires: the logits X @ w (2 n d flops), the Bernoulli terms and
their derivative (20 n), the gradient X^T r (2 n d) and the update of the
d + 1 coordinates (10 per coordinate). X and y are read twice per step of
the fleet, once for the logits and once for the gradient, and that read is
shared by all ``chains`` chains of one vmapped step; each chain also
writes and reads back its logits three times (4 bytes each)."""


def cost(sizes: dict, chains: int):
    n, d = int(sizes["n"]), int(sizes["dim"])
    flops = 4.0 * n * d + 20.0 * n + 10.0 * (d + 1)
    bytes_ = (2 * 4.0 * n * d + 2 * 4.0 * n) / chains + 3 * 4.0 * n
    return flops, bytes_
