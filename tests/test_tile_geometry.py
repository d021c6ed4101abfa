"""Tile geometry of the fused log-density reductions, derived from the length.

``tile_geometry`` sizes the ``(rows, 128)`` tiles and the row-block of each
grid step from the true element count: short inputs become one block of
their own size, long ones keep blocks of up to ``block_rows`` rows. The
parity cases run the Pallas kernels (interpret mode) under ``jax.vmap``
over chains, as the chain programs do, at lengths on both sides of one
256-row block, against the pure-jnp oracles in ``fused_logpdf.ref``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_logpdf import kernel as K
from repro.kernels.fused_logpdf import ops, ref

BLOCK_ROWS = 256
CHAINS = 4
TOL = 1e-5


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b) / (1.0 + np.abs(b)))


@pytest.mark.parametrize(
    "n", [1, 101, 128, 1_024, 1_025, 10_000, 32_768, 40_000, 1_000_000])
def test_tile_geometry(n):
    rows, br = ops.tile_geometry(n, BLOCK_ROWS)
    blocks = rows // br
    assert br % K.SUB == 0
    assert rows % br == 0
    assert br <= BLOCK_ROWS
    assert rows * K.LANE >= n
    # padding under one (8, 128) tile per grid step
    assert rows * K.LANE - n < blocks * K.SUB * K.LANE
    if n <= BLOCK_ROWS * K.LANE:
        assert blocks == 1


def _inputs(n):
    keys = jax.random.split(jax.random.PRNGKey(n), 3)
    a = jax.random.normal(keys[0], (CHAINS, n))
    b = jax.random.normal(keys[1], (CHAINS, n))
    y = (jax.random.uniform(keys[2], (n,)) < 0.4).astype(jnp.float32)
    return a, b, y


# family -> (segment from the batched a, b and the shared y; its oracle)
_FAMILIES = {
    "std_normal": (lambda a, b, y: (a,), ref.std_normal_logpdf_sum_ref),
    "bernoulli_logits": (lambda a, b, y: (a, y),
                         ref.bernoulli_logits_logpmf_sum_ref),
    "normal": (lambda a, b, y: (a, 0.5 * b, jnp.full_like(y, 1.7)),
               ref.normal_logpdf_sum_ref),
}


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("n", [101, 10_000, 40_000])
@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_vmapped_site_block_sum_matches_ref(family, n):
    segment, oracle = _FAMILIES[family]
    a, b, y = _inputs(n)

    def fused(a, b):
        return ops.site_block_sum(family, [segment(a, b, y)],
                                  use_pallas=True, interpret=True)

    def want(a, b):
        return oracle(*segment(a, b, y))

    got_v, got_g = jax.vmap(jax.value_and_grad(fused, argnums=(0, 1)))(a, b)
    ref_v, ref_g = jax.vmap(jax.value_and_grad(want, argnums=(0, 1)))(a, b)
    assert got_v.shape == (CHAINS,)
    assert _rel(got_v, ref_v) < TOL
    for gg, gw in zip(got_g, ref_g):
        assert _rel(gg, gw) < TOL
