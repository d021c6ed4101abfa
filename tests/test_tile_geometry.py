"""Tile geometry of the fused log-density reductions, derived from the length,
and which path sums a block: a Pallas launch or XLA.

``tile_geometry`` sizes the ``(rows, 128)`` tiles and the row-block of each
grid step from the true element count: short inputs become one block of
their own size, long ones keep blocks of up to ``block_rows`` rows. The
parity cases run the Pallas kernels (interpret mode) under ``jax.vmap``
over chains, as the chain programs do, at lengths on both sides of one
256-row block, against the pure-jnp oracles in ``fused_logpdf.ref``.

On a TPU ``site_block_sum`` launches a kernel only for blocks longer than
one (8, 128) f32 tile (``ops.pallas_pays``); shorter ones are summed by the
oracle, which XLA fuses with its neighbours. The rule reads the block's
static per-call length, so tracing with the TPU test patched to true shows
the chip's choice on a CPU: tracing lowers nothing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import cache_stats
from repro.kernels.fused_logpdf import kernel as K
from repro.kernels.fused_logpdf import ops, ref
from repro.models import paper_suite

BLOCK_ROWS = 256
CHAINS = 4
TOL = 1e-5
LENGTHS = [101, 1_024, 1_025, 10_000]


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


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


def _vg(family, y, **path):
    segment, _ = _FAMILIES[family]

    def f(a, b):
        return ops.site_block_sum(family, [segment(a, b, y)], **path)

    return jax.vmap(jax.value_and_grad(f, argnums=(0, 1)))


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(sub)


@pytest.mark.parametrize("n", LENGTHS + [1, 1_000_000])
@pytest.mark.parametrize("family", ops.SITE_BLOCK_FAMILIES)
def test_pallas_pays(family, n):
    per_row = family in ("categorical_logits", "mvnormal_prec")
    assert ops.pallas_pays(family, n) == (per_row or n > ops.ONE_TILE)
    assert ops.ONE_TILE == 1_024


@pytest.mark.parametrize("n", LENGTHS)
@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_auto_path_launches_pallas_past_one_tile(family, n, on_tpu):
    a, b, y = _inputs(n)
    jaxpr = jax.make_jaxpr(_vg(family, y))(a, b)
    assert ("pallas_call" in set(_primitives(jaxpr.jaxpr))) == (n > 1_024)


def test_forced_paths_ignore_the_length(on_tpu):
    a, b, y = _inputs(101)
    forced = jax.make_jaxpr(_vg("std_normal", y, use_pallas=True))(a, b)
    assert "pallas_call" in set(_primitives(forced.jaxpr))
    a, b, y = _inputs(10_000)
    barred = jax.make_jaxpr(_vg("std_normal", y, use_pallas=False))(a, b)
    assert "pallas_call" not in set(_primitives(barred.jaxpr))


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("n", [101, 1_024])
@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_auto_path_matches_oracle_and_pallas(family, n, on_tpu):
    segment, oracle = _FAMILIES[family]
    a, b, y = _inputs(n)

    def want(a, b):
        return oracle(*segment(a, b, y))

    ref_v, ref_g = jax.vmap(jax.value_and_grad(want, argnums=(0, 1)))(a, b)
    for path in ({}, {"use_pallas": True, "interpret": True}):
        got_v, got_g = _vg(family, y, **path)(a, b)
        assert got_v.shape == (CHAINS,)
        assert _rel(got_v, ref_v) < TOL
        for gg, gw in zip(got_g, ref_g):
            assert _rel(gg, gw) < TOL


def test_logreg_density_counts_one_block_per_path(on_tpu):
    # the 101-coordinate prior goes to XLA, the 10,000 logits to Pallas
    pm = paper_suite.logreg()
    tvi = pm.model.typed_varinfo(jax.random.PRNGKey(0)).link()
    ld = pm.model.make_logdensity_fn(tvi, backend="fused")
    before = cache_stats()
    jax.make_jaxpr(jax.value_and_grad(ld))(tvi.flat())
    after = cache_stats()
    assert after["fused_blocks_xla"] - before["fused_blocks_xla"] == 1
    assert after["fused_blocks_pallas"] - before["fused_blocks_pallas"] == 1
