"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing here runs on a chip: the v5e topology is described, and each test
lowers and compiles one kernel program at the paper's Table-1 widths with
the chip's own compiler. That catches what interpret mode cannot (tiling
and alignment rules, VMEM limits, primitives without a Mosaic lowering).
Each compiled program must hold the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.fused_leapfrog import (fused_leapfrog,
                                          potential_value_and_grad)
from repro.kernels.fused_leapfrog.spec import N_OPS, PotentialSpec
from repro.kernels.fused_logpdf import ops
from repro.kernels.fused_logpdf.ops import site_block_sum

DIM = 10_000        # gaussian_10k, logreg rows
CHAINS = 64
N_CAT, C_CAT = 10_000, 100   # lda: ~10 docs x 1,000 words over V=100
N_MVN, D_MVN = 1_000, 40     # naive_bayes: 1,000 rows x 40 PCA dims


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(op: int, dim: int = DIM) -> PotentialSpec:
    rng = np.random.default_rng(op)
    pos = lambda: rng.uniform(0.5, 2.0, dim)  # noqa: E731 (c1 slots >= 0)
    return PotentialSpec(op=np.full(dim, op), c0=pos(), c1=pos(), c2=pos(),
                         c3=pos(), const=0.0, dim=dim)


@pytest.mark.parametrize("op", range(N_OPS))
def test_leapfrog_with_mass_vmapped(op, one_chip):
    spec = _spec(op)

    def step(q, p, g, inv_mass, eps):
        def one(q, p, g):
            return fused_leapfrog(spec, q, p, g, eps, 4, inv_mass=inv_mass,
                                  use_pallas=True, interpret=False)
        return jax.vmap(one)(q, p, g)

    f32 = jnp.float32
    text = _compile_text(step, ((CHAINS, DIM), f32), ((CHAINS, DIM), f32),
                         ((CHAINS, DIM), f32), ((DIM,), f32), ((), f32),
                         sharding=one_chip)
    assert "tpu_custom_call" in text


def test_potential_vg_vmapped_in_while_loop(one_chip):
    """NUTS evaluates tree leaves with the fused value+grad under vmap
    inside ``lax.while_loop``; the mixed-opcode spec takes the kernel's
    cross-opcode branch."""
    spec = PotentialSpec(op=np.arange(DIM) % N_OPS, c0=np.ones(DIM),
                         c1=np.ones(DIM), c2=np.ones(DIM), c3=np.ones(DIM),
                         const=0.0, dim=DIM)
    assert spec.uniform_op is None

    def leaves(u):
        def vg(x):
            return potential_value_and_grad(spec, x, use_pallas=True,
                                            interpret=False)

        def body(c):
            i, u, acc = c
            lp, g = jax.vmap(vg)(u)
            return i + 1, u + 1e-3 * g, acc + lp

        return jax.lax.while_loop(lambda c: c[0] < 8, body,
                                  (0, u, jnp.zeros(u.shape[0])))

    text = _compile_text(leaves, ((CHAINS, DIM), jnp.float32),
                         sharding=one_chip)
    assert "tpu_custom_call" in text


_F32 = jnp.float32
# family -> (segment shapes); the first array is differentiated
_FAMILIES = {
    "std_normal": (((DIM,), _F32),),
    "normal": (((DIM,), _F32),) * 3,
    "bernoulli_logits": (((DIM,), _F32),) * 2,
    "gamma": (((DIM,), _F32),) * 3,
    "beta": (((DIM,), _F32),) * 3,
    "student_t": (((DIM,), _F32),) * 2,
    "categorical_logits": (((N_CAT, C_CAT), _F32), ((N_CAT,), jnp.int32)),
    "mvnormal_prec": (((N_MVN, D_MVN), _F32), ((D_MVN, D_MVN), _F32)),
}


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_site_block_sum_value_and_grad(family, one_chip):
    def value_and_grad(first, *rest):
        return jax.value_and_grad(
            lambda a: site_block_sum(family, [(a,) + rest], use_pallas=True,
                                     interpret=False))(first)

    text = _compile_text(value_and_grad, *_FAMILIES[family],
                         sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [101, 10_000, 40_000])
@pytest.mark.parametrize("family", ["bernoulli_logits", "std_normal"])
def test_site_block_sum_vmapped_lengths(family, n, one_chip):
    # the chain programs' path: one density per chain under vmap, at the
    # logreg prior's and likelihood's lengths and one past a 256-row block
    shapes = _FAMILIES[family]

    def value_and_grad(first, *rest):
        return jax.value_and_grad(
            lambda a: site_block_sum(family, [(a,) + rest], use_pallas=True,
                                     interpret=False))(first)

    text = _compile_text(jax.vmap(value_and_grad),
                         *[((CHAINS, n), d) for _, d in shapes],
                         sharding=one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("family, n, kernel, present", [
    ("std_normal", 101, "fused_std_normal_logpdf", False),
    ("bernoulli_logits", 10_000, "fused_bernoulli_logpdf", True),
])
def test_site_block_sum_auto_path(family, n, kernel, present, one_chip,
                                  monkeypatch):
    # the path left to the rule, as the fused evaluators call it: the
    # logreg prior's one-tile block is XLA's, its logits keep the kernel
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    shapes = _FAMILIES[family]

    def value_and_grad(first, *rest):
        return jax.value_and_grad(
            lambda a: site_block_sum(family, [(a,) + rest],
                                     interpret=False))(first)

    text = _compile_text(jax.vmap(value_and_grad),
                         *[((CHAINS, n), d) for _, d in shapes],
                         sharding=one_chip)
    assert (kernel in text) == present
