"""Expert-parallel (shard_map) MoE vs the gspmd reference dispatch."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding
from repro.nn import lm, moe
from repro.nn.common import Initializer


def _mesh_and_rules():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    return mesh, sharding.DEFAULT_RULES.with_mesh(mesh)


def test_ep_matches_gspmd_dispatch():
    mesh, rules = _mesh_and_rules()
    init = Initializer(0, jnp.float32)
    p = moe.init_moe_params(init, "m", 32, 64, 8, n_shared=1, d_shared=64)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    with sharding.use_rules(rules), mesh:
        y_ref = moe.moe_ffn(p, x, top_k=2)
        y_ep = moe.moe_ffn_ep(p, x, top_k=2)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               rtol=1e-6, atol=1e-6)


def test_ep_gradients_finite():
    mesh, rules = _mesh_and_rules()
    init = Initializer(1, jnp.float32)
    p = moe.init_moe_params(init, "m", 16, 32, 4)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 16))

    def loss(p):
        with sharding.use_rules(rules), mesh:
            return jnp.sum(moe.moe_ffn_ep(p, x, top_k=2) ** 2)

    g = jax.grad(loss)(p)
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree_util.tree_leaves(g))


def test_ep_falls_back_without_mesh():
    init = Initializer(2, jnp.float32)
    p = moe.init_moe_params(init, "m", 16, 32, 4)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 16))
    y_ref = moe.moe_ffn(p, x, top_k=2)
    y_ep = moe.moe_ffn_ep(p, x, top_k=2)  # no active rules -> fallback
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref))


def test_ep_arch_forward():
    """deepseek smoke config with moe_impl='ep' under a 1x1 mesh."""
    from repro import configs
    cfg = dataclasses.replace(
        configs.get_smoke_config("deepseek-v2-lite-16b"), moe_impl="ep")
    params = lm.init_params(cfg, seed=0)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, cfg.vocab)
    mesh, rules = _mesh_and_rules()
    with sharding.use_rules(rules), mesh:
        logits = lm.forward_train(cfg, params, tokens)
    assert logits.shape == (2, 16, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
