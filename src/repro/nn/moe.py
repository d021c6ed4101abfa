"""Mixture-of-Experts FFN: top-k routing, capacity dispatch, shared experts.

Two dispatch implementations:

* ``moe_ffn`` (gspmd) — scatter-based (sort-free Switch-style): each
  (token, choice) pair gets a position within its expert via a masked
  cumulative sum; the (experts, capacity, d) buffer shards over the
  ``experts``->``model`` mesh axis. Simple, but GSPMD lowers the
  cross-shard scatter/gather to ALL-REDUCES OF THE WHOLE DISPATCH BUFFER
  (measured: 940 GB/device/step on deepseek train_4k — §Perf).

* ``moe_ffn_ep`` (shard_map expert parallelism) — tokens are data-sharded
  and REPLICATED across the model axis, so each model rank can locally
  dispatch to ITS OWN experts with zero communication; the only collective
  is one psum of the combined output per layer. The capacity is enforced
  per data-shard (cap_local = ceil(N_local*k/E*factor)), the standard
  production relaxation.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import sharding
from repro.nn.common import Initializer
from repro.sharding import constrain

__all__ = ["init_moe_params", "moe_ffn", "moe_ffn_ep", "shared_expert_ffn"]


def init_moe_params(init: Initializer, path: str, d_model: int,
                    d_expert: int, n_experts: int, n_shared: int = 0,
                    d_shared: Optional[int] = None) -> Dict[str, Any]:
    p = {
        "router": init.dense(f"{path}/router", (d_model, n_experts)),
        "experts": {
            "w_gate": init.dense(f"{path}/e_gate", (n_experts, d_model, d_expert)),
            "w_up": init.dense(f"{path}/e_up", (n_experts, d_model, d_expert)),
            "w_down": init.dense(f"{path}/e_down", (n_experts, d_expert, d_model),
                                 fan_in=d_expert),
        },
    }
    if n_shared > 0:
        ds = d_shared if d_shared is not None else n_shared * d_expert
        p["shared"] = {
            "w_gate": init.dense(f"{path}/s_gate", (d_model, ds)),
            "w_up": init.dense(f"{path}/s_up", (d_model, ds)),
            "w_down": init.dense(f"{path}/s_down", (ds, d_model), fan_in=ds),
        }
    return p


def moe_ffn(params, x, *, top_k: int, capacity_factor: float = 1.25,
            norm_topk_probs: bool = True) -> jax.Array:
    """x: (B,S,D) -> (B,S,D)."""
    B, S, D = x.shape
    E = params["router"].shape[1]
    N = B * S
    xt = x.reshape(N, D)

    # --- routing (f32 for numerics) -----------------------------------------
    logits = jnp.einsum("nd,de->ne", xt, params["router"],
                        preferred_element_type=jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(gates, top_k)          # (N, k)
    if norm_topk_probs:
        top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    # --- capacity positions ----------------------------------------------------
    cap = int(math.ceil(N * top_k / E * capacity_factor))
    flat_expert = top_idx.reshape(N * top_k)                 # (Nk,)
    flat_gate = top_vals.reshape(N * top_k).astype(x.dtype)
    token_of = jnp.repeat(jnp.arange(N, dtype=jnp.int32), top_k)

    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)  # (Nk, E)
    pos_all = jnp.cumsum(onehot, axis=0) - 1                  # (Nk, E)
    pos_in_e = jnp.sum(pos_all * onehot, axis=-1)             # (Nk,)
    keep = pos_in_e < cap
    pos_safe = jnp.where(keep, pos_in_e, 0)

    # --- dispatch: (E, cap, D) expert input buffers ------------------------
    contrib = jnp.where(keep[:, None], xt[token_of], 0).astype(x.dtype)
    xe = jnp.zeros((E, cap, D), x.dtype).at[flat_expert, pos_safe].add(
        contrib, mode="drop")
    xe = constrain(xe, "experts", None, None)

    # --- expert computation (batched einsum; shards over experts) ----------
    ew = params["experts"]
    g = jnp.einsum("ecd,edf->ecf", xe, ew["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, ew["w_up"])
    h = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u)
    ye = jnp.einsum("ecf,efd->ecd", h, ew["w_down"])
    ye = constrain(ye, "experts", None, None)

    # --- combine ------------------------------------------------------------
    y_tok = ye[flat_expert, pos_safe] * flat_gate[:, None]
    y_tok = jnp.where(keep[:, None], y_tok, 0)
    y = jnp.zeros((N, D), x.dtype).at[token_of].add(y_tok, mode="drop")

    if "shared" in params:
        y = y + shared_expert_ffn(params["shared"], xt)

    return y.reshape(B, S, D)


def shared_expert_ffn(sp, xt):
    """Dense always-on experts (computed OUTSIDE the EP region: it is a
    plain TP matmul, not a routed computation)."""
    sg = jnp.einsum("nd,df->nf", xt, sp["w_gate"])
    su = jnp.einsum("nd,df->nf", xt, sp["w_up"])
    sh = jax.nn.silu(sg.astype(jnp.float32)).astype(xt.dtype) * su
    return jnp.einsum("nf,fd->nd", sh, sp["w_down"])


def _ep_local_dispatch(router, ew, xt, *, top_k, capacity_factor, E, e_per,
                       axis, norm_topk_probs=True):
    """Per-(data, model)-rank body: route local tokens, dispatch to the
    LOCAL experts only, compute, combine, psum over the expert axis."""
    N, D = xt.shape
    logits = jnp.einsum("nd,de->ne", xt, router,
                        preferred_element_type=jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(gates, top_k)
    if norm_topk_probs:
        top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)

    rank = jax.lax.axis_index(axis)
    lo = rank * e_per
    cap = int(math.ceil(N * top_k / E * capacity_factor))

    flat_expert = top_idx.reshape(N * top_k)
    flat_gate = top_vals.reshape(N * top_k).astype(xt.dtype)
    token_of = jnp.repeat(jnp.arange(N, dtype=jnp.int32), top_k)

    mine = (flat_expert >= lo) & (flat_expert < lo + e_per)
    local_e = jnp.where(mine, flat_expert - lo, 0)
    onehot = jnp.where(mine[:, None],
                       jax.nn.one_hot(local_e, e_per, dtype=jnp.int32), 0)
    pos_in_e = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    keep = mine & (pos_in_e < cap)
    pos_safe = jnp.where(keep, pos_in_e, 0)

    contrib = jnp.where(keep[:, None], xt[token_of], 0).astype(xt.dtype)
    xe = jnp.zeros((e_per, cap, D), xt.dtype).at[local_e, pos_safe].add(
        contrib, mode="drop")

    g = jnp.einsum("ecd,edf->ecf", xe, ew["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, ew["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
    ye = jnp.einsum("ecf,efd->ecd", h, ew["w_down"])

    y_tok = ye[local_e, pos_safe] * flat_gate[:, None]
    y_tok = jnp.where(keep[:, None], y_tok, 0)
    y = jnp.zeros((N, D), xt.dtype).at[token_of].add(y_tok, mode="drop")
    return jax.lax.psum(y, axis)


def moe_ffn_ep(params, x, *, top_k: int, capacity_factor: float = 1.25,
               norm_topk_probs: bool = True) -> jax.Array:
    """shard_map expert parallelism (see module docstring). Falls back to
    the gspmd path when no mesh / expert axis is active (CPU tests)."""
    rules = sharding.active_rules()
    axis = rules.mapping.get("experts") if rules is not None else None
    mesh = rules.mesh if rules is not None else None
    E = params["router"].shape[1]
    if mesh is None or axis is None:
        return moe_ffn(params, x, top_k=top_k,
                       capacity_factor=capacity_factor,
                       norm_topk_probs=norm_topk_probs)
    n_ranks = sharding.axes_size(mesh, axis)
    if E % n_ranks != 0:
        return moe_ffn(params, x, top_k=top_k,
                       capacity_factor=capacity_factor,
                       norm_topk_probs=norm_topk_probs)
    e_per = E // n_ranks

    B, S, D = x.shape
    batch_axes = rules.mapping.get("batch")
    x_spec = sharding.fit_spec(P(batch_axes, None, None), (B, S, D), mesh)
    ew = params["experts"]

    def body(router, ew_local, x_local):
        b, s, _ = x_local.shape
        xt = x_local.reshape(b * s, D)
        y = _ep_local_dispatch(router, ew_local, xt, top_k=top_k,
                               capacity_factor=capacity_factor, E=E,
                               e_per=e_per, axis=axis,
                               norm_topk_probs=norm_topk_probs)
        return y.reshape(b, s, D)

    y = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(axis, None, None), x_spec),
        out_specs=x_spec, check_vma=False,
    )(params["router"], ew, x)

    if "shared" in params:
        y = y + shared_expert_ffn(params["shared"],
                                  x.reshape(B * S, D)).reshape(B, S, D)
    return y
