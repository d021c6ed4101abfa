"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid: (B, H, NC) with the chunk axis innermost and SEQUENTIAL — the per-head
SSM state (d_state x head_dim, f32) lives in VMEM scratch and is carried
across chunk iterations, so the recurrence never round-trips HBM. Within a
chunk everything is MXU matmuls on (chunk x n) / (n x p) / (chunk x chunk)
tiles (chunk=128 aligns the systolic array):

  y_intra = [(C B^T) .* decay .* dt] @ x          (attention-like, causal)
  y_inter = (exp(cum) * C) @ S_in                 (state broadcast)
  S_out   = exp(cum_L) * S_in + B^T @ (seg .* dt .* x)

Grouped B/C (g groups, h heads) are resolved by the BlockSpec index map
(head -> group = h // (H//G)), so grouped tensors are never materialised
per-head in HBM — the kernel reads the same group tile for all its heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)        # (L, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # (L,)
    a = a_ref[0]                                     # scalar, f32
    Bm = b_ref[0, :, 0, :].astype(jnp.float32)       # (L, N)
    Cm = c_ref[0, :, 0, :].astype(jnp.float32)       # (L, N)

    L = x.shape[0]
    dA = dt * a                                      # (L,) <= 0
    cum = jnp.cumsum(dA)                             # (L,)

    # intra-chunk (causal attention-like term); mask inside exp — the
    # anticausal diffs are positive and can overflow f32
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = ii >= jj
    diff = jnp.where(causal, cum[:, None] - cum[None, :], 0.0)
    decay = jnp.exp(diff)                            # (L, L)
    cb = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    w = jnp.where(causal, cb * decay, 0.0) * dt[None, :]
    y_intra = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk (incoming state contribution)
    state = state_ref[...]                           # (N, P) f32
    c_scaled = Cm * jnp.exp(cum)[:, None]            # (L, N)
    y_inter = jax.lax.dot_general(c_scaled, state, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[0, :, 0, :] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S <- exp(cum_L) S + B^T (seg .* dt .* x)
    seg = jnp.exp(cum[-1] - cum) * dt                # (L,)
    xw = x * seg[:, None]                            # (L, P)
    s_new = jax.lax.dot_general(Bm, xw, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    state_ref[...] = state * jnp.exp(cum[-1]) + s_new


def ssd_scan_bh(x, dt, A, B, C, *, chunk: int, n_groups: int,
                interpret: bool = False):
    """x: (b,s,h,p); dt: (b,s,h); A: (h,) f32; B, C: (b,s,g,n)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    nc = s // chunk
    rep = h // n_groups
    grid = (b, h, nc)

    return pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda bi, hi, ci: (bi, ci, hi // rep, 0)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda bi, hi, ci: (bi, ci, hi // rep, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, p),
                               lambda bi, hi, ci: (bi, ci, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(A.astype(jnp.float32), x, dt, B, C)
