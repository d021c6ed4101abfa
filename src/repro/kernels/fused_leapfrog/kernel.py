"""Pallas TPU kernel running the ENTIRE n-step leapfrog in one launch.

For a separable potential (see ``spec.py``) every coordinate's leapfrog
trajectory is independent of every other coordinate: the gradient is an
elementwise map, so momentum/position updates never mix lanes. That
means a row-block of the flat state can run all ``n_steps`` to
completion inside the kernel — q, p and the gradient stay in VREGs/VMEM
across steps, and only the final state plus ONE scalar (the potential
at the final position, needed for the MH correction) leave the chip.

Compare the unfused step: n_steps x (logp kernel + VJP kernel) with q/p
round-tripping through HBM between every launch. Here it is a single
launch with no backward pass at all — the gradient is the analytic
opcode table from ``spec.py``.

Layout mirrors ``fused_logpdf``: flat vectors padded to (R, 128) tiles,
grid walking row-blocks, VMEM (8, 128) accumulator for the potential
sum, (1, 1) SMEM scalar outputs. Padded lanes carry all-zero
coefficients, which make every opcode return exactly 0 value and 0
gradient — no masking needed anywhere.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_leapfrog.spec import (potential_elem_grad,
                                               potential_elem_value)
from repro.kernels.fused_logpdf.kernel import LANE, SUB

__all__ = ["leapfrog_2d", "potential_vg_2d", "LANE", "SUB"]


def _make_leapfrog_kernel(n_steps: int, uniform_op, with_mass: bool):
    def kern(*refs):
        if with_mass:
            (eps_ref, q_ref, p_ref, g_ref, op_ref, c0_ref, c1_ref, c2_ref,
             c3_ref, im_ref, qo_ref, po_ref, go_ref, lp_ref, acc_ref) = refs
        else:
            (eps_ref, q_ref, p_ref, g_ref, op_ref, c0_ref, c1_ref, c2_ref,
             c3_ref, qo_ref, po_ref, go_ref, lp_ref, acc_ref) = refs

        i = pl.program_id(0)
        ni = pl.num_programs(0)

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        eps = eps_ref[0, 0]
        q = q_ref[...].astype(jnp.float32)
        p = p_ref[...].astype(jnp.float32)
        g = g_ref[...].astype(jnp.float32)
        op = op_ref[...]
        c0 = c0_ref[...].astype(jnp.float32)
        c1 = c1_ref[...].astype(jnp.float32)
        c2 = c2_ref[...].astype(jnp.float32)
        c3 = c3_ref[...].astype(jnp.float32)
        im = im_ref[...].astype(jnp.float32) if with_mass else None

        def body(_, carry):
            q, p, g = carry
            p_half = p + 0.5 * eps * g
            vel = p_half * im if with_mass else p_half
            q_new = q + eps * vel
            g_new = potential_elem_grad(op, c0, c1, c2, c3, q_new,
                                        uniform_op=uniform_op)
            p_new = p_half + 0.5 * eps * g_new
            return (q_new, p_new, g_new)

        q, p, g = jax.lax.fori_loop(0, n_steps, body, (q, p, g))

        # potential value only at the FINAL position (MH correction)
        v = potential_elem_value(op, c0, c1, c2, c3, q,
                                 uniform_op=uniform_op)
        acc_ref[...] += jnp.sum(v.reshape(-1, SUB, LANE), axis=0)
        qo_ref[...] = q
        po_ref[...] = p
        go_ref[...] = g

        @pl.when(i == ni - 1)
        def _fin():
            lp_ref[0, 0] = jnp.sum(acc_ref[...])

    return kern


def leapfrog_2d(eps, q, p, g, op, c0, c1, c2, c3, im, n_steps: int,
                uniform_op, block_rows: int, interpret: bool):
    """One launch: n_steps leapfrog on (R, 128) tiles.

    ``eps`` is (1, 1) float32 (SMEM); ``q/p/g`` float32 and ``op`` int32
    tiles plus the four coefficient tiles, all (R, 128) with R a multiple
    of ``block_rows``; ``im`` is an optional diagonal inverse-mass tile.
    Returns ``(q, p, g, logp)`` with logp scalar (potential at final q,
    WITHOUT the spec const — the wrapper adds it).
    """
    rows = q.shape[0]
    grid = (rows // block_rows,)
    with_mass = im is not None
    tile = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    smem = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    in_specs = [smem] + [tile] * (9 if with_mass else 8)
    kern = _make_leapfrog_kernel(n_steps, uniform_op, with_mass)
    args = (eps, q, p, g, op, c0, c1, c2, c3) + ((im,) if with_mass else ())
    qf, pf, gf, lp = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=(tile, tile, tile, smem),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((SUB, LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_leapfrog",
    )(*args)
    return qf, pf, gf, lp[0, 0]


def _make_potential_vg_kernel(uniform_op):
    def kern(q_ref, op_ref, c0_ref, c1_ref, c2_ref, c3_ref,
             go_ref, lp_ref, acc_ref):
        i = pl.program_id(0)
        ni = pl.num_programs(0)

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[...].astype(jnp.float32)
        op = op_ref[...]
        c0 = c0_ref[...].astype(jnp.float32)
        c1 = c1_ref[...].astype(jnp.float32)
        c2 = c2_ref[...].astype(jnp.float32)
        c3 = c3_ref[...].astype(jnp.float32)
        v = potential_elem_value(op, c0, c1, c2, c3, q,
                                 uniform_op=uniform_op)
        acc_ref[...] += jnp.sum(v.reshape(-1, SUB, LANE), axis=0)
        go_ref[...] = potential_elem_grad(op, c0, c1, c2, c3, q,
                                          uniform_op=uniform_op)

        @pl.when(i == ni - 1)
        def _fin():
            lp_ref[0, 0] = jnp.sum(acc_ref[...])

    return kern


def potential_vg_2d(q, op, c0, c1, c2, c3, uniform_op, block_rows: int,
                    interpret: bool):
    """Single-eval fused potential value + analytic gradient (for NUTS
    tree leaves and chain init). Returns ``(grad_tiles, logp_scalar)``;
    logp excludes the spec const."""
    rows = q.shape[0]
    grid = (rows // block_rows,)
    tile = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    smem = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    kern = _make_potential_vg_kernel(uniform_op)
    gf, lp = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[tile] * 6,
        out_specs=(tile, smem),
        out_shape=(
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((SUB, LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_potential_vg",
    )(q, op, c0, c1, c2, c3)
    return gf, lp[0, 0]
