"""Pallas TPU kernels fusing elementwise log-density + reduction in VMEM.

The hot loop of the paper's Table-1 benchmarks is a vectorised tilde
statement: ``x .~ Normal(mu, sigma)`` lowers to an elementwise logpdf
followed by a full-sum reduce, executed 4 leapfrog x 2000 iterations per
chain. Unfused, XLA materialises the logpdf vector in HBM between the two
stages; these kernels keep the elementwise values in VREGs and reduce into
a VMEM accumulator tile, writing ONE scalar per grid pass — the memory
traffic drops from 3N reads/writes to N reads.

Layout: inputs are flattened and padded to (R, 128) tiles whose geometry
``ops.tile_geometry`` derives from the true length n (static at trace
time): ceil(n / 128) rows rounded up to a multiple of 8, cut into
g = ceil(rows / block_rows) row-blocks of equal height (a multiple of 8,
block_rows = 256 by default), so a short input is one block of its own
size and padding stays under 8 rows per block. The grid walks the
row-blocks sequentially, accumulating partial sums in a VMEM (8, 128)
accumulator that is reduced to the (1, 1) output on the last step.
Padding is masked with an iota test against n.

Where the kernels run: ``ops.site_block_sum`` launches them on TPU only
for blocks longer than one (8, 128) f32 tile, ``ops.ONE_TILE`` = 1,024
elements per call (``ops.pallas_pays``). A block that fits one tile has
nothing to stream, while a launch costs a fixed time per grid step (one
step per chain under ``vmap``) and its custom call keeps XLA from fusing
the pad to tiles and the sum with the ops that produce the block; such
blocks go to the ``ref.py`` oracle, which XLA fuses. ``use_pallas=True``
still forces a kernel at any length.

Three variants cover the paper's benchmark suite:
  normal:          x ~ Normal(mu, sigma)            (gaussian_10k, gdemo, ...)
  bernoulli_logit: y ~ BernoulliLogits(l)           (logreg)
  categorical:     y ~ CategoricalLogits(logits)    (naive bayes, HMM, LDA)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUB = 8
_HALF_LOG_2PI = 0.5 * math.log(2.0 * math.pi)


def _mask_block(i, block_rows, n_valid):
    """(block_rows, LANE) bool mask of in-range elements for row-block i."""
    row0 = i * block_rows
    rr = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANE), 0)
    cc = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANE), 1)
    flat = (row0 + rr) * LANE + cc
    return flat < n_valid


# ---------------------------------------------------------------------------
# standard Normal — pre-standardised z = (x - mu) / sigma (see ops.py).
# Streams ONE array instead of three: the log|sigma| term is accumulated
# analytically outside, so the kernel only reduces -z^2/2 - log(2 pi)/2.
# ---------------------------------------------------------------------------
def _std_normal_kernel(z_ref, o_ref, acc_ref, *, n_valid: int):
    i = pl.program_id(0)
    ni = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    z = z_ref[...].astype(jnp.float32)
    lp = -0.5 * z * z - _HALF_LOG_2PI
    lp = jnp.where(_mask_block(i, z.shape[0], n_valid), lp, 0.0)
    acc_ref[...] += jnp.sum(lp.reshape(-1, SUB, LANE), axis=0)

    @pl.when(i == ni - 1)
    def _fin():
        o_ref[0, 0] = jnp.sum(acc_ref[...])


# ---------------------------------------------------------------------------
# Normal(mu, sigma) — elementwise params (pre-broadcast by ops.py)
# ---------------------------------------------------------------------------
def _normal_kernel(x_ref, mu_ref, sig_ref, o_ref, acc_ref, *, n_valid: int):
    i = pl.program_id(0)
    ni = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)
    sig = sig_ref[...].astype(jnp.float32)
    z = (x - mu) / sig
    lp = -0.5 * z * z - jnp.log(sig) - _HALF_LOG_2PI
    lp = jnp.where(_mask_block(i, x.shape[0], n_valid), lp, 0.0)
    # per-lane partial sums into the (SUB, LANE) accumulator tile
    acc_ref[...] += jnp.sum(lp.reshape(-1, SUB, LANE), axis=0)

    @pl.when(i == ni - 1)
    def _fin():
        o_ref[0, 0] = jnp.sum(acc_ref[...])


def _bernoulli_logit_kernel(l_ref, y_ref, o_ref, acc_ref, *, n_valid: int):
    i = pl.program_id(0)
    ni = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    logit = l_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    # y*log sig(l) + (1-y)*log sig(-l) = -softplus(-l) - (1-y)*l  (stable)
    lp = -jnp.logaddexp(0.0, -logit) - (1.0 - y) * logit
    lp = jnp.where(_mask_block(i, logit.shape[0], n_valid), lp, 0.0)
    acc_ref[...] += jnp.sum(lp.reshape(-1, SUB, LANE), axis=0)

    @pl.when(i == ni - 1)
    def _fin():
        o_ref[0, 0] = jnp.sum(acc_ref[...])


# ---------------------------------------------------------------------------
# Categorical cross-entropy: logits (N, C), labels (N,)
# ---------------------------------------------------------------------------
def _categorical_kernel(l_ref, y_ref, o_ref, acc_ref, *, n_valid: int,
                        c_valid: int):
    i = pl.program_id(0)
    ni = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    logits = l_ref[...].astype(jnp.float32)        # (bn, Cp)
    y = y_ref[...]                                 # (bn, 1) int32
    bn, cp = logits.shape
    cc = jax.lax.broadcasted_iota(jnp.int32, (bn, cp), 1)
    cmask = cc < c_valid
    logits = jnp.where(cmask, logits, -1e30)
    m = jnp.max(logits, axis=1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=1, keepdims=True)) + m
    picked = jnp.sum(jnp.where(cc == y, logits, 0.0), axis=1, keepdims=True)
    lp = picked - lse                              # (bn, 1)
    rr = jax.lax.broadcasted_iota(jnp.int32, (bn, 1), 0)
    lp = jnp.where(rr + i * bn < n_valid, lp, 0.0)
    acc_ref[...] += jnp.sum(lp.reshape(-1, SUB, 1), axis=0)

    @pl.when(i == ni - 1)
    def _fin():
        o_ref[0, 0] = jnp.sum(acc_ref[...])


# ---------------------------------------------------------------------------
# Gamma / Beta / Student-t: elementwise reduce kernels over the streamed
# (unnormalised) terms. gammaln has no Mosaic lowering, so the analytic
# normalisers are accumulated OUTSIDE the kernel by the fused evaluators —
# the same split std_normal uses for -sum(log scale).
# ---------------------------------------------------------------------------
def _gamma_kernel(x_ref, am1_ref, rate_ref, o_ref, acc_ref, *, n_valid: int):
    i = pl.program_id(0)
    ni = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    am1 = am1_ref[...].astype(jnp.float32)
    rate = rate_ref[...].astype(jnp.float32)
    lp = am1 * jnp.log(x) - rate * x
    lp = jnp.where(_mask_block(i, x.shape[0], n_valid), lp, 0.0)
    acc_ref[...] += jnp.sum(lp.reshape(-1, SUB, LANE), axis=0)

    @pl.when(i == ni - 1)
    def _fin():
        o_ref[0, 0] = jnp.sum(acc_ref[...])


def _beta_kernel(x_ref, am1_ref, bm1_ref, o_ref, acc_ref, *, n_valid: int):
    i = pl.program_id(0)
    ni = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    am1 = am1_ref[...].astype(jnp.float32)
    bm1 = bm1_ref[...].astype(jnp.float32)
    lp = am1 * jnp.log(x) + bm1 * jnp.log1p(-x)
    lp = jnp.where(_mask_block(i, x.shape[0], n_valid), lp, 0.0)
    acc_ref[...] += jnp.sum(lp.reshape(-1, SUB, LANE), axis=0)

    @pl.when(i == ni - 1)
    def _fin():
        o_ref[0, 0] = jnp.sum(acc_ref[...])


def _student_t_kernel(z_ref, df_ref, o_ref, acc_ref, *, n_valid: int):
    i = pl.program_id(0)
    ni = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    z = z_ref[...].astype(jnp.float32)
    df = df_ref[...].astype(jnp.float32)
    lp = -0.5 * (df + 1.0) * jnp.log1p(z * z / df)
    lp = jnp.where(_mask_block(i, z.shape[0], n_valid), lp, 0.0)
    acc_ref[...] += jnp.sum(lp.reshape(-1, SUB, LANE), axis=0)

    @pl.when(i == ni - 1)
    def _fin():
        o_ref[0, 0] = jnp.sum(acc_ref[...])


# ---------------------------------------------------------------------------
# Dense MvNormal quadratic form: xc (N, D) rows against one precision P
# (D, D), flash-attention-style — the xc row-block stays VMEM-resident
# while the grid streams P column-blocks through the MXU; only the scalar
# leaves the kernel. The matching xc column block arrives through its own
# BlockSpec: Mosaic has no lowering for a dynamic slice of a loaded value.
# Zero-padding of xc/P makes padded rows/cols contribute exactly 0, so no
# masks are needed.
# ---------------------------------------------------------------------------
def _mvn_quad_kernel(x_ref, xj_ref, p_ref, o_ref, acc_ref):
    i = pl.program_id(0)
    j = pl.program_id(1)
    ni = pl.num_programs(0)
    nj = pl.num_programs(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xc = x_ref[...].astype(jnp.float32)            # (bn, Dp) full rows
    pj = p_ref[...].astype(jnp.float32)            # (Dp, bc) column block
    t = jnp.dot(xc, pj, preferred_element_type=jnp.float32)  # (bn, bc) MXU
    part = t * xj_ref[...].astype(jnp.float32)      # (bn, bc)
    acc_ref[...] += jnp.sum(part.reshape(-1, SUB, LANE), axis=0)

    @pl.when((i == ni - 1) & (j == nj - 1))
    def _fin():
        o_ref[0, 0] = -0.5 * jnp.sum(acc_ref[...])


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------
def _reduce_call(kernel, n_inputs: int, rows: int, block_rows: int,
                 lanes: int, acc_shape, dtypes, interpret: bool, name: str):
    grid = (rows // block_rows,)
    in_specs = [pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
                for _ in range(n_inputs)]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM(acc_shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )


def std_normal_sum_2d(z, n_valid: int, block_rows: int, interpret: bool):
    rows = z.shape[0]
    kern = functools.partial(_std_normal_kernel, n_valid=n_valid)
    call = _reduce_call(kern, 1, rows, block_rows, LANE, (SUB, LANE),
                        None, interpret, "fused_std_normal_logpdf")
    return call(z)[0, 0]


def normal_sum_2d(x, mu, sig, n_valid: int, block_rows: int,
                  interpret: bool):
    rows = x.shape[0]
    kern = functools.partial(_normal_kernel, n_valid=n_valid)
    call = _reduce_call(kern, 3, rows, block_rows, LANE, (SUB, LANE),
                        None, interpret, "fused_normal_logpdf")
    return call(x, mu, sig)[0, 0]


def bernoulli_logit_sum_2d(logits, y, n_valid: int, block_rows: int,
                           interpret: bool):
    rows = logits.shape[0]
    kern = functools.partial(_bernoulli_logit_kernel, n_valid=n_valid)
    call = _reduce_call(kern, 2, rows, block_rows, LANE, (SUB, LANE),
                        None, interpret, "fused_bernoulli_logpdf")
    return call(logits, y)[0, 0]


def gamma_sum_2d(x, am1, rate, n_valid: int, block_rows: int,
                 interpret: bool):
    rows = x.shape[0]
    kern = functools.partial(_gamma_kernel, n_valid=n_valid)
    call = _reduce_call(kern, 3, rows, block_rows, LANE, (SUB, LANE),
                        None, interpret, "fused_gamma_logpdf")
    return call(x, am1, rate)[0, 0]


def beta_sum_2d(x, am1, bm1, n_valid: int, block_rows: int,
                interpret: bool):
    rows = x.shape[0]
    kern = functools.partial(_beta_kernel, n_valid=n_valid)
    call = _reduce_call(kern, 3, rows, block_rows, LANE, (SUB, LANE),
                        None, interpret, "fused_beta_logpdf")
    return call(x, am1, bm1)[0, 0]


def student_t_sum_2d(z, df, n_valid: int, block_rows: int,
                     interpret: bool):
    rows = z.shape[0]
    kern = functools.partial(_student_t_kernel, n_valid=n_valid)
    call = _reduce_call(kern, 2, rows, block_rows, LANE, (SUB, LANE),
                        None, interpret, "fused_student_t_logpdf")
    return call(z, df)[0, 0]


def mvn_quad_sum_2d(xc, prec, block_rows: int, block_cols: int,
                    interpret: bool):
    """xc (Np, Dp), prec (Dp, Dp) — both zero-padded to tile multiples."""
    np_, dp = xc.shape
    grid = (np_ // block_rows, dp // block_cols)
    return pl.pallas_call(
        _mvn_quad_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
            pl.BlockSpec((dp, block_cols), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i, j: (0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((SUB, LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="fused_mvn_quadform",
    )(xc, xc, prec)[0, 0]


def categorical_sum_2d(logits, labels, n_valid: int, c_valid: int,
                       block_rows: int, interpret: bool):
    rows, cp = logits.shape
    grid = (rows // block_rows,)
    kern = functools.partial(_categorical_kernel, n_valid=n_valid,
                             c_valid=c_valid)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, cp), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1), lambda i: (0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((SUB, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_categorical_logpdf",
    )(logits, labels)[0, 0]
