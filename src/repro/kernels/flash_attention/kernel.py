"""Pallas TPU flash-attention kernel (GQA, causal, sliding-window, softcap).

Blockwise online-softmax attention. The grid is (BH, nq, nk) with the
kv-block axis innermost and SEQUENTIAL ("arbitrary" dimension semantics):
the running max / sum / accumulator for one (head, q-block) live in VMEM
scratch across the nk iterations — the canonical TPU flash schedule
(HBM->VMEM streaming of K/V tiles; the MXU sees (block_q x hd) @
(hd x block_k) and (block_q x block_k) @ (block_k x hd) matmuls).

Masking is POSITION-BASED: q/kv positions arrive as arrays, so the same
kernel serves training (positions = arange), prefill, ring-buffer decode
(positions permuted by the ring layout) and padded caches (kv validity
mask). Blocks that are provably fully-masked (causal: min kv pos > max q
pos; window: max kv pos <= min q pos - window) are SKIPPED dynamically
with ``pl.when`` — the dominant saving for causal training, ~2x.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30  # finite: keeps exp()/max() NaN-free for fully-masked rows


def _flash_kernel(qpos_ref, kpos_ref, kvalid_ref, q_ref, k_ref, v_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, scale: float,
                  causal: bool, window: Optional[int], cap: Optional[float]):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qp = qpos_ref[0, :].astype(jnp.int32)      # (bq,)
    kp = kpos_ref[0, :].astype(jnp.int32)      # (bk,)
    ok = kvalid_ref[0, :] > 0                  # (bk,) bool

    # --- dynamic block-skip predicates (positions are runtime values) ------
    compute = jnp.any(ok)
    if causal:
        # fully masked iff every kv pos in the block is beyond every q pos
        compute = jnp.logical_and(compute, jnp.min(kp) <= jnp.max(qp))
    if window is not None:
        # fully masked iff min_i(qp_i) - max_j(valid kp_j) >= window
        # (padded q rows carry qp = -2^30: conservative, never skips early)
        kp_val = jnp.where(ok, kp.astype(jnp.float32), NEG_INF)
        compute = jnp.logical_and(
            compute,
            jnp.max(kp_val) > (jnp.min(qp) - window).astype(jnp.float32))

    @pl.when(compute)
    def _block():
        q = q_ref[0].astype(jnp.float32)       # (bq, hd)
        k = k_ref[0].astype(jnp.float32)       # (bk, hd)
        v = v_ref[0].astype(jnp.float32)       # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (bq, bk)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)
        mask = jnp.broadcast_to(ok[None, :], s.shape)
        if causal:
            mask = jnp.logical_and(mask, kp[None, :] <= qp[:, None])
        if window is not None:
            mask = jnp.logical_and(mask, qp[:, None] - kp[None, :] < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                                  # (bq,)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)                       # (bq,)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(mask, p, 0.0)   # robust when a whole row is masked
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_ref[:, 0] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, 0]
        l = jnp.where(l == 0.0, 1.0, l)        # fully-masked rows -> 0 output
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhd(q, k, v, q_positions, kv_positions, kv_valid, *,
                        group: int, n_q_heads_per_batch: int,
                        causal: bool, window: Optional[int],
                        cap: Optional[float], block_q: int, block_k: int,
                        interpret: bool = False):
    """Core pallas_call. q: (BH, Sq, hd) with BH = B*KV*G (head-major per
    batch); k, v: (BKV, Sk, hd) with BKV = B*KV; positions (B, S*)."""
    BH, Sq, hd = q.shape
    _, Sk, _ = k.shape
    scale = 1.0 / (hd ** 0.5)
    nq = Sq // block_q
    nk = Sk // block_k
    grid = (BH, nq, nk)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, cap=cap)
    hpb = n_q_heads_per_batch

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q), lambda bh, iq, ik: (bh // hpb, iq)),
            pl.BlockSpec((1, block_k), lambda bh, iq, ik: (bh // hpb, ik)),
            pl.BlockSpec((1, block_k), lambda bh, iq, ik: (bh // hpb, ik)),
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda bh, iq, ik: (bh // group, ik, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda bh, iq, ik: (bh // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_gqa",
    )(q_positions, kv_positions, kv_valid, q, k, v)
