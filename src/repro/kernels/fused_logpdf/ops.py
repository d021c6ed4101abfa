"""jit'd wrappers: flatten/pad/broadcast, then call the fused reduce kernels.

Two call surfaces:

* ``normal_logpdf_sum`` / ``bernoulli_logits_logpmf_sum`` /
  ``categorical_logits_logpmf_sum`` — one fused VMEM reduce per array
  (the original per-distribution entry points).
* ``site_block_sum`` — the flat-buffer log-joint hot path: ALL same-family
  tilde sites of one model evaluation, pre-flattened into segments by the
  fused evaluators, summed in a single launch. On TPU this is the Pallas
  kernel; elsewhere it falls back to the pure-jnp oracle in ``ref.py``
  (mathematically identical, still one fused XLA reduction over the
  concatenated block).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.fused_logpdf import kernel as K
from repro.kernels.fused_logpdf import ref

__all__ = ["normal_logpdf_sum", "std_normal_logpdf_sum", "tile_geometry",
           "bernoulli_logits_logpmf_sum", "categorical_logits_logpmf_sum",
           "gamma_unnorm_logpdf_sum", "beta_unnorm_logpdf_sum",
           "student_t_unnorm_logpdf_sum", "mvnormal_prec_quadform_sum",
           "site_block_sum", "all_reduce_block_sum", "SITE_BLOCK_FAMILIES"]


def all_reduce_block_sum(total: jax.Array, axis_name=None) -> jax.Array:
    """All-reduce seam between the fused block reductions and the mesh.

    ``site_block_sum`` reduces each family's site blocks to one scalar
    per device; when those blocks were cut from data sharded over a mesh
    axis (``repro.sharding.data_parallel``), the device-local partial
    sums are combined here with ONE ``psum`` over ``axis_name``. With no
    axis name this is the identity, so single-device callers pay
    nothing. Kept next to the kernels because this is where a fused
    cross-device reduction (reduce-scatter into the block kernels) would
    slot in; today it is a single collective over the already-reduced
    scalars, which is optimal for scalar log-densities.
    """
    if axis_name is None:
        return total
    return jax.lax.psum(total, axis_name)


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def tile_geometry(n: int, block_rows: int,
                  per_row: int = K.LANE) -> Tuple[int, int]:
    """Row geometry ``(rows, br)`` of a fused reduction over ``n`` elements.

    The elements fill ``ceil(n / per_row)`` rows, rounded up to a whole
    f32 ``(8, 128)`` tile. Those rows are cut into ``g = ceil(rows /
    block_rows)`` grid steps of ``br`` rows each, ``br`` a multiple of 8
    no larger than ``block_rows``, and padded to ``rows = g * br``. A short
    input is one block of its own size, a long one keeps blocks of up to
    ``block_rows`` rows, and the padding stays under 8 rows per block.
    ``n`` is static (a shape), so the geometry is fixed at trace time.
    """
    sub = K.SUB
    rows = max(sub, -(-n // per_row))
    rows = -(-rows // sub) * sub
    g = -(-rows // max(sub, block_rows // sub * sub))
    br = -(-rows // (g * sub)) * sub
    return g * br, br


def _to_tiles(x, rows: int, pad_value: float = 0.0):
    """Flatten to 1-D and pad to ``(rows, 128)``, rows from ``tile_geometry``.

    ``pad_value`` picks the fill so padding slots stay finite through the
    kernel's elementwise math (e.g. 1.0 for a log() input) — padded lanes
    are masked out of the reduction regardless.
    """
    flat = jnp.ravel(x)
    flat = jnp.pad(flat, (0, rows * K.LANE - flat.shape[0]),
                   constant_values=pad_value)
    return flat.reshape(rows, K.LANE)


def std_normal_logpdf_sum(z, *, block_rows: int = 256,
                          interpret: Optional[bool] = None):
    """``sum(StdNormal.log_prob(z))`` as one fused single-input reduce.

    The flat-buffer log-joint standardises every Normal site to
    ``z = (x - loc) / scale`` before fusing, accumulating the
    ``-sum(log scale)`` Jacobian term analytically — so this kernel
    streams ONE array (N reads) where ``normal_logpdf_sum`` streams three.

    Parameters
    ----------
    z : jax.Array, any shape
        Standardised values; flattened to 1-D and padded to
        ``(rows, 128)`` tiles.

    Returns
    -------
    jax.Array, scalar float32
        ``sum(-z^2 / 2 - log(2 pi) / 2)``. Differentiable
        (analytic custom_vjp: ``dz = -z * g``).
    """
    if interpret is None:
        interpret = _auto_interpret()
    z = jnp.asarray(z, jnp.float32)
    return _std_normal_sum_vjp(z, block_rows, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _std_normal_sum_vjp(z, block_rows, interpret):
    return _std_normal_sum_impl(z, block_rows=block_rows,
                                interpret=interpret)


def _std_normal_sum_fwd(z, block_rows, interpret):
    out = _std_normal_sum_impl(z, block_rows=block_rows,
                               interpret=interpret)
    return out, z


def _std_normal_sum_bwd(block_rows, interpret, z, g):
    return (g * (-z),)


_std_normal_sum_vjp.defvjp(_std_normal_sum_fwd, _std_normal_sum_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _std_normal_sum_impl(z, *, block_rows: int, interpret: bool):
    n = z.size
    rows, br = tile_geometry(n, block_rows)
    return K.std_normal_sum_2d(_to_tiles(z, rows), n, br, interpret)


def normal_logpdf_sum(x, loc, scale, *, block_rows: int = 256,
                      interpret: Optional[bool] = None):
    """``sum(Normal(loc, scale).log_prob(x))`` as one fused VMEM reduce.

    Parameters
    ----------
    x : jax.Array, any shape
        Values; flattened to 1-D and padded to ``(rows, 128)`` tiles.
    loc, scale : jax.Array
        Broadcastable against ``x`` (scalars and full arrays both fine).
    block_rows : int
        Grid row-block size (tile rows reduced per grid step).
    interpret : bool, optional
        Run the Pallas kernel in interpret mode (default: auto — on
        whenever the backend is not TPU).

    Returns
    -------
    jax.Array, scalar float32
        The summed log-density. Differentiable: analytic custom_vjp
        (elementwise; XLA fuses it), with broadcast handled outside so
        scalar params get summed cotangents.
    """
    if interpret is None:
        interpret = _auto_interpret()
    x = jnp.asarray(x, jnp.float32)
    mu = jnp.broadcast_to(jnp.asarray(loc, jnp.float32), x.shape)
    sig = jnp.broadcast_to(jnp.asarray(scale, jnp.float32), x.shape)
    return _normal_sum_vjp(x, mu, sig, block_rows, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _normal_sum_vjp(x, mu, sig, block_rows, interpret):
    return _normal_sum_impl(x, mu, sig, block_rows=block_rows,
                            interpret=interpret)


def _normal_sum_fwd(x, mu, sig, block_rows, interpret):
    out = _normal_sum_impl(x, mu, sig, block_rows=block_rows,
                           interpret=interpret)
    return out, (x, mu, sig)


def _normal_sum_bwd(block_rows, interpret, res, g):
    x, mu, sig = res
    z = (x - mu) / sig
    dx = g * (-z / sig)
    dmu = g * (z / sig)
    dsig = g * ((z * z - 1.0) / sig)
    return dx, dmu, dsig


_normal_sum_vjp.defvjp(_normal_sum_fwd, _normal_sum_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _normal_sum_impl(x, mu, sig, *, block_rows: int, interpret: bool):
    n = x.size
    rows, br = tile_geometry(n, block_rows)
    # pad sigma with 1s: log(sig)=0 on padding (masked anyway; avoids log 0)
    sig2 = _to_tiles(sig - 1.0, rows) + 1.0
    return K.normal_sum_2d(_to_tiles(x, rows), _to_tiles(mu, rows), sig2,
                           n, br, interpret)


def bernoulli_logits_logpmf_sum(logits, y, *, block_rows: int = 256,
                                interpret: Optional[bool] = None):
    """``sum(y * logsig(l) + (1 - y) * logsig(-l))`` as one fused reduce.

    Parameters
    ----------
    logits : jax.Array, any shape
        Bernoulli logits ``l``; flattened/padded like ``normal_logpdf_sum``.
    y : jax.Array
        0/1 observations, broadcastable against ``logits``.

    Returns
    -------
    jax.Array, scalar float32
        Summed log-pmf. Differentiable in ``logits`` (analytic:
        ``y - sigmoid(l)``) and ``y`` (cotangent ``l``).
    """
    if interpret is None:
        interpret = _auto_interpret()
    logits = jnp.asarray(logits, jnp.float32)
    y = jnp.broadcast_to(jnp.asarray(y, jnp.float32), logits.shape)
    return _bern_sum_vjp(logits, y, block_rows, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _bern_sum_vjp(logits, y, block_rows, interpret):
    return _bern_sum_impl(logits, y, block_rows=block_rows,
                          interpret=interpret)


def _bern_sum_fwd(logits, y, block_rows, interpret):
    out = _bern_sum_impl(logits, y, block_rows=block_rows,
                         interpret=interpret)
    return out, (logits, y)


def _bern_sum_bwd(block_rows, interpret, res, g):
    logits, y = res
    dl = g * (y - jax.nn.sigmoid(logits))
    dy = g * logits
    return dl, dy


_bern_sum_vjp.defvjp(_bern_sum_fwd, _bern_sum_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _bern_sum_impl(logits, y, *, block_rows: int, interpret: bool):
    n = logits.size
    rows, br = tile_geometry(n, block_rows)
    return K.bernoulli_logit_sum_2d(_to_tiles(logits, rows),
                                    _to_tiles(y, rows), n, br, interpret)


def categorical_logits_logpmf_sum(logits, labels, *, block_rows: int = 128,
                                  interpret: Optional[bool] = None):
    """``sum(log softmax(logits)[labels])`` as one fused reduce.

    Parameters
    ----------
    logits : jax.Array, shape ``(..., C)``
        Unnormalised class scores; reshaped to ``(N, C)`` and padded to
        lane multiples.
    labels : jax.Array, shape ``(...)``, int
        Class indices in ``[0, C)``; leading shape must match ``logits``.

    Returns
    -------
    jax.Array, scalar float32
        Summed log-pmf. Differentiable in ``logits``
        (``onehot(labels) - softmax(logits)``); labels get a float0
        cotangent.
    """
    if interpret is None:
        interpret = _auto_interpret()
    C = logits.shape[-1]
    logits2 = jnp.asarray(logits, jnp.float32).reshape(-1, C)
    labels2 = jnp.asarray(labels, jnp.int32).reshape(-1)
    return _cat_sum_vjp(logits2, labels2, block_rows, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _cat_sum_vjp(logits, labels, block_rows, interpret):
    return _cat_sum_impl(logits, labels, block_rows=block_rows,
                         interpret=interpret)


def _cat_sum_fwd(logits, labels, block_rows, interpret):
    out = _cat_sum_impl(logits, labels, block_rows=block_rows,
                        interpret=interpret)
    return out, (logits, labels)


def _cat_sum_bwd(block_rows, interpret, res, g):
    import numpy as np
    logits, labels = res
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    dl = g * (onehot - jax.nn.softmax(logits, axis=-1))
    dlab = np.zeros(labels.shape, dtype=jax.dtypes.float0)
    return dl, dlab


_cat_sum_vjp.defvjp(_cat_sum_fwd, _cat_sum_bwd)


# ---------------------------------------------------------------------------
# Gamma — streamed part sum((a-1) log x - b x); normaliser with the caller
# ---------------------------------------------------------------------------
def gamma_unnorm_logpdf_sum(x, am1, rate, *, block_rows: int = 256,
                            interpret: Optional[bool] = None):
    """``sum(am1 * log(x) - rate * x)`` as one fused VMEM reduce.

    The Gamma normaliser ``a log b - gammaln(a)`` has no Pallas lowering
    and is accumulated analytically by the fused evaluator; this kernel
    streams only the x-dependent terms. All three inputs must share one
    shape (pre-broadcast by the caller). Differentiable (analytic
    custom_vjp): ``dx = am1/x - rate``, ``dam1 = log x``, ``drate = -x``.
    """
    if interpret is None:
        interpret = _auto_interpret()
    x = jnp.asarray(x, jnp.float32)
    am1 = jnp.broadcast_to(jnp.asarray(am1, jnp.float32), x.shape)
    rate = jnp.broadcast_to(jnp.asarray(rate, jnp.float32), x.shape)
    return _gamma_sum_vjp(x, am1, rate, block_rows, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gamma_sum_vjp(x, am1, rate, block_rows, interpret):
    return _gamma_sum_impl(x, am1, rate, block_rows=block_rows,
                           interpret=interpret)


def _gamma_sum_fwd(x, am1, rate, block_rows, interpret):
    out = _gamma_sum_impl(x, am1, rate, block_rows=block_rows,
                          interpret=interpret)
    return out, (x, am1, rate)


def _gamma_sum_bwd(block_rows, interpret, res, g):
    x, am1, rate = res
    return g * (am1 / x - rate), g * jnp.log(x), g * (-x)


_gamma_sum_vjp.defvjp(_gamma_sum_fwd, _gamma_sum_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _gamma_sum_impl(x, am1, rate, *, block_rows: int, interpret: bool):
    n = x.size
    rows, br = tile_geometry(n, block_rows)
    # pad x with 1s: log(1)=0 keeps the padded lanes NaN-free
    return K.gamma_sum_2d(_to_tiles(x, rows, pad_value=1.0),
                          _to_tiles(am1, rows), _to_tiles(rate, rows),
                          n, br, interpret)


# ---------------------------------------------------------------------------
# Beta — streamed part sum((a-1) log x + (b-1) log1p(-x))
# ---------------------------------------------------------------------------
def beta_unnorm_logpdf_sum(x, am1, bm1, *, block_rows: int = 256,
                           interpret: Optional[bool] = None):
    """``sum(am1 * log(x) + bm1 * log1p(-x))`` as one fused VMEM reduce.

    The log-beta-function normaliser is the caller's business (no gammaln
    in Pallas). ``x`` must lie strictly inside (0, 1). Differentiable
    (analytic custom_vjp): ``dx = am1/x - bm1/(1-x)``.
    """
    if interpret is None:
        interpret = _auto_interpret()
    x = jnp.asarray(x, jnp.float32)
    am1 = jnp.broadcast_to(jnp.asarray(am1, jnp.float32), x.shape)
    bm1 = jnp.broadcast_to(jnp.asarray(bm1, jnp.float32), x.shape)
    return _beta_sum_vjp(x, am1, bm1, block_rows, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _beta_sum_vjp(x, am1, bm1, block_rows, interpret):
    return _beta_sum_impl(x, am1, bm1, block_rows=block_rows,
                          interpret=interpret)


def _beta_sum_fwd(x, am1, bm1, block_rows, interpret):
    out = _beta_sum_impl(x, am1, bm1, block_rows=block_rows,
                         interpret=interpret)
    return out, (x, am1, bm1)


def _beta_sum_bwd(block_rows, interpret, res, g):
    x, am1, bm1 = res
    return (g * (am1 / x - bm1 / (1.0 - x)),
            g * jnp.log(x), g * jnp.log1p(-x))


_beta_sum_vjp.defvjp(_beta_sum_fwd, _beta_sum_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _beta_sum_impl(x, am1, bm1, *, block_rows: int, interpret: bool):
    n = x.size
    rows, br = tile_geometry(n, block_rows)
    # pad x with 0.5: both log(x) and log1p(-x) stay finite on padding
    return K.beta_sum_2d(_to_tiles(x, rows, pad_value=0.5),
                         _to_tiles(am1, rows), _to_tiles(bm1, rows),
                         n, br, interpret)


# ---------------------------------------------------------------------------
# Student-t — streamed part sum(-(df+1)/2 log1p(z^2/df)) on standardised z
# ---------------------------------------------------------------------------
def student_t_unnorm_logpdf_sum(z, df, *, block_rows: int = 256,
                                interpret: Optional[bool] = None):
    """``sum(-(df+1)/2 * log1p(z^2/df))`` as one fused VMEM reduce.

    ``z = (x - loc)/scale`` is standardised by the caller (like
    ``std_normal``); the gammaln / ``-log scale`` normaliser is accumulated
    analytically outside. Differentiable (analytic custom_vjp):
    ``dz = -(df+1) z / (df + z^2)``.
    """
    if interpret is None:
        interpret = _auto_interpret()
    z = jnp.asarray(z, jnp.float32)
    df = jnp.broadcast_to(jnp.asarray(df, jnp.float32), z.shape)
    return _student_t_sum_vjp(z, df, block_rows, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _student_t_sum_vjp(z, df, block_rows, interpret):
    return _student_t_sum_impl(z, df, block_rows=block_rows,
                               interpret=interpret)


def _student_t_sum_fwd(z, df, block_rows, interpret):
    out = _student_t_sum_impl(z, df, block_rows=block_rows,
                              interpret=interpret)
    return out, (z, df)


def _student_t_sum_bwd(block_rows, interpret, res, g):
    z, df = res
    z2 = z * z
    dz = g * (-(df + 1.0) * z / (df + z2))
    ddf = g * (-0.5 * jnp.log1p(z2 / df)
               + 0.5 * (df + 1.0) * z2 / (df * (df + z2)))
    return dz, ddf


_student_t_sum_vjp.defvjp(_student_t_sum_fwd, _student_t_sum_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _student_t_sum_impl(z, df, *, block_rows: int, interpret: bool):
    n = z.size
    rows, br = tile_geometry(n, block_rows)
    # pad df with 1s: log1p(z^2/df) stays finite on padding
    return K.student_t_sum_2d(_to_tiles(z, rows),
                              _to_tiles(df, rows, pad_value=1.0),
                              n, br, interpret)


# ---------------------------------------------------------------------------
# Dense MvNormal quadratic form — flash-style tiled xc @ P reduce
# ---------------------------------------------------------------------------
def mvnormal_prec_quadform_sum(xc, prec, *, block_rows: int = 256,
                               interpret: Optional[bool] = None):
    """``-0.5 * sum_n xc_n^T P xc_n`` as one tiled MXU launch.

    Parameters
    ----------
    xc : jax.Array, shape ``(N, D)``
        Centred observations ``x - loc``, one row per event.
    prec : jax.Array, shape ``(D, D)``
        Dense precision matrix ``P = L^-T L^-1`` (precomputed by the
        caller from the Cholesky factor; assumed symmetric).

    The ``-N (sum log diag L + D/2 log 2 pi)`` normaliser is accumulated
    analytically by the fused evaluator. Differentiable (analytic
    custom_vjp): ``dxc = -0.5 (P + P^T) xc``, ``dP = -0.5 xc^T xc``.
    """
    if interpret is None:
        interpret = _auto_interpret()
    xc = jnp.asarray(xc, jnp.float32)
    prec = jnp.asarray(prec, jnp.float32)
    return _mvn_quad_vjp(xc, prec, block_rows, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mvn_quad_vjp(xc, prec, block_rows, interpret):
    return _mvn_quad_impl(xc, prec, block_rows=block_rows,
                          interpret=interpret)


def _mvn_quad_fwd(xc, prec, block_rows, interpret):
    out = _mvn_quad_impl(xc, prec, block_rows=block_rows,
                         interpret=interpret)
    return out, (xc, prec)


def _mvn_quad_bwd(block_rows, interpret, res, g):
    xc, prec = res
    dxc = (-0.5 * g) * (xc @ (prec + prec.T))
    dprec = (-0.5 * g) * (xc.T @ xc)
    return dxc, dprec


_mvn_quad_vjp.defvjp(_mvn_quad_fwd, _mvn_quad_bwd)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _mvn_quad_impl(xc, prec, *, block_rows: int, interpret: bool):
    n, d = xc.shape
    dp = ((d + K.LANE - 1) // K.LANE) * K.LANE
    n_pad, br = tile_geometry(n, block_rows, per_row=1)
    # zero padding: padded rows/cols contribute exactly 0 to the quadform
    xc2 = jnp.pad(xc, ((0, n_pad - n), (0, dp - d)))
    prec2 = jnp.pad(prec, ((0, dp - d), (0, dp - d)))
    return K.mvn_quad_sum_2d(xc2, prec2, br, K.LANE, interpret)


# ---------------------------------------------------------------------------
# site_block_sum — the flat-buffer log-joint entry point
# ---------------------------------------------------------------------------
SITE_BLOCK_FAMILIES = ("std_normal", "normal", "bernoulli_logits",
                       "categorical_logits", "gamma", "beta", "student_t",
                       "mvnormal_prec")


def site_block_sum(family: str, segments: Sequence[Tuple],
                   *, use_pallas: Optional[bool] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Sum the log-densities of all same-family site segments in ONE launch.

    This is the hot-path primitive behind the fused log-joint backend: the
    fused evaluators gather every fusible tilde site of a model run into
    per-family segment lists, and this function evaluates each family with a
    single kernel launch over the concatenated flat block — per-site Python
    structure never reaches the compiled program.

    Parameters
    ----------
    family : str
        One of ``SITE_BLOCK_FAMILIES``:

        * ``"std_normal"``  — segments ``(z,)``, 1-D standardised values;
          the ``-sum(log scale)`` Jacobian term is the caller's business
          (the fused evaluators accumulate it analytically per site).
        * ``"normal"``      — segments ``(x, loc, scale)``, each 1-D of one
          common length per segment (pre-broadcast by the caller).
        * ``"bernoulli_logits"`` — segments ``(logits, y)``, each 1-D.
        * ``"categorical_logits"`` — segments ``(logits, labels)`` with
          ``logits (N_i, C)`` and ``labels (N_i,)`` int; all segments in one
          call must share ``C``.
        * ``"gamma"``       — segments ``(x, a - 1, rate)``, each 1-D;
          streamed part only (``a log b - gammaln(a)`` stays with the
          caller, like the std_normal Jacobian term).
        * ``"beta"``        — segments ``(x, a - 1, b - 1)``, each 1-D;
          log-beta normaliser stays with the caller.
        * ``"student_t"``   — segments ``(z, df)``, 1-D standardised
          values; gammaln / log-scale normaliser stays with the caller.
        * ``"mvnormal_prec"`` — segments ``(xc (N_i, D), prec (D, D))``;
          each segment keeps its own precision, so segments are evaluated
          per-launch (not concatenated) and summed.
    segments : sequence of tuples of jax.Array
        Per-site flattened parameter/value blocks as above.
    use_pallas : bool, optional
        Force (``True``) or forbid (``False``) the Pallas kernel; default
        auto-selects it on TPU and uses the ``ref.py`` jnp oracle elsewhere
        (interpret-mode Pallas is for validation, not speed).
    interpret : bool, optional
        Passed through to the Pallas wrappers when ``use_pallas``.

    Returns
    -------
    jax.Array, scalar float32
        ``sum_i sum(logpdf(segment_i))``. Differentiable in the segment
        arrays (analytic custom VJPs on the Pallas path, plain jnp on the
        reference path).
    """
    if family not in SITE_BLOCK_FAMILIES:
        raise ValueError(f"unknown site-block family '{family}'; "
                         f"expected one of {SITE_BLOCK_FAMILIES}")
    if not segments:
        return jnp.zeros((), jnp.float32)
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if family == "mvnormal_prec":
        # each segment carries its own precision matrix: one launch per site
        total = jnp.zeros((), jnp.float32)
        for xc, prec in segments:
            if use_pallas:
                total = total + mvnormal_prec_quadform_sum(
                    xc, prec, interpret=interpret)
            else:
                total = total + ref.mvnormal_prec_quadform_sum_ref(xc, prec)
        return total
    if len(segments) == 1:
        cols = segments[0]
    else:
        cols = tuple(jnp.concatenate(parts, axis=0)
                     for parts in zip(*segments))
    if family == "std_normal":
        (z,) = cols
        if use_pallas:
            return std_normal_logpdf_sum(z, interpret=interpret)
        return ref.std_normal_logpdf_sum_ref(z)
    if family == "normal":
        x, mu, sig = cols
        if use_pallas:
            return normal_logpdf_sum(x, mu, sig, interpret=interpret)
        return ref.normal_logpdf_sum_ref(x, mu, sig)
    if family == "bernoulli_logits":
        logits, y = cols
        if use_pallas:
            return bernoulli_logits_logpmf_sum(logits, y, interpret=interpret)
        return ref.bernoulli_logits_logpmf_sum_ref(logits, y)
    if family == "gamma":
        x, am1, rate = cols
        if use_pallas:
            return gamma_unnorm_logpdf_sum(x, am1, rate, interpret=interpret)
        return ref.gamma_unnorm_logpdf_sum_ref(x, am1, rate)
    if family == "beta":
        x, am1, bm1 = cols
        if use_pallas:
            return beta_unnorm_logpdf_sum(x, am1, bm1, interpret=interpret)
        return ref.beta_unnorm_logpdf_sum_ref(x, am1, bm1)
    if family == "student_t":
        z, df = cols
        if use_pallas:
            return student_t_unnorm_logpdf_sum(z, df, interpret=interpret)
        return ref.student_t_unnorm_logpdf_sum_ref(z, df)
    logits, labels = cols
    if use_pallas:
        return categorical_logits_logpmf_sum(logits, labels,
                                             interpret=interpret)
    return ref.categorical_logits_logpmf_sum_ref(logits, labels)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _cat_sum_impl(logits, labels, *, block_rows: int, interpret: bool):
    n, C = logits.shape
    labels = labels.reshape(-1, 1)
    cp = ((C + K.LANE - 1) // K.LANE) * K.LANE
    n_pad, br = tile_geometry(n, block_rows, per_row=1)
    logits = jnp.pad(logits, ((0, n_pad - n), (0, cp - C)))
    labels = jnp.pad(labels, ((0, n_pad - n), (0, 0)))
    return K.categorical_sum_2d(logits, labels, n, C, br, interpret)
