"""Data-parallel fused log-density: shard tall data, psum the likelihood.

For a linked trace the fused log-joint decomposes exactly as

    density(q) = prior(q) + likelihood(q)
               = PriorContext logp  (param sites + log|det J|)
               + LikelihoodContext logp  (observe sites)

and the likelihood is a sum over observations — so partitioning every
tall observed array along its leading axis over the mesh ``data`` axis
and all-reducing the per-shard likelihood with one ``psum``
(:func:`repro.kernels.fused_logpdf.ops.all_reduce_block_sum`) reproduces
the unsharded density bit-for-bit up to float summation order. Each
device traces the SAME fused evaluator over its shard, so
``FusedEvaluator`` block gathering and the kernel launches are unchanged
— one compiled program per device, collective at the end.

Correctness contract (validated where cheap, documented where not):

* every ``shard_sites`` array must have the observation axis leading and
  divisible by the shard count (:func:`shard_slices` checks);
* every likelihood-context site of the model must depend on the sharded
  data (a likelihood term that ignores the data — e.g. a bare
  ``factor`` — would be summed once PER SHARD by the psum).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from repro.core.contexts import LikelihoodContext, PriorContext

__all__ = ["make_sharded_logdensity", "shard_slices", "sharded_arrays"]


def shard_slices(model, shard_sites: Tuple[str, ...],
                 num_shards: int) -> Dict[str, Tuple[int, int]]:
    """Validate shardability; return {site: (total_rows, rows_per_shard)}.

    Raises with the offending site named when a site is not bound, not
    an array, or has a leading dim not divisible by ``num_shards``.
    """
    out = {}
    for site in shard_sites:
        if site not in model.data:
            raise ValueError(
                f"shard site '{site}' is not bound data of model "
                f"'{model.name}' (bound: {sorted(model.data)})")
        arr = np.asarray(model.data[site])
        if arr.ndim < 1:
            raise ValueError(
                f"shard site '{site}' is a scalar; data sharding "
                "partitions the leading (observation) axis")
        if arr.shape[0] % num_shards != 0:
            raise ValueError(
                f"shard site '{site}' has leading dim {arr.shape[0]}, not "
                f"divisible by {num_shards} data shards; pad or rebatch")
        out[site] = (int(arr.shape[0]), int(arr.shape[0]) // num_shards)
    return out


def sharded_arrays(model, plan):
    """The plan's shard-site arrays, device_put along the data axis.

    Placing the inputs once up front (rather than letting jit move full
    replicas) is what keeps per-device memory at ``rows/num_shards``.
    """
    import jax
    shard_slices(model, plan.shard_sites, plan.num_data_shards)
    sh = plan.data_sharding()
    return tuple(jax.device_put(np.asarray(model.data[s]), sh)
                 for s in plan.shard_sites)


def make_sharded_logdensity(model, tvi_linked, plan, *,
                            backend: str = "fused",
                            cache=None) -> Callable:
    """Flat unconstrained log-density ``R^num_flat -> R`` over the mesh.

    The returned callable closes over the device_put shard arrays; its
    body runs under ``shard_map``: the prior is evaluated replicated,
    the likelihood per shard against the locally bound data, and the two
    are joined through the ``psum`` all-reduce seam. With one data shard
    this degenerates to the plain fused density.

    The jitted program is cached in the shared ``ProgramCache`` under a
    key whose ``sharding`` component is the plan fingerprint, so sharded
    and unsharded densities of the same model never collide.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core.program import (CompiledProgram, ProgramKey,
                                    model_fingerprint, program_cache)
    from repro.kernels.fused_logpdf.ops import all_reduce_block_sum

    if plan.num_data_shards == 1:
        return model.make_logdensity_fn(tvi_linked, backend=backend)

    sites = plan.shard_sites
    shard_slices(model, sites, plan.num_data_shards)
    shards = sharded_arrays(model, plan)

    def local_density(flat_u, *local):
        # bind THIS device's rows; the model re-executes against them,
        # so data-derived shapes inside the model are per-shard
        mm = model.bind(**dict(zip(sites, local)))
        tvi_q = tvi_linked.replace_flat(flat_u)
        prior = mm.logp_with_context(tvi_q, PriorContext(), backend=backend)
        lik = mm.logp_with_context(tvi_q, LikelihoodContext(),
                                   backend=backend)
        return prior + all_reduce_block_sum(lik, plan.data_axis)

    mapped = jax.shard_map(
        local_density, mesh=plan.mesh,
        in_specs=(P(),) + (P(plan.data_axis),) * len(sites),
        out_specs=P(), check_vma=False)

    key = ProgramKey(model_fingerprint(model), "density", tvi_linked.layout,
                     (), backend, (), plan.fingerprint())
    cache = cache if cache is not None else program_cache()
    prog = cache.get_or_build(
        key, lambda: CompiledProgram(
            key, lambda flat_u, *sh: mapped(flat_u, *sh)))

    @functools.wraps(local_density)
    def logdensity(flat_u):
        return prog(flat_u, *shards)

    # expose the unjitted mesh program for callers that embed this
    # density in a larger jitted computation (grad, vmap over draws)
    logdensity.raw = lambda flat_u: mapped(flat_u, *shards)
    logdensity.program = prog
    return logdensity
