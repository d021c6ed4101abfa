"""Production mesh construction + per-cell sharding rule selection.

The production target is TPU v5e: one pod = 16x16 = 256 chips, multi-pod
= 2 pods = 512 chips with a leading "pod" axis (data-parallel across the
DCI). Defined as FUNCTIONS so importing this module never initialises the
jax backend (the dry-run must set XLA_FLAGS before first device touch).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro import sharding

__all__ = ["make_production_mesh", "make_mesh", "rules_for_cell",
           "POD_SHAPE", "MULTIPOD_SHAPE"]

POD_SHAPE: Tuple[int, int] = (16, 16)
MULTIPOD_SHAPE: Tuple[int, int, int] = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTIPOD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (elastic re-mesh path; see runtime.elastic).

    Axes are ``Auto``: the model code places activations with
    ``with_sharding_constraint``, which refuses ``Explicit`` axes."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# trainable-parameter bytes/chip thresholds: Adam(f32 m+v) + bf16 param +
# grad ~ 12 B/param; v5e HBM = 16 GiB. Archs above the threshold train
# with FSDP (ZeRO-3 over the data axis); smaller archs stay DP+TP.
_FSDP_BYTES_PER_PARAM = 12
_HBM_BUDGET = 11e9  # leave ~5 GiB for activations/collectives


# DP+ZeRO-3 variant: when an arch's head/kv counts do not divide the model
# axis (smollm: 15H/5KV), tensor parallelism buys nothing and the model
# axis redundantly recomputes attention on every rank. Instead: batch over
# (data x model) — 256-way data parallel — with parameters ZeRO-3-sharded
# over the same 256 ranks (weight all-gather per layer replaces 16x
# redundant compute). The pod axis stays plain DP.
DP_ZERO_RULES = sharding.Rules(dict(
    sharding.DEFAULT_RULES.mapping,
    batch=("data", "model"),
    heads=None, kv_heads=None, mlp=None, vocab=None, experts=None,
    data_axes=("data", "model"),
), fsdp=True)


def rules_for_cell(kind: str, *, n_params: float = 0.0,
                   model_axis: int = 16,
                   train_fsdp: Optional[bool] = None,
                   variant: Optional[str] = None) -> sharding.Rules:
    """Sharding rules for a (shape-kind, arch-size) cell.

    train/prefill/decode: batch over (pod, data), TP over model.
    long-context decode (batch=1): KV length over (pod, data) instead.
    variant="dp_zero": see DP_ZERO_RULES (perf iteration, §Perf).
    """
    if variant == "dp_zero":
        return DP_ZERO_RULES
    if kind == "long":
        return sharding.LONG_DECODE_RULES
    rules = sharding.DEFAULT_RULES
    if kind == "train":
        fsdp = train_fsdp
        if fsdp is None:
            fsdp = (n_params * _FSDP_BYTES_PER_PARAM / model_axis
                    > _HBM_BUDGET)
        return rules.with_fsdp(fsdp)
    if kind in ("decode", "prefill"):
        # flash-decoding layout: the cache LENGTH shards over the model
        # axis whenever kv_heads cannot (GQA kv=8 < |model|=16 would
        # otherwise replicate a 32k-token cache on every rank). The cache
        # spec resolver deconflicts when kv_heads DO shard (see
        # lowering._cache_spec_for).
        return rules.replace(kv_seq="model")
    return rules
