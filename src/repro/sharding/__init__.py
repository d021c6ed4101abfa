"""Logical-axis sharding rules -> PartitionSpecs (MaxText-style).

Model code names activation/parameter dimensions with LOGICAL axes
("batch", "embed", "heads", "mlp", "vocab", "experts", "kv_seq", ...).
A rule set maps logical axes to physical mesh axes; the launcher activates
a rule set, and ``constrain``/``spec`` resolve specs at trace time. With no
active rules (CPU unit tests) everything is a no-op, so the same model code
runs single-device and multi-pod.

``param_spec_for`` maps every parameter leaf of the LM tree to its
tensor-parallel layout by leaf name (wq/wk/wv/wo, gate/up/down, experts,
embed_table, ...), handling the extra leading dim of scan-stacked layers.
With ``fsdp=True`` it additionally shards each large leaf's biggest
still-replicated dim over the data axis (ZeRO-3); optimizer state reuses
the same specs through identical tree structure.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.sharding.data_parallel import (make_sharded_logdensity,
                                          shard_slices, sharded_arrays)
from repro.sharding.mesh import ShardedRun
from repro.sharding.minibatch import (Minibatch, MinibatchLogDensity,
                                      make_minibatch_logdensity)

__all__ = ["Rules", "spec", "constrain", "use_rules", "active_rules",
           "DEFAULT_RULES", "LONG_DECODE_RULES", "named_sharding",
           "param_spec_for", "param_shardings", "FSDP_MIN_SIZE",
           "fit_spec", "axes_size",
           # inference mesh layer (chains x data)
           "ShardedRun", "make_sharded_logdensity", "shard_slices",
           "sharded_arrays", "Minibatch", "MinibatchLogDensity",
           "make_minibatch_logdensity"]

AxisVal = Union[None, str, Tuple[str, ...]]


class Rules:
    def __init__(self, mapping: Dict[str, AxisVal], mesh: Optional[Mesh] = None,
                 fsdp: bool = False):
        self.mapping = dict(mapping)
        self.mesh = mesh
        self.fsdp = fsdp

    def with_mesh(self, mesh: Mesh) -> "Rules":
        # drop rules that reference axes the mesh does not have
        valid = set(mesh.axis_names)

        def ok(v: AxisVal) -> AxisVal:
            if v is None:
                return None
            if isinstance(v, str):
                return v if v in valid else None
            kept = tuple(a for a in v if a in valid)
            return kept or None

        return Rules({k: ok(v) for k, v in self.mapping.items()}, mesh,
                     self.fsdp)

    def with_fsdp(self, on: bool = True) -> "Rules":
        return Rules(self.mapping, self.mesh, on)

    def replace(self, **updates) -> "Rules":
        return Rules(dict(self.mapping, **updates), self.mesh, self.fsdp)

    def spec(self, *logical: Optional[str]) -> PartitionSpec:
        out = []
        for name in logical:
            out.append(None if name is None else self.mapping.get(name))
        return PartitionSpec(*out)


# batch over (pod, data); tensor-parallel over model; experts over model (EP)
DEFAULT_RULES = Rules({
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "q_lora": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "expert_mlp": None,
    "conv": None,
    "state": None,
    "data_axes": ("pod", "data"),  # FSDP target axes (params/opt states)
})

# long-context single-sequence decode: batch=1, shard the KV length instead
LONG_DECODE_RULES = DEFAULT_RULES.replace(batch=None, kv_seq=("pod", "data"))

_tls = threading.local()


def active_rules() -> Optional[Rules]:
    return getattr(_tls, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[Rules]):
    prev = getattr(_tls, "rules", None)
    _tls.rules = rules
    try:
        yield rules
    finally:
        _tls.rules = prev


def spec(*logical: Optional[str]) -> PartitionSpec:
    r = active_rules()
    if r is None:
        return PartitionSpec()
    return r.spec(*logical)


def constrain(x, *logical: Optional[str]):
    """with_sharding_constraint against the active rules (no-op if none).
    Axes that do not divide the dim are dropped (see ``fit_spec``)."""
    r = active_rules()
    if r is None or r.mesh is None:
        return x
    s = fit_spec(r.spec(*logical), tuple(x.shape), r.mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(r.mesh, s))


def named_sharding(mesh: Mesh, *logical: Optional[str],
                   rules: Optional[Rules] = None) -> NamedSharding:
    r = (rules or active_rules() or DEFAULT_RULES).with_mesh(mesh)
    return NamedSharding(mesh, r.spec(*logical))


# ---------------------------------------------------------------------------
# parameter layouts
# ---------------------------------------------------------------------------
# base logical spec per leaf name, WITHOUT the scan-stack leading dim.
# (the trailing entries align to the leaf's trailing dims)
_LEAF_SPECS: Dict[str, Tuple[Optional[str], ...]] = {
    # attention (GQA / cross)
    "wq": (None, "heads", None),
    "wk": (None, "kv_heads", None),
    "wv": (None, "kv_heads", None),
    "wo": ("heads", None, None),
    # MLA
    "w_dkv": (None, None),
    "w_krope": (None, None),
    "w_uk": (None, "heads", None),
    "w_uv": (None, "heads", None),
    # MLP (gated + relu2)
    "w_gate": (None, "mlp"),
    "w_up": (None, "mlp"),
    "w_down": ("mlp", None),
    # router replicated (tiny, latency-critical)
    "router": (None, None),
    # mamba2
    "in_proj": (None, "mlp"),
    "out_proj": ("mlp", None),
    "conv_w": (None, "mlp"),
    # rg-lru
    "in_x": (None, "mlp"),
    "in_gate": (None, "mlp"),
    "w_a": ("mlp", None),
    "w_x": ("mlp", None),
    "out": ("mlp", None),
    # embeddings / projections
    "embed_table": ("vocab", None),
    "prefix_proj": (None, "mlp"),
}

# experts leaves carry a leading (n_experts,) dim on top of the MLP spec
_EXPERT_SPECS: Dict[str, Tuple[Optional[str], ...]] = {
    "w_gate": ("experts", None, "expert_mlp"),
    "w_up": ("experts", None, "expert_mlp"),
    "w_down": ("experts", "expert_mlp", None),
}

FSDP_MIN_SIZE = 2 ** 18  # leaves below 256Ki elements stay replicated


def _leaf_name(path: Tuple) -> Tuple[str, bool]:
    """(final dict key, inside-experts?) from a tree path."""
    keys = [k.key for k in path if hasattr(k, "key")]
    name = keys[-1] if keys else ""
    return name, "experts" in keys


def axes_size(mesh: Optional[Mesh], axisval: AxisVal) -> int:
    if axisval is None or mesh is None:
        return 1
    names = (axisval,) if isinstance(axisval, str) else axisval
    n = 1
    for a in names:
        n *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]
    return n


def fit_spec(spec: PartitionSpec, shape: Tuple[int, ...],
             mesh: Optional[Mesh]) -> PartitionSpec:
    """Drop spec entries whose mesh-axis product does not divide the dim —
    pjit argument shardings require exact divisibility (replicate instead).
    Non-divisible cases in the assigned archs: smollm 15H/5KV vs model=16,
    GQA kv=8 < model=16, odd vocab sizes (49155, 92553, 256206, 50280)."""
    if mesh is None:
        return spec
    out = []
    for i, entry in enumerate(tuple(spec)):
        n = axes_size(mesh, entry)
        out.append(entry if (n > 1 and shape[i] % n == 0) or n == 1
                   else None)
    return PartitionSpec(*out)


def param_spec_for(path, shape: Tuple[int, ...], rules: Rules
                   ) -> PartitionSpec:
    """Logical layout for one parameter leaf (see module docstring)."""
    name, in_experts = _leaf_name(tuple(path))
    ndim = len(shape)
    base = _EXPERT_SPECS.get(name) if in_experts else _LEAF_SPECS.get(name)
    if base is None or ndim < len(base):
        logical = [None] * ndim          # norms, biases, scalars: replicate
    else:
        # scan-stacked params carry extra LEADING dims (segment stacking)
        logical = [None] * (ndim - len(base)) + list(base)

    base_spec = fit_spec(rules.spec(*logical), shape, rules.mesh)
    if rules.fsdp and int(np.prod(shape)) >= FSDP_MIN_SIZE:
        data_axes = rules.mapping.get("data_axes") or "data"
        n_data = axes_size(rules.mesh, data_axes)
        # shard the largest still-unsharded DIVISIBLE dim over data (ZeRO-3)
        order = sorted(range(ndim), key=lambda i: -shape[i])
        for i in order:
            if (base_spec[i] is None and shape[i] > 1
                    and shape[i] % max(n_data, 1) == 0):
                return PartitionSpec(*[
                    data_axes if j == i else base_spec[j]
                    for j in range(ndim)])
    return base_spec


def param_shardings(mesh: Mesh, shapes_tree, rules: Rules):
    """NamedSharding pytree for a parameter (or optimizer-state) tree of
    ShapeDtypeStructs; non-array leaves (scalars) get fully-replicated."""
    r = rules.with_mesh(mesh)

    def one(path, leaf):
        shape = tuple(getattr(leaf, "shape", ()) or ())
        return NamedSharding(mesh, param_spec_for(path, shape, r))

    return jax.tree_util.tree_map_with_path(one, shapes_tree)
