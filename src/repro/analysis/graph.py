"""ModelGraph — the dependency-graph IR behind ``Model.analyze()``.

The paper's economics is "pay a one-time analysis of the trace, then run
specialised code". This module is that analysis: it replays a model three
times and distils the result into a small graph IR that the lint passes
(``repro.analysis.lints``), the fusion coverage report
(``repro.analysis.coverage``) and the potential compiler
(``repro.core.potential``) all consume.

1. **Eager structural replay** — a recording ``Evaluator`` subclass runs
   the model once on the typed trace's concrete values and captures every
   tilde site (parameter and observation), ``factor()`` /
   ``prior_factor()`` term and ``reject_if`` condition, in program order,
   with the concrete distribution instances (mirroring how
   ``build_potential_spec`` records sites).
2. **Traced dataflow replay** — the same replay under ``jax.make_jaxpr``
   with every parameter site's stored value as a function input. A
   forward union-propagation over the jaxpr (each equation's outputs
   depend on the union of its inputs' dependency sets — a sound
   over-approximation through ``scan``/``cond``/``pjit``) yields, for
   every site, WHICH parameter sites each distribution-parameter field
   depends on. Python control flow on a random variable surfaces here as
   a ``ConcretizationTypeError`` and marks the graph *dynamic*.
3. **Retrace probe** — the model structure is discovered twice more with
   fresh PRNG keys; a diverging site sequence (names/shapes/kinds) also
   marks the graph dynamic (structure depends on drawn values even when
   no tracer error fires, e.g. value-dependent loop lengths).

Nodes carry the same static metadata the flat buffer is built from
(support, shape, dtype, unconstrained slice from ``FlatLayout``), so a
graph verdict always talks about the exact slots the samplers run on.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.contexts import Context
from repro.core.interpreters import Evaluator, Sampler
from repro.core.model import Model
from repro.core.varinfo import FlatLayout, TypedVarInfo, typify

__all__ = ["GraphNode", "ModelGraph", "SiteRecord", "build_model_graph"]


from jax.extend.core import Literal as _Literal


@dataclasses.dataclass
class SiteRecord:
    """One recorded event of the eager structural replay (concrete values).

    ``kind`` is ``"param"`` / ``"observed"`` / ``"factor"`` / ``"reject"``.
    ``value`` is the constrained site value for params, the observed data
    for observations, the log-probability term for factors and the
    condition for rejects. ``dist`` is the concrete distribution instance
    (``None`` for factor/reject records).
    """

    kind: str
    name: str
    vn: Any
    dist: Any
    value: Any


@dataclasses.dataclass(frozen=True)
class GraphNode:
    """One site/observation/factor node of the :class:`ModelGraph`.

    ``deps`` lists the parameter-site symbols this node's distribution
    parameters (or factor value) depend on — the parameter-level dataflow
    edges point FROM each dep TO this node. ``field_deps`` breaks the same
    information down per distribution-parameter field (``loc``, ``scale``,
    ...), which is what the conditionally-separable compiler needs to
    decide whether an observation attaches to a leaf site.
    """

    name: str
    kind: str                    # "param" | "observed" | "factor" | "reject"
    dist: Optional[str]          # distribution class name
    support: Optional[str]
    shape: Tuple[int, ...]
    dtype: str
    unc_offset: int              # flat unconstrained slice (params; else -1/0)
    unc_size: int
    deps: Tuple[str, ...]
    field_deps: Tuple[Tuple[str, Tuple[str, ...]], ...] = ()

    def field_dep(self, field: str) -> Tuple[str, ...]:
        for f, d in self.field_deps:
            if f == field:
                return d
        return ()


@dataclasses.dataclass
class ModelGraph:
    """Dependency-graph IR of one (model, typed trace) pair."""

    nodes: Tuple[GraphNode, ...]
    layout: FlatLayout
    dynamic_reason: Optional[str]
    duplicates: Tuple[str, ...]
    records: List[SiteRecord]

    def __post_init__(self):
        self._by_name = {n.name: n for n in self.nodes}

    # -- lookups -------------------------------------------------------------
    @property
    def dynamic(self) -> bool:
        return self.dynamic_reason is not None

    def node(self, name: str) -> GraphNode:
        return self._by_name[name]

    def param_nodes(self) -> List[GraphNode]:
        return [n for n in self.nodes if n.kind == "param"]

    def data_nodes(self) -> List[GraphNode]:
        """Observation / factor / reject nodes (everything non-parameter)."""
        return [n for n in self.nodes if n.kind != "param"]

    def edges(self) -> List[Tuple[str, str]]:
        """Parameter-level dataflow edges ``(from_param_sym, to_node)``."""
        return [(dep, n.name) for n in self.nodes for dep in n.deps]

    def dependents(self, sym: str) -> List[GraphNode]:
        return [n for n in self.nodes if sym in n.deps]

    # -- derived structure ----------------------------------------------------
    def coupling_edge(self) -> Optional[Tuple[str, str]]:
        """First edge that breaks full separability, or ``None``.

        Any parameter site feeding another site's distribution parameters
        (including itself, including observations and factors) makes the
        density non-separable coordinate-by-coordinate.
        """
        for n in self.nodes:
            for dep in n.deps:
                return (dep, n.name)
        return None

    def head_syms(self) -> List[str]:
        """Parameter syms that another PARAMETER site's dist params (or a
        factor/reject term) depend on, transitively closed upward (deps of
        heads are heads). These are the coupled "top level" of a
        hierarchy; the complement is the candidate separable-leaf set —
        leaves may still feed observations, which the conditionally-
        separable compiler handles via its attach analysis."""
        head = {dep for n in self.nodes if n.kind != "observed"
                for dep in n.deps}
        psyms = {n.name for n in self.param_nodes()}
        head &= psyms
        changed = True
        while changed:
            changed = False
            for n in self.param_nodes():
                if n.name in head:
                    for dep in n.deps:
                        if dep in psyms and dep not in head:
                            head.add(dep)
                            changed = True
        return [n.name for n in self.param_nodes() if n.name in head]

    def reaches_data(self, sym: str) -> bool:
        """Whether ``sym`` has a dataflow path to any observation/factor."""
        seen, frontier = {sym}, [sym]
        while frontier:
            cur = frontier.pop()
            for n in self.dependents(cur):
                if n.kind != "param":
                    return True
                if n.name not in seen:
                    seen.add(n.name)
                    frontier.append(n.name)
        return False

    def __repr__(self):
        e = self.edges()
        return (f"ModelGraph({len(self.param_nodes())} params, "
                f"{len(self.data_nodes())} data nodes, {len(e)} edges"
                + (", dynamic" if self.dynamic else "") + ")")


# ---------------------------------------------------------------------------
# Recording interpreters
# ---------------------------------------------------------------------------
class _RecordingMixin:
    """Capture every tilde/factor/reject event in program order."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.site_records: List[SiteRecord] = []
        self._reject_count = 0

    def tilde(self, vn, dist, value, observed):
        out = super().tilde(vn, dist, value, observed)
        self.site_records.append(SiteRecord(
            "observed" if observed else "param", str(vn), vn, dist,
            value if observed else out))
        return out

    def factor_site(self, name, logp, observed):
        self.site_records.append(
            SiteRecord("factor", str(name), None, None, logp))
        super().factor_site(name, logp, observed)

    def reject_if(self, cond):
        self._reject_count += 1
        self.site_records.append(SiteRecord(
            "reject", f"_reject_{self._reject_count}", None, None, cond))
        super().reject_if(cond)


class _RecordingEvaluator(_RecordingMixin, Evaluator):
    pass


class _RecordingSampler(_RecordingMixin, Sampler):
    pass


# ---------------------------------------------------------------------------
# Dataflow: jaxpr forward union-propagation
# ---------------------------------------------------------------------------
def _propagate_deps(closed_jaxpr) -> List[frozenset]:
    """Per-output set of input indices each jaxpr output depends on.

    Forward pass: every equation's outputs inherit the union of its
    inputs' dependency sets. Sub-jaxpr operands (scan carries, pjit
    arguments, cond branches) all appear as equation invars, so the flat
    pass is a sound over-approximation without recursing.
    """
    jaxpr = closed_jaxpr.jaxpr
    empty: frozenset = frozenset()
    env: Dict[Any, frozenset] = {v: frozenset([i])
                                 for i, v in enumerate(jaxpr.invars)}

    def read(v):
        if isinstance(v, _Literal):
            return empty
        return env.get(v, empty)

    for eqn in jaxpr.eqns:
        deps = empty
        for v in eqn.invars:
            deps = deps | read(v)
        for ov in eqn.outvars:
            env[ov] = deps
    return [read(v) for v in jaxpr.outvars]


def _dist_fields(dist) -> List[Tuple[str, Any]]:
    if dist is None:
        return []
    return [(f.name, getattr(dist, f.name))
            for f in dataclasses.fields(dist)]


def _trace_field_deps(model: Model, tvi: TypedVarInfo, ctx: Optional[Context]):
    """Map each recorded site to per-field parameter dependencies.

    Returns ``(deps, None)`` on success — ``deps[record_index]`` is a dict
    ``field_name -> frozenset(param_sym)`` (factor/reject records use the
    pseudo-field ``"value"``) — or ``(None, reason)`` when the replay
    cannot be traced (RV-dependent Python control flow).
    """
    syms = [m.name for m in tvi.metas]
    out_meta: List[Tuple[int, str]] = []

    def fn(*values):
        rec = _RecordingEvaluator(tvi.replace_values(values), ctx=ctx,
                                  eager=False)
        model._run(rec)
        out_meta.clear()
        outs = []
        for ri, r in enumerate(rec.site_records):
            if r.kind in ("factor", "reject"):
                out_meta.append((ri, "value"))
                outs.append(jnp.asarray(r.value))
                continue
            for fname, fval in _dist_fields(r.dist):
                out_meta.append((ri, fname))
                outs.append(jnp.asarray(fval))
        outs.append(jnp.zeros(()))  # keep the trace non-empty
        return tuple(outs)

    try:
        closed = jax.make_jaxpr(fn)(*tvi.values)
    except jax.errors.ConcretizationTypeError as e:
        first = str(e).splitlines()[0] if str(e) else repr(e)
        return None, ("model structure depends on a traced random "
                      f"variable ({first})")
    out_deps = _propagate_deps(closed)

    deps: List[Dict[str, frozenset]] = []
    for (ri, fname), dep in zip(out_meta, out_deps):
        while len(deps) <= ri:
            deps.append({})
        cur = deps[ri].get(fname, frozenset())
        deps[ri][fname] = cur | frozenset(syms[i] for i in dep)
    return deps, None


# ---------------------------------------------------------------------------
# Builder
# ---------------------------------------------------------------------------
def _structure_signature(model: Model, key) -> Optional[Tuple]:
    """Site-structure fingerprint of one fresh discovery run."""
    rec = _RecordingSampler(key)
    try:
        model._run(rec)
    except Exception:
        return None
    return tuple((r.kind, r.name, tuple(np.shape(r.value)))
                 for r in rec.site_records)


def build_model_graph(model: Model, tvi: Optional[TypedVarInfo] = None,
                      ctx: Optional[Context] = None,
                      key=None) -> ModelGraph:
    """Build the :class:`ModelGraph` for ``model`` on trace ``tvi``.

    ``tvi`` may be linked or unlinked (the analysis always replays on the
    constrained trace; the flat-slice metadata on the nodes is the
    UNCONSTRAINED layout the samplers address). When ``tvi`` is omitted a
    discovery run with ``key`` (default ``PRNGKey(0)``) supplies it.
    """
    if key is None:
        key = jax.random.PRNGKey(0)
    if tvi is None:
        tvi = typify(model.untyped_trace(key))
    if tvi.linked:
        tvi = tvi.invlink()
    layout = tvi.layout

    # 1. eager structural replay (concrete dists + duplicate detection)
    rec = _RecordingEvaluator(tvi, ctx=ctx, eager=False)
    model._run(rec)
    records = rec.site_records

    seen_names: Dict[str, int] = {}
    seen_sym_forms: Dict[str, set] = {}
    duplicates: List[str] = []
    for r in records:
        if r.kind in ("factor", "reject"):
            continue
        seen_names[r.name] = seen_names.get(r.name, 0) + 1
        if seen_names[r.name] == 2:
            duplicates.append(r.name)
        if r.kind == "param":
            forms = seen_sym_forms.setdefault(r.vn.sym, set())
            forms.add("indexed" if r.vn.indexed else "whole")
            if len(forms) == 2 and r.vn.sym not in duplicates:
                duplicates.append(r.vn.sym)

    # 2. traced dataflow replay
    field_deps, dyn_reason = _trace_field_deps(model, tvi, ctx)

    # 3. retrace probe: structure must not move with the drawn values
    if dyn_reason is None:
        sigs = [_structure_signature(model, jax.random.fold_in(key, k))
                for k in (101, 202)]
        sigs = [s for s in sigs if s is not None]
        if len(sigs) == 2 and sigs[0] != sigs[1]:
            a = {n for _, n, _ in sigs[0]}
            b = {n for _, n, _ in sigs[1]}
            moved = sorted((a | b) - (a & b)) or ["<shape change>"]
            dyn_reason = ("model structure changed between discovery runs "
                          f"(sites {', '.join(moved)} appear conditionally)")

    # assemble nodes: one per param SYMBOL (grouped element sites merge),
    # one per observation/factor/reject record
    param_acc: Dict[str, Dict[str, frozenset]] = {}
    param_meta: Dict[str, SiteRecord] = {}
    order: List[Tuple[str, Optional[int]]] = []
    for ri, r in enumerate(records):
        fd = field_deps[ri] if (field_deps is not None
                                and ri < len(field_deps)) else {}
        if r.kind == "param":
            sym = r.vn.sym
            if sym not in param_acc:
                param_acc[sym] = {}
                param_meta[sym] = r
                order.append((sym, None))
            acc = param_acc[sym]
            for f, d in fd.items():
                acc[f] = acc.get(f, frozenset()) | d
        else:
            order.append((r.name, ri))

    nodes: List[GraphNode] = []
    for name, ri in order:
        if ri is None:  # param node (grouped element records merged)
            i = tvi.site_index(name)
            meta, sl = tvi.metas[i], layout.sites[i]
            acc = param_acc[name]
            deps = sorted(set().union(*acc.values()) if acc else set())
            d0 = param_meta[name].dist
            nodes.append(GraphNode(
                name=name, kind="param",
                dist=type(d0).__name__ if d0 is not None else None,
                support=meta.support, shape=meta.shape, dtype=meta.dtype,
                unc_offset=sl.unc_offset, unc_size=sl.unc_size,
                deps=tuple(deps),
                field_deps=tuple((f, tuple(sorted(d)))
                                 for f, d in acc.items())))
        else:
            r = records[ri]
            fd = field_deps[ri] if (field_deps is not None
                                    and ri < len(field_deps)) else {}
            deps = sorted(set().union(*fd.values()) if fd else set())
            nodes.append(GraphNode(
                name=name, kind=r.kind,
                dist=type(r.dist).__name__ if r.dist is not None else None,
                support=getattr(r.dist, "support", None),
                shape=tuple(np.shape(r.value)),
                dtype=str(jnp.asarray(r.value).dtype),
                unc_offset=-1, unc_size=0,
                deps=tuple(deps),
                field_deps=tuple((f, tuple(sorted(d)))
                                 for f, d in fd.items())))

    return ModelGraph(nodes=tuple(nodes), layout=layout,
                      dynamic_reason=dyn_reason,
                      duplicates=tuple(duplicates), records=records)
