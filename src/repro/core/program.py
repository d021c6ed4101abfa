"""Compiled program ABI + cache — ONE jitted function per (model, query).

Every consumer of the flat-buffer representation — the ``prob`` query
DSL, the samplers in ``repro.infer``, the segmented driver, the
query-serving tier — used to build its own jitted closure per call.
``jax.jit`` caches on *function identity*, so a fresh closure means a
fresh trace even when the computation is identical; repeated
``run_chains`` calls and every posterior-predictive draw paid a
recompile. This module gives all of them one shared ABI:

* :class:`ProgramKey` — the explicit cache key: ``(model fingerprint,
  kind, FlatLayout, batch shape, backend, extra)``. Everything in it is
  hashable and value-complete: model identity is the ``ModelGen`` uid
  plus a content hash of the bound data (arrays are fingerprinted by
  shape/dtype/sha1), so rebinding data to new values can never silently
  reuse a stale program.
* :class:`CompiledProgram` — a jitted function over the flat
  unconstrained/constrained buffer that counts its own traces (the
  Python body of a jitted function runs once per trace, so a counter
  inside it IS a retrace counter) and Python-level calls.
* :class:`ProgramCache` — keyed store with hit/miss/eviction counters
  and LRU eviction. Entries are either ``CompiledProgram`` s or plain
  compile artefacts (``PotentialCompileResult``, ``ModelGraph``, the
  segment-function tuples of the resumable driver) that are themselves
  expensive to rebuild.

The module-level default cache (``program_cache()``) is what
``prob``, ``run_chains``, ``run_segmented``, the samplers, and
``Model.analyze`` share; ``cache_stats()``/``clear_cache()`` expose it
for tests, health reports, and the serving tier.

Host spans: :func:`span` writes a ``jax.profiler.TraceAnnotation`` into
the profiler's trace, on the same clock as the device's events, tagged
``call=<n>`` with the ``run_chains`` call it belongs to (:func:`call_span`
numbers the calls; 0 outside one). The cache writes
``repro.program.fingerprint`` around fingerprinting a bound model's data
and ``repro.program.build`` around building or retracing a program. With
the profiler off a span costs about a microsecond.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import hashlib
import itertools
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

__all__ = ["CompiledProgram", "ProgramCache", "ProgramKey",
           "cache_stats", "cached_potential", "call_span", "clear_cache",
           "data_fingerprint", "density_program", "kernel_fingerprint",
           "model_fingerprint", "model_graph", "program_cache", "span",
           "trace_fingerprint"]


# ---------------------------------------------------------------------------
# Host spans in the profiler's trace
# ---------------------------------------------------------------------------
_CALLS = itertools.count(1)
_CALL = contextvars.ContextVar("repro_call", default=0)


def span(name: str, **stats):
    """Host span ``name`` in the profiler's trace, tagged with the number
    of the user call it belongs to (``call=0`` outside one). The stats are
    encoded only while a trace is active."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name, call=_CALL.get(), **stats)


@contextlib.contextmanager
def call_span(name: str, **stats):
    """Root span of one user call: the process-wide call counter's next
    number tags it and every :func:`span` opened inside it."""
    token = _CALL.set(next(_CALLS))
    try:
        with span(name, **stats):
            yield
    finally:
        _CALL.reset(token)


# bytes hashed by data_fingerprint in this process (cache_stats()'s
# ``fingerprint_bytes``)
_FINGERPRINT_BYTES = 0


# ---------------------------------------------------------------------------
# Fingerprints: hashable, value-complete identities for key components
# ---------------------------------------------------------------------------
def data_fingerprint(v) -> Tuple:
    """Hashable content fingerprint of one bound-data value.

    Arrays hash by (shape, dtype, sha1 of bytes) — a program compiled
    against one dataset can never be served for another. Tracers are
    refused loudly: a traced value has no content to fingerprint, and
    keying on it would alias every trace-time value to one program.
    """
    import numpy as np

    from repro.core.primitives import missing

    if v is missing:
        return ("missing",)
    if v is None:
        return ("none",)
    if isinstance(v, (bool, int, float, complex, str, bytes)):
        return ("lit", type(v).__name__, v)
    if isinstance(v, dict):
        return ("dict", tuple(sorted((str(k), data_fingerprint(x))
                                     for k, x in v.items())))
    if isinstance(v, (tuple, list)):
        return ("seq", type(v).__name__,
                tuple(data_fingerprint(x) for x in v))
    try:
        import jax
        if isinstance(v, jax.core.Tracer):
            raise ValueError(
                "cannot fingerprint a traced value for a ProgramKey; "
                "traced data must be an INPUT of the compiled program, "
                "not part of its cache key")
    except ImportError:  # pragma: no cover - jax is a hard dep elsewhere
        pass
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        global _FINGERPRINT_BYTES
        arr = np.asarray(v)
        digest = hashlib.sha1(arr.tobytes()).hexdigest()[:16]
        _FINGERPRINT_BYTES += arr.nbytes
        return ("arr", tuple(arr.shape), str(arr.dtype), digest)
    # Model/ModelGen values (submodel-style bindings) get structural ids
    fp = _maybe_model_fingerprint(v)
    if fp is not None:
        return fp
    return ("id", type(v).__name__, id(v))


def _maybe_model_fingerprint(v) -> Optional[Tuple]:
    from repro.core.model import Model, ModelGen
    if isinstance(v, (Model, ModelGen)):
        return model_fingerprint(v)
    return None


def model_fingerprint(m) -> Tuple:
    """Identity of a Model/ModelGen: generator uid + bound-data content.

    The uid is a process-monotonic counter stamped in
    ``ModelGen.__init__`` — unlike ``id()`` it is never reused after
    garbage collection, so two distinct generators can never collide on
    one cached program.
    """
    from repro.core.model import Model, ModelGen
    if isinstance(m, ModelGen):
        return ("modelgen", m.name, m._uid)
    if isinstance(m, Model):
        with span("repro.program.fingerprint") as s:
            hashed = _FINGERPRINT_BYTES
            data = tuple(sorted((k, data_fingerprint(v))
                                for k, v in m.data.items()))
            s.set_metadata(bytes=_FINGERPRINT_BYTES - hashed)
        return ("model", m.gen.name, m.gen._uid, data)
    raise TypeError(f"expected Model or ModelGen, got {type(m).__name__}")


def trace_fingerprint(tvi) -> Tuple:
    """Identity of a typed trace for programs that BAKE its dist params.

    ``package_draws``-style programs invlink through the trace's stored
    distributions, whose parameters may depend on the discovery draw
    (e.g. ``Uniform(lo, hi)`` bounds computed from another site) — so the
    layout alone is not enough and the dist-tree leaves are content-
    hashed in. Density programs re-execute the model and do NOT need
    this (they key on layout only).
    """
    import jax
    leaves = jax.tree_util.tree_leaves(tvi.dists)
    return ("tvi", tvi.layout, bool(tvi.linked),
            tuple(data_fingerprint(x) for x in leaves))


def kernel_fingerprint(kernel) -> Optional[Tuple]:
    """Configuration fingerprint of a sampler (HMC/NUTS/RWMH dataclass).

    Returns ``None`` for non-dataclass kernels — callers must then
    bypass the cache rather than risk aliasing two behaviours.
    """
    if not dataclasses.is_dataclass(kernel):
        return None
    try:
        fields = tuple((f.name, data_fingerprint(getattr(kernel, f.name)))
                       for f in dataclasses.fields(kernel))
    except ValueError:
        return None
    return ("kernel", type(kernel).__name__, fields)


# ---------------------------------------------------------------------------
# The program ABI
# ---------------------------------------------------------------------------
class ProgramKey(NamedTuple):
    """Explicit cache key: every axis a compiled program specialises on.

    Attributes
    ----------
    model : tuple
        :func:`model_fingerprint` of the bound model (or a bare
        ``("modelgen", ...)`` fingerprint for data-as-input programs).
    kind : str
        Program family — ``"density"``, ``"potential"``, ``"graph"``,
        ``"chain"``, ``"package"``, ``"segment_fns"``, ``"advi_step"``,
        ``"sgld_step"``, ``"query/prior"``, ``"query/likelihood"``,
        ``"query/joint"``, ``"query/posterior_predictive"``, ...
    layout : FlatLayout or None
        The flat-buffer layout the program addresses (None for programs
        built before a trace exists, e.g. data-shaped query programs).
    batch : tuple
        Batch shape — ``(M,)`` stacked draws for posterior predictives,
        ``(num_chains, num_warmup, num_samples)`` for chain programs,
        ``()`` for scalar programs.
    backend : str
        Density backend (``"fused"``/``"reference"``).
    extra : tuple
        Kind-specific hashable tail (context, kernel fingerprint, data
        shape signature, ...).
    sharding : tuple
        Device-placement fingerprint — ``()`` for the single-device
        path, a :meth:`repro.sharding.ShardedRun.fingerprint` tuple
        (mesh shape, axis names, sharded sites) for mesh-dispatched
        programs. A sharded program bakes collective ops and per-shard
        shapes into its HLO, so it must NEVER be served for an
        unsharded call with an otherwise identical key (and vice
        versa); making the placement part of the key is what guarantees
        that.
    """

    model: Tuple
    kind: str
    layout: Any
    batch: Tuple
    backend: str
    extra: Tuple = ()
    sharding: Tuple = ()


class CompiledProgram:
    """One jitted function over the flat buffer, with trace accounting.

    ``retraces`` counts actual jit traces (the wrapped Python body runs
    once per trace); ``calls`` counts Python-level invocations. A cached
    program that is hit N times and retraced once is the whole point of
    the ABI — ``retraces`` staying flat across repeated runs is what the
    "zero recompiles" tests assert.
    """

    def __init__(self, key: ProgramKey, raw: Callable, *, jit: bool = True,
                 static_argnums=()):
        import jax
        self.key = key
        self.raw = raw
        self.calls = 0
        self.retraces = 0

        def traced(*args, **kwargs):
            self.retraces += 1
            with span("repro.program.build", kind=key.kind, retrace=1):
                return raw(*args, **kwargs)

        self._fn = (jax.jit(traced, static_argnums=static_argnums)
                    if jit else traced)

    def __call__(self, *args, **kwargs):
        self.calls += 1
        return self._fn(*args, **kwargs)

    def __repr__(self):
        return (f"CompiledProgram({self.key.kind}, calls={self.calls}, "
                f"retraces={self.retraces})")


class ProgramCache:
    """Keyed LRU store of compiled programs and compile artefacts.

    ``get_or_build(key, builder)`` is the only write path: a hit moves
    the entry to the MRU end; a miss invokes ``builder()`` and may evict
    the LRU entry. All counters are plain ints, cheap enough to snapshot
    per driver segment.
    """

    def __init__(self, maxsize: int = 128):
        self.maxsize = int(maxsize)
        self._entries: "OrderedDict[ProgramKey, Any]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_or_build(self, key: ProgramKey, builder: Callable[[], Any]):
        with self._lock:
            if key in self._entries:
                self.hits += 1
                self._entries.move_to_end(key)
                return self._entries[key]
            self.misses += 1
        # build OUTSIDE the lock: builders trace models and may reenter
        # the cache (e.g. a chain program building its density program)
        with span("repro.program.build", kind=key.kind):
            value = builder()
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
        return value

    def get(self, key: ProgramKey):
        """Peek without building (no hit/miss accounting)."""
        return self._entries.get(key)

    def __contains__(self, key: ProgramKey) -> bool:
        return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def keys(self):
        return list(self._entries.keys())

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = self.misses = self.evictions = 0

    def stats(self) -> Dict[str, int]:
        """Aggregate counters, including per-program trace accounting.
        ``fingerprint_bytes`` is process-wide: the bytes of bound data
        hashed to key programs, which ``clear()`` leaves as they are."""
        progs = [v for v in self._entries.values()
                 if isinstance(v, CompiledProgram)]
        return {
            "size": len(self._entries),
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "retraces": sum(p.retraces for p in progs),
            "calls": sum(p.calls for p in progs),
            "fingerprint_bytes": _FINGERPRINT_BYTES,
        }


_DEFAULT_CACHE = ProgramCache()


def program_cache() -> ProgramCache:
    """The process-wide default cache shared by queries/samplers/serving."""
    return _DEFAULT_CACHE


def cache_stats() -> Dict[str, int]:
    return _DEFAULT_CACHE.stats()


def clear_cache() -> None:
    _DEFAULT_CACHE.clear()


# ---------------------------------------------------------------------------
# Shared builders (lazy imports: program.py sits below model/potential)
# ---------------------------------------------------------------------------
def density_program(model, tvi_linked, ctx=None, backend: str = "fused",
                    cache: Optional[ProgramCache] = None) -> CompiledProgram:
    """Cached flat unconstrained log-density ``R^num_flat -> R``.

    The program re-executes the model under the fused evaluator, so it
    is a pure function of (model incl. data, layout, ctx, backend) —
    the trace's VALUES are inputs, not constants, which is why two
    ``run_chains`` calls with different discovery draws share one
    program.
    """
    from repro.core.contexts import DefaultContext
    cache = cache if cache is not None else _DEFAULT_CACHE
    ctx_key = ctx if ctx is not None else DefaultContext()
    key = ProgramKey(model_fingerprint(model), "density", tvi_linked.layout,
                     (), backend, (ctx_key,))

    def build():
        raw = model.make_logdensity_fn(tvi_linked, ctx=ctx, backend=backend)
        return CompiledProgram(key, raw)

    return cache.get_or_build(key, build)


def cached_potential(model, tvi_linked, ctx=None, backend: str = "fused",
                     allow_conditional: bool = True,
                     cache: Optional[ProgramCache] = None):
    """Cached :func:`repro.core.potential.compile_potential` result.

    The compile is graph-gated and runs several replay probes — caching
    it is what makes repeated ``run_chains`` calls and the
    analysis-after-sampling path free.
    """
    cache = cache if cache is not None else _DEFAULT_CACHE
    key = ProgramKey(model_fingerprint(model), "potential",
                     tvi_linked.layout, (), backend,
                     (ctx, bool(allow_conditional)))

    def build():
        from repro.core.potential import compile_potential
        return compile_potential(model, tvi_linked, ctx=ctx, backend=backend,
                                 allow_conditional=allow_conditional)

    return cache.get_or_build(key, build)


def model_graph(model, tvi, ctx=None,
                cache: Optional[ProgramCache] = None):
    """Cached :func:`repro.analysis.graph.build_model_graph`.

    The graph builder invlinks linked traces itself and its output is
    structural (value-independent; dynamic structure is detected by its
    own multi-key probe), so linked and unlinked callers — the potential
    compiler and ``Model.analyze`` — share one entry keyed on
    (model, layout, ctx).
    """
    cache = cache if cache is not None else _DEFAULT_CACHE
    layout = tvi.layout if tvi is not None else None
    key = ProgramKey(model_fingerprint(model), "graph", layout, (),
                     "fused", (ctx,))

    def build():
        from repro.analysis.graph import build_model_graph
        return build_model_graph(model, tvi, ctx=ctx)

    return cache.get_or_build(key, build)
