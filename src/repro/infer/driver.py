"""Segmented, resumable, fault-tolerant multi-chain driver.

``run_segmented`` is the checkpointed sibling of the single-scan
``run_chains`` path. The warmup+sampling loop is cut into
``checkpoint_every``-sized ``jit(vmap(lax.scan))`` segments over a
complete :class:`RunState` pytree (per-chain kernel state including
adaptation, the segment cursor, and the draw/stat buffers). Between
segments the host

* snapshots ``RunState`` through the atomic keep-N ``repro.ckpt`` layer
  (async write, ``COMMITTED`` marker last, torn snapshots ignored on
  restore),
* polls a :class:`~repro.runtime.preemption.PreemptionHandler` and on
  preemption writes a final SYNCHRONOUS checkpoint and returns the
  partial chain cleanly (exit-0 semantics: the scheduler restarts the
  job and the next ``run_chains`` call resumes), and
* runs chain-health guard rails — non-finite state, divergence counts,
  stuck chains (zero acceptance), straggler-style log-density outliers —
  into a :class:`ChainHealth` report attached to the returned ``Chain``.

Graceful degradation: a segment whose state goes non-finite under the
fused/potential-spec path is retried once from the pre-segment state on
the REFERENCE backend (autodiff leapfrog, per-site densities) and the
fallback is recorded in the report.

Bit-exactness: per-draw PRNG keys are presplit with the SAME derivation
as the single-scan driver (``fold_in(chain_key, 1|2)`` then ``split``),
and segments scan the exact same ``kern.warm``/``kern.step`` closures
over key slices — so a segmented run is draw-for-draw identical to an
unsegmented one, and a run interrupted and resumed from the latest
committed snapshot is bit-exact vs an uninterrupted run.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

from repro.ckpt.checkpoint import (AsyncCheckpointer, latest_step, read_meta,
                                   restore, save)
from repro.infer.chains import Chain, package_draws, setup_chain_driver
from repro.runtime.preemption import PreemptionHandler

__all__ = ["ChainHealth", "RunState", "health_from_stats",
           "reference_variant", "run_segmented"]


class RunState(NamedTuple):
    """The complete, checkpointable state of a segmented run.

    Everything needed to continue the run lives here — restoring this
    pytree and re-deriving the (deterministic) per-draw keys from the
    master key reproduces the remaining draws bit-exactly.
    """

    iteration: Any        # () int64 — completed warmup+sampling transitions
    kernel_state: Any     # vmapped sampler state (leading chain axis)
    q_buf: Any            # (chains, num_samples, dim) unconstrained draws
    stat_bufs: Any        # dict name -> (chains, num_samples, ...) stats
    counters: Any         # dict: health counters accumulated so far


@dataclasses.dataclass
class ChainHealth:
    """Guard-rail report for a (possibly partial) multi-chain run."""

    num_chains: int
    target_warmup: int
    target_samples: int
    completed: int                  # warmup+sampling transitions done
    divergences: np.ndarray         # (chains,) divergent-draw counts
    nonfinite: np.ndarray           # (chains,) non-finite segment events
    stuck: Tuple[int, ...] = ()     # chains with a zero-acceptance streak
    outliers: Tuple[int, ...] = ()  # straggler-style log-density outliers
    fallback_segments: int = 0      # segments rerun on the reference path
    preempted: bool = False
    resumed_from: Optional[int] = None
    checkpoint_dir: Optional[str] = None
    cache_hits: int = 0             # ProgramCache hits during this run
    cache_misses: int = 0           # programs compiled during this run
    cache_retraces: int = 0         # jit traces of cached programs
    fingerprint_bytes: int = 0      # bound data hashed to key programs

    @property
    def completed_samples(self) -> int:
        return max(0, self.completed - self.target_warmup)

    @property
    def ok(self) -> bool:
        return (not self.preempted and not self.stuck and not self.outliers
                and int(np.sum(self.nonfinite)) == 0
                and self.completed == self.target_warmup + self.target_samples)

    def report(self) -> str:
        lines = [f"chain health: {'OK' if self.ok else 'ISSUES'}"]
        lines.append(
            f"  draws {self.completed_samples}/{self.target_samples} per "
            f"chain x {self.num_chains} chains "
            f"(+{min(self.completed, self.target_warmup)}/"
            f"{self.target_warmup} warmup)")
        n_div = int(np.sum(self.divergences))
        if n_div:
            per = ", ".join(str(int(d)) for d in self.divergences)
            lines.append(f"  divergences: {n_div} (per chain: {per})")
        if int(np.sum(self.nonfinite)):
            bad = [i for i, c in enumerate(self.nonfinite) if c]
            lines.append(f"  non-finite state events in chains {bad}")
        if self.fallback_segments:
            lines.append(f"  fused->reference fallback on "
                         f"{self.fallback_segments} segment(s)")
        if self.stuck:
            lines.append(f"  stuck chains (zero acceptance): "
                         f"{list(self.stuck)}")
        if self.outliers:
            lines.append(f"  outlier chains (log-density far from fleet "
                         f"median): {list(self.outliers)}")
        if self.preempted:
            where = (f"; resumable from {self.checkpoint_dir}"
                     if self.checkpoint_dir else "")
            lines.append(f"  PREEMPTED at iteration {self.completed}{where}")
        if self.resumed_from is not None:
            lines.append(f"  resumed from committed iteration "
                         f"{self.resumed_from}")
        if (self.cache_hits or self.cache_misses or self.cache_retraces
                or self.fingerprint_bytes):
            lines.append(f"  program cache: {self.cache_hits} hit(s), "
                         f"{self.cache_misses} miss(es), "
                         f"{self.cache_retraces} retrace(s), "
                         f"{self.fingerprint_bytes} byte(s) fingerprinted")
        return "\n".join(lines)


class _GuardRails:
    """Streak-based stuck/outlier detection over per-segment summaries.

    Mirrors ``runtime.straggler``: robust at small chain counts (a
    median/MAD test instead of a self-inflating z-score) and requiring
    ``patience`` CONSECUTIVE flagged segments so a transient blip (one
    hard region of the posterior) does not flag a healthy chain.
    """

    def __init__(self, num_chains: int, stuck_accept: float = 1e-3,
                 outlier_scale: float = 10.0, patience: int = 3):
        self.stuck_accept = stuck_accept
        self.outlier_scale = outlier_scale
        self.patience = patience
        self._stuck_streak = np.zeros(num_chains, np.int64)
        self._out_streak = np.zeros(num_chains, np.int64)

    def record(self, accept_mean: np.ndarray, logp_mean: np.ndarray) -> None:
        flag = ~np.isfinite(accept_mean) | (accept_mean < self.stuck_accept)
        self._stuck_streak = np.where(flag, self._stuck_streak + 1, 0)
        finite = np.isfinite(logp_mean)
        if finite.any():
            med = np.median(logp_mean[finite])
            mad = np.median(np.abs(logp_mean[finite] - med))
            thr = self.outlier_scale * (mad + 1e-3) + 1.0
            out = ~finite | (np.abs(logp_mean - med) > thr)
        else:
            out = np.ones_like(finite)
        self._out_streak = np.where(out, self._out_streak + 1, 0)

    def stuck(self) -> Tuple[int, ...]:
        return tuple(int(i) for i in
                     np.nonzero(self._stuck_streak >= self.patience)[0])

    def outliers(self) -> Tuple[int, ...]:
        return tuple(int(i) for i in
                     np.nonzero(self._out_streak >= self.patience)[0])


def reference_variant(sampler):
    """Best-effort reference-backend twin of ``sampler``.

    The twin must produce a kernel with the SAME state pytree structure
    (so a mid-run state carries over) but no fused kernels anywhere —
    the graceful-degradation target when the fused path goes non-finite.
    Returns ``None`` when the sampler is already fully on the reference
    path (nothing to fall back to) or cannot be rebuilt.
    """
    custom = getattr(sampler, "reference_variant", None)
    if callable(custom):
        return custom()
    if not dataclasses.is_dataclass(sampler):
        return None
    fields = {f.name for f in dataclasses.fields(sampler)}
    changes = {}
    if "leapfrog" in fields and sampler.leapfrog != "reference":
        changes["leapfrog"] = "reference"
    if "backend" in fields and sampler.backend != "reference":
        changes["backend"] = "reference"
    if not changes:
        return None
    return dataclasses.replace(sampler, **changes)


def health_from_stats(stats: Dict[str, np.ndarray], *, num_warmup: int,
                      num_samples: int, num_chains: int,
                      stuck_accept: float = 1e-3,
                      outlier_scale: float = 10.0) -> ChainHealth:
    """Post-hoc ChainHealth for the single-scan driver (whole run = one
    segment's worth of evidence, so streaks degenerate to one test)."""
    logp = np.asarray(stats.get("logp", np.zeros((num_chains, 0))))
    div = stats.get("diverging")
    divergences = (np.asarray(div).astype(np.int64).sum(axis=1)
                   if div is not None else np.zeros(num_chains, np.int64))
    nonfinite = (~np.isfinite(logp)).any(axis=1).astype(np.int64) \
        if logp.size else np.zeros(num_chains, np.int64)
    rails = _GuardRails(num_chains, stuck_accept=stuck_accept,
                        outlier_scale=outlier_scale, patience=1)
    acc = stats.get("accept_prob")
    if acc is not None and logp.size:
        rails.record(np.asarray(acc).mean(axis=1), logp.mean(axis=1))
    return ChainHealth(
        num_chains=num_chains, target_warmup=num_warmup,
        target_samples=num_samples, completed=num_warmup + num_samples,
        divergences=divergences, nonfinite=nonfinite,
        stuck=rails.stuck(), outliers=rails.outliers())


def _check_meta(saved: Dict, want: Dict, directory: str) -> None:
    keys = ("format", "num_chains", "num_warmup", "num_samples", "dim",
            "sampler", "key_data", "backend")
    bad = [k for k in keys if saved.get(k) != want.get(k)]
    if bad:
        detail = {k: (saved.get(k), want.get(k)) for k in bad}
        raise ValueError(
            f"checkpoint in {directory} is from a different run "
            f"configuration; mismatched (saved, requested): {detail}. "
            "Resuming would NOT reproduce the original draws — point "
            "checkpoint_dir at a fresh directory or rerun with the "
            "original arguments/key.")


def run_segmented(key, model, sampler, num_samples: int, *,
                  num_warmup: int = 0, num_chains: int = 4,
                  init_varinfo=None, init_jitter: float = 1.0,
                  backend: str = "fused", mesh=None,
                  checkpoint_dir: Optional[str] = None,
                  checkpoint_every: Optional[int] = None,
                  checkpoint_keep: int = 3, preemption=None,
                  fallback: bool = True, stuck_accept: float = 1e-3,
                  outlier_scale: float = 10.0, patience: int = 3) -> Chain:
    """Checkpointed, preemptible, health-guarded ``run_chains``.

    See the module docstring for the contract. Normally reached through
    ``repro.infer.run_chains(..., checkpoint_dir=..., checkpoint_every=
    ...)`` rather than called directly.

    ``mesh`` (a ``repro.sharding.ShardedRun``) dispatches the chain
    fleet across the plan's ``chains`` devices: the per-chain kernel
    state and presplit key slices are laid over the mesh, and the
    placement propagates through every segment program. Because the
    per-chain math and key derivation are untouched, a sharded
    segmented run — including interrupt + resume — stays bit-exact
    against the single-device one, and checkpoints are placement-
    agnostic (a run snapshotted under a mesh can resume without one and
    vice versa; the meta check deliberately excludes placement).
    Data-parallel plans (``data`` shards > 1) are not supported here —
    use the single-scan driver for those.
    """
    import jax
    import jax.numpy as jnp

    if num_samples <= 0:
        raise ValueError("num_samples must be positive")
    total = num_warmup + num_samples
    seg = int(checkpoint_every) if checkpoint_every else max(1, total // 10)
    if seg <= 0:
        raise ValueError("checkpoint_every must be positive")

    from repro.sharding.mesh import ShardedRun
    plan = ShardedRun.normalize(mesh)
    if plan is not None and plan.is_trivial:
        plan = None
    if plan is not None:
        if plan.num_data_shards > 1:
            raise ValueError(
                "the segmented driver shards chains only; data-parallel "
                "plans (data shards > 1) require the single-scan "
                "run_chains path (checkpointing disabled)")
        plan.validate_chains(num_chains)

    from repro.core.program import (ProgramKey, kernel_fingerprint,
                                    model_fingerprint, program_cache, span)
    cache = program_cache()
    cstats0 = cache.stats()

    with span("repro.run_chains.setup"):
        tvi, kern, dim, q0s, chain_keys = setup_chain_driver(
            key, model, sampler, num_chains=num_chains,
            init_varinfo=init_varinfo, init_jitter=init_jitter,
            backend=backend)

    # presplit per-draw keys with the SAME derivation as the single-scan
    # driver — slicing a presplit block is what makes segment boundaries
    # invisible to the chain. Held as HOST arrays: numpy slicing is free,
    # whereas slicing a device array compiles a fresh mini-executable per
    # distinct slice window (one per segment)
    wkeys = (np.asarray(jax.vmap(lambda ck: jax.random.split(
        jax.random.fold_in(ck, 1), num_warmup))(chain_keys))
        if num_warmup > 0 else None)
    skeys = np.asarray(jax.vmap(lambda ck: jax.random.split(
        jax.random.fold_in(ck, 2), num_samples))(chain_keys))

    # the health summary (NaN flag, per-chain accept/logp means, divergence
    # count) is computed INSIDE the segment program — one fused reduction
    # per segment and only O(num_chains) scalars cross to the host, so the
    # guard rails add no per-segment transfer of the draw buffers
    def _bad(tree):
        b = jnp.zeros((), bool)
        for leaf in jax.tree_util.tree_leaves(tree):
            arr = jnp.asarray(leaf)
            # NaN — not inf — is the trigger: a legitimately impossible
            # state has logp == -inf, a blown-up kernel produces NaN
            if jnp.issubdtype(arr.dtype, jnp.floating):
                b = b | jnp.isnan(arr).any()
        return b

    # strip weak types from the states that FEED segment programs: a
    # weak-typed leaf out of init (python-scalar step size etc.) has a
    # different aval than the same leaf out of warm/step, so without this
    # the warm and sample programs would each compile TWICE per run — once
    # for the init-shaped carry and again for their own output
    def _strong(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.lax.convert_element_type(x, jnp.asarray(x).dtype),
            tree)

    def _segment_fns(k):
        def warm_seg(state, ts, ks):
            def body(s, inp):
                return k.warm(s, inp[0], inp[1]), None
            s, _ = jax.lax.scan(body, state, (ts, ks))
            return s, _bad(s)

        def samp_seg(state, ks):
            s, outs = jax.lax.scan(k.step, state, ks)
            summ = {
                "bad": _bad(s) | _bad(outs),
                "logp_mean": outs["logp"].mean(),
                "acc_mean": (outs["accept_prob"].mean()
                             if "accept_prob" in outs
                             else jnp.ones(())),
                "div": (outs["diverging"].sum().astype(jnp.int32)
                        if "diverging" in outs else jnp.zeros((), jnp.int32)),
            }
            return s, outs, summ

        return (jax.jit(lambda q: _strong(jax.vmap(k.init)(q))),
                jax.jit(jax.vmap(warm_seg)),
                jax.jit(jax.vmap(samp_seg)),
                jax.jit(lambda s: _strong(jax.vmap(k.finalize)(s))))

    # the segment-function tuple is cached like the single-scan chain
    # program: a resumed (or merely repeated) run with the same (model,
    # layout, sampler config, backend) reuses the SAME jitted closures,
    # so jax's executable cache — which keys on function identity —
    # carries over and no segment re-traces
    kfp = kernel_fingerprint(sampler)
    if kfp is not None:
        seg_key = ProgramKey(model_fingerprint(model), "segment_fns",
                             tvi.layout, (), backend, (kfp, "primary"),
                             plan.fingerprint() if plan is not None else ())
        fns = cache.get_or_build(seg_key, lambda: _segment_fns(kern))
    else:
        fns = _segment_fns(kern)
    init_fn, warm_fn, samp_fn, final_fn = fns

    # chains-only mesh placement: lay the fleet inputs over the chain
    # devices once; the sharding then propagates through init and every
    # segment program (the carry keeps its placement across segments)
    _shard_keys = lambda a: a  # noqa: E731 - identity off-mesh
    if plan is not None:
        csh = plan.chain_sharding()
        q0s = jax.device_put(q0s, csh)
        _shard_keys = lambda a: jax.device_put(jnp.asarray(a), csh)  # noqa: E731
    state = init_fn(q0s)

    # preallocate full-run draw/stat buffers from the step's out spec
    out_spec = jax.eval_shape(samp_fn, state, skeys[:, :1])[1]
    q_buf = np.zeros((num_chains, num_samples, dim),
                     dtype=out_spec["q"].dtype)
    stat_bufs = {k: np.zeros((num_chains, num_samples) + v.shape[2:],
                             dtype=v.dtype)
                 for k, v in out_spec.items() if k != "q"}
    counters = {"nonfinite": np.zeros(num_chains, np.int64),
                "divergences": np.zeros(num_chains, np.int64),
                "fallbacks": np.zeros((), np.int64),
                "cache_misses": np.zeros((), np.int64),
                "cache_retraces": np.zeros((), np.int64)}

    # format bumped to /2 when the cache counters joined RunState: a /1
    # snapshot has a different pytree and is refused by the meta check;
    # bumped to /3 when NUTS's stat_bufs gained ``n_leapfrog``
    meta = {"format": "run_chains/3", "num_chains": int(num_chains),
            "num_warmup": int(num_warmup), "num_samples": int(num_samples),
            "dim": int(dim), "sampler": type(sampler).__name__,
            "backend": backend,
            "key_data": np.asarray(jax.random.key_data(key)).tolist()}

    # draw blocks stay ON DEVICE until a checkpoint (or the end of the
    # run) needs the host buffers — with checkpointing disabled the
    # segmented driver transfers exactly as much as the single-scan one
    pending = []

    def _flush():
        for d0, d1, o in pending:
            o = jax.device_get(o)
            q_buf[:, d0:d1] = o["q"]
            for name, buf in stat_bufs.items():
                buf[:, d0:d1] = o[name]
        pending.clear()

    # cache counters accumulate ACROSS resumes: the restored totals are
    # the base, this session's cache-stat delta is added on top at every
    # snapshot (retraces include nested density-program traces)
    cache_base = {"misses": 0, "retraces": 0}

    def _sync_cache_counters():
        s = cache.stats()
        counters["cache_misses"] = np.int64(
            cache_base["misses"] + max(0, s["misses"] - cstats0["misses"]))
        counters["cache_retraces"] = np.int64(
            cache_base["retraces"]
            + max(0, s["retraces"] - cstats0["retraces"]))

    def _snapshot(it):
        # buffers are COPIED: the async writer must see a frozen view
        # while the next segment mutates the live ones
        _flush()
        _sync_cache_counters()
        return RunState(np.int64(it), state, q_buf.copy(),
                        {k: v.copy() for k, v in stat_bufs.items()},
                        {k: v.copy() for k, v in counters.items()})

    it = 0
    resumed_from = None
    ckpt = None
    if checkpoint_dir:
        ckpt = AsyncCheckpointer(checkpoint_dir, keep=checkpoint_keep)
        last = latest_step(checkpoint_dir)
        if last is not None:
            _check_meta(read_meta(checkpoint_dir, last), meta, checkpoint_dir)
            _, restored = restore(checkpoint_dir, last, target=_snapshot(0))
            it = int(restored.iteration)
            state = restored.kernel_state
            q_buf = np.asarray(restored.q_buf)
            stat_bufs = {k: np.asarray(v)
                         for k, v in restored.stat_bufs.items()}
            counters = {k: np.asarray(v)
                        for k, v in restored.counters.items()}
            cache_base = {"misses": int(counters["cache_misses"]),
                          "retraces": int(counters["cache_retraces"])}
            resumed_from = it

    own_handler = preemption is None and checkpoint_dir is not None
    if own_handler:
        preemption = PreemptionHandler()

    # graceful degradation target: same state structure, reference-only
    # numerics; built lazily (the fallback path is the cold path)
    ref_fns = None

    def _get_ref_fns():
        nonlocal ref_fns
        if ref_fns is not None:
            return ref_fns
        ref_sampler = reference_variant(sampler)
        if ref_sampler is None:
            ref_fns = False
            return ref_fns
        ld_ref = model.make_logdensity_fn(tvi, backend="reference")
        ref_kern = ref_sampler.make_kernel(ld_ref, dim)
        proto = jax.eval_shape(jax.vmap(ref_kern.init), q0s)
        if (jax.tree_util.tree_structure(proto)
                != jax.tree_util.tree_structure(state)):
            warnings.warn(
                "reference fallback disabled: reference kernel state "
                "structure differs from the primary kernel's",
                RuntimeWarning)
            ref_fns = False
            return ref_fns
        ref_fns = _segment_fns(ref_kern)
        return ref_fns

    rails = _GuardRails(num_chains, stuck_accept=stuck_accept,
                        outlier_scale=outlier_scale, patience=patience)
    preempted = False

    try:
        while it < total:
            in_warmup = it < num_warmup
            end = min(it + seg, num_warmup if in_warmup else total)
            prev_state = state
            if in_warmup:
                ts = np.broadcast_to(
                    np.arange(it, end, dtype=np.float32),
                    (num_chains, end - it))
                wk = _shard_keys(wkeys[:, it:end])
                state, badv = warm_fn(state, ts, wk)
                bad = np.asarray(badv)
                if bad.any():
                    counters["nonfinite"] += bad.astype(np.int64)
                    rf = _get_ref_fns() if fallback else False
                    if rf:
                        state, _ = rf[1](prev_state, ts, wk)
                        counters["fallbacks"] = counters["fallbacks"] + 1
            else:
                d0, d1 = it - num_warmup, end - num_warmup
                sk = _shard_keys(skeys[:, d0:d1])
                state, outs, summ = samp_fn(state, sk)
                summ = jax.device_get(summ)
                bad = np.asarray(summ["bad"])
                if bad.any():
                    counters["nonfinite"] += bad.astype(np.int64)
                    rf = _get_ref_fns() if fallback else False
                    if rf:
                        state, outs, summ = rf[2](prev_state, sk)
                        summ = jax.device_get(summ)
                        counters["fallbacks"] = counters["fallbacks"] + 1
                pending.append((d0, d1, outs))
                counters["divergences"] += \
                    np.asarray(summ["div"]).astype(np.int64)
                rails.record(np.asarray(summ["acc_mean"], np.float64),
                             np.asarray(summ["logp_mean"], np.float64))
            it = end
            if num_warmup and it == num_warmup:
                # freeze adapted quantities exactly once, at the boundary
                # — a resumed run restores a post-finalize state, so this
                # fires only when warmup completed in THIS process
                state = final_fn(state)
            if preemption is not None and preemption.preempted:
                preempted = True
                if ckpt:
                    ckpt.wait()
                    save(checkpoint_dir, it, _snapshot(it),
                         keep=checkpoint_keep, meta=meta)
                break
            if ckpt:
                ckpt.save(it, _snapshot(it), meta=meta)
        if ckpt:
            ckpt.wait()
            if not preempted and latest_step(checkpoint_dir) != total:
                save(checkpoint_dir, total, _snapshot(total),
                     keep=checkpoint_keep, meta=meta)
    finally:
        if ckpt:
            ckpt.wait()
        if own_handler:
            preemption.uninstall()

    _flush()
    _sync_cache_counters()
    completed_samples = max(0, it - num_warmup)
    stats = {k: v[:, :completed_samples] for k, v in stat_bufs.items()}
    if completed_samples:
        chain = package_draws(tvi, jnp.asarray(q_buf[:, :completed_samples]),
                              stats=stats)
    else:
        proto = tvi.invlink().as_dict()
        chain = Chain({k: np.zeros((num_chains, 0) + np.shape(v))
                       for k, v in proto.items()}, stats=stats)
    chain.health = ChainHealth(
        num_chains=num_chains, target_warmup=num_warmup,
        target_samples=num_samples, completed=it,
        divergences=counters["divergences"].copy(),
        nonfinite=counters["nonfinite"].copy(),
        stuck=rails.stuck(), outliers=rails.outliers(),
        fallback_segments=int(counters["fallbacks"]),
        preempted=preempted, resumed_from=resumed_from,
        checkpoint_dir=checkpoint_dir,
        cache_hits=max(0, cache.stats()["hits"] - cstats0["hits"]),
        cache_misses=int(counters["cache_misses"]),
        cache_retraces=int(counters["cache_retraces"]),
        fingerprint_bytes=(cache.stats()["fingerprint_bytes"]
                           - cstats0["fingerprint_bytes"]))
    return chain
