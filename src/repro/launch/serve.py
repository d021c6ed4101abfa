"""Batched-request serving drivers: LM decode and probability queries.

Two serving paths share this module:

* **LM path** (``serve_batch``) — continuous-batching-lite: requests are
  grouped into a fixed batch, each request tracks its own position;
  decode steps run until every request emits ``max_new`` tokens. The
  decode step is the same compiled function the dry-run lowers for the
  ``decode_*`` / ``long_*`` cells.
* **Query path** (``QueryServer``) — heterogeneous ``prob`` requests are
  lowered through :func:`repro.core.queries.prepare_query`, grouped by
  program-cache key (model x query kind x shape signature), padded to a
  power-of-two lane count, and evaluated as ONE vmapped program per
  group. Latency/throughput/padding counters ride along.

Usage:
  python -m repro.launch.serve --arch smollm-360m --smoke \\
      --batch 4 --prompt-len 32 --max-new 16
  python -m repro.launch.serve --queries --requests 32
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.models import bayes_lm
from repro.nn import lm
from repro.runtime.compile_cache import enable_compile_cache


def serve_batch(arch: str, *, smoke: bool = True, batch: int = 4,
                prompt_len: int = 32, max_new: int = 16,
                temperature: float = 0.0, seed: int = 0):
    cfg = configs.get_smoke_config(arch) if smoke else configs.get_config(arch)
    params = lm.init_params(cfg, seed=seed)
    key = jax.random.PRNGKey(seed)
    k_prompt, k_extra, key = jax.random.split(key, 3)
    prompts = jax.random.randint(k_prompt, (batch, prompt_len), 0, cfg.vocab)

    extras = {}
    memory_kv = None
    n_prefix = 0
    if cfg.enc_layers > 0:
        frames = jax.random.normal(
            k_extra, (batch, cfg.n_prefix, cfg.d_model),
            jnp.float32).astype(cfg.dtype) * 0.1
        extras["enc_frames"] = frames
        memory = lm.encode(cfg, params, frames)
        memory_kv = lm.make_cross_kv(cfg, params, memory)
    elif cfg.n_prefix > 0:
        extras["prefix_embeds"] = jax.random.normal(
            k_extra, (batch, cfg.n_prefix, cfg.d_model),
            jnp.float32).astype(cfg.dtype) * 0.1
        n_prefix = cfg.n_prefix

    max_len = prompt_len + n_prefix + max_new
    cache = lm.init_cache(cfg, batch, max_len)

    prefill = jax.jit(bayes_lm.make_prefill_step(cfg))
    decode = jax.jit(bayes_lm.make_serve_step(cfg, temperature),
                     donate_argnums=(2,))

    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts, cache, **extras)
    first = jnp.argmax(logits[:, -1, :].astype(jnp.float32), -1)
    first = first.astype(jnp.int32)[:, None]
    jax.block_until_ready(first)
    t_prefill = time.perf_counter() - t0

    out_tokens = [first]
    token = first
    pos = jnp.full((batch,), prompt_len + n_prefix, jnp.int32)
    t0 = time.perf_counter()
    for i in range(max_new - 1):
        key, sub = jax.random.split(key)
        token, _, cache = decode(params, token, cache, pos + i,
                                 memory_kv=memory_kv)
        out_tokens.append(token)
    jax.block_until_ready(token)
    t_decode = time.perf_counter() - t0

    generated = jnp.concatenate(out_tokens, axis=1)
    stats = {
        "prefill_s": t_prefill,
        "decode_s_per_token": t_decode / max(max_new - 1, 1),
        "tokens": np.asarray(generated),
    }
    return generated, stats


# ---------------------------------------------------------------------------
# Probability-query serving
# ---------------------------------------------------------------------------
def _next_pow2(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


@dataclasses.dataclass
class QueryServerStats:
    """Counters for one ``QueryServer`` lifetime."""

    requests: int = 0
    batches: int = 0
    groups: int = 0            # distinct cache keys seen
    padded_lanes: int = 0      # wasted (padding) evaluations
    latency_s: float = 0.0     # wall time spent evaluating batches
    cache_hits: int = 0        # program-cache hits while serving
    cache_misses: int = 0      # programs compiled on behalf of requests

    @property
    def throughput_qps(self) -> float:
        return self.requests / self.latency_s if self.latency_s > 0 else 0.0

    def as_dict(self) -> dict:
        return {
            "requests": self.requests, "batches": self.batches,
            "groups": self.groups, "padded_lanes": self.padded_lanes,
            "latency_s": self.latency_s,
            "throughput_qps": self.throughput_qps,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
        }


class QueryServer:
    """Batch heterogeneous ``prob`` requests into padded vmapped programs.

    Requests are (spec, bindings) pairs. Each is lowered with
    ``prepare_query``; requests sharing a program-cache key (same model,
    query kind, shape signature) are stacked into one batch, padded to
    the next power-of-two lane count (so a trickle of odd batch sizes
    compiles a handful of bucket programs, not one per size), and
    evaluated by a cached ``vmap`` of the per-request program.
    """

    def __init__(self, cache=None):
        from repro.core.program import program_cache
        self.cache = cache if cache is not None else program_cache()
        self.stats = QueryServerStats()
        self._seen_keys = set()

    def _batched_program(self, pq, bucket: int):
        """Cached vmap of ``pq``'s raw program over ``bucket`` lanes."""
        from repro.core.program import CompiledProgram, ProgramKey
        k = pq.key
        bkey = ProgramKey(k.model, k.kind + "/batched", k.layout,
                          k.batch + (bucket,), k.backend, k.extra)
        return self.cache.get_or_build(
            bkey, lambda: CompiledProgram(bkey, jax.vmap(pq.program.raw)))

    def serve(self, requests: Sequence[Tuple[str, Dict[str, Any]]]
              ) -> List[jax.Array]:
        """Evaluate a batch of (spec, bindings) requests.

        Returns per-request log probabilities in request order; updates
        the latency/throughput/padding counters.
        """
        from repro.core.queries import prepare_query

        cstats0 = self.cache.stats()
        t0 = time.perf_counter()
        prepared = [prepare_query(spec, dict(b), cache=self.cache)
                    for spec, b in requests]

        groups: Dict[Any, List[int]] = {}
        for i, pq in enumerate(prepared):
            groups.setdefault(pq.key, []).append(i)

        results: List[Optional[jax.Array]] = [None] * len(prepared)
        for key, idxs in groups.items():
            self._seen_keys.add(key)
            bucket = _next_pow2(len(idxs))
            pad = bucket - len(idxs)
            # pad by repeating the last request's lane; padded lanes are
            # computed then dropped
            lanes = idxs + [idxs[-1]] * pad
            n_args = len(prepared[idxs[0]].args)
            stacked = tuple(
                jnp.stack([prepared[i].args[j] for i in lanes])
                for j in range(n_args))
            prog = self._batched_program(prepared[idxs[0]], bucket)
            out = prog(*stacked)
            for lane, i in enumerate(idxs):
                results[i] = out[lane]
            self.stats.padded_lanes += pad
        jax.block_until_ready([r for r in results if r is not None])

        self.stats.latency_s += time.perf_counter() - t0
        self.stats.requests += len(requests)
        self.stats.batches += 1
        self.stats.groups = len(self._seen_keys)
        cstats1 = self.cache.stats()
        self.stats.cache_hits += max(0, cstats1["hits"] - cstats0["hits"])
        self.stats.cache_misses += max(
            0, cstats1["misses"] - cstats0["misses"])
        return results


def _demo_query_requests(num_requests: int, seed: int = 0):
    """Heterogeneous demo workload over a small linear-regression model."""
    from repro import model, observe, sample
    from repro.dists import InverseGamma, MvNormalDiag, Normal

    @model
    def linreg(X, y):
        w = sample("w", MvNormalDiag(jnp.zeros(3), jnp.ones(3)))
        s = sample("s", InverseGamma(2.0, 3.0))
        observe("y", Normal(X @ w, jnp.sqrt(s)), y)

    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(num_requests):
        X = rng.normal(size=(4, 3)).astype(np.float32)
        y = rng.normal(size=(4,)).astype(np.float32)
        w = rng.normal(size=(3,)).astype(np.float32)
        if i % 3 == 2:  # every third request: posterior predictive
            chain = {"w": rng.normal(size=(8, 3)).astype(np.float32),
                     "s": np.ones(8, np.float32)}
            reqs.append(("X = Xn, y = yn | chain = c, model = m",
                         {"Xn": X, "yn": y, "c": chain, "m": linreg}))
        elif i % 3 == 1:  # prior query (data as traced query inputs so
            # requests with different content share one program)
            reqs.append(("w = w0, s = 1.0 | X = Xn, y = yn, model = m",
                         {"Xn": X, "yn": y, "w0": w, "m": linreg}))
        else:  # likelihood query
            reqs.append(("X = Xn, y = yn | w = w0, s = 1.0, model = m",
                         {"Xn": X, "yn": y, "w0": w, "m": linreg}))
    return reqs


def serve_queries(num_requests: int = 32, batch: int = 8,
                  seed: int = 0) -> QueryServerStats:
    """CLI/CI entry: run the demo workload through a ``QueryServer``."""
    server = QueryServer()
    reqs = _demo_query_requests(num_requests, seed=seed)
    for off in range(0, len(reqs), batch):
        server.serve(reqs[off:off + batch])
    return server.stats


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=configs.ARCH_NAMES,
                   help="LM serving path (required unless --queries)")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--queries", action="store_true",
                   help="serve batched probability queries instead of LM")
    p.add_argument("--requests", type=int, default=32,
                   help="(--queries) number of demo requests")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    enable_compile_cache()

    if args.queries:
        stats = serve_queries(num_requests=args.requests,
                              batch=args.batch if args.batch > 0 else 8,
                              seed=args.seed)
        d = stats.as_dict()
        print(f"[serve] {d['requests']} queries in {d['batches']} batches "
              f"({d['groups']} program groups, {d['padded_lanes']} padded "
              f"lanes)")
        print(f"[serve] latency {d['latency_s']:.3f}s total, "
              f"{d['throughput_qps']:.1f} queries/s; program cache "
              f"{d['cache_hits']} hit(s) / {d['cache_misses']} miss(es)")
        return 0

    if args.arch is None:
        p.error("--arch is required unless --queries is given")
    gen, stats = serve_batch(args.arch, smoke=args.smoke, batch=args.batch,
                             prompt_len=args.prompt_len,
                             max_new=args.max_new,
                             temperature=args.temperature)
    print(f"[serve] prefill {stats['prefill_s']:.3f}s, "
          f"decode {stats['decode_s_per_token'] * 1e3:.1f} ms/token")
    print(f"[serve] generated shape {gen.shape}; "
          f"first row: {np.asarray(gen)[0][:12]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
