"""End-to-end distributed Bayesian-LM training driver.

Wires every substrate layer together: configs -> data pipeline ->
DynamicPPL log-joint (MiniBatchContext) -> MAP-Adam / SGLD step under
pjit -> async checkpointing -> fault-tolerance (preemption flag,
straggler monitor, heartbeats) -> auto-resume.

On the CPU container this trains the reduced (smoke) configs end-to-end
(see examples/bayesian_lm_train.py); on TPU the same driver takes the
full configs — the step function, shardings and checkpoint format are
identical (that is the point of the dry-run).

Usage:
  python -m repro.launch.train --arch smollm-360m --smoke --steps 200 \\
      --batch 8 --seq 128 --ckpt-dir /tmp/run0 [--mode map|sgld]
"""
from __future__ import annotations

import argparse
import functools
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, sharding
from repro.ckpt import AsyncCheckpointer, latest_step, restore
from repro.data import SyntheticTokens
from repro.launch import mesh as mesh_lib
from repro.models import bayes_lm
from repro.nn import lm
from repro.runtime import PreemptionHandler, StragglerDetector
from repro.runtime.compile_cache import enable_compile_cache


def make_mesh_or_none(data: int, model: int):
    n = len(jax.devices())
    if data * model > n:
        return None  # single-device CPU path: no mesh, no rules
    return mesh_lib.make_mesh((data, model), ("data", "model"))


def train(arch: str, *, smoke: bool = True, steps: int = 100,
          batch: int = 8, seq: int = 128, mode: str = "map",
          lr: float = 3e-4, microbatch: int = 1, ckpt_dir: str = "",
          ckpt_every: int = 50, keep: int = 3, seed: int = 0,
          mesh_shape: Optional[tuple] = None, log_every: int = 10,
          preempt: Optional[PreemptionHandler] = None):
    cfg = configs.get_smoke_config(arch) if smoke else configs.get_config(arch)
    data = SyntheticTokens(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                           seed=seed)
    init_fn, step_fn = bayes_lm.make_train_step(
        cfg, total_tokens=float(steps * batch * seq), mode=mode,
        learning_rate=lr, microbatch=microbatch)

    mesh = make_mesh_or_none(*mesh_shape) if mesh_shape else None
    rules = (sharding.DEFAULT_RULES.with_mesh(mesh) if mesh is not None
             else None)

    params = lm.init_params(cfg, seed=seed)
    state = init_fn(params)
    start = 0

    ckpt = AsyncCheckpointer(ckpt_dir, keep=keep) if ckpt_dir else None
    if ckpt_dir and latest_step(ckpt_dir) is not None:
        start, state = restore(ckpt_dir, target=state)
        print(f"[train] resumed from step {start}", flush=True)

    jit_step = jax.jit(step_fn, donate_argnums=(0,))
    preempt = preempt or PreemptionHandler(install=False)
    straggler = StragglerDetector(num_hosts=1)
    key = jax.random.PRNGKey(seed + 1)

    history = []
    t_last = time.perf_counter()
    ctx = sharding.use_rules(rules) if rules is not None else _nullcontext()
    with ctx:
        for step in range(start, steps):
            key, sub = jax.random.split(key)
            batch_t = data.batch(step)
            state, metrics = jit_step(state, sub, batch_t)
            if (step + 1) % log_every == 0 or step + 1 == steps:
                m = jax.device_get(metrics)
                now = time.perf_counter()
                straggler.record_step({0: now - t_last})
                t_last = now
                history.append((step + 1, float(m["nll"])))
                print(f"[train] step {step + 1}/{steps} "
                      f"nll/token {float(m['nll']):.4f} "
                      f"logjoint {float(m['logjoint']):.3e} "
                      f"gnorm {float(m['grad_norm']):.2f}", flush=True)
            if ckpt and ((step + 1) % ckpt_every == 0 or step + 1 == steps):
                ckpt.save(step + 1, state)
            if preempt.preempted:
                print("[train] preemption: final checkpoint + exit",
                      flush=True)
                if ckpt:
                    ckpt.save(step + 1, state)
                    ckpt.wait()
                return state, history
    if ckpt:
        ckpt.wait()
    return state, history


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True, choices=configs.ARCH_NAMES)
    p.add_argument("--smoke", action="store_true",
                   help="reduced config (CPU-feasible)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--mode", default="map", choices=("map", "sgld"))
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--microbatch", type=int, default=1)
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    args = p.parse_args(argv)
    enable_compile_cache()
    # context manager: SIGTERM/SIGINT handlers are restored on exit even
    # if train() raises, so embedding callers keep their own handlers
    with PreemptionHandler() as preempt:
        _, history = train(args.arch, smoke=args.smoke, steps=args.steps,
                           batch=args.batch, seq=args.seq, mode=args.mode,
                           lr=args.lr, microbatch=args.microbatch,
                           ckpt_dir=args.ckpt_dir,
                           ckpt_every=args.ckpt_every,
                           seed=args.seed, log_every=args.log_every,
                           preempt=preempt)
    if len(history) >= 2 and history[-1][1] >= history[0][1]:
        print("[train] WARNING: nll did not improve", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
