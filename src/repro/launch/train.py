"""Fault-tolerant training launcher.

Survival story (designed for 1000+ nodes, exercised here on one host):
  * resume: on start, restore the newest valid checkpoint in --ckpt-dir
    (atomic commits mean a SIGKILL mid-write never corrupts; the preemption
    test kills -9 and resumes bitwise-identically);
  * elastic: checkpoints are topology-free (host numpy + manifest); restore
    re-device_puts onto whatever mesh the current launch built, so restarts
    may change device counts;
  * deterministic data: the stream is counter-keyed by (seed, step, host) —
    resuming at step k replays exactly batch k without reading history;
  * straggler mitigation: input pipeline prefetch thread + per-step deadline
    watchdog (steps slower than --straggler-factor x median are logged and
    counted; on multi-host this is where you'd trigger re-balancing);
  * SIGTERM (preemption notice): checkpoint immediately, exit 0.

Usage (reduced config, CPU):
  PYTHONPATH=src python -m repro.launch.train --arch llama3_2_1b --reduced \
      --steps 20 --ckpt-dir /tmp/ckpt --ckpt-every 5
"""
from __future__ import annotations

import argparse
import signal
import sys
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--keep", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--sleep-per-step", type=float, default=0.0)  # test hook
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    from repro.configs import get_config, get_reduced_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import init_params
    from repro.train import AdamWConfig, adamw_init, make_train_step
    from repro.train.checkpoint import (
        latest_checkpoint, restore_checkpoint, save_checkpoint)
    from repro.train.data import DataConfig, PrefetchIterator, TokenStream

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    mesh = make_host_mesh(model=args.model_parallel)
    opt_cfg = AdamWConfig(peak_lr=args.lr, warmup_steps=max(2, args.steps // 10),
                          total_steps=args.steps)

    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    opt_state = adamw_init(params)
    start_step = 0

    if args.ckpt_dir:
        newest = latest_checkpoint(args.ckpt_dir)
        if newest is not None:
            _, state = restore_checkpoint(
                args.ckpt_dir, newest, {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            params = jax.tree.map(jnp.asarray, params)
            opt_state = jax.tree.map(jnp.asarray, opt_state)
            start_step = newest
            if not args.quiet:
                print(f"resumed from step {newest}", flush=True)

    train_step = jax.jit(make_train_step(cfg, opt_cfg, mesh,
                                         microbatches=args.microbatches))
    data = TokenStream(DataConfig(cfg.vocab_size, args.seq, args.batch,
                                  seed=args.seed))
    it = PrefetchIterator(data, start_step=start_step)

    stop = {"now": False}

    def on_sigterm(signum, frame):
        stop["now"] = True

    signal.signal(signal.SIGTERM, on_sigterm)

    step_times = []
    stragglers = 0
    step = start_step
    try:
        while step < args.steps:
            t0 = time.perf_counter()
            step, batch = next(it)
            if step >= args.steps:
                break
            jb = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt_state, metrics = train_step(params, opt_state, jb)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            if args.sleep_per_step:
                time.sleep(args.sleep_per_step)
            step_times.append(dt)
            med = float(np.median(step_times[-20:]))
            if len(step_times) > 3 and dt > args.straggler_factor * med:
                stragglers += 1
                if not args.quiet:
                    print(f"straggler: step {step} took {dt:.2f}s "
                          f"(median {med:.2f}s)", flush=True)
            if not args.quiet:
                print(f"step {step + 1}/{args.steps} loss={loss:.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} {dt:.2f}s",
                      flush=True)
            step += 1
            if args.ckpt_dir and (step % args.ckpt_every == 0 or step == args.steps
                                  or stop["now"]):
                save_checkpoint(args.ckpt_dir, step,
                                {"params": params, "opt": opt_state},
                                keep=args.keep)
            if stop["now"]:
                if not args.quiet:
                    print("SIGTERM: checkpointed, exiting", flush=True)
                break
    finally:
        it.close()
    if not args.quiet:
        print(f"done at step {step}; stragglers flagged: {stragglers}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
