"""Serving launchers.

Two entry points:

* **model decode** (default): batched decode with a KV/state cache — prefill
  over the prompt batch, then streamed decode steps; reports tokens/s and
  per-step latency.  With ``--offload``, layer weights stream from host
  memory through the out-of-core windowed schedule
  (:class:`repro.models.offload.StreamedDecoder` — the paper's technique
  applied to serving models larger than device memory); at most ``--window``
  layer slices are device-resident at any point.

* **stencil serving** (``stencil`` subcommand): the multi-tenant
  :class:`repro.serve.StencilServer` — N CloverLeaf2D tenants submitted from
  threads onto a shared ``sim:K`` lane pool with ledger-oracle admission
  control::

      python -m repro.launch.serve stencil --tenants 4 --mesh sim:2 \\
          --policy sjf --steps 3
"""
from __future__ import annotations

import argparse
import sys
import time


def stencil_main(argv=None):
    """Serve N stencil tenants through a shared StencilServer."""
    ap = argparse.ArgumentParser(prog="serve stencil")
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--mesh", default="sim:2",
                    help="lane pool, e.g. sim:4 (default sim:2)")
    ap.add_argument("--policy", default="fifo",
                    help="scheduling policy: fifo | sjf")
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--nx", type=int, default=48)
    ap.add_argument("--ny", type=int, default=48)
    ap.add_argument("--capacity-mb", type=float, default=4.0,
                    help="per-lane fast-memory capacity (forces tiling)")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    import threading

    from repro.apps.cloverleaf2d import CloverLeaf2D
    from repro.serve import StencilServer

    t0 = time.perf_counter()
    with StencilServer(args.mesh, policy=args.policy,
                       capacity_bytes=args.capacity_mb * 1e6) as server:
        errs = []

        def tenant_work(i: int) -> None:
            try:
                app = CloverLeaf2D(nx=args.nx, ny=args.ny,
                                   summary_every=args.steps)
                rt = server.session(f"tenant-{i}", priority=i % 2)
                try:
                    app.run(rt, steps=args.steps)
                finally:
                    rt.close()
            except BaseException as e:
                errs.append((i, e))

        threads = [threading.Thread(target=tenant_work, args=(i,))
                   for i in range(args.tenants)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = server.stats()
    if errs:
        print(f"tenant failures: {errs}", file=sys.stderr)
        return 1
    if not args.quiet:
        print(stats.summary())
        print(f"wall {time.perf_counter() - t0:.2f}s for "
              f"{stats.jobs_completed} chains across {args.tenants} tenants")
    return 0


def main(argv=None):
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "stencil":
        return stencil_main(argv[1:])

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--offload", action="store_true",
                    help="stream layer weights from host memory through the "
                         "out-of-core windowed schedule (dense/vlm families)")
    ap.add_argument("--window", type=int, default=3,
                    help="device-resident layer slices with --offload")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config, get_reduced_config
    from repro.models import decode_step, init_params
    from repro.models.transformer import init_cache

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    B = args.batch
    max_len = args.prompt_len + args.gen_tokens
    prompts = jax.random.randint(key, (B, args.prompt_len), 0, cfg.vocab_size)

    cache = init_cache(cfg, B, max_len, enc_len=args.prompt_len)
    if cfg.encdec:
        # stub frontend: random frame embeddings -> encoder KV via one forward
        cache["enc_k"] = jnp.zeros_like(cache["enc_k"]) + 0.01
        cache["enc_v"] = jnp.zeros_like(cache["enc_v"]) + 0.01

    streamer = None
    if args.offload:
        if cfg.family not in ("dense", "vlm"):
            print(f"--offload supports dense/vlm families, not {cfg.family}",
                  file=sys.stderr)
            return 2
        from repro.models.offload import StreamedDecoder

        streamer = StreamedDecoder(params, cfg, window=args.window)

        def step(p, c, t):
            return streamer.decode(c, t)
    else:
        step = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t))

    # prefill = teacher-forced decode over the prompt (exercises the cache
    # write path; a production server would batch-prefill via forward())
    t0 = time.perf_counter()
    for i in range(args.prompt_len):
        logits, cache = step(params, cache, prompts[:, i])
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    tok = jnp.argmax(logits, -1)
    lat = []
    generated = [tok]
    for i in range(args.gen_tokens - 1):
        t0 = time.perf_counter()
        logits, cache = step(params, cache, tok)
        tok = jnp.argmax(logits, -1)
        tok.block_until_ready()
        lat.append(time.perf_counter() - t0)
        generated.append(tok)
    out = jnp.stack(generated, 1)
    assert bool(jnp.isfinite(logits).all()), "non-finite logits"
    if not args.quiet:
        lat_ms = 1e3 * float(np.mean(lat)) if lat else 0.0
        line = (f"arch={cfg.name} batch={B} prefill={t_prefill:.2f}s "
                f"decode={lat_ms:.1f}ms/tok "
                f"({B * 1e3 / max(lat_ms, 1e-9):.0f} tok/s) "
                f"sample={np.asarray(out[0, :8]).tolist()}")
        if streamer is not None:
            line += (f" offload[window={streamer.window} "
                     f"resident={streamer.device_resident_bytes() / 1e6:.1f}MB "
                     f"modelled={streamer.stats.modelled_step_s * 1e3:.2f}ms/step]")
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
