"""End-to-end training driver: train a llama-style model through the full
production path — sharded train_step, AdamW + cosine schedule, deterministic
data pipeline, periodic checkpointing and resume.

Default ("tiny") trains a CPU-sized model for 40 steps in ~2 minutes and
verifies the loss dropped.  ``--preset 100m --steps 300`` runs a ~100M-param
model for a few hundred steps (hours on this CPU container, the intended
config on real hardware) — the code path is IDENTICAL to what the dry-run
compiles for the 512-chip mesh.

  PYTHONPATH=src python examples/train_lm.py [--preset 100m] [--steps N]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_reduced_config  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.train import AdamWConfig, adamw_init, make_train_step  # noqa: E402
from repro.train.checkpoint import latest_checkpoint, restore_checkpoint, save_checkpoint  # noqa: E402
from repro.train.data import DataConfig, PrefetchIterator, TokenStream  # noqa: E402


def build_config(preset: str):
    base = get_reduced_config("llama3_2_1b")
    if preset == "tiny":
        return base.with_(num_layers=4, d_model=256, num_heads=8,
                          num_kv_heads=4, head_dim=32, d_ff=512,
                          vocab_size=2048), 8, 128
    # ~100M params
    return base.with_(num_layers=12, d_model=768, num_heads=12,
                      num_kv_heads=4, head_dim=64, d_ff=2048,
                      vocab_size=32000), 8, 512


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=["tiny", "100m"], default="tiny")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()

    cfg, batch, seq = build_config(args.preset)
    steps = args.steps or (40 if args.preset == "tiny" else 300)
    n_params = cfg.param_count()
    print(f"model: {cfg.name}-{args.preset} ({n_params / 1e6:.1f}M params) "
          f"| {steps} steps x batch {batch} x seq {seq}")

    mesh = make_host_mesh()
    opt_cfg = AdamWConfig(peak_lr=3e-3, warmup_steps=max(5, steps // 10),
                          total_steps=steps)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt_state = adamw_init(params)
    start = 0
    newest = latest_checkpoint(args.ckpt_dir)
    if newest is not None:
        _, st = restore_checkpoint(args.ckpt_dir, newest, {"p": params, "o": opt_state})
        params = jax.tree.map(jnp.asarray, st["p"])
        opt_state = jax.tree.map(jnp.asarray, st["o"])
        start = newest
        print(f"resumed from step {start}")

    step_fn = jax.jit(make_train_step(cfg, opt_cfg, mesh))
    stream = TokenStream(DataConfig(cfg.vocab_size, seq, batch))
    it = PrefetchIterator(stream, start_step=start)
    first_loss = None
    try:
        while True:
            s, batch_np = next(it)
            if s >= steps:
                break
            t0 = time.perf_counter()
            jb = {k: jnp.asarray(v) for k, v in batch_np.items()}
            params, opt_state, m = step_fn(params, opt_state, jb)
            loss = float(m["loss"])
            if first_loss is None:
                first_loss = loss
            if (s + 1) % 5 == 0 or s == 0:
                print(f"step {s + 1:4d}/{steps} loss={loss:.4f} "
                      f"lr={float(m['lr']):.2e} ({time.perf_counter() - t0:.2f}s)")
            if (s + 1) % 10 == 0 or s + 1 == steps:
                save_checkpoint(args.ckpt_dir, s + 1, {"p": params, "o": opt_state})
    finally:
        it.close()
    print(f"loss: {first_loss:.4f} -> {loss:.4f} "
          f"({'improved' if loss < first_loss else 'NO IMPROVEMENT'})")
    assert loss < first_loss, "training failed to reduce loss"


if __name__ == "__main__":
    main()
