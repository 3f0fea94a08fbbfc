import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
    or "--xla_force_host_platform_device_count=512"
)

"""Multi-pod dry-run driver.

For every (architecture x input-shape x mesh) cell: build the production
mesh, lower the REAL step function (train_step / prefill / serve_step — the
same code the launcher runs) with ShapeDtypeStruct inputs and explicit
shardings, ``.compile()`` it, and record ``memory_analysis()`` +
``cost_analysis()`` + the post-SPMD HLO for the roofline pass.

  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax
import numpy as np


def _cell_step_and_args(cfg, shape, mesh, *, microbatches=1, compress=False,
                        fsdp=True, remat=True, tp=True):
    from repro.distributed.sharding import (
        batch_specs, cache_specs, param_specs, shardings_of)
    from repro.launch.specs import input_specs
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import make_prefill_step, make_serve_step, make_train_step
    from jax.sharding import NamedSharding, PartitionSpec as P

    ns = lambda spec: NamedSharding(mesh, spec)
    specs = input_specs(cfg, shape)
    p_specs = param_specs(specs["params"], cfg, mesh, fsdp=fsdp, tp=tp)
    p_sh = jax.tree.map(lambda s: ns(s), p_specs,
                        is_leaf=lambda x: isinstance(x, P))

    if shape.kind == "train":
        o_specs = {
            "mu": p_specs, "nu": p_specs, "step": P(),
        }
        o_sh = jax.tree.map(lambda s: ns(s), o_specs,
                            is_leaf=lambda x: isinstance(x, P))
        b_spec_tree = batch_specs(cfg, mesh, shape.global_batch,
                                  include_model=not tp)
        b_sh = {k: ns(b_spec_tree[k]) for k in specs["batch"]}
        fn = make_train_step(
            cfg, AdamWConfig(), mesh,
            microbatches=microbatches, compress_pod_grads=compress, remat=remat)
        jitted = jax.jit(
            fn,
            in_shardings=(p_sh, o_sh, b_sh),
            donate_argnums=(0, 1),
        )
        return jitted, (specs["params"], specs["opt_state"], specs["batch"])

    if shape.kind == "prefill":
        b_spec_tree = batch_specs(cfg, mesh, shape.global_batch)
        b_sh = {k: ns(b_spec_tree[k]) for k in specs["batch"]}
        fn = make_prefill_step(cfg, mesh)
        jitted = jax.jit(fn, in_shardings=(p_sh, b_sh))
        return jitted, (specs["params"], specs["batch"])

    # decode
    c_spec_tree = cache_specs(cfg, mesh, shape.global_batch)
    c_sh = {k: ns(c_spec_tree[k]) for k in specs["cache"]}
    ba = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    nb = int(np.prod([mesh.shape[a] for a in ba]))
    tok_sh = ns(P(ba if shape.global_batch % nb == 0 else None))
    fn = make_serve_step(cfg, mesh)
    jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, tok_sh), donate_argnums=(1,))
    return jitted, (specs["params"], specs["cache"], specs["tokens"])


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Optional[str],
             *, microbatches=1, compress=False, fsdp=True, remat=True, tp=True,
             save_hlo=True, tag=""):
    from repro.configs import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.models.config import SHAPES

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with mesh:
        jitted, args = _cell_step_and_args(
            cfg, shape, mesh, microbatches=microbatches, compress=compress,
            fsdp=fsdp, remat=remat, tp=tp)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    elapsed = time.time() - t0
    n_dev = int(np.prod(list(mesh.shape.values())))
    result = {
        "arch": cfg.name,
        "shape": shape_name,
        "mesh": "x".join(f"{k}={v}" for k, v in mesh.shape.items()),
        "devices": n_dev,
        "kind": shape.kind,
        "compile_s": round(elapsed, 1),
        "memory": {
            "argument_bytes_per_device": int(mem.argument_size_in_bytes),
            "output_bytes_per_device": int(mem.output_size_in_bytes),
            "temp_bytes_per_device": int(mem.temp_size_in_bytes),
            "alias_bytes_per_device": int(mem.alias_size_in_bytes),
            "peak_estimate_per_device": int(
                mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
        },
        "cost_analysis": {k: float(v) for k, v in cost.items()
                          if isinstance(v, (int, float)) and not k.startswith("utilization")},
        "flags": {"microbatches": microbatches, "compress": compress,
                  "fsdp": fsdp, "remat": remat, "tp": tp},
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        stem = f"{arch.replace('.', '_')}_{shape_name}_{'pod2' if multi_pod else 'pod1'}{tag}"
        with open(os.path.join(out_dir, stem + ".json"), "w") as f:
            json.dump(result, f, indent=1)
        if save_hlo:
            with open(os.path.join(out_dir, stem + ".hlo.txt"), "w") as f:
                f.write(compiled.as_text())
    return result, compiled


def main():
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", choices=["on", "off", "both"], default="both")
    ap.add_argument("--out", default="reports/dryrun")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-tp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-hlo", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    from repro.configs import ARCH_IDS, all_cells, shape_cells, get_config
    from repro.models.config import SHAPES

    if args.all:
        cells = [(a, s.name) for a, s in all_cells()]
    else:
        archs = [args.arch] if args.arch else ARCH_IDS
        cells = []
        for a in archs:
            names = ([args.shape] if args.shape
                     else [s.name for s in shape_cells(a)])
            for n in names:
                cells.append((a, n))

    pods = {"on": [True], "off": [False], "both": [False, True]}[args.multi_pod]
    failures = []
    for arch, shape_name in cells:
        for mp in pods:
            label = f"{arch} x {shape_name} x {'2-pod(512)' if mp else '1-pod(256)'}"
            try:
                res, compiled = run_cell(
                    arch, shape_name, mp, args.out,
                    microbatches=args.microbatches, compress=args.compress,
                    fsdp=not args.no_fsdp, remat=not args.no_remat,
                    tp=not args.no_tp,
                    save_hlo=not args.no_hlo, tag=args.tag)
                peak = res["memory"]["peak_estimate_per_device"] / 1e9
                flops = res["cost_analysis"].get("flops", 0)
                print(f"OK   {label}: peak/dev={peak:.2f}GB "
                      f"hlo_flops={flops:.3e} compile={res['compile_s']}s",
                      flush=True)
                del compiled
            except Exception as e:  # noqa: BLE001 — report and continue
                failures.append((label, repr(e)))
                print(f"FAIL {label}: {e}", flush=True)
                traceback.print_exc()
    print(f"\n{len(cells) * len(pods) - len(failures)} passed, {len(failures)} failed")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
