"""Chip benchmark: one cell of ``BENCHMARK.json``, one process.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell asks
for.  Without a TPU, or with fewer chips, it exits non-zero and prints no
result.  It builds the cell's fields from ``--seed``, warms up every chain
shape the window uses (set-up), runs whole timesteps until ``--seconds``
have passed (``--trace 1``: the mix's ``trace_steps`` timesteps under the
profiler instead), compares what the timed path produced with the plain
reference, and prints one JSON object as its last line of output: the
cell's end-to-end metrics (``--trace 0``) or per-layer metrics
(``--trace 1``), the device, and under ``checks`` each number compared with
its limit (also the last lines of standard error).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # The checkout's own program and benchmark, never an installed copy; the
    # script's directory is not put first, so its modules shadow nothing.
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    import repro  # noqa: F401  (the system under test must be beside us)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU: JAX reports platform {devices[0].platform!r}; "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"run.py: {args.workload} needs {cell['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    harness.peaks_for(devices[0].device_kind)
    print(f"[bench] compile cache: {harness.configure_compile_cache()}")
    log = harness.CompileLog()
    rec, checks = harness.run_cell(cell, args.seed, args.seconds,
                                   bool(args.trace), T_START, log, devices[0])
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    line = harness.result_line(cell, rec, checks, bool(args.trace), info)
    print(f"[bench] {args.workload} seed {args.seed}: {rec['steps']} timesteps "
          f"in {rec['window_s']:.6f} s; set-up {rec['setup_s']:.6f} s "
          f"({rec['setup_compiles']} compiles, {rec['setup_compile_s']:.3f} s "
          f"compiling, {rec['setup_plan_s']:.3f} s planning); compiles in the "
          f"window: {rec['window_compiles']['compiles']} "
          f"({rec['window_compiles']['compile_s']:.3f} s; per step "
          f"{rec['step_compiles']}; persistent cache off); planning in the "
          f"window {rec['window_plan_s']:.3f} s; "
          f"tiles per chain {rec['window_tiles']}; step walls "
          f"{[round(w, 4) for w in rec['step_walls']]}; "
          f"{rec['total_steps']} timesteps compared with the reference")
    for name, c in checks.items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
