"""The control's readings, the upper ends the limits of ``correct`` are
set from, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --steps <n> \\
        --seeds <s1,s2,...>

For each seed, the control (the plain reference in bfloat16 in the
program's place, ``harness.control``) over ``--steps`` timesteps, in one
process.  Prints one JSON line per seed; the benchmark's own runs never run
it.  The lower ends are the ``checks`` of the benchmark's own runs.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip import harness

    import jax

    cell = harness.load_cell(args.workload)
    if jax.devices()[0].platform == "tpu":
        harness.configure_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        checks = harness.control(cell, seed, args.steps)
        print(json.dumps({"reading": "control", "seed": seed,
                          "steps": args.steps, "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
