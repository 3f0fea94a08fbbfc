"""The chip benchmark's harness rehearsed on the CPU at tiny grids: every
cell of BENCHMARK.json runs through the harness's functions (set-up, window,
comparison with the plain reference, result line), and the command refuses
to run anywhere but on a TPU with the program beside it."""
import json
import shutil
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
TINY = {"cloverleaf2d": [48, 40], "opensbli": [16, 16, 16]}
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def log():
    return harness.CompileLog()


# The resident mix has no cell yet; it is rehearsed so that a later cell
# needs only its entry in BENCHMARK.json.
RESIDENT = {"config": "clover2d-bm16", "traffic": "resident", "chips": 1}


def tiny_cell(name: str) -> dict:
    cell = harness.load_cell(name, workload=RESIDENT if name not in CELLS else None)
    cell["config"]["grid"] = TINY[cell["config"]["app"]]
    return cell


RUNS = ([(name, 0) for name in CELLS]
        + [("clover2d-bm16-resident", 0), ("clover2d-bm16-resident", 1)])


@pytest.mark.parametrize("name,trace", RUNS)
def test_cell_rehearsal(name, trace, log):
    cell = tiny_cell(name)
    check_run(cell, trace, log)


def test_cadences_of_a_mix(log):
    """A mix's reduction and summary cadences (no cell uses them yet)."""
    cell = tiny_cell("clover2d-bm16-resident")
    cell["mix"].update(dt_read_every=2, summary_every=2)
    check_run(cell, 0, log)


def check_run(cell, trace, log):
    rec, checks = harness.run_cell(cell, 2 ** 31 + 7, 0.2, bool(trace),
                                   time.perf_counter(), log, jax.devices()[0])
    info = {"platform": "cpu", "kind": "cpu", "count": 1}
    line = harness.result_line(cell, rec, checks, bool(trace), info)
    assert list(line)[:5] == CONTRACT_KEYS and list(line)[-1] == "checks"
    assert line["correct"], checks
    assert jax.config.jax_enable_compilation_cache   # on again after the window
    assert line["attempted"] == rec["steps"] >= 1 and line["failed"] == 0
    assert rec["window_compiles"]["compiles"] == 0
    listed = {m["name"]: m for m in cell["per_layer" if trace else "end_to_end"]}
    assert set(line["metrics"]) <= set(listed)
    for metric, m in line["metrics"].items():
        assert m["unit"] == listed[metric]["unit"]
    if not trace:
        assert set(line["metrics"]) == {"step_s", "setup_s"}   # no HBM on the CPU
    else:
        # The CPU trace has no TPU plane: the trace's metrics stay silent.
        assert {"plan_setup_s", "compile_setup_s",
                "link_bytes_per_step"} <= set(line["metrics"])
        assert "device_idle_pct" not in line["metrics"]
    json.dumps(line)


def _run_command(cwd: Path):
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": str(cwd)})


def _has_result(out: str) -> bool:
    return any(ln.startswith("{") for ln in out.splitlines())


def test_command_refuses_without_tpu():
    proc = _run_command(ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not _has_result(proc.stdout)


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_command(tmp_path)
    assert proc.returncode != 0
    assert "No module named 'repro'" in proc.stderr
    assert not _has_result(proc.stdout)


def test_unknown_device_kind_fails():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.peaks_for("TPU v99")


def test_benchmark_json_files_are_found_by_name():
    bench_dir = ROOT / "benchmarks" / "chip"
    for conf in BENCH["configs"]:
        cfg = json.loads((ROOT / conf["file"]).read_text())
        assert cfg["name"] == conf["name"] and cfg["source"] == conf["source"]
        assert set(conf["reduced"]) == set(cfg["reduced"])
        assert (bench_dir / "apps" / f"{cfg['app']}.py").exists()
        assert (bench_dir / "reference" / f"{cfg['app']}.py").exists()
        assert set(cfg["limits"]) == {"field_err", "reduction_err"}
    for cell in BENCH["workloads"]:
        assert (bench_dir / "traffic" / f"{cell['traffic']}.json").exists()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (bench_dir / "metrics" / f"{m['name']}.py").exists()
