"""What decides ``correct`` has to be able to fail: the control (the plain
reference one precision lower, in the program's place) and a program broken
underneath the timed path each come out not correct, at a tiny grid on the
CPU.  The readings the limits are set from come from the chip
(``benchmarks/chip/control.py``, PERF.md)."""
import sys
import time
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness  # noqa: E402
from repro.core import engine  # noqa: E402

TINY = {"cloverleaf2d": [48, 40], "opensbli": [16, 16, 16]}


def tiny_cell(name: str) -> dict:
    resident = {"config": "clover2d-bm16", "traffic": "resident", "chips": 1}
    cell = harness.load_cell(
        name, workload=resident if name == "clover2d-bm16-resident" else None)
    cell["config"]["grid"] = TINY[cell["config"]["app"]]
    return cell


def failed(checks: dict) -> bool:
    return any(not c["value"] <= c["limit"] for c in checks.values())


@pytest.mark.parametrize("name", ["clover2d-bm16-ooc3x", "opensbli-tgv256-ooc3x"])
def test_control_fails(name):
    for seed in (3, 2 ** 31 + 11):
        assert failed(harness.control(tiny_cell(name), seed, 6))


# Faults planted in what a tile program returns, from the first step on.
def unchanged(old, new):
    """The step returns its state unchanged."""
    return old


def half(old, new):
    """Half of each tile's rows left out: they keep their old values."""
    out = dict(new)
    for k, v in new.items():
        h = v.shape[0] // 2
        out[k] = v.at[h:].set(old[k][h:])
    return out


def altered(old, new):
    """One answer altered where it is produced: a 1% change to one cell of
    every array the tile program writes."""
    return {k: v.at[(2,) * v.ndim].multiply(1.01) for k, v in new.items()}


FAULTS = [unchanged, half, altered]


def run_with_fault(monkeypatch, name, fault):
    cell = tiny_cell(name)
    active = []
    build = engine.TileEngine._build

    def broken_build(self, sig):
        fn = build(self, sig)

        def tile_fn(slots, starts, origins):
            new, reds = fn(slots, starts, origins)
            return (fault(slots, new) if active else new), reds
        return tile_fn

    drv_cls = harness.driver_module(cell["config"]).Driver
    init = drv_cls.init

    def init_then_break(self, sess, seed, cyclic):
        init(self, sess, seed, cyclic)
        active.append(True)

    monkeypatch.setattr(engine.TileEngine, "_build", broken_build)
    monkeypatch.setattr(drv_cls, "init", init_then_break)
    rec, checks = harness.run_cell(cell, 2 ** 31 + 3, 0.2, False,
                                   time.perf_counter(), harness.CompileLog(),
                                   jax.devices()[0])
    return harness.result_line(cell, rec, checks, False,
                               {"platform": "cpu", "kind": "cpu", "count": 1})


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", ["opensbli-tgv256-ooc3x", "clover2d-bm16-resident"])
def test_fault_is_not_correct(monkeypatch, name, fault):
    line = run_with_fault(monkeypatch, name, fault)
    assert line["correct"] is False and line["failed"] == line["attempted"]
