"""The trace reduction on hand-made events: busy union, idle share, device
time per module and the labels on the idle gaps."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import xtrace  # noqa: E402


def test_union_clip_gaps():
    assert xtrace.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    assert xtrace.clip([(0, 3), (5, 10)], 2, 7) == [(2, 3), (5, 7)]
    assert xtrace.gaps([(2, 3), (5, 7)], 0, 10) == [(0, 2), (3, 5), (7, 10)]


def test_reduce_hand_made():
    ops = [(100, 200), (150, 250), (400, 450), (900, 1000), (1100, 1200)]
    modules = [("jit_tile_fn", 100, 250), ("jit_dynamic_update_slice", 400, 450),
               ("jit_tile_fn", 900, 1000), ("jit_tile_fn", 1100, 1200)]
    chips = {"/device:TPU:0": {"ops": ops, "modules": modules}}
    notes = [("bench:step", 0, 1000), ("bench:calc_dt_read", 250, 400),
             ("bench:step", 1000, 1100), ("bench:setup", -500, 0)]
    r = xtrace.reduce(chips, notes)
    assert r["window_s"] == pytest.approx(1100e-9)
    # busy: [100, 250) + [400, 450) + [900, 1000) inside [0, 1100)
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["tile_s"] == pytest.approx(250e-9)
    assert r["staging_s"] == pytest.approx(50e-9)
    assert r["device_ops"][0] == ("jit_tile_fn", pytest.approx(250e-9))
    idle = dict(r["idle_gaps"])
    # [0,100) and [450,900) under step, [250,400) under the inner dt read,
    # [1000,1100) under the second step
    assert idle["calc_dt_read"] == pytest.approx(150e-9)
    assert idle["step"] == pytest.approx((100 + 450 + 100) * 1e-9)
    assert r["busy_s"] + sum(idle.values()) == pytest.approx(r["window_s"])


def test_idle_gaps_cut_at_host_spans():
    """A gap that spans several host spans is split among them, and JAX's
    compile spans are charged as ``xla_compile``."""
    chips = {"/device:TPU:0": {"ops": [(0, 10), (990, 1000)], "modules": []}}
    notes = [("bench:step", 0, 1000), ("bench:calc_dt_read", 100, 900),
             ("backend_compile_and_load", 300, 700)]
    idle = dict(xtrace.reduce(chips, notes)["idle_gaps"])
    assert idle["step"] == pytest.approx((90 + 90) * 1e-9)
    assert idle["calc_dt_read"] == pytest.approx((200 + 200) * 1e-9)
    assert idle["xla_compile"] == pytest.approx(400e-9)
    assert sum(idle.values()) == pytest.approx(980e-9)


def test_reduce_needs_a_window_and_device_work():
    assert xtrace.reduce({}, [("bench:step", 0, 10)]) is None
    chips = {"/device:TPU:0": {"ops": [(0, 5)], "modules": []}}
    assert xtrace.reduce(chips, []) is None
    assert xtrace.module_name("jit_tile_fn(4412)") == "jit_tile_fn"


RECORDED = ROOT / "benchmarks" / "chip" / "testdata" / "clover256_ooc_step.xplane.pb.gz"


def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5 lite (CloverLeaf 2D at 256², ooc at a
    third of the working set, one traced timestep; trimmed to the chip's
    ``XLA Modules`` and ``XLA Ops`` lines and the benchmark's annotations).
    The expected numbers were read from the same file with the XPlane
    protobuf directly, not through this reducer."""
    chips, notes = xtrace.load(str(RECORDED))
    assert list(chips) == ["/device:TPU:0"]
    assert {n for n, _, _ in notes} == {"bench:step", "bench:calc_dt_read",
                                        "bench:record"}
    r = xtrace.reduce(chips, notes)
    assert r["window_s"] == pytest.approx(2.236537581, rel=1e-6)
    assert r["busy_s"] == pytest.approx(3.325729644e-3, rel=5e-3)
    idle_share = 1 - r["busy_s"] / r["window_s"]
    assert idle_share == pytest.approx(1 - 3.325729644e-3 / 2.236537581, abs=1e-5)
    assert r["tile_s"] == pytest.approx(1.662452030e-3, rel=1e-3)
    assert r["tile_s"] + r["staging_s"] == pytest.approx(5.558154812e-3, rel=1e-3)
    assert r["device_ops"][0][0] == "jit_tile_fn"
    idle = dict(r["idle_gaps"])
    # Idle time inside each annotation's interval, the innermost first
    # (the step's idle time less that of the two annotations inside it).
    assert set(idle) == {"calc_dt_read", "record", "step"}
    assert idle["calc_dt_read"] == pytest.approx(2.230548383, rel=1e-3)
    assert idle["record"] == pytest.approx(2.471570e-3, rel=1e-2)
    assert idle["step"] == pytest.approx(1.97320e-4, rel=5e-2)
