"""The program's spans on the profiler's clock (``benchmarks/chip/spans.py``):
the anchors' offset, idle time charged to the innermost program span, the
compile split, the driver's idle share, self times, and a CPU rehearsal of
every cell with the tracer on."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import harness, spans, xtrace  # noqa: E402
from repro.obs import Span  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
TINY = {"cloverleaf2d": [48, 40], "opensbli": [16, 16, 16]}
RECORDED = ROOT / "benchmarks" / "chip" / "testdata" / "clover256_ooc_step.xplane.pb.gz"


def test_clock_offset_from_the_anchors():
    # Tracer seconds; the profiler's clock runs 1000 ns ahead, then 1040.
    tracer = [Span(spans.ANCHORS[0], "anchor", "anchor", 1e-6, 3e-6),
              Span(spans.ANCHORS[1], "anchor", "anchor", 9e-6, 11e-6),
              Span("chain", "chain", "chain", 4e-6, 8e-6)]
    notes = [(spans.ANCHORS[0], 2500, 3500), (spans.ANCHORS[1], 10540, 11540),
             ("bench:step", 4000, 9000)]
    offset, skew = spans.clock_offset(notes, tracer)
    assert offset == pytest.approx(1020.0)
    assert skew == pytest.approx(0.040)
    assert spans.align(tracer, offset) == [("chain", 5020, 9020)]
    assert spans.clock_offset(notes[1:], tracer) is None


def test_idle_charged_to_the_innermost_program_span():
    chips = {"/device:TPU:0": {"ops": [(0, 10), (990, 1000)], "modules": []}}
    notes = [("bench:step", 0, 1000), ("bench:flush", 50, 950)]
    program = [("chain", 100, 900), ("tile", 200, 800),
               ("stage_in", 300, 400), ("d2h", 320, 340),
               ("tile_dispatch", 500, 600)]
    r = spans.reduce(chips, notes, program)
    idle = dict(r["idle_gaps"])
    assert idle["d2h"] == pytest.approx(20e-9)
    assert idle["stage_in"] == pytest.approx(80e-9)
    assert idle["tile_dispatch"] == pytest.approx(100e-9)
    assert idle["tile"] == pytest.approx(400e-9)
    assert idle["chain"] == pytest.approx(200e-9)
    assert idle["flush"] == pytest.approx(100e-9)
    assert idle["step"] == pytest.approx(80e-9)
    assert sum(idle.values()) == pytest.approx(980e-9)
    # No program span covers [10, 100) and [900, 990).
    assert r["driver_idle_s"] == pytest.approx(180e-9)


def test_program_spans_are_inner_to_annotations():
    """A chain span that the clocks' alignment puts a little before the
    annotation around its flush is still inner to it."""
    chips = {"/device:TPU:0": {"ops": [(0, 10), (990, 1000)], "modules": []}}
    notes = [("bench:step", 0, 1000), ("bench:flush", 100, 980)]
    program = [("chain", 97, 950), ("plan", 98, 120)]
    idle = dict(spans.reduce(chips, notes, program)["idle_gaps"])
    assert idle == pytest.approx({"step": 97e-9, "chain": (1 + 830) * 1e-9,
                                  "plan": 22e-9, "flush": 30e-9})


def test_compile_inside_tile_compile_is_labelled_tile_compile():
    chips = {"/device:TPU:0": {"ops": [(0, 10), (990, 1000)], "modules": []}}
    notes = [("bench:step", 0, 1000), ("backend_compile_and_load", 200, 300),
             ("backend_compile", 600, 700)]
    program = [("chain", 100, 900), ("tile_dispatch", 150, 400),
               ("tile_compile", 160, 380)]
    idle = dict(spans.reduce(chips, notes, program)["idle_gaps"])
    # The compile inside tile_compile is the tile program's; the one in
    # the chain outside it (an eager op's) stays xla_compile.
    assert idle["tile_compile"] == pytest.approx(220e-9)
    assert idle["xla_compile"] == pytest.approx(100e-9)
    assert idle["tile_dispatch"] == pytest.approx(30e-9)
    assert idle["chain"] == pytest.approx((50 + 200 + 200) * 1e-9)


def test_driver_idle_averages_over_chips():
    chips = {"/device:TPU:0": {"ops": [(0, 100)], "modules": []},
             "/device:TPU:1": {"ops": [(0, 500)], "modules": []}}
    notes = [("bench:step", 0, 1000)]
    r = spans.reduce(chips, notes, [("chain", 400, 800)])
    # chip 0: idle [100, 1000), uncovered 500; chip 1: [500, 1000), 200.
    assert r["driver_idle_s"] == pytest.approx(350e-9)


HAND_MADE = [
    ({"/device:TPU:0": {"ops": [(100, 200), (150, 250), (400, 450),
                                (900, 1000), (1100, 1200)],
                        "modules": [("jit_tile_fn", 100, 250),
                                    ("jit_scatter", 400, 450)]}},
     [("bench:step", 0, 1000), ("bench:calc_dt_read", 250, 400),
      ("bench:step", 1000, 1100), ("bench:setup", -500, 0)]),
    ({"/device:TPU:0": {"ops": [(0, 10), (990, 1000)], "modules": []}},
     [("bench:step", 0, 1000), ("bench:calc_dt_read", 100, 900),
      ("backend_compile_and_load", 300, 700), ("bench:anchor.end", 1005, 1006)]),
    ({}, [("bench:step", 0, 10)]),
]


@pytest.mark.parametrize("case", range(len(HAND_MADE)))
def test_without_program_spans_reads_as_xtrace(case):
    chips, notes = HAND_MADE[case]
    assert spans.reduce(chips, notes) == xtrace.reduce(chips, notes)


def test_recorded_trace_reads_as_xtrace():
    chips, notes = xtrace.load(str(RECORDED))
    assert spans.reduce(chips, notes) == xtrace.reduce(chips, notes)


def test_self_times_and_counts():
    tr = [Span("tile_dispatch", "dispatch", "compute", 0.0, 5.0),
          Span("tile_compile", "compile", "compute", 1.0, 4.0),
          Span("tile_dispatch", "dispatch", "compute", 6.0, 7.0),
          Span("stage_out", "stage", "download", 10.0, 12.0, {"bytes": 64}),
          Span("d2h", "wait", "download", 10.5, 11.0, {"bytes": 64}),
          # same name on another track: not nested in the stage_out
          Span("d2h", "wait", "upload", 10.5, 11.5),
          Span("stage_in", "stage", "upload", 20.0, 21.0, {"bytes": 32}),
          Span("plan", "plan", "chain", 30.0, 32.0)]
    t = spans.totals(tr)
    assert t["tile_dispatch"]["count"] == 2
    assert t["tile_dispatch"]["s"] == pytest.approx(6.0)
    assert t["tile_dispatch"]["self_s"] == pytest.approx(3.0)
    assert t["stage_out"]["self_s"] == pytest.approx(1.5)
    assert t["stage_out"]["bytes"] == 64 and t["stage_in"]["bytes"] == 32
    assert t["d2h"]["s"] == pytest.approx(1.5) and t["d2h"]["count"] == 2
    got = spans.read({"steps": 2, "dropped": 0, "spans": t,
                      "trace": {"driver_idle_s": 0.004}})
    assert got == pytest.approx({
        "plan_ms_per_step": 1000.0, "tile_compile_ms_per_step": 1500.0,
        "tile_dispatch_ms_per_step": 1500.0,
        "host_staging_ms_per_step": 1250.0,
        "device_wait_ms_per_step": 750.0, "driver_idle_ms_per_step": 2.0})


def test_dropped_spans_read_as_none(capsys):
    got = spans.read({"steps": 1, "dropped": 3, "spans": {}, "trace": None})
    assert set(got.values()) == {None} and len(got) == 6
    assert "dropped 3 spans" in capsys.readouterr().err


@pytest.mark.parametrize("name", CELLS)
def test_cell_with_the_tracer_on(name):
    """Every cell at a tiny grid on the CPU: the traced window's five
    program-span readings are numbers, the anchors agree, and the result is
    the reference's.  The CPU trace has no TPU plane: no device readings.
    At a tiny grid CloverLeaf's dt sits at its cap, so its window plans
    (cache hits) but compiles nothing."""
    cell = harness.load_cell(name)
    cell["config"]["grid"] = TINY[cell["config"]["app"]]
    rec = spans.measure(cell, 2 ** 31 + 11, pairs=1)
    line = spans.summary(rec)
    assert line["correct"], rec["checks"]
    assert line["dropped"] == 0 and len(line["steps_on_s"]) == 1
    got = line["metrics"]
    assert got.pop("driver_idle_ms_per_step") is None
    assert all(v >= 0 for v in got.values()), got
    assert got["plan_ms_per_step"] > 0 and got["host_staging_ms_per_step"] > 0
    assert rec["trace"] is None and abs(line["clock_skew_us"]) < 1e4
    counts = {n: t["count"] for n, t in rec["spans"].items()}
    assert counts["plan"] >= 1 and counts["tile_dispatch"] >= 1
    assert counts["stage_in"] >= 1 and counts["stage_out"] >= 1
    if cell["config"]["app"] == "cloverleaf2d":
        assert counts["reduction_read"] >= 1
    json.dumps(line)
