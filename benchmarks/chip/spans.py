"""The program's own spans (``repro.obs``) on the profiler's clock: one cell
measured with the runtime's tracer on.

    python3 benchmarks/chip/spans.py --workload <cell> --seed <n> [--pairs k]

A ``Session`` traced through ``repro.obs.Tracer`` records host spans on
``time.perf_counter``: planning (``plan``), tile-program compiles
(``tile_compile``), tile launches (``tile_dispatch``), host staging of each
field (``stage_in``, ``stage_out``) and host waits on the device (``d2h``,
``reduction_read``, ``upload_wait``), inside its ``chain``, ``tile`` and
plan-op spans.  ``Tracer.anchor`` marks one instant on that clock and in the
``jax.profiler`` trace; the two anchors (``ANCHORS``), one just after
``start_trace`` and one just before ``stop_trace``, give the offset that
places the spans on the trace, and their difference the clocks' skew over
the window.

The command sets the cell up as ``run.py`` does, then runs ``--pairs``
pairs of timesteps with the tracer off and on (alternating which goes first:
what tracing costs), then the mix's ``trace_steps`` timesteps under the
profiler with the tracer on, compares the fields with the plain reference,
and prints one JSON line: the six per-step readings of ``read``, the skew,
the idle breakdown with the program's spans among the labels, and the step
walls of each kind.  It runs on any backend; without a TPU plane the device
readings (``driver_idle_ms_per_step``, the breakdown) stay absent.
"""
from __future__ import annotations

import argparse
import gc
import heapq
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from bisect import bisect_left, bisect_right
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    # The checkout's own program and benchmark, never an installed copy.
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import xtrace  # noqa: E402

ANCHORS = ("bench:anchor.start", "bench:anchor.end")
WAITS = ("d2h", "reduction_read", "upload_wait")
# What each span's self time leaves out: spans of these names nested in it.
SELF_LESS = {"tile_dispatch": ("tile_compile",),
             "stage_in": WAITS, "stage_out": WAITS}
PROGRAM_SPANS = ("plan", "tile_compile", "tile_dispatch", "stage_in",
                 "stage_out") + WAITS
TILE_COMPILE = "tile_compile"

Labelled = Tuple[str, int, int]


def clock_offset(notes: Sequence[Labelled], spans) -> Optional[Tuple[float, float]]:
    """``(offset_ns, skew_us)``: profiler time minus tracer time at the
    anchors' midpoints, the mean of the two, and the second's offset less
    the first's; None where either anchor is missing on either clock."""
    trace = {n: (lo + hi) / 2 for n, lo, hi in notes if n in ANCHORS}
    tracer = {s.name: (s.t_start + s.t_end) / 2 * 1e9
              for s in spans if s.cat == "anchor" and s.name in ANCHORS}
    if not all(a in trace and a in tracer for a in ANCHORS):
        return None
    first, last = (trace[a] - tracer[a] for a in ANCHORS)
    return (first + last) / 2, (last - first) * 1e-3


def label(span) -> str:
    """A span's label in the idle breakdown: its name, ``tile`` for the
    per-tile spans."""
    return "tile" if span.cat == "tile" else span.name


def align(spans, offset_ns: float) -> List[Labelled]:
    """The tracer's spans, anchors left out, as ``(label, lo, hi)`` in the
    profiler's nanoseconds."""
    return [(label(s), round(s.t_start * 1e9 + offset_ns),
             round(s.t_end * 1e9 + offset_ns))
            for s in spans if s.cat != "anchor"]


class _Innermost:
    """The innermost of the candidate spans ``(label, lo, hi, rank)``
    covering an instant, for instants asked in increasing order: of the
    covering spans of the lowest ``rank``, the one that starts last (of
    those, the shortest; then the later listed).  With every rank equal
    this is ``xtrace.reduce``'s pick."""

    def __init__(self, cands):
        self.cands = cands
        self.order = sorted(range(len(cands)),
                            key=lambda i: (cands[i][1], -cands[i][2]))
        self.next = 0
        self.heap: list = []

    def at(self, t: int):
        cands, order = self.cands, self.order
        while self.next < len(order) and cands[order[self.next]][1] <= t:
            i = order[self.next]
            _, lo, hi, rank = cands[i]
            heapq.heappush(self.heap, (rank, -lo, hi, -self.next, i))
            self.next += 1
        while self.heap and self.heap[0][2] <= t:
            heapq.heappop(self.heap)
        return cands[self.heap[0][4]] if self.heap else None


def _covers(cover: List[Tuple[int, int]], t: int) -> bool:
    k = bisect_right(cover, (t, float("inf"))) - 1
    return k >= 0 and cover[k][0] <= t < cover[k][1]


def _overlap(spans: List[Tuple[int, int]], a: int, b: int) -> int:
    """Length of ``[a, b)`` that the disjoint sorted ``spans`` cover."""
    k = max(0, bisect_right(spans, (a, float("inf"))) - 1)
    got = 0
    for lo, hi in spans[k:]:
        if lo >= b:
            break
        got += max(0, min(hi, b) - max(lo, a))
    return got


def reduce(chips: Dict[str, dict], notes: Sequence[Labelled],
           program: Sequence[Labelled] = (), top: int = 10) -> Optional[dict]:
    """``xtrace.reduce`` with the program's aligned spans ``program`` among
    the candidates an idle stretch is charged to: the innermost covering
    span wins, a program span counting as inner to any ``bench:``
    annotation, and a JAX compile inside a ``tile_compile`` span is charged
    to ``tile_compile`` (what stays ``xla_compile`` is the eager ops').
    With program spans it adds ``driver_idle_s``: the idle time, per chip,
    that no program span covers.  Without them the result is
    ``xtrace.reduce``'s, number for number."""
    steps = [(lo, hi) for n, lo, hi in notes if n == xtrace.WINDOW]
    if not steps:
        return None
    lo = min(a for a, _ in steps)
    hi = max(b for _, b in steps)
    # The benchmark's annotations wrap calls into the program, so a program
    # span is inner to any that covers it, whatever a few microseconds of
    # clock alignment say.
    outer = 1 if program else 0
    labelled = [(xtrace._label(n), s, e) for n, s, e in notes]
    cands = ([(n, s, e, 0 if n == xtrace.COMPILE_LABEL else outer)
              for n, s, e in labelled]
             + [(n, s, e, 0) for n, s, e in program])
    edges = sorted({t for _, s, e, _ in cands for t in (s, e) if lo < t < hi})
    cover = xtrace.union([(s, e) for _, s, e in program])
    compiling = xtrace.union([(s, e) for n, s, e in program
                              if n == TILE_COMPILE])
    busy_ns, used, driver_ns = 0, 0, 0
    modules: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    for chip in chips.values():
        busy = xtrace.union(xtrace.clip(chip["ops"], lo, hi))
        if not busy:
            continue
        used += 1
        busy_ns += sum(b - a for a, b in busy)
        for name, a, b in chip["modules"]:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                modules[name] += (b - a) * 1e-9
        inner = _Innermost(cands)
        for a, b in xtrace.gaps(busy, lo, hi):
            if program:
                driver_ns += (b - a) - _overlap(cover, a, b)
            cuts = [a] + edges[bisect_right(edges, a):bisect_left(edges, b)] + [b]
            for p, q in zip(cuts, cuts[1:]):
                mid = (p + q) // 2
                got = inner.at(mid)
                name = "none" if got is None else got[0]
                if name == xtrace.COMPILE_LABEL and _covers(compiling, mid):
                    name = TILE_COMPILE
                idle[name] += (q - p) * 1e-9
    if not used:
        return None
    window_s = (hi - lo) * 1e-9
    tile_s = sum(v for k, v in modules.items()
                 if k.startswith(xtrace.TILE_MODULE))
    out = {
        "window_s": window_s,
        "busy_s": busy_ns * 1e-9 / used,
        "chips": used,
        "tile_s": tile_s,
        "staging_s": sum(modules.values()) - tile_s,
        "device_ops": sorted(modules.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:top],
    }
    if program:
        out["driver_idle_s"] = driver_ns * 1e-9 / used
    return out


def totals(spans) -> Dict[str, dict]:
    """For each name of ``PROGRAM_SPANS``: its spans' count, summed seconds
    (``s``), seconds less those of the spans ``SELF_LESS`` names nested in
    them on the same track (``self_s``), and summed ``bytes`` args."""
    out = {n: {"count": 0, "s": 0.0, "self_s": 0.0, "bytes": 0}
           for n in PROGRAM_SPANS}
    for s in spans:
        t = out.get(s.name)
        if t is not None:
            t["count"] += 1
            t["s"] += s.duration
            t["bytes"] += (s.args or {}).get("bytes", 0)
    for t in out.values():
        t["self_s"] = t["s"]
    for name, kids in SELF_LESS.items():
        parents: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        for s in spans:
            if s.name == name:
                parents[s.track].append((s.t_start, s.t_end))
        for ps in parents.values():
            ps.sort()
        nested = 0.0
        for s in spans:
            ps = parents.get(s.track) if s.name in kids else None
            if ps:
                k = bisect_right(ps, (s.t_start, float("inf"))) - 1
                if k >= 0 and ps[k][1] >= s.t_end:
                    nested += s.duration
        out[name]["self_s"] -= nested
    return out


def read(rec: dict) -> Dict[str, Optional[float]]:
    """The six per-step readings (ms per timestep of the traced window) of a
    run record with ``steps``, ``spans`` (``totals``), ``dropped`` and
    ``trace`` (``reduce``'s, or None); all None where the tracer's ring
    dropped spans, and ``driver_idle_ms_per_step`` None without a device
    trace."""
    names = ("plan_ms_per_step", "tile_compile_ms_per_step",
             "tile_dispatch_ms_per_step", "host_staging_ms_per_step",
             "device_wait_ms_per_step", "driver_idle_ms_per_step")
    if rec["dropped"]:
        print(f"spans: the tracer dropped {rec['dropped']} spans; its "
              f"readings are left out", file=sys.stderr)
        return dict.fromkeys(names)
    t, per_ms = rec["spans"], 1e3 / rec["steps"]
    tr = rec.get("trace") or {}
    idle = tr.get("driver_idle_s")
    return dict(zip(names, (
        t["plan"]["s"] * per_ms,
        t["tile_compile"]["s"] * per_ms,
        t["tile_dispatch"]["self_s"] * per_ms,
        (t["stage_in"]["self_s"] + t["stage_out"]["self_s"]) * per_ms,
        sum(t[w]["s"] for w in WAITS) * per_ms,
        None if idle is None else idle * per_ms)))


def _synced_step(drv, sess) -> float:
    """Seconds of one timestep, through the host read or flush that ends
    it."""
    t0 = time.perf_counter()
    while not drv.step(sess):
        pass
    return time.perf_counter() - t0


def measure(cell: dict, seed: int, pairs: int) -> dict:
    """Set up ``cell`` from ``seed``; time ``pairs`` pairs of timesteps with
    the executor's tracer off and on; trace the mix's ``trace_steps``
    timesteps with the profiler and the tracer on; compare with the plain
    reference.  The record ``read`` takes, plus the walls and checks."""
    import jax

    from repro.obs import NULL_TRACER, Tracer

    from benchmarks.chip import harness

    cfg, mix = cell["config"], cell["mix"]
    with xtrace.annotate("setup"):
        drv, sess = harness.set_up(cfg, mix, seed)
    ex = sess.backend
    tracer = Tracer(capacity=1 << 20)
    walls: Dict[str, List[float]] = {"off": [], "on": []}
    for k in range(pairs):
        for mode in (("off", "on") if k % 2 == 0 else ("on", "off")):
            ex.tracer = tracer if mode == "on" else NULL_TRACER
            walls[mode].append(_synced_step(drv, sess))
    ex.tracer = tracer
    tracer.clear()
    tmp = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=harness._trace_options())
        tracer.anchor(ANCHORS[0])
        steps, t0 = 0, time.perf_counter()
        while True:
            with xtrace.annotate("step"):
                synced = drv.step(sess)
            steps += 1
            if synced and steps >= int(mix["trace_steps"]):
                break
        t1 = time.perf_counter()
        tracer.anchor(ANCHORS[1])
        jax.profiler.stop_trace()
        ex.tracer = NULL_TRACER
        harness.persistent_cache(True)
        spans = tracer.spans()
        chips, notes = xtrace.load(xtrace.find_xspace(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    clock = clock_offset(notes, spans)
    rec = {"cell": cell["name"], "seed": seed, "steps": steps,
           "window_s": t1 - t0, "walls": walls, "dropped": tracer.dropped,
           "spans": totals(spans),
           "clock_skew_us": None if clock is None else clock[1],
           "trace": (reduce(chips, notes, align(spans, clock[0]))
                     if clock is not None else None)}

    reds = drv.finish(sess)
    fields = {k: np.array(v, copy=True)
              for k, v in drv.fields(cfg["compare"]).items()}
    total_steps = drv.steps
    sess.close()
    del sess, drv, ex
    gc.collect()
    ref_fields, ref_reds = harness.reference_module(cfg).run(
        cfg, seed, total_steps, fields=cfg["compare"])
    rec["checks"] = harness.compare(cfg, fields, reds, ref_fields, ref_reds)
    return rec


def summary(rec: dict) -> dict:
    """The JSON line ``main`` prints for ``rec``."""
    tr = rec["trace"]
    med = {k: statistics.median(v) if v else None
           for k, v in rec["walls"].items()}
    out = {"cell": rec["cell"], "seed": rec["seed"],
           "correct": all(c["value"] <= c["limit"]
                          for c in rec["checks"].values()),
           "metrics": read(rec), "clock_skew_us": rec["clock_skew_us"],
           "dropped": rec["dropped"],
           "traced_s_per_step": rec["window_s"] / rec["steps"],
           "steps_off_s": rec["walls"]["off"], "steps_on_s": rec["walls"]["on"],
           "on_over_off": (med["on"] / med["off"]
                           if med["on"] and med["off"] else None),
           "spans": {n: t for n, t in rec["spans"].items() if t["count"]},
           "checks": rec["checks"]}
    if tr:
        idle_s = tr["window_s"] - tr["busy_s"]
        out.update(window_s=tr["window_s"], busy_s=tr["busy_s"],
                   idle_ms_per_step=idle_s * 1e3 / rec["steps"],
                   driver_idle_share=(tr["driver_idle_s"] / idle_s
                                      if idle_s > 0 else None),
                   device_ops=tr["device_ops"], idle_gaps=tr["idle_gaps"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pairs", type=int, default=2,
                    help="timesteps with the tracer off and on, alternating")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    harness.configure_compile_cache()
    line = summary(measure(cell, args.seed, args.pairs))
    print(json.dumps(line))
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
