"""Reduce a JAX profiler trace (``*.xplane.pb``) to the numbers the per-layer
metrics read.

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Modules`` line has one event per program run (a jitted tile program is
``jit_tile_fn(<id>)``; an eager staging op is a module of its own) and whose
``XLA Ops`` line has one event per operation.  The host plane
(``/host:CPU``) holds the benchmark's ``jax.profiler.TraceAnnotation``
spans, named ``bench:<what>``.  Timestamps of both are on one clock.

The reduction, over the traced window (first to last ``bench:step``
annotation):

* busy: the union of the operation intervals of each chip, averaged over
  the chips that ran anything;
* device time per module, summed over chips;
* idle gaps: the stretches of the window with no operation on the chip,
  cut at the host spans' edges, each piece charged to the innermost host
  span that covers it: a ``bench:`` annotation, or ``xla_compile`` where
  JAX was compiling a program (its ``backend_compile*`` spans).
"""
from __future__ import annotations

import glob
import os
from bisect import bisect_left, bisect_right
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

TILE_MODULE = "jit_tile_fn"
ANNOTATION = "bench:"
WINDOW = "bench:step"
COMPILE_SPANS = ("backend_compile_and_load", "backend_compile")
COMPILE_LABEL = "xla_compile"

Interval = Tuple[int, int]


def annotate(what: str):
    """A host span ``bench:<what>`` in the profiler's trace (free when no
    trace is being taken)."""
    import jax

    return jax.profiler.TraceAnnotation(ANNOTATION + what)


def module_name(event_name: str) -> str:
    """``jit_tile_fn(123)`` -> ``jit_tile_fn``."""
    return event_name.split("(", 1)[0].strip()


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def find_xspace(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, "
                           f"found {files}")
    return files[0]


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.start_ns + e.duration_ns)


def load(path: str):
    """Device op and module events and host annotations from one trace
    (``.xplane.pb``, or gzipped as ``.xplane.pb.gz``):
    ``({chip: {"ops": [...], "modules": [(name, lo, hi), ...]}},
    [(name, lo, hi), ...])``."""
    import gzip

    import jax

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            data = jax.profiler.ProfileData.from_serialized_xspace(fh.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    chips: Dict[str, dict] = {}
    notes: List[Tuple[str, int, int]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            chips[plane.name] = {
                "ops": [(lo, hi) for _, lo, hi in _events(lines["XLA Ops"])],
                "modules": ([(module_name(n), lo, hi) for n, lo, hi
                             in _events(lines["XLA Modules"])]
                            if "XLA Modules" in lines else []),
            }
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                notes.extend(ev for ev in _events(ln)
                             if ev[0].startswith(ANNOTATION)
                             or ev[0] in COMPILE_SPANS)
    return chips, notes


def _label(span_name: str) -> str:
    if span_name.startswith(ANNOTATION):
        return span_name[len(ANNOTATION):]
    return COMPILE_LABEL


def reduce(chips: Dict[str, dict], notes: List[Tuple[str, int, int]],
           top: int = 10) -> Optional[dict]:
    """The numbers of a traced window; None where the trace holds no
    ``bench:step`` annotation or no device operation inside it."""
    steps = [(lo, hi) for n, lo, hi in notes if n == WINDOW]
    if not steps:
        return None
    lo = min(a for a, _ in steps)
    hi = max(b for _, b in steps)
    busy_ns, used = 0, 0
    modules: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    inner = sorted(notes, key=lambda n: (n[1], -n[2]))
    edges = sorted({t for _, s, e in notes for t in (s, e) if lo < t < hi})
    for chip in chips.values():
        busy = union(clip(chip["ops"], lo, hi))
        if not busy:
            continue
        used += 1
        busy_ns += sum(b - a for a, b in busy)
        for name, a, b in chip["modules"]:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                modules[name] += (b - a) * 1e-9
        for a, b in gaps(busy, lo, hi):
            cuts = [a] + edges[bisect_right(edges, a):bisect_left(edges, b)] + [b]
            for p, q in zip(cuts, cuts[1:]):
                mid = (p + q) // 2
                label = "none"
                for name, s, e in inner:
                    if s > mid:
                        break
                    if mid < e:
                        label = _label(name)
                idle[label] += (q - p) * 1e-9
    if not used:
        return None
    window_s = (hi - lo) * 1e-9
    tile_s = sum(v for k, v in modules.items() if k.startswith(TILE_MODULE))
    return {
        "window_s": window_s,
        "busy_s": busy_ns * 1e-9 / used,
        "chips": used,
        "tile_s": tile_s,
        "staging_s": sum(modules.values()) - tile_s,
        "device_ops": sorted(modules.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:top],
    }


def reduce_file(path: str) -> Optional[dict]:
    return reduce(*load(path))
