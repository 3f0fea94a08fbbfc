"""One cell of the chip benchmark, in one process: set-up, a measured window
of whole timesteps, an optional profiler trace, the comparison with the
plain reference that decides ``correct``, and the result line.

Everything that belongs to one configuration, traffic mix or metric is data
found by name: ``configs/<config>.json`` (sizes, the fields compared and
their limits), ``traffic/<mix>.json`` (backend, capacity, cadences),
``apps/<app>.py`` (how the app's own ``record_*`` methods are driven through
``repro.core.Session``), ``reference/<app>.py`` (the plain reference) and
``metrics/<metric>.py`` (one reader per metric).

A mix whose programs depend on the seed's values (CloverLeaf's tile
programs hold each step's dt as a constant) names ``fresh_steps``: set-up
ends with that many more warm-up steps compiled without the persistent
cache, so every run's window follows the same fresh compiles, whether or
not an earlier run in the checkout had its seed; the window, with the
cache off, compiles what a new simulation compiles.
"""
from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import math
import shutil
import tempfile
import time
from pathlib import Path
from typing import Dict, Optional

import numpy as np

from . import compulsory, xtrace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parents[1]


def _read_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str, root: Path = ROOT,
              workload: Optional[dict] = None) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration, mix
    and the metrics it reports; ``workload``, an entry of the file's
    ``workloads`` form, stands for a cell the file does not list."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload is not None:
        cells[name] = workload
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"name": name, "chips": cell["chips"],
            "config": _read_json(root / conf["file"]),
            "mix": _read_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def peaks_for(kind: str) -> dict:
    """The published peaks of ``kind``; a device missing from the table is
    an error."""
    table = _read_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"({sorted(table)}); add its published peaks")
    return table[kind]


def configure_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache in ``.jax_cache/`` of the
    checkout, whatever ``$JAX_COMPILATION_CACHE_DIR`` says: a fixed path
    (the path is part of what a later run looks up) that no other checkout
    shares."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def persistent_cache(enabled: bool) -> None:
    """Turn the persistent compilation cache on or off for the compiles
    that follow.  It is off in a mix's ``fresh_steps`` and inside the
    window: CloverLeaf's tile programs hold each step's dt
    as a constant, so a timestep compiles programs that only a rerun of the
    same seed would find again, and a run must cost the same whether or not
    an earlier run had its seed."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


class CompileLog:
    """Counts XLA compiles and persistent-cache hits through
    ``jax.monitoring`` (listeners are process-wide: make one per process)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.hits)

    def since(self, snap) -> dict:
        s, c, h = snap
        return {"compile_s": self.seconds - s, "compiles": self.compiles - c,
                "cache_hits": self.hits - h}


def make_session(mix: dict, working_set: int):
    from repro.core import Session

    kw = dict(mix.get("session", {}))
    if mix.get("capacity_divisor"):
        kw["capacity_bytes"] = working_set / mix["capacity_divisor"]
    return Session(mix["backend"], **kw)


def compulsory_bytes(cfg: dict, mix: dict) -> int:
    """Compulsory bytes of one step, counted on the loops a small copy of
    the app records (the loops do not depend on the grid's size)."""
    from repro.core import Session

    small = dict(cfg, grid=[16] * len(cfg["grid"]))
    drv = driver_module(cfg).Driver(small, mix)
    sess = Session("reference")
    drv.record_step(sess)
    loops, sess.queue = sess.queue, []
    return compulsory.step_bytes(loops, cfg["grid"], cfg["dtype"])


def driver_module(cfg: dict):
    return importlib.import_module(f"{__package__}.apps.{cfg['app']}")


def reference_module(cfg: dict):
    return importlib.import_module(f"{__package__}.reference.{cfg['app']}")


def read_metric(name: str, rec: dict) -> Optional[float]:
    """Run ``metrics/<name>.py``'s ``read(rec)``: the metric's value, or
    None where the run holds nothing for it to read."""
    spec = importlib.util.spec_from_file_location(
        f"_bench_metric_{name.replace('.', '_').replace('-', '_')}",
        BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(rec)
    return None if value is None else float(value)


def _relative(got: float, want: float) -> float:
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    return abs(got - want) / max(abs(want), 1e-30)


def compare(cfg: dict, fields: Dict[str, np.ndarray], reds: Dict[str, float],
            ref_fields: Dict[str, np.ndarray], ref_reds: Dict[str, float]
            ) -> Dict[str, dict]:
    """The numbers that decide ``correct``, each with its limit.

    ``field_err``: over the compared fields, the largest absolute gap
    between the program's and the reference's interior, as a share of the
    largest magnitude the reference holds in that field; the worst field.
    ``reduction_err``: the largest relative gap over the reductions the
    program read (each ``calc_dt`` and the summary after the window)."""
    field_err = 0.0
    for name in cfg["compare"]:
        got, want = fields.get(name), ref_fields.get(name)
        if got is None or want is None or got.shape != want.shape:
            field_err = math.inf
            continue
        scale = float(np.max(np.abs(want))) or 1.0
        err = float(np.max(np.abs(got.astype(np.float64) - want))) / scale
        field_err = max(field_err, err if math.isfinite(err) else math.inf)
    keys = set(reds) | {k for k in ref_reds if not k.startswith("dt.")}
    red_err = 0.0 if keys else math.inf
    for key in keys:
        red_err = max(red_err, _relative(reds.get(key, math.nan),
                                         ref_reds.get(key, math.nan)))
    limits = cfg["limits"]
    return {"field_err": {"value": field_err, "limit": limits["field_err"]},
            "reduction_err": {"value": red_err,
                              "limit": limits["reduction_err"]}}


def control(cell: dict, seed: int, steps: int) -> Dict[str, dict]:
    """The control: the plain reference one precision lower (bfloat16 for
    the float32 the configurations state) put in the program's place and
    compared with the float32 reference; it has to fail the limits."""
    import jax.numpy as jnp

    cfg = cell["config"]
    ref = reference_module(cfg)
    want = ref.run(cfg, seed, steps, jnp.float32, fields=cfg["compare"])
    got = ref.run(cfg, seed, steps, jnp.bfloat16, fields=cfg["compare"])
    return compare(cfg, *got, *want)


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # Python calls: far too many events
    opts.host_tracer_level = 1        # keeps the TraceAnnotations
    opts.enable_hlo_proto = False
    return opts


def set_up(cfg: dict, mix: dict, seed: int) -> tuple:
    """A driver and session with the fields built from ``seed``, after the
    mix's warm-up steps and then its ``fresh_steps`` without the
    persistent cache."""
    drv = driver_module(cfg).Driver(cfg, mix)
    sess = make_session(mix, drv.total_bytes())
    drv.init(sess, seed, bool(mix.get("cyclic", True)))
    synced = False
    while drv.steps < int(mix["warm_steps"]) or not synced:
        synced = drv.step(sess)
    persistent_cache(False)
    for _ in range(int(mix.get("fresh_steps", 0))):
        synced = False
        while not synced:
            synced = drv.step(sess)
    return drv, sess


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_start: float, log: CompileLog, device) -> tuple:
    """Set up, measure, compare: the run record and the comparison.

    ``t_start`` is the process's start on the ``time.perf_counter`` clock;
    ``device`` the JAX device whose memory statistics are read."""
    cfg, mix = cell["config"], cell["mix"]
    rec: dict = {"cell": cell["name"], "seed": seed}
    with xtrace.annotate("setup"):
        drv, sess = set_up(cfg, mix, seed)
    rec.update(setup_plan_s=sum(h.plan_s for h in sess.history),
               setup_compile_s=log.seconds, setup_compiles=log.compiles)

    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        import jax

        jax.profiler.start_trace(tmp, profiler_options=_trace_options())
    n_hist, snap = len(sess.history), log.snapshot()
    steps, t0 = 0, time.perf_counter()
    rec["setup_s"] = t0 - t_start
    marks, step_compiles = [t0], []
    while True:
        n_compiles = log.compiles
        with xtrace.annotate("step"):
            synced = drv.step(sess)
        steps += 1
        marks.append(time.perf_counter())
        step_compiles.append(log.compiles - n_compiles)
        if synced and (steps >= int(mix["trace_steps"]) if trace
                       else time.perf_counter() - t0 >= seconds):
            break
    t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    persistent_cache(True)
    window = sess.history[n_hist:]
    rec.update(steps=steps, window_s=t1 - t0,
               step_walls=[b - a for a, b in zip(marks, marks[1:])],
               window_compiles=log.since(snap), step_compiles=step_compiles,
               window_plan_s=sum(h.plan_s for h in window),
               window_link_bytes=sum(h.uploaded + h.downloaded for h in window),
               window_tiles=[h.num_tiles for h in window])
    rec["peak_hbm_bytes"] = (device.memory_stats() or {}).get("peak_bytes_in_use")
    rec["peaks"] = (peaks_for(device.device_kind)
                    if device.platform == "tpu" else None)
    if trace:
        try:
            rec["trace"] = xtrace.reduce_file(xtrace.find_xspace(tmp))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    reds = drv.finish(sess)
    total_steps = drv.steps
    fields = {k: np.array(v, copy=True)
              for k, v in drv.fields(cfg["compare"]).items()}
    sess.close()
    del sess, drv, window
    gc.collect()
    ref_fields, ref_reds = reference_module(cfg).run(
        cfg, seed, total_steps, fields=cfg["compare"])
    rec["total_steps"] = total_steps
    rec["compulsory_bytes_per_step"] = compulsory_bytes(cfg, mix)
    return rec, compare(cfg, fields, reds, ref_fields, ref_reds)


def result_line(cell: dict, rec: dict, checks: dict, trace: bool,
                device_info: dict) -> dict:
    """The last line of standard output."""
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    metrics = {}
    for m in cell["per_layer" if trace else "end_to_end"]:
        value = read_metric(m["name"], rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(device_info, memory_peak_bytes=rec["peak_hbm_bytes"])
    out = {"correct": correct, "attempted": rec["steps"],
           "failed": 0 if correct else rec["steps"],
           "metrics": metrics, "device": device}
    tr = rec.get("trace")
    if trace and tr:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        out["breakdown"] = {"device_ops": [list(x) for x in tr["device_ops"]],
                            "idle_gaps": [list(x) for x in tr["idle_gaps"]]}
    out["checks"] = checks
    return out
