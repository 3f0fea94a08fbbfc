"""Chip smoke: the out-of-core main path end to end on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip mesh phase only

One chip runs three phases, each printing its own lines:

* device: JAX must report a TPU; prints its kind, count and HBM limit.
* cloverleaf: CloverLeaf 2D at the clover_bm16 deck size (3840² cells,
  fp32, 25 fields, about 1.5 GB) for 3 timesteps with a field summary —
  ``Session("ooc")`` with a third of the working set as device capacity
  (the paper's 3x over-capacity ratio, tiles streamed host<->HBM),
  ``Session("resident")``, and ``Session("reference")``, compared on every
  field a timestep carries and on every summary.  A second ``ooc`` run in the same process
  shows whether its compiles came from the persistent compilation cache.
* pallas: star sweeps through the ``pallas`` backend with compiled kernels
  at 3840² and 256³ against ``reference``, with no loop falling back.

``--chips 4`` runs only the mesh phase: one CloverLeaf 2D timestep with its
field summary on ``Session("ooc", mesh="jax:4")`` against the unsharded
``ooc`` run, every carried field bit-identical (``sum`` reductions to a
relative tolerance), each shard on its own chip.

Times are host wall-clock seconds on the named device; each one ends in a
host read of device results.  The last line of output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase raises, and the script exits non-zero without that line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

CLOVER_N = 3840          # clover_bm16.in: x_cells = y_cells = 3840
CLOVER_STEPS = 3
# One timestep on the mesh: each of the four shards compiles its own tile
# programs on its own chip, and one step already runs both sweep directions.
MESH_STEPS = 1
PALLAS_N2 = 3840
PALLAS_N3 = 256
DENSITY_TOL = 1e-4       # examples/cloverleaf_outofcore.py's bar
SUMMARY_RTOL = 1e-3      # summaries vs reference (float32, other order)
KERNEL_ATOL = 1e-5       # Pallas sweep vs reference, values in [0, 1)


class CompileLog:
    """Counts XLA compiles and persistent-cache lookups through
    ``jax.monitoring`` (process-wide listeners, registered once)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.hits, self.misses)

    def since(self, snap):
        s, c, h, m = snap
        return {"compile_s": self.seconds - s, "compiles": self.compiles - c,
                "cache_hits": self.hits - h, "cache_misses": self.misses - m}


def check_device(chips: int) -> dict:
    """The device phase: a TPU with at least ``chips`` devices, or exit."""
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — JAX reports platform "
                         f"{dev.platform!r}; nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX sees "
                         f"{len(devs)} device(s)")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    stats = dev.memory_stats() or {}
    print(f"[device] {info['kind']} x{info['count']}, bytes_limit "
          f"{stats.get('bytes_limit')}")
    return info


def _peak_bytes(dev):
    """``peak_bytes_in_use`` of ``dev``; None where the backend keeps no
    memory statistics (the CPU)."""
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def _close_summaries(got: dict, want: dict, rtol: float, what: str) -> None:
    for k, w in want.items():
        g = got[k]
        if not (np.isfinite(g) and g != 0.0):
            raise AssertionError(f"{what}: summary {k} = {g!r} is not a "
                                 f"finite non-zero value")
        if abs(g - w) > rtol * abs(w):
            raise AssertionError(f"{what}: summary {k} = {g!r}, reference "
                                 f"{w!r} (rtol {rtol})")


def carried_fields() -> list:
    """The fields a CloverLeaf timestep reads before it writes them — the
    state carried from step to step.  Cyclic execution leaves the other
    (temporary) fields' home copies undefined, so only these compare."""
    from repro.apps import CloverLeaf2D
    from repro.core import Session
    from repro.core.dependency import chain_live_set

    probe = CloverLeaf2D(8, 8)
    sess = Session("reference")
    probe.record_timestep(sess)
    return sorted(chain_live_set(sess.queue))


def _run_clover(n: int, steps: int, backend: str, **cfg):
    from repro.apps import CloverLeaf2D
    from repro.core import Session

    app = CloverLeaf2D(n, n, summary_every=steps)
    if backend == "ooc" and "capacity_bytes" not in cfg:
        cfg["capacity_bytes"] = app.total_bytes() / 3
    with Session(backend, **cfg) as sess:
        t0 = time.perf_counter()
        summary = app.run(sess, steps=steps)
        wall = time.perf_counter() - t0
        return app, summary, wall, sess


def phase_cloverleaf(n: int = CLOVER_N, steps: int = CLOVER_STEPS,
                     label: str = "cpu", log: CompileLog = None) -> dict:
    """ooc (capacity = working set / 3, prefetch) and resident against
    reference; returns the measured numbers."""
    import jax

    ref, ref_sum, ref_wall, _ = _run_clover(n, steps, "reference")
    total = ref.total_bytes()
    print(f"[cloverleaf] {n}x{n} fp32, {len(ref.dats)} fields, "
          f"{total / 1e9:.3f} GB working set, {steps} steps; reference "
          f"{ref_wall:.3f} s")
    fields = carried_fields()
    out = {"working_set_bytes": total}
    runs = [("ooc", "ooc", dict(prefetch=True)),
            ("ooc (2nd, same config)", "ooc", dict(prefetch=True)),
            ("resident", "resident", {})]
    for name, backend, cfg in runs:
        snap = log.snapshot() if log else None
        app, summary, wall, sess = _run_clover(n, steps, backend, **cfg)
        errs = {f: float(np.abs(app.d(f).interior()
                                - ref.d(f).interior()).max()) for f in fields}
        err = errs["density0"]
        worst = max(errs, key=errs.get)
        if not errs[worst] < DENSITY_TOL:
            raise AssertionError(f"{name}: max|d{worst}| {errs[worst]:.3e} "
                                 f"vs reference >= {DENSITY_TOL}")
        _close_summaries(summary, ref_sum, SUMMARY_RTOL, name)
        hist = sess.history
        tiles = max(c.num_tiles for c in hist)
        per_step = " ".join(f"{w:.3f}" for w in app.step_walls)
        print(f"[cloverleaf] {name}: max|drho0| {err:.3e}, over all "
              f"{len(fields)} carried fields {errs[worst]:.3e} ({worst}); "
              f"summaries within rtol {SUMMARY_RTOL} of reference; "
              f"{len(hist)} chains, <= {tiles} tiles/chain")
        print(f"[cloverleaf] {name}: wall s/step on {label}: {per_step} "
              f"(whole run incl. init {wall:.3f} s)")
        print(f"[cloverleaf] {name}: staged {sum(c.uploaded for c in hist)} "
              f"B up, {sum(c.downloaded for c in hist)} B down; planning "
              f"{sum(c.plan_s for c in hist):.3f} s")
        row = {"max_drho0": err, "step_walls": app.step_walls,
               "run_s": wall, "tiles": tiles, "summary": summary}
        if log:
            c = log.since(snap)
            row.update(c)
            print(f"[cloverleaf] {name}: {c['compiles']} XLA compiles, "
                  f"{c['compile_s']:.3f} s compiling; persistent cache "
                  f"{c['cache_hits']} hits / {c['cache_misses']} misses")
        out[name] = row
        if backend == "ooc" and not tiles > 1:
            raise AssertionError("ooc run did not tile")
    if log:
        warm = out["ooc (2nd, same config)"]
        print(f"[cloverleaf] second compile of the step programs hit the "
              f"cache: {'yes' if warm['cache_hits'] else 'no'} "
              f"({warm['cache_hits']} hits, {warm['cache_misses']} misses)")
    peak = _peak_bytes(jax.devices()[0])
    print(f"[cloverleaf] peak_bytes_in_use on device 0: {peak}")
    print("[cloverleaf] pass")
    return out


def phase_pallas(n2: int = PALLAS_N2, n3: int = PALLAS_N3,
                 interpret: bool = False, label: str = "cpu") -> dict:
    """Two star sweeps per grid through the ``pallas`` backend, against
    ``reference`` on the same seeded data; no loop may fall back."""
    from repro.core import Block, Session, make_dataset
    from repro.core.backends import PallasBackend
    from repro.kernels import star2d_kernel, star3d_kernel

    rng = np.random.default_rng(0)
    cases = [("2d", (n2, n2), star2d_kernel, (0.5, 0.125, 0.125)),
             ("3d", (n3, n3, n3), star3d_kernel, (0.4, 0.1, 0.1, 0.1))]
    out = {}
    for tag, size, make_kernel, coeffs in cases:
        init = rng.random(size, dtype=np.float32)
        pallas = PallasBackend(interpret=interpret)
        results = {}
        for name, sess in (("reference", Session("reference")),
                           ("pallas", Session(backend=pallas))):
            blk = Block(f"star{tag}", size)
            u = make_dataset(blk, "u", halo=1, init=init)
            v = make_dataset(blk, "v", halo=1)
            t0 = time.perf_counter()
            sess.par_loop("sweep_uv", blk, blk.full_range(), [u, v],
                          make_kernel("u", "v", coeffs))
            sess.par_loop("sweep_vu", blk, blk.full_range(), [v, u],
                          make_kernel("v", "u", coeffs))
            results[name] = sess.fetch(u)
            wall = time.perf_counter() - t0
        if pallas.fallback_loops != 0 or pallas.pallas_loops != 2:
            raise AssertionError(
                f"pallas {tag}: {pallas.pallas_loops} loops on the kernels, "
                f"{pallas.fallback_loops} fell back")
        out[tag] = {"wall_s": wall}
        err = float(np.abs(results["pallas"] - results["reference"]).max())
        if not err < KERNEL_ATOL:
            raise AssertionError(f"pallas {tag}: max error {err:.3e} vs "
                                 f"reference >= {KERNEL_ATOL}")
        out[tag]["max_err"] = err
        print(f"[pallas] {tag} {'x'.join(map(str, size))}: 2 sweeps on the "
              f"kernels (interpret={interpret}), 0 fallbacks, max error "
              f"{err:.3e}; {out[tag]['wall_s']:.3f} s on {label} incl. "
              f"compile and host copies")
    print("[pallas] pass")
    return out


def phase_mesh(n: int = CLOVER_N, steps: int = MESH_STEPS,
               chips: int = 4, label: str = "cpu") -> dict:
    """CloverLeaf 2D sharded over ``jax:<chips>`` against the unsharded
    ``ooc`` run: bit-identical fields, each shard on its own device."""
    import jax

    base, base_sum, base_wall, _ = _run_clover(n, steps, "ooc")
    app, summary, wall, sess = _run_clover(
        n, steps, "ooc", mesh=f"jax:{chips}",
        capacity_bytes=base.total_bytes() / 3)
    ex = sess.backend
    if ex.exchange_path != "ppermute":
        raise AssertionError(f"mesh exchange path {ex.exchange_path!r}")
    fields = carried_fields()
    for name in fields:
        a, b = base.d(name).interior(), app.d(name).interior()
        if not np.array_equal(a, b):
            raise AssertionError(f"mesh: field {name} differs from the "
                                 f"unsharded run (max {np.abs(a - b).max():.3e})")
    for k, w in base_sum.items():
        g = summary[k]
        exact = k.startswith(("max_", "min_"))
        if (g != w) if exact else abs(g - w) > SUMMARY_RTOL * abs(w):
            raise AssertionError(f"mesh: summary {k} {g!r} vs unsharded "
                                 f"{w!r}")
    devs = jax.devices()[:chips]
    shards = []
    for s, inner in enumerate(ex.inner):
        placed = {c.devices for c in inner.history}
        if placed != {(devs[s].id,)}:
            raise AssertionError(f"mesh: shard {s} slot arrays on devices "
                                 f"{placed}, expected {devs[s].id}")
        shards.append(devs[s].id)
        print(f"[mesh] shard {s}: {len(inner.history)} chains, slot arrays "
              f"on device {devs[s].id} ({devs[s]})")
    peaks = [_peak_bytes(d) for d in devs]
    print(f"[mesh] peak_bytes_in_use per device: {peaks}")
    if devs[0].platform == "tpu" and not all(peaks):
        raise AssertionError(f"mesh: a device held no memory: {peaks}")
    print(f"[mesh] {n}x{n}, {steps} steps: all {len(fields)} carried fields "
          f"bit-identical to unsharded ooc, min/max summaries exact, sums "
          f"within rtol {SUMMARY_RTOL}")
    print(f"[mesh] wall s/step on {label}: sharded "
          f"{' '.join(f'{w:.3f}' for w in app.step_walls)}; unsharded "
          f"{' '.join(f'{w:.3f}' for w in base.step_walls)}")
    print(f"[mesh] halo exchange: {ex.halo_stats.messages} messages, "
          f"{ex.halo_stats.bytes} bytes (ppermute, through the host)")
    print("[mesh] pass")
    return {"shard_devices": shards, "peak_bytes": peaks,
            "step_walls": app.step_walls, "base_step_walls": base.step_walls,
            "run_s": wall, "base_run_s": base_wall}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise SystemExit(f"chip_smoke: the repro package is not beside this "
                         f"script ({e})")
    info = check_device(args.chips)
    from repro.compile_cache import enable_compile_cache

    print(f"[device] compilation cache: {enable_compile_cache()}")
    label = f"{info['kind']} ({info['platform']})"
    if args.chips == 4:
        phase_mesh(chips=4, label=label)
    else:
        phase_cloverleaf(label=label, log=CompileLog())
        phase_pallas(interpret=False, label=label)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
