"""Observability-spine tests: the span tracer, metrics instruments, Chrome
export, the modelled-vs-achieved drift audit, and the end-to-end wiring
through executor / transfer lanes / sharded mesh / serve.

Two load-bearing properties:

* **Disabled is free, enabled is inert.**  Untraced sessions pay one
  attribute check; traced runs are *bit-identical* to untraced runs on all
  three bundled apps (tracing only observes, never perturbs).
* **The sim interpreter is its own oracle.**  Modelled spans are emitted at
  the simulated ledger events' exact timestamps, so the drift audit must
  report a per-stream achieved/modelled ratio of exactly 1.0 — not
  approximately.
"""
import json
import threading
import time

import numpy as np
import pytest

from repro.apps.cloverleaf2d import CloverLeaf2D
from repro.apps.cloverleaf3d import CloverLeaf3D
from repro.apps.opensbli import OpenSBLI
from repro.core import Session
from repro.obs import (
    NULL_TRACER,
    MetricsRegistry,
    NullTracer,
    Tracer,
    as_tracer,
    chrome_trace,
    compare,
    merge_histogram_snapshots,
    spans_from_chrome,
    validate_chrome_trace,
)
from repro.serve import StencilServer


# -- tracer core --------------------------------------------------------------------

def test_tracer_ring_is_bounded():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.emit(f"s{i}", t_start=float(i), t_end=float(i) + 0.5)
    assert len(tr) == 4
    assert tr.dropped == 6
    # Oldest spans were evicted, newest retained.
    assert [s.name for s in tr.spans()] == ["s6", "s7", "s8", "s9"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_emit_is_thread_safe():
    tr = Tracer(capacity=1 << 14)
    n_threads, per_thread = 8, 200

    def work(k):
        for i in range(per_thread):
            tr.emit("e", track=f"t{k}", t_start=float(i), t_end=float(i + 1))

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr) == n_threads * per_thread
    assert tr.dropped == 0
    per_track = {}
    for s in tr.spans():
        per_track[s.track] = per_track.get(s.track, 0) + 1
    assert all(v == per_thread for v in per_track.values())


def test_span_context_manager_nests():
    ticks = iter(range(100))
    tr = Tracer(clock=lambda: float(next(ticks)))
    with tr.span("outer", track="a"):
        with tr.span("inner", track="a", args={"k": 1}):
            pass
    spans = {s.name: s for s in tr.spans()}
    assert set(spans) == {"outer", "inner"}
    # Inner closes first (emit-on-exit) and sits inside outer's interval.
    inner, outer = spans["inner"], spans["outer"]
    assert outer.t_start <= inner.t_start <= inner.t_end <= outer.t_end
    assert inner.args == {"k": 1}
    assert inner.duration == inner.t_end - inner.t_start


def test_null_tracer_fast_path_allocates_nothing():
    nt = as_tracer(None)
    assert nt is NULL_TRACER and nt is as_tracer(False)
    assert not nt.enabled
    # span() returns one module-level singleton: no per-call allocation.
    assert nt.span("a") is nt.span("b")
    assert nt.emit("x", t_start=0.0, t_end=1.0) is None
    assert nt.spans() == [] and len(nt) == 0
    assert nt.anchor("x") is None
    # Shared instances pass through; fresh tracer on True; junk rejected.
    tr = Tracer()
    assert as_tracer(tr) is tr
    assert isinstance(as_tracer(True), Tracer)
    assert isinstance(as_tracer(NullTracer()), NullTracer)
    with pytest.raises(TypeError):
        as_tracer("yes")


class _RefusingNullTracer(NullTracer):
    """A disabled tracer whose span/emit/anchor fail: a site that calls them
    (and so builds their arguments) without checking ``enabled`` first."""

    def span(self, *a, **kw):
        raise AssertionError("span() called on a disabled tracer")

    def emit(self, *a, **kw):
        raise AssertionError("emit() called on a disabled tracer")

    def anchor(self, *a, **kw):
        raise AssertionError("anchor() called on a disabled tracer")


def _clover_steps(sess, steps=2):
    """CloverLeaf 2D's init, then ``steps`` timesteps with the dt read and
    the field summary: plans, compiles, staging, pinned fields, prefetch
    and reductions."""
    app = CloverLeaf2D(40, 24, summary_every=0)
    app.record_init(sess)
    sess.flush()
    sess.cyclic = True
    for _ in range(steps):
        app._ideal_gas(sess, "density0", "energy0", "_dt")
        app._viscosity(sess)
        app._calc_dt(sess)
        app.dt = float(min(1e-4, sess.reduction("dt")))
        app.record_timestep(sess)
    for name in app.record_summary(sess):
        sess.reduction(name)


def test_new_span_sites_guard_on_enabled():
    """Every span site checks ``tracer.enabled`` before building a span:
    an untraced run never calls the tracer (so allocates nothing for it)."""
    sess = Session("ooc", num_tiles=3, capacity_bytes=float("inf"),
                   prefetch=True, pinned=("density0",),
                   trace=_RefusingNullTracer())
    try:
        _clover_steps(sess)
        assert sess.trace() is None
        assert sum(h.uploaded for h in sess.history) > 0
    finally:
        sess.close()


def test_untraced_session_exposes_no_trace():
    sess = Session("ooc", num_tiles=2, capacity_bytes=float("inf"))
    try:
        assert sess.trace() is None
    finally:
        sess.close()


# -- metrics ------------------------------------------------------------------------

def test_metrics_registry_instruments():
    mr = MetricsRegistry()
    mr.counter("jobs").inc()
    mr.counter("jobs").inc(2.0)
    mr.gauge("depth").set(3)
    mr.histogram("wait").observe(1e-5)
    mr.histogram("wait").observe(2.0)
    snap = mr.snapshot()
    assert snap["counters"]["jobs"] == 3.0
    assert snap["gauges"]["depth"] == 3.0
    h = snap["histograms"]["wait"]
    assert h["count"] == 2 and h["min"] == 1e-5 and h["max"] == 2.0
    assert sum(c for _, c in h["buckets"]) + h["overflow"] == 2
    # snapshot is JSON-able as-is
    assert json.loads(mr.to_json())["counters"]["jobs"] == 3.0
    # same-name accessor returns the same instrument
    assert mr.counter("jobs") is mr.counter("jobs")


def test_histogram_snapshots_merge():
    from repro.obs import Histogram

    a, b = Histogram(), Histogram()
    a.observe(1e-4)
    b.observe(0.5)
    b.observe(50.0)
    m = merge_histogram_snapshots(a.snapshot(), b.snapshot())
    assert m["count"] == 3
    assert m["min"] == 1e-4 and m["max"] == 50.0
    assert sum(c for _, c in m["buckets"]) + m["overflow"] == 3
    # empty snapshots pass through; mismatched bounds refuse
    assert merge_histogram_snapshots({}, a.snapshot())["count"] == 1
    with pytest.raises(ValueError):
        merge_histogram_snapshots(a.snapshot(),
                                  Histogram(bounds=(1.0, 2.0)).snapshot())


# -- chrome export ------------------------------------------------------------------

def test_chrome_trace_round_trip():
    tr = Tracer()
    tr.emit("up", cat="lane", track="upload", t_start=0.25, t_end=1.5,
            args={"eid": 3, "bytes": 4096})
    tr.emit("k0", cat="model", track="compute", t_start=1.5, t_end=2.75)
    doc = tr.chrome()
    validate_chrome_trace(doc)
    # one metadata record per track + process name, then the X events
    names = [e["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"]
    assert len(names) == 2
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(xs) == 2
    back = spans_from_chrome(doc)
    got = {s.name: s for s in back}
    assert got["up"].track == "upload"
    assert got["up"].args["bytes"] == 4096
    assert got["up"].t_start == pytest.approx(0.25, abs=1e-6)
    assert got["up"].duration == pytest.approx(1.25, abs=1e-6)
    # serialisable end to end
    json.dumps(doc)


def test_chrome_validation_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": "nope"})
    bad = chrome_trace([])
    bad["traceEvents"].append({"ph": "X", "name": "x"})  # missing ts/dur/tid
    with pytest.raises(ValueError):
        validate_chrome_trace(bad)


# -- drift audit: the sim interpreter is its own oracle -----------------------------

def _sim_traced_session(app):
    sess = Session("sim", num_tiles=4,
                   capacity_bytes=app.total_bytes() * 0.5, trace=True)
    app.record_init(sess)
    sess.flush()
    app.dt = 1e-4
    app.record_timestep(sess)
    sess.flush()
    return sess


def test_sim_drift_audit_is_oracle_exact():
    app = CloverLeaf2D(40, 24, summary_every=0)
    sess = _sim_traced_session(app)
    tr = sess.trace()
    assert tr is not None and len(tr) > 0
    ledgers = sess.backend.ledgers
    assert len(ledgers) == len(sess.history)
    seen_streams = set()
    for ci, ledger in enumerate(ledgers):
        rep = compare(ledger, tr, chain=ci)
        assert rep.unmatched_events == 0
        assert rep.overall_ratio == 1.0
        for sd in rep.streams.values():
            # Exact equality is the whole point: modelled spans *are* the
            # simulated events, so the sums agree bitwise.
            assert sd.ratio == 1.0, (ci, sd.name)
            assert sd.matched == sd.events
            seen_streams.add(sd.name)
        # every audited op cites a plan op index >= 0 (format_plan's #N)
        assert all(o.op >= 0 for o in rep.ops)
        assert rep.summary(top_k=3)  # renders without error
    assert {"compute", "upload", "download"} <= seen_streams
    sess.close()


def test_drift_audit_tolerates_foreign_spans():
    """Spans from other chains/layers must not leak into a chain's audit."""
    app = CloverLeaf2D(40, 24, summary_every=0)
    sess = _sim_traced_session(app)
    tr = sess.trace()
    tr.emit("noise", cat="serve", track="tenant/x", t_start=0.0, t_end=9.9)
    rep = compare(sess.backend.ledgers[-1], tr,
                  chain=len(sess.backend.ledgers) - 1)
    assert rep.overall_ratio == 1.0
    sess.close()


# -- data-plane wiring --------------------------------------------------------------

def test_threaded_run_traces_all_streams():
    app = CloverLeaf2D(48, 32, summary_every=0)
    sess = Session("ooc-async", num_tiles=4, capacity_bytes=float("inf"),
                   trace=True)
    app.run(sess, steps=2)
    tr = sess.trace()
    tracks = {s.track for s in tr.spans()}
    assert {"chain", "compute", "upload", "download"} <= tracks
    # lane spans carry their ledger event id and queue-wait
    lane_spans = [s for s in tr.spans() if s.cat == "lane"]
    assert lane_spans
    for s in lane_spans:
        assert "eid" in s.args and "queue_wait_s" in s.args
    validate_chrome_trace(tr.chrome())
    # per-lane queue-wait/service histograms ride transfer_stats()
    lanes = sess.transfer_stats()["lanes"]
    assert lanes, "threaded engine reported no lane histograms"
    for lane, hists in lanes.items():
        assert hists["queue_wait"]["count"] > 0, lane
        assert hists["service"]["count"] > 0, lane
    # wall-clock achieved vs TPU-modelled: wildly different scales, but the
    # audit must still match every handle-backed event it can see
    rep = compare(sess.backend.ledgers[0], tr, chain=0)
    assert rep.spans_seen > 0
    assert all(sd.ratio > 0.0 for sd in rep.streams.values()
               if sd.achieved_s > 0)
    sess.close()


def test_traced_chain_records_ledger_and_chain_spans():
    app = CloverLeaf2D(32, 24, summary_every=0)
    sess = Session("ooc", num_tiles=2, capacity_bytes=float("inf"),
                   trace=True)
    app.record_init(sess)
    sess.flush()
    tr = sess.trace()
    chain_spans = [s for s in tr.spans() if s.cat == "chain"]
    assert len(chain_spans) == len(sess.history) == 1
    assert chain_spans[0].args["chain"] == 0
    assert len(sess.backend.ledgers) == 1
    sess.close()


def _inside(inner, outer):
    return outer.t_start <= inner.t_start and inner.t_end <= outer.t_end


def _one_covering(span, candidates):
    got = [c for c in candidates if c is not span and _inside(span, c)]
    assert got, f"{span!r} lies in none of {len(candidates)} candidates"
    return got


def test_runtime_spans_nest_and_carry_chain_and_tile():
    import jax

    compiled = []

    def on_compile(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(time.perf_counter())

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    tr = Tracer()
    sess = Session("ooc", num_tiles=3, capacity_bytes=float("inf"),
                   prefetch=True, pinned=("density0",), trace=tr)
    try:
        _clover_steps(sess, steps=4)
        spans = tr.spans()
        by = {}
        for sp in spans:
            by.setdefault(sp.name, []).append(sp)
        for name, cat in [("plan", "plan"), ("tile_compile", "compile"),
                          ("tile_dispatch", "dispatch"), ("stage_in", "stage"),
                          ("stage_out", "stage"), ("d2h", "wait"),
                          ("reduction_read", "wait"), ("upload_wait", "wait")]:
            assert by.get(name), name
            for sp in by[name]:
                assert sp.cat == cat and isinstance(sp.args["chain"], int)
        chains = {sp.args["chain"]: sp for sp in by["chain"]}
        for sp in by["plan"]:
            assert isinstance(sp.args["cache_hit"], bool)
            assert sp.track == "chain"
            assert _inside(sp, chains[sp.args["chain"]])
        assert any(sp.args["cache_hit"] for sp in by["plan"])
        tiles = {(sp.args["chain"], sp.args["tile"]): sp
                 for sp in spans if sp.cat == "tile"}
        for name in ("tile_dispatch", "upload_wait", "reduction_read"):
            for sp in by[name]:
                assert sp.track == "compute"
                assert _inside(sp, tiles[sp.args["chain"], sp.args["tile"]])
        # Each tile program compiles once, inside the launch that needed
        # it, and that launch compiles nothing after it.
        for sp in by["tile_compile"]:
            launch, = _one_covering(sp, by["tile_dispatch"])
            assert launch.args == {k: v for k, v in sp.args.items()
                                   if k != "sig"}
            assert not [t for t in compiled if sp.t_end < t <= launch.t_end]
        engines = [cp.engine for cp in sess.backend._plans.values()]
        assert len(by["tile_compile"]) == sum(len(e._cache) for e in engines)
        for sp in by["d2h"]:
            outer = _one_covering(sp, by["stage_out"])
            assert outer[0].track == sp.track == "download"
        for name, lane in (("stage_in", "upload"), ("stage_out", "download")):
            for sp in by[name]:
                assert sp.track == lane and sp.args["bytes"] > 0
                assert sp.args["dat"] in sess.datasets
        # The staging spans out count the chains' raw bytes down.
        assert sum(sp.args["bytes"] for sp in by["stage_out"]) == sum(
            h.downloaded for h in sess.history)
        # The pinned field stages whole, outside any tile, both ways.
        for name in ("stage_in", "stage_out"):
            pinned = [sp for sp in by[name] if sp.args["dat"] == "density0"]
            assert pinned and all("tile" not in sp.args for sp in pinned)
        validate_chrome_trace(tr.chrome())
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
        sess.close()


def test_anchors_put_spans_on_the_profilers_clock(tmp_path):
    """On the CPU, under ``jax.profiler``: the two anchors give offsets
    that agree within 100 us (beyond each anchor's own slack: the time its
    span takes beyond its annotation's), and every chain span, moved by
    the offset, lies inside the annotation around its flush."""
    import glob
    import os

    import jax

    tr = Tracer()
    app = CloverLeaf2D(32, 24, summary_every=0)
    sess = Session("ooc", num_tiles=2, capacity_bytes=float("inf"), trace=tr)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        first = tr.anchor("anchor.first")
        app.record_init(sess)
        with jax.profiler.TraceAnnotation("flush.init"):
            sess.flush()
        app.dt = 1e-4
        app.record_timestep(sess)
        with jax.profiler.TraceAnnotation("flush.step"):
            sess.flush()
        last = tr.anchor("anchor.last")
    finally:
        jax.profiler.stop_trace()
        sess.close()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    notes = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("anchor.", "flush.")):
                    notes[ev.name] = (ev.start_ns, ev.start_ns + ev.duration_ns)

    def offset_and_slack(span):
        lo, hi = notes[span.name]
        slack = (span.duration * 1e9 - (hi - lo)) / 2
        return (lo + hi) / 2 - (span.t_start + span.t_end) / 2 * 1e9, slack

    (o1, s1), (o2, s2) = offset_and_slack(first), offset_and_slack(last)
    assert abs(o2 - o1) <= 100e3 + s1 + s2
    offset = (o1 + o2) / 2
    flushes = [notes["flush.init"], notes["flush.step"]]
    chains = [sp for sp in tr.spans() if sp.cat == "chain"]
    assert len(chains) == 2
    for sp, (lo, hi) in zip(chains, flushes):
        a, b = sp.t_start * 1e9 + offset, sp.t_end * 1e9 + offset
        assert lo - 100e3 - s1 - s2 <= a and b <= hi + 100e3 + s1 + s2


# -- bit-identity: tracing observes, never perturbs ---------------------------------

@pytest.mark.parametrize("factory", [
    lambda: CloverLeaf2D(32, 24, summary_every=0),
    lambda: CloverLeaf3D(12, 10, 8, summary_every=0),
    lambda: OpenSBLI(16),
], ids=["cloverleaf2d", "cloverleaf3d", "opensbli"])
def test_traced_run_bit_identical(factory):
    def run(trace):
        app = factory()
        sess = Session("ooc", num_tiles=2, capacity_bytes=float("inf"),
                       trace=trace)
        try:
            app.record_init(sess)
            sess.flush()
            app.dt = 1e-4
            app.record_timestep(sess)
            sess.flush()
            return {k: d.materialize() for k, d in app.dats.items()}
        finally:
            sess.close()

    plain, traced = run(False), run(True)
    assert set(plain) == set(traced)
    for k in plain:
        np.testing.assert_array_equal(plain[k], traced[k],
                                      err_msg=f"tracing perturbed {k!r}")


# -- plan-op indices ----------------------------------------------------------------

def test_format_plan_numbers_ops():
    app = CloverLeaf2D(40, 24, summary_every=0)
    sess = Session("sim", num_tiles=4,
                   capacity_bytes=app.total_bytes() * 0.5)
    app.record_init(sess)
    sess.queue.clear()
    app.dt = 1e-4
    app.record_timestep(sess)
    text = sess.explain()
    assert "#0" in text, "format_plan lost its op indices"
    plans = sess.plan()
    # the highest printed index addresses a real op in some chain's plan
    idx = max(int(tok[1:]) for tok in text.split() if tok.startswith("#")
              and tok[1:].isdigit())
    assert idx < max(len(p.ops) for p in plans)
    # verifier diagnostics still render alongside the indices
    assert "modelled makespan" in sess.explain(verify=True)


# -- sharded mesh -------------------------------------------------------------------

def test_sharded_trace_tags_devices():
    app = CloverLeaf2D(32, 24, summary_every=0)
    sess = Session("ooc", mesh="sim:2", num_tiles=2,
                   capacity_bytes=float("inf"), trace=True)
    app.record_init(sess)
    sess.flush()
    tr = sess.trace()
    tracks = {s.track for s in tr.spans()}
    assert any(t.startswith("dev0/") for t in tracks)
    assert any(t.startswith("dev1/") for t in tracks)
    assert "mesh" in tracks  # scatter/gather (+ halo when depth > 0)
    lanes = sess.transfer_stats()["lanes"]
    assert lanes and all(h["queue_wait"]["count"] >= 0
                         for h in lanes.values())
    sess.close()


# -- serve layer --------------------------------------------------------------------

def test_serve_spans_metrics_and_shared_clock():
    """One injected clock feeds tenant queue-wait stats *and* serve spans:
    with time frozen, every serve-layer duration is exactly zero."""
    frozen = 1234.5

    with StencilServer("sim:1", capacity_bytes=2e6, trace=True,
                       clock=lambda: frozen) as srv:
        app = CloverLeaf2D(24, 24, summary_every=0)
        rt = srv.session("t0")
        app.record_init(rt)
        rt.flush()
        st = srv.stats()
        assert st.tenants["t0"].queue_wait_s == 0.0
        tr = srv.tracer
        assert rt.trace() is tr  # server-backed sessions see the spine
        serve_spans = [s for s in tr.spans() if s.cat in ("serve", "lease")]
        assert {s.name for s in serve_spans} >= {"admit", "queue-wait", "t0"}
        for s in serve_spans:
            assert s.t_start == frozen and s.t_end == frozen
        lease = [s for s in serve_spans if s.cat == "lease"]
        assert lease and lease[0].track == "lane0"
        m = srv.metrics()
        assert m["counters"]["jobs_completed"] == 1.0
        assert m["histograms"]["queue_wait_s"]["count"] == 1
        assert m["histograms"]["queue_wait_s"]["sum"] == 0.0
        assert m["gauges"]["free_lanes"] == 1.0
        rt.close()


def test_serve_lane_tags_and_oracle_stays_untraced():
    with StencilServer("sim:2", capacity_bytes=2e6, trace=True) as srv:
        app = CloverLeaf2D(24, 24, summary_every=2)
        rt = srv.session("t0")
        app.run(rt, steps=1)
        rt.close()
        tracks = {s.track for s in srv.tracer.spans()}
        assert any(t.startswith("lane0/") for t in tracks)
        # The admission oracle shares the lanes' config but must not leak
        # phantom sim runs into the trace: every span is tagged by a lane,
        # a tenant, or the serve layer itself.
        for s in srv.tracer.spans():
            assert (s.track.startswith(("lane", "tenant/"))
                    or s.cat == "lease"), s.track


def test_serve_untraced_by_default():
    with StencilServer("sim:1", capacity_bytes=2e6) as srv:
        assert not srv.tracer.enabled
        rt = srv.session("t0")
        assert rt.trace() is None
        rt.close()
        assert srv.metrics()["counters"] == {}
