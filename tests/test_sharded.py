"""Device-mesh sharded execution (the ooc-sharded backend): decomposition
geometry, halo ops in the Plan IR, per-device interpreters, exchange
accounting, and the Session surface (mesh=, context manager, tune meshes)."""
import threading

import jax
import numpy as np
import pytest

from repro.apps import CloverLeaf2D
from repro.core import (
    DeviceMesh,
    HaloExchange,
    MeshError,
    Plan,
    Session,
    parse_mesh,
)
from repro.core.mesh import shard_geometries
from repro.core.sharded import ShardingError, split_segments

LIVE_FIELDS = ("density0", "energy0", "pressure", "viscosity", "soundspeed",
               "xvel0", "yvel0", "volume", "xarea", "yarea")


def drive(rt, app, steps=1):
    """Init + timesteps without the cyclic flag or dt chain breakers, so
    every dataset's home copy is fully defined (no elided temporaries)."""
    app.record_init(rt)
    rt.flush()
    for _ in range(steps):
        app.dt = 1e-4
        app.record_timestep(rt)
        rt.flush()


def assert_all_dats_equal(ref_app, app):
    for name in ref_app.dats:
        np.testing.assert_array_equal(
            ref_app.d(name).materialize(), app.d(name).materialize(),
            err_msg=name)


# -- mesh / geometry ---------------------------------------------------------------


class TestMesh:
    def test_parse_specs(self):
        assert parse_mesh(None) is None
        assert parse_mesh(4) == DeviceMesh.sim(4)
        assert parse_mesh("sim:4") == DeviceMesh.sim(4)
        assert parse_mesh("jax:2") == DeviceMesh(2, kind="jax")
        m = DeviceMesh.sim(3)
        assert parse_mesh(m) is m
        with pytest.raises(MeshError):
            parse_mesh("nope:4")
        with pytest.raises(MeshError):
            parse_mesh("sim:0")

    def test_geometries_partition_and_skirts(self):
        geos = shard_geometries(34, 4, skirt=5)
        assert [(g.lo, g.hi) for g in geos] == [(0, 9), (9, 18), (18, 26),
                                                (26, 34)]
        assert geos[0].skirt_lo == 0 and geos[0].skirt_hi == 5
        assert geos[1].skirt_lo == 5 and geos[1].skirt_hi == 5
        assert geos[-1].skirt_hi == 0
        assert geos[2].to_local(geos[2].lo) == 5
        with pytest.raises(MeshError):
            shard_geometries(3, 4, skirt=1)

    def test_jax_mesh_needs_devices(self):
        with pytest.raises(MeshError):
            DeviceMesh.sim(2).jax_mesh()
        if len(jax.devices()) >= 2:
            mesh = DeviceMesh.devices(2).jax_mesh()
            assert mesh.shape["shard"] == 2


class TestSegmentation:
    def test_budget_split(self):
        app = CloverLeaf2D(24, 24, summary_every=0)
        rt = Session("reference")
        app.record_init(rt)
        rt.queue.clear()
        app.record_timestep(rt)
        loops = list(rt.queue)
        segs = split_segments(loops, dim=1, budget=6)
        assert sum(len(s) for s in segs) == len(loops)
        from repro.core.sharded import loop_halo_extent

        for seg in segs:
            assert sum(loop_halo_extent(lp, 1) for lp in seg) <= 6

    def test_loop_wider_than_budget_raises(self):
        app = CloverLeaf2D(24, 24, summary_every=0)
        rt = Session("reference")
        app.record_timestep(rt)
        with pytest.raises(ShardingError):
            split_segments(list(rt.queue), dim=1, budget=1)


# -- the backend -------------------------------------------------------------------


class TestShardedBackend:
    def test_one_device_mesh_bit_identical_to_ooc(self):
        """Acceptance: ooc-sharded on a 1-device mesh == ooc, bitwise,
        through the full app driver (cyclic + dt breakers + summaries)."""
        ref = CloverLeaf2D(40, 32, summary_every=2)
        s_ref = ref.run(Session("ooc", num_tiles=4,
                                capacity_bytes=float("inf")), steps=2)
        app = CloverLeaf2D(40, 32, summary_every=2)
        s = app.run(Session("ooc-sharded", num_tiles=4,
                            capacity_bytes=float("inf")), steps=2)
        assert_all_dats_equal(ref, app)
        assert s_ref == s

    def test_virtual_mesh_bit_identical_to_ooc(self):
        """Acceptance: a 4-virtual-device data-plane run reproduces the
        unsharded executor bitwise (redundant skirt compute is the same
        arithmetic on the same values)."""
        ref = CloverLeaf2D(40, 32, summary_every=0)
        drive(Session("ooc", num_tiles=4, capacity_bytes=float("inf")), ref,
              steps=2)
        app = CloverLeaf2D(40, 32, summary_every=0)
        sess = Session("ooc-sharded", mesh="sim:4", num_tiles=4,
                       capacity_bytes=float("inf"))
        drive(sess, app, steps=2)
        assert_all_dats_equal(ref, app)

    def test_virtual_mesh_matches_reference_runtime(self):
        """Acceptance: the 4-device data plane matches the eager NumPy
        oracle within the usual JAX-vs-NumPy float32 tolerance, including
        cross-shard (min exact / sum combined) reductions."""
        ref = CloverLeaf2D(40, 32, summary_every=2)
        s_ref = ref.run(Session("reference"), steps=2)
        app = CloverLeaf2D(40, 32, summary_every=2)
        sess = Session("ooc-sharded", mesh="sim:4", num_tiles=4,
                       capacity_bytes=float("inf"))
        s = app.run(sess, steps=2)
        for name in LIVE_FIELDS:
            np.testing.assert_allclose(
                ref.d(name).interior(), app.d(name).interior(),
                rtol=1e-4, atol=1e-5, err_msg=name)
        for k in s_ref:
            np.testing.assert_allclose(s_ref[k], s[k], rtol=1e-3)

    @pytest.mark.skipif(len(jax.devices()) < 4,
                        reason="needs 4 XLA devices (conftest forces 8)")
    def test_jax_mesh_ppermute_path_bit_identical(self):
        """Real-device mesh: the exchange runs the exchange_halos ppermute
        collective under shard_map and still reproduces ooc bitwise."""
        ref = CloverLeaf2D(40, 32, summary_every=0)
        drive(Session("ooc", num_tiles=4, capacity_bytes=float("inf")), ref)
        app = CloverLeaf2D(40, 32, summary_every=0)
        sess = Session("ooc-sharded", mesh="jax:4", num_tiles=4,
                       capacity_bytes=float("inf"))
        drive(sess, app)
        assert sess.backend.exchange_path == "ppermute"
        assert_all_dats_equal(ref, app)
        # Each shard staged and computed on its own mesh device.
        for s, ex in enumerate(sess.backend.inner):
            assert ex.history
            assert {c.devices for c in ex.history} == {
                (jax.devices()[s].id,)}
        assert sess.history[-1].devices == tuple(
            d.id for d in jax.devices()[:4])
        st = sess.transfer_stats()
        assert st["halo_messages"] == sess.backend.halo_stats.messages
        assert st["halo_bytes"] == sess.backend.halo_stats.bytes

    def test_ledger_model_agrees_with_achieved_halo_stats(self):
        """Acceptance: halo message/byte counts from the per-device ledger
        plans equal the collective runtime's achieved HaloExchangeStats."""
        app = CloverLeaf2D(40, 32, summary_every=0)
        sess = Session("ooc-sharded", mesh="sim:4", num_tiles=4,
                       capacity_bytes=float("inf"))
        drive(sess, app)
        st = sess.transfer_stats()
        assert st["halo_messages"] > 0 and st["halo_bytes"] > 0
        assert st["halo_messages"] == sess.backend.halo_stats.messages
        assert st["halo_bytes"] == sess.backend.halo_stats.bytes

    def test_mesh_on_plain_ooc_backend_routes_to_sharded(self):
        from repro.core.sharded import ShardedOutOfCoreExecutor

        sess = Session("ooc", mesh=2)
        assert isinstance(sess.backend, ShardedOutOfCoreExecutor)
        sess.close()

    def test_plan_cache_hits_across_steps(self):
        """Localised loops must replay cached per-device plans: a repeated
        identical timestep pays no re-analysis.  (Sweep direction alternates
        per step, so step 3 is the first structural repeat of step 1.)"""
        app = CloverLeaf2D(40, 32, summary_every=0)
        sess = Session("ooc-sharded", mesh="sim:2", num_tiles=3,
                       capacity_bytes=float("inf"))
        drive(sess, app, steps=3)
        assert sess.history[-1].plan_cache_hit
        assert sess.backend.plan_hit_rate > 0.3

    def test_too_many_devices_raises(self):
        app = CloverLeaf2D(12, 6, summary_every=0)
        sess = Session("ooc-sharded", mesh="sim:8", num_tiles=2,
                       capacity_bytes=float("inf"))
        with pytest.raises(MeshError):
            drive(sess, app)

    def test_threaded_transfer_bit_identical(self):
        """ooc-async (threaded staging workers) composed with a mesh still
        reproduces ooc bitwise — per-shard engines drain before the next
        shard runs, so the exchange/gather ordering holds."""
        ref = CloverLeaf2D(32, 24, summary_every=0)
        drive(Session("ooc", num_tiles=3, capacity_bytes=float("inf")), ref,
              steps=2)
        app = CloverLeaf2D(32, 24, summary_every=0)
        with Session("ooc-async", mesh="sim:3", num_tiles=3,
                     capacity_bytes=float("inf")) as sess:
            drive(sess, app, steps=2)
            assert_all_dats_equal(ref, app)

    def test_checkpoint_restore_resume_bit_identical(self):
        """A sharded run killed after checkpoint() resumes bitwise: restore
        resets the shard version tracking so locals re-scatter from the
        restored globals, and the manifest carries the inner executors'
        plan signatures."""
        import os
        import tempfile

        app = CloverLeaf2D(32, 24, summary_every=0)
        with Session("ooc-sharded", mesh="sim:3", num_tiles=3,
                     capacity_bytes=float("inf")) as sess:
            drive(sess, app, steps=1)
            with tempfile.TemporaryDirectory() as td:
                path = os.path.join(td, "ck.npz")
                manifest = sess.checkpoint(path)
                assert manifest["plan_signatures"]
                app.dt = 1e-4
                app.record_timestep(sess)
                sess.flush()
                after = {n: app.d(n).materialize().copy() for n in app.dats}
                sess.restore(path, datasets=list(app.dats.values()))
                app.step_count -= 1   # sweep direction rewinds with restore
                app.dt = 1e-4
                app.record_timestep(sess)
                sess.flush()
                for n in app.dats:
                    np.testing.assert_array_equal(
                        after[n], app.d(n).materialize(), err_msg=n)

    def test_app_mesh_knob(self):
        from repro.core.sharded import ShardedOutOfCoreExecutor

        app = CloverLeaf2D(24, 16, summary_every=0, mesh="sim:2")
        sess = app.make_session(num_tiles=2, capacity_bytes=float("inf"))
        assert isinstance(sess.backend, ShardedOutOfCoreExecutor)
        drive(sess, app)
        assert np.isfinite(app.d("density0").interior()).all()
        sess.close()


# -- plans, explain, tune ----------------------------------------------------------


class TestShardedPlans:
    def _session(self):
        app = CloverLeaf2D(40, 32, summary_every=0)
        sess = Session("sim", mesh="sim:4", num_tiles=4,
                       capacity_bytes=float("inf"))
        app.record_init(sess)
        sess.queue.clear()
        app.dt = 1e-4
        app.record_timestep(sess)
        return app, sess

    def test_plan_per_device_with_halo_ops(self):
        _, sess = self._session()
        plans = sess.plan()
        assert {p.device for p in plans} == {0, 1, 2, 3}
        assert all(p.mesh_devices == 4 for p in plans)
        halos = [op for p in plans for op in p.ops
                 if isinstance(op, HaloExchange)]
        assert halos and all(op.messages > 0 and op.nbytes > 0
                             for op in halos)
        # Plan-level totals, ledger interpretation and ChainStats agree.
        total = sum(p.totals()["halo_messages"] for p in plans)
        sess.flush()
        assert total == sum(c.halo_messages for c in sess.history)

    def test_capacity_split_plans_match_execution(self):
        """When a shard-local segment doesn't fit fast memory, plan_chain
        must mirror run_chain's MemoryError split: the planned streams'
        totals equal what execution records."""
        def build(cap_frac):
            app = CloverLeaf2D(40, 32, summary_every=0)
            sess = Session("sim", mesh="sim:2",
                           capacity_bytes=app.total_bytes() * cap_frac)
            app.record_init(sess)
            sess.queue.clear()
            app.dt = 1e-4
            app.record_timestep(sess)
            return sess

        sess = build(0.1)   # tight: forces per-shard chain splitting
        plans = sess.plan()
        planned_halo = sum(p.totals()["halo_messages"] for p in plans)
        planned_computes = sum(p.counts()["computes"] for p in plans)
        sess.flush()
        assert planned_halo == sum(c.halo_messages for c in sess.history)
        assert planned_computes == sum(
            c.op_counts["computes"] for c in sess.history)

    def test_plan_json_v3_roundtrip(self):
        _, sess = self._session()
        for p in sess.plan():
            back = Plan.from_json(p.to_json())
            assert back == p

    def test_explain_per_device_makespans(self):
        """Acceptance: explain() on a sharded plan shows per-device
        makespans and nonzero halo message/byte counts."""
        _, sess = self._session()
        text = sess.explain()
        assert "device 0/4" in text and "device 3/4" in text
        assert "halo-exchange" in text
        assert "mesh summary: per-device makespans" in text
        assert "modelled makespan (device" in text

    def test_tune_enumerates_shard_counts(self):
        _, sess = self._session()
        res = sess.tune(meshes=[1, 2, 4], num_tiles=(4,), num_slots=(3,),
                        tiled_dims=(0,))
        meshes = {r["mesh"] for r in res.rows}
        assert {"sim:2", "sim:4"} <= meshes
        assert res.best_makespan <= res.baseline_makespan

    def test_sim_and_data_plane_model_identically(self):
        """The sim backend and the data plane interpret the same sharded
        instruction streams: modelled makespans and halo counters match."""
        app1 = CloverLeaf2D(40, 32, summary_every=0)
        sim = Session("sim", mesh="sim:2", num_tiles=4,
                      capacity_bytes=float("inf"))
        drive(sim, app1)
        app2 = CloverLeaf2D(40, 32, summary_every=0)
        real = Session("ooc-sharded", mesh="sim:2", num_tiles=4,
                       capacity_bytes=float("inf"))
        drive(real, app2)
        assert len(sim.history) == len(real.history)
        for a, b in zip(sim.history, real.history):
            assert a.halo_messages == b.halo_messages
            assert a.halo_bytes == b.halo_bytes
            assert a.modelled_s == pytest.approx(b.modelled_s)


# -- session lifecycle -------------------------------------------------------------


class TestSessionContextManager:
    def test_exit_closes_worker_threads(self):
        app = CloverLeaf2D(24, 16, summary_every=0)
        with Session("ooc-async", num_tiles=2,
                     capacity_bytes=float("inf")) as sess:
            drive(sess, app)
            workers = [t for t in threading.enumerate()
                       if t.name.startswith("transfer-")]
            assert workers, "threaded engine should have spawned workers"
            backend = sess.backend
        assert backend.transfer._workers == {}
        for t in workers:
            t.join(timeout=5)
            assert not t.is_alive()

    def test_exception_drops_queue_without_executing(self):
        """A with-body that dies mid-recording must NOT execute the
        half-recorded queue during unwinding (and must still release the
        backend)."""
        app = CloverLeaf2D(16, 8, summary_every=0)
        with pytest.raises(RuntimeError, match="boom"):
            with Session("ooc", num_tiles=2,
                         capacity_bytes=float("inf")) as sess:
                app.record_init(sess)
                raise RuntimeError("boom")
        assert not sess.queue
        assert sess.chains_flushed == 0
        # Home copies untouched: density0 still zeros.
        assert not app.d("density0").interior().any()

    def test_enter_returns_session_and_flushes_on_exit(self):
        app = CloverLeaf2D(16, 8, summary_every=0)
        with Session("ooc", num_tiles=2,
                     capacity_bytes=float("inf")) as sess:
            assert isinstance(sess, Session)
            app.record_init(sess)
            assert sess.queue
        assert not sess.queue          # __exit__ flushed
        assert sess.chains_flushed >= 1
