"""The StencilProgram/Session frontend: stencil inference vs hand-declared
access, backend-registry dispatch, and chain-plan memoisation."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.apps import CloverLeaf2D, CloverLeaf3D, OpenSBLI
from repro.core import (
    READ,
    RW,
    WRITE,
    AccessMode,
    Arg,
    Block,
    ExecutionConfig,
    Session,
    StencilProgram,
    StencilValidationError,
    available_backends,
    make_dataset,
    offset_stencil,
    point_stencil,
    star_stencil,
)
from repro.core.dependency import loop_kernel_fingerprint, plan_signature
from repro.kernels import star2d_kernel


def _heat_loops(sess, n=40, m=20, steps=3, declared=False):
    blk = Block("grid", (n, m))
    rng = np.random.RandomState(7)
    u = make_dataset(blk, "u", halo=1, init=rng.rand(n, m).astype(np.float32))
    tmp = make_dataset(blk, "tmp", halo=1)
    interior = ((1, n - 1), (1, m - 1))
    S, Z = star_stencil(2, 1), point_stencil(2)
    diffuse = lambda acc: {"tmp": 0.25 * (acc("u", (1, 0)) + acc("u", (-1, 0))
                                          + acc("u", (0, 1)) + acc("u", (0, -1)))}
    commit = lambda acc: {"u": acc("tmp")}
    for s in range(steps):
        if declared:
            sess.par_loop(f"d{s}", blk, interior,
                          [Arg(u, S, READ), Arg(tmp, Z, WRITE)], diffuse)
            sess.par_loop(f"c{s}", blk, interior,
                          [Arg(tmp, Z, READ), Arg(u, Z, RW)], commit)
        else:
            sess.par_loop(f"d{s}", blk, interior, [u, tmp], diffuse)
            sess.par_loop(f"c{s}", blk, interior, [tmp, u], commit)
    return sess.fetch(u)


# -- stencil inference -----------------------------------------------------------


class TestInference:
    def test_inferred_equals_declared_execution(self):
        a = _heat_loops(Session("reference"), declared=True)
        b = _heat_loops(Session("reference"), declared=False)
        np.testing.assert_array_equal(a, b)

    def test_inferred_modes_and_stencils(self):
        sess = Session("reference")
        _heat_loops(sess, steps=1)
        # fetch flushed the queue; re-record to inspect
        blk = Block("g", (8, 8))
        u = make_dataset(blk, "u", halo=1)
        t = make_dataset(blk, "t", halo=1)
        sess.par_loop("d", blk, ((1, 7), (1, 7)), [u, t],
                      lambda acc: {"t": acc("u", (1, 0)) + acc("u", (0, -1))})
        lp = sess.queue[-1]
        by = {(a.dat.name, a.mode): a for a in lp.args}
        assert set(by[("u", READ)].stencil.points) == {(1, 0), (0, -1)}
        assert by[("t", WRITE)].stencil.is_zero()

    def test_rw_and_split_read_write(self):
        blk = Block("g", (10, 6))
        u = make_dataset(blk, "u", halo=2)
        sess = Session("reference")
        # zero-offset read + write -> RW
        sess.par_loop("scale", blk, ((0, 10), (0, 6)), [u],
                      lambda acc: {"u": acc("u") * 0.5})
        assert [a.mode for a in sess.queue[-1].args] == [RW]
        # halo-mirror style: offset read + write over disjoint regions ->
        # READ(stencil) + WRITE(zero) pair
        sess.par_loop("halo", blk, ((-1, 0), (0, 6)), [u],
                      lambda acc: {"u": acc("u", (1, 0))})
        modes = [(a.mode, tuple(a.stencil.points)) for a in sess.queue[-1].args]
        assert (AccessMode.READ, ((1, 0),)) in modes
        assert (AccessMode.WRITE, ((0, 0),)) in modes

    def test_inc_hint(self):
        blk = Block("g", (8, 4))
        u = make_dataset(blk, "u", halo=0, init=np.ones((8, 4), np.float32))
        sess = Session("reference")
        sess.par_loop("inc", blk, blk.full_range(), [u],
                      lambda acc: {"u": jnp.full(acc.shape, 2.0)}, inc=["u"])
        assert sess.queue[-1].args[0].mode is AccessMode.INC
        got = sess.fetch(u)
        assert float(got[0, 0]) == 3.0

    def test_unused_dataset_rejected(self):
        blk = Block("g", (8, 4))
        u = make_dataset(blk, "u", halo=0)
        v = make_dataset(blk, "v", halo=0)
        sess = Session("reference")
        with pytest.raises(ValueError, match="neither reads nor writes"):
            sess.par_loop("l", blk, blk.full_range(), [u, v],
                          lambda acc: {"u": acc("u") * 2})

    def test_unknown_read_rejected(self):
        blk = Block("g", (8, 4))
        u = make_dataset(blk, "u", halo=0)
        sess = Session("reference")
        with pytest.raises(KeyError, match="not passed to"):
            sess.par_loop("l", blk, blk.full_range(), [u],
                          lambda acc: {"u": acc("ghost")})

    def test_explicit_stencil_must_cover_traced_reads(self):
        blk = Block("g", (8, 8))
        u = make_dataset(blk, "u", halo=1)
        t = make_dataset(blk, "t", halo=1)
        sess = Session("reference")
        with pytest.raises(StencilValidationError, match="does not cover"):
            sess.par_loop("l", blk, ((1, 7), (1, 7)), [u, t],
                          lambda acc: {"t": acc("u", (1, 0))},
                          explicit_stencil={"u": point_stencil(2)})

    def test_explicit_stencil_typo_rejected(self):
        blk = Block("g", (8, 8))
        u = make_dataset(blk, "u", halo=1)
        t = make_dataset(blk, "t", halo=1)
        sess = Session("reference")
        with pytest.raises(ValueError, match="not among the inferred"):
            sess.par_loop("l", blk, ((1, 7), (1, 7)), [u, t],
                          lambda acc: {"t": acc("u")},
                          explicit_stencil={"uu": star_stencil(2, 1)})

    def test_inc_with_offset_self_read_rejected(self):
        blk = Block("g", (8, 8))
        w = make_dataset(blk, "w", halo=2)
        sess = Session("reference")
        with pytest.raises(ValueError, match="split the loop"):
            sess.par_loop("h", blk, ((-1, 0), (0, 8)), [w],
                          lambda acc: {"w": acc("w", (1, 0))}, inc=["w"])

    def test_explicit_stencil_escape_hatch(self):
        blk = Block("g", (12, 6))
        u = make_dataset(blk, "u", halo=2)
        t = make_dataset(blk, "t", halo=2)
        wide = offset_stencil((-2, 0), (-1, 0), (0, 0), (1, 0), (2, 0))
        sess = Session("reference")
        sess.par_loop("l", blk, ((2, 10), (0, 6)), [u, t],
                      lambda acc: {"t": acc("u", (-1, 0)) + acc("u")},
                      explicit_stencil={"u": wide})
        arg = next(a for a in sess.queue[-1].args if a.dat.name == "u")
        assert set(arg.stencil.points) == set(wide.points)


class TestInferenceOnApps:
    """Inference reproduces the hand-declared access patterns of the apps."""

    def _loops(self, app):
        rt = Session("reference")
        app.record_init(rt)
        rt.queue.clear()
        app.dt = 1e-4
        app.record_timestep(rt)
        return {lp.name: lp for lp in rt.queue}

    @staticmethod
    def _read_points(lp, dat_name):
        pts = set()
        for a in lp.args:
            if a.dat.name == dat_name and a.mode.reads:
                pts |= set(a.stencil.points)
        return pts

    def test_cloverleaf2d(self):
        app = CloverLeaf2D(24, 24, summary_every=0)
        loops = self._loops(app)
        assert self._read_points(loops["viscosity"], "xvel0") == {(0, 0), (1, 0)}
        assert self._read_points(loops["accelerate"], "density0") == set(
            app.S_node.points)
        # escape hatch preserved the paper's 5-point donor stencil
        assert self._read_points(loops["advec_cell_x_flux"], "density1") == set(
            app.S_adv_x.points)
        # halo loops split into offset READ + zero WRITE
        halo = loops["update_halo_eos_0"]
        assert self._read_points(halo, "pressure") == {(1, 0)}
        assert any(a.dat.name == "pressure" and a.mode is WRITE
                   and a.stencil.is_zero() for a in halo.args)
        # every write-mode arg is zero-stencil (the OPS restriction)
        for lp in loops.values():
            for a in lp.args:
                if a.mode.writes:
                    assert a.stencil.is_zero()

    def test_cloverleaf3d(self):
        app = CloverLeaf3D(10, 8, 8, summary_every=0)
        loops = self._loops(app)
        assert self._read_points(loops["viscosity3d"], "xvel0") == {
            (0, 0, 0), (1, 0, 0)}
        assert self._read_points(loops["accelerate3d"], "density0") == set(
            app.S_node.points)
        # pressure gradient only reads the three negative-axis neighbours
        assert self._read_points(loops["accelerate3d"], "pressure") == {
            (0, 0, 0), (-1, 0, 0), (0, -1, 0), (0, 0, -1)}

    def test_opensbli(self):
        app = OpenSBLI(12)
        loops = self._loops(app)
        # shear reads u at +/-1 along every axis (one merged stencil)
        expect = {(0, 0, 0)} | {
            tuple(s * o for o in ax)
            for s in (1, -1) for ax in ((1, 0, 0), (0, 1, 0), (0, 0, 1))}
        got = self._read_points(loops["shear_s0"], "u")
        assert got == expect - {(0, 0, 0)} or got == expect
        # rho residual: central +/-1 derivative stencil on rho
        rho_pts = self._read_points(loops["residual_rho_s0"], "rho")
        assert (1, 0, 0) in rho_pts and (-1, 0, 0) in rho_pts
        # rk_update is pure zero-stencil RW on conserved + work arrays
        rk = loops["rk_update_s0"]
        assert all(a.stencil.is_zero() for a in rk.args)


class TestValidation:
    def test_declared_too_narrow_rejected(self):
        blk = Block("g", (10, 6))
        u = make_dataset(blk, "u", halo=1)
        t = make_dataset(blk, "t", halo=1)
        Z = point_stencil(2)
        sess = Session(ExecutionConfig(backend="reference",
                                       validate_stencils=True))
        with pytest.raises(StencilValidationError, match="not covered"):
            sess.par_loop("l", blk, ((1, 9), (1, 5)),
                          [Arg(u, Z, READ), Arg(t, Z, WRITE)],
                          lambda acc: {"t": acc("u", (1, 0))})

    def test_declared_wider_accepted(self):
        blk = Block("g", (10, 6))
        u = make_dataset(blk, "u", halo=1)
        t = make_dataset(blk, "t", halo=1)
        sess = Session(ExecutionConfig(backend="reference",
                                       validate_stencils=True))
        sess.par_loop("l", blk, ((1, 9), (1, 5)),
                      [Arg(u, star_stencil(2, 1), READ),
                       Arg(t, point_stencil(2), WRITE)],
                      lambda acc: {"t": acc("u", (1, 0))})
        assert len(sess.queue) == 1

    def test_mixed_declared_and_inferred_still_validated(self):
        blk = Block("g", (10, 6))
        u = make_dataset(blk, "u", halo=1)
        t = make_dataset(blk, "t", halo=1)
        sess = Session(ExecutionConfig(backend="reference",
                                       validate_stencils=True))
        with pytest.raises(StencilValidationError, match="not covered"):
            sess.par_loop("l", blk, ((1, 9), (1, 5)),
                          [Arg(u, point_stencil(2), READ), t],
                          lambda acc: {"t": acc("u", (1, 0))})

    def test_undeclared_write_rejected(self):
        blk = Block("g", (10, 6))
        u = make_dataset(blk, "u", halo=1)
        t = make_dataset(blk, "t", halo=1)
        Z = point_stencil(2)
        sess = Session(ExecutionConfig(backend="reference",
                                       validate_stencils=True))
        with pytest.raises(StencilValidationError, match="undeclared"):
            sess.par_loop("l", blk, ((1, 9), (1, 5)),
                          [Arg(u, Z, RW), Arg(t, Z, READ)],
                          lambda acc: {"u": acc("u") + acc("t"), "t": acc("t")})


# -- backend registry -------------------------------------------------------------


class TestBackends:
    def test_registry_lists_builtins(self):
        names = available_backends()
        for want in ("reference", "resident", "ooc", "ooc-cyclic", "sim",
                     "pallas"):
            assert want in names

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="unknown backend"):
            Session("no-such-backend")

    def test_hw_preset_by_name(self):
        sess = Session("ooc", hw="p100-nvlink")
        assert sess.config.hw.name == "p100-nvlink"
        with pytest.raises(ValueError, match="preset"):
            Session("ooc", hw="not-a-preset")

    def test_ooc_matches_reference(self):
        ref = _heat_loops(Session("reference"))
        got = _heat_loops(Session("ooc", num_tiles=4,
                                  capacity_bytes=float("inf")))
        np.testing.assert_allclose(ref, got, rtol=1e-5, atol=1e-6)

    def test_ooc_cyclic_and_sim(self):
        ref = _heat_loops(Session("reference"))
        cyc = Session("ooc-cyclic", num_tiles=4, capacity_bytes=float("inf"))
        got = _heat_loops(cyc)
        np.testing.assert_allclose(ref, got, rtol=1e-5, atol=1e-6)
        assert cyc.cyclic
        sim = Session("sim", num_tiles=4, capacity_bytes=float("inf"))
        _heat_loops(sim)          # no data plane; just runs & ledgers
        assert sim.history[-1].num_tiles == 4

    def test_pallas_backend_fast_path(self):
        def star_prog(sess, steps=2):
            blk = Block("g", (24, 16))
            rng = np.random.RandomState(3)
            u = make_dataset(blk, "u", halo=1,
                             init=rng.rand(24, 16).astype(np.float32))
            t = make_dataset(blk, "t", halo=1)
            interior = ((1, 23), (1, 15))
            k = star2d_kernel("u", "t", (0.5, 0.25, 0.25))
            for s in range(steps):
                sess.par_loop("sweep", blk, interior, [u, t], k)
                sess.par_loop("commit", blk, interior, [t, u],
                              lambda acc: {"u": acc("t")})
            return sess.fetch(u)

        ref = star_prog(Session("reference"))
        sp = Session("pallas")
        got = star_prog(sp)
        np.testing.assert_allclose(ref, got, rtol=1e-5, atol=1e-6)
        assert sp.backend.pallas_loops == 2      # both sweeps fast-pathed
        assert sp.backend.fallback_loops == 2    # commits via reference

    def test_runtime_shims_deprecated(self):
        from repro.core import ReferenceRuntime, Runtime

        with pytest.warns(DeprecationWarning):
            rt = ReferenceRuntime()
        assert isinstance(rt, Session)
        with pytest.warns(DeprecationWarning):
            rt2 = Runtime()
        assert isinstance(rt2, Session)
        assert StencilProgram is Session


# -- chain-plan memoisation -------------------------------------------------------


class TestPlanCache:
    def test_identical_chains_planned_once(self):
        sess = Session("ooc", num_tiles=3, capacity_bytes=float("inf"))
        blk = Block("g", (30, 12))
        u = make_dataset(blk, "u", halo=1,
                         init=np.random.RandomState(0).rand(30, 12).astype(np.float32))
        t = make_dataset(blk, "t", halo=1)
        k1 = lambda acc: {"t": acc("u", (1, 0)) + acc("u", (-1, 0))}
        k2 = lambda acc: {"u": acc("t")}
        for step in range(5):
            sess.par_loop("a", blk, ((1, 29), (0, 12)), [u, t], k1)
            sess.par_loop("b", blk, ((1, 29), (0, 12)), [t, u], k2)
            sess.fetch(u)
        st = sess.plan_stats()
        assert st["plan_misses"] == 1
        assert st["plan_hits"] == 4

    def test_changed_kernel_constant_forces_replan(self):
        """A captured scalar change must re-plan (stale-closure safety)."""
        sess = Session("ooc", num_tiles=2, capacity_bytes=float("inf"))
        blk = Block("g", (16, 8))
        u = make_dataset(blk, "u", halo=0,
                         init=np.ones((16, 8), np.float32))

        def record(scale):
            def k(acc):
                return {"u": acc("u") * scale}
            sess.par_loop("scale", blk, blk.full_range(), [u], k)
            return sess.fetch(u)

        record(2.0)
        got = record(3.0)
        assert sess.plan_stats()["plan_misses"] == 2
        np.testing.assert_allclose(got[0, 0], 6.0)

    def test_same_line_kernels_do_not_collide(self):
        """co_code references constants/globals by index: two kernels defined
        on one source line must still fingerprint differently."""
        sess = Session("ooc", num_tiles=2, capacity_bytes=float("inf"))
        blk = Block("g", (16, 8))
        u = make_dataset(blk, "u", halo=1, init=np.ones((16, 8), np.float32))
        t = make_dataset(blk, "t", halo=1)
        ks = [lambda acc: {"u": acc("u") * 2.0}, lambda acc: {"u": acc("u") * 3.0}]
        for k in ks:
            sess.par_loop("k", blk, ((1, 15), (1, 7)), [u], k)
            sess.fetch(u)
        np.testing.assert_allclose(sess.fetch(u)[1, 1], 6.0)
        # same-line kernels with different read offsets: inference must not
        # serve the first kernel's stencil to the second
        rs = [lambda acc: {"t": acc("u", (1, 0))}, lambda acc: {"t": acc("u", (0, 1))}]
        sref = Session("reference")
        for i, k in enumerate(rs):
            sref.par_loop(f"r{i}", blk, ((1, 15), (1, 7)), [u, t], k)
        pts = [next(a for a in lp.args if a.dat.name == "u").stencil.points
               for lp in sref.queue]
        assert pts[0] == ((1, 0),) and pts[1] == ((0, 1),)

    def test_changed_array_capture_forces_replan(self):
        """Captured ndarrays fingerprint by content, not type — a changed
        coefficient array must not replay the cached plan."""
        sess = Session("ooc", num_tiles=2, capacity_bytes=float("inf"))
        blk = Block("g", (16, 8))
        u = make_dataset(blk, "u", halo=0, init=np.ones((16, 8), np.float32))

        def record(coeffs):
            c = np.asarray(coeffs, np.float32)

            def k(acc):
                return {"u": acc("u") * c[0]}
            sess.par_loop("scale", blk, blk.full_range(), [u], k)
            return sess.fetch(u)

        record([2.0])
        got = record([5.0])
        assert sess.plan_stats()["plan_misses"] == 2
        np.testing.assert_allclose(got[0, 0], 10.0)

    def test_cloverleaf_repeated_timesteps_analyzed_once(self):
        """N>1 timesteps: analysis/scheduling once per distinct chain shape,
        independent of N — every further step is a cache hit."""
        def run(steps):
            app = CloverLeaf2D(28, 20, summary_every=0)
            sess = Session("ooc", num_tiles=3, capacity_bytes=float("inf"))
            app.run(sess, steps=steps)
            return sess.plan_stats(), sess.chains_flushed

        st4, chains4 = run(4)
        st6, chains6 = run(6)
        # distinct chain shapes don't grow with step count
        assert st6["plan_misses"] == st4["plan_misses"]
        assert st6["plan_hits"] == st4["plan_hits"] + (chains6 - chains4)
        assert st6["plan_hits"] > 0


# -- loop scalars passed as data (gbls) --------------------------------------------


def _scale_loop(sess, u, value):
    """One loop that reads its gbl ``s``, recorded as apps record a loop:
    a fresh closure each call."""
    def k(acc):
        return {"u": acc("u") * acc.gbl("s")}
    sess.par_loop("scale", u.block, u.block.full_range(), [u], k,
                  gbls={"s": value})


class TestGbls:
    def test_values_share_fingerprint_and_inferred_args(self):
        sess = Session("reference")
        blk = Block("g", (8, 4))
        u = make_dataset(blk, "u", halo=0, init=np.ones((8, 4), np.float32))
        _scale_loop(sess, u, np.float32(2.0))
        _scale_loop(sess, u, np.float32(3.0))
        a, b = sess.queue
        assert loop_kernel_fingerprint(a) == loop_kernel_fingerprint(b)
        assert a.args is b.args           # one inference serves both
        assert plan_signature([a]) == plan_signature([b])
        other = Session("reference")
        _scale_loop(other, u, 2.0)        # a new dtype is a new plan
        assert plan_signature([a]) != plan_signature(other.queue)
        np.testing.assert_array_equal(sess.fetch(u), 6.0)

    def test_undeclared_gbl_is_a_key_error(self):
        blk = Block("g", (8, 4))
        u = make_dataset(blk, "u", halo=0, init=np.ones((8, 4), np.float32))
        k = lambda acc: {"u": acc("u") * acc.gbl("dt")}
        with pytest.raises(KeyError, match="dt"):     # at inference
            Session("reference").par_loop(
                "scale", blk, blk.full_range(), [u], k, gbls={"s": 1.0})
        for backend in ("reference", "ooc"):          # declared args: at run
            sess = Session(backend)
            sess.par_loop("scale", blk, blk.full_range(),
                          [Arg(u, point_stencil(2), RW)], k, gbls={"s": 1.0})
            with pytest.raises(KeyError, match="did not declare"):
                sess.flush()

    def test_pallas_backend_runs_gbl_loops_on_the_generic_path(self):
        blk = Block("g", (16, 8))
        rng = np.random.RandomState(1)
        u = make_dataset(blk, "u", halo=1,
                         init=rng.rand(16, 8).astype(np.float32))
        v = make_dataset(blk, "v", halo=1)
        sess = Session("pallas")
        sess.par_loop("sweep", blk, ((1, 15), (1, 7)), [u, v],
                      star2d_kernel("u", "v", (0.5, 0.125, 0.125)),
                      gbls={"unused": np.float32(1.0)})
        sess.flush()
        assert (sess.backend.pallas_loops, sess.backend.fallback_loops) == (0, 1)

