"""The paper's applications: OOC == reference, invariants, chain structure."""
import numpy as np
import pytest

from repro.apps import CloverLeaf2D, CloverLeaf3D, OpenSBLI
from repro.core import (
    Accessor, OOCConfig, OutOfCoreExecutor, ReferenceRuntime, Runtime, Session,
    analyze_chain,
)


@pytest.fixture(scope="module")
def cl2d_reference():
    app = CloverLeaf2D(40, 32, summary_every=3)
    summary = app.run(ReferenceRuntime(), steps=3)
    return app, summary


class TestCloverLeaf2D:
    def test_out_of_core_matches(self, cl2d_reference):
        ref_app, ref_summary = cl2d_reference
        app = CloverLeaf2D(40, 32, summary_every=3)
        ex = OutOfCoreExecutor(OOCConfig(num_tiles=4, capacity_bytes=float("inf"),
                                         prefetch=True))
        summary = app.run(Runtime(ex), steps=3)
        np.testing.assert_allclose(
            ref_app.d("density0").interior(), app.d("density0").interior(),
            rtol=1e-4, atol=1e-5)
        for k in ref_summary:
            np.testing.assert_allclose(ref_summary[k], summary[k], rtol=1e-3)

    def test_split_chains_keep_carried_fields(self, cl2d_reference):
        """Capacity a third of the working set splits the timestep chain on
        MemoryError.  Under Cyclic a split half must not elide a field the
        whole chain reads first (regression: ``reset_field`` in the tail
        made ``xvel0`` look write-first there and its update was dropped,
        leaving the boundary column at its initial value)."""
        ref_app, ref_summary = cl2d_reference
        app = CloverLeaf2D(40, 32, summary_every=3)
        ex = OutOfCoreExecutor(OOCConfig(
            capacity_bytes=app.total_bytes() / 3, prefetch=True))
        summary = app.run(Runtime(ex), steps=3)
        assert len(ex.history) > 3 * 2 + 1      # chains did split
        for name in ("density0", "energy0", "xvel0", "yvel0"):
            np.testing.assert_allclose(
                ref_app.d(name).interior(), app.d(name).interior(),
                rtol=1e-4, atol=1e-5, err_msg=name)
        for k in ref_summary:
            np.testing.assert_allclose(ref_summary[k], summary[k], rtol=1e-3)

    def test_dataset_count_matches_paper(self):
        assert len(CloverLeaf2D(16, 16).dats) == 25  # §5.1: 25 variables

    def test_fields_finite_and_physical(self, cl2d_reference):
        app, summary = cl2d_reference
        rho = app.d("density0").interior()
        assert np.isfinite(rho).all()
        assert (rho > 0).all()
        assert summary["min_rho"] > 0

    def test_chain_structure(self):
        """One timestep chain (no breakers): 27 physics + 24 halo loops."""
        app = CloverLeaf2D(24, 24, summary_every=0)
        rt = ReferenceRuntime()
        app.record_init(rt)
        rt.flush()
        app.record_timestep(rt)
        assert len(rt.queue) == 51
        info = analyze_chain(rt.queue)
        assert info.skew_slope == 3  # halo mirror reads reach +/-3
        # the §4.1 temporaries exist and are write-first
        for tmp in ("pre_vol", "post_vol", "pre_mass", "ener_flux"):
            assert tmp in info.write_first


class _Constants(Accessor):
    """``acc`` with ``gbl`` answering from constants of the kernel's
    closure."""

    def __init__(self, acc, consts):
        self._acc, self._consts = acc, consts
        self.shape = acc.shape

    def coords(self):
        return self._acc.coords()

    def __call__(self, name, offset=None):
        return self._acc(name, offset)

    def gbl(self, name):
        return self._consts[name]


class _CapturedGblsSession(Session):
    """The oracle: each loop's gbls captured as Python floats in its
    kernel's closure, as CloverLeaf captured dt before passing it as data,
    so every new dt re-plans and recompiles."""

    def par_loop(self, name, block, range_, args, kernel, reductions=(), *,
                 gbls=None, **kw):
        if gbls:
            consts = {n: float(v) for n, v in gbls.items()}
            inner = kernel

            def kernel(acc):
                return inner(_Constants(acc, consts))
        super().par_loop(name, block, range_, args, kernel, reductions, **kw)


def _clover_window(sess_type, trace):
    """CloverLeaf 2D out of core at a third of its working set, 6 timesteps
    driven as the chip benchmark drives them, each with its own dt below
    the cap.  800 rows: the timestep chain fits unsplit, as it does at
    3840^2.  The tracer is cleared once the first two timesteps (the two
    sweep orders) have run, so it holds the rest."""
    app = CloverLeaf2D(800, 8, summary_every=0)
    sess = sess_type("ooc", capacity_bytes=app.total_bytes() / 3,
                     prefetch=True, trace=trace)
    app.record_init(sess)
    sess.flush()
    sess.cyclic = True
    for step in range(7):
        app._ideal_gas(sess, "density0", "energy0", "_dt")
        app._viscosity(sess)
        app._calc_dt(sess)
        sess.reduction("dt")
        if step == 2 and trace:
            sess.trace().clear()
        if step == 6:
            break
        app.dt = (0.5 + 0.05 * step) * 1e-4
        app.record_timestep(sess)
    fields = {n: app.d(n).interior().copy()
              for n in ("density0", "energy0", "xvel0", "yvel0")}
    spans = sess.trace().spans() if trace else []
    sess.close()
    return fields, spans


def test_cloverleaf2d_new_dt_compiles_nothing():
    """dt passed as a gbl: after the two sweep orders have compiled, every
    later step replays its cached plan and compiled tile programs, and the
    fields are those of the same run with dt captured as a constant."""
    fields, spans = _clover_window(Session, trace=True)
    plans = [sp for sp in spans if sp.name == "plan"]
    assert len(plans) == 4
    assert all(sp.args["cache_hit"] for sp in plans)
    assert not [sp for sp in spans if sp.name == "tile_compile"]
    # Each launch passes the dt of both PdVs, accelerate and flux_calc.
    assert {sp.args["gbls"] for sp in spans if sp.name == "tile_dispatch"
            } == {4}
    want, _ = _clover_window(_CapturedGblsSession, trace=False)
    for name in want:
        np.testing.assert_array_equal(fields[name], want[name], name)


class TestCloverLeaf3D:
    def test_out_of_core_matches(self):
        ref = CloverLeaf3D(14, 12, 10, summary_every=2)
        s_ref = ref.run(ReferenceRuntime(), steps=2)
        app = CloverLeaf3D(14, 12, 10, summary_every=2)
        ex = OutOfCoreExecutor(OOCConfig(num_tiles=3, capacity_bytes=float("inf")))
        s = app.run(Runtime(ex), steps=2)
        np.testing.assert_allclose(ref.d("density0").interior(),
                                   app.d("density0").interior(),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(s_ref["sum_mass"], s["sum_mass"], rtol=1e-3)

    def test_dataset_count_matches_paper(self):
        assert len(CloverLeaf3D(8, 8, 8).dats) == 30  # §5.1: 30 variables


class TestOpenSBLI:
    def test_out_of_core_matches_and_multistep_chains(self):
        ref = OpenSBLI(16, chain_steps=1)
        ref.run(ReferenceRuntime(), steps=2)
        app = OpenSBLI(16, chain_steps=2)  # tile ACROSS both timesteps
        # NOTE: cyclic is NOT set here — app.run() enables it after the init
        # phase, per the paper §4.1 (enabling it for the init chain is the
        # documented unsafe case and corrupts the fields).
        ex = OutOfCoreExecutor(OOCConfig(num_tiles=3, capacity_bytes=float("inf"),
                                         prefetch=True))
        rt = Runtime(ex)
        app.run(rt, steps=2)
        np.testing.assert_allclose(ref.d("rho").interior(),
                                   app.d("rho").interior(), rtol=1e-4, atol=1e-5)
        # both timesteps flushed as ONE chain: init + 1 big chain + summary
        big = max(st.num_tiles for st in ex.history)
        assert rt.chains_flushed <= 4

    def test_dataset_count_matches_paper(self):
        assert len(OpenSBLI(8).dats) == 29  # §5.1: 29 datasets

    def test_27_loops_per_step(self):
        app = OpenSBLI(12)
        rt = ReferenceRuntime()
        app.record_init(rt)
        rt.flush()
        app.record_timestep(rt)
        assert len(rt.queue) == 24  # 3 stages x (prim + shear + 5 resid + rk)
