"""The paper's applications: OOC == reference, invariants, chain structure."""
import numpy as np
import pytest

from repro.apps import CloverLeaf2D, CloverLeaf3D, OpenSBLI
from repro.core import (
    OOCConfig, OutOfCoreExecutor, ReferenceRuntime, Runtime, analyze_chain,
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
