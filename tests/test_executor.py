"""Integration + property tests: out-of-core executor == reference oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional test extra: example-based tests run without it
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.core import (
    Arg, Block, INC, OOCConfig, OutOfCoreExecutor, ParallelLoop, READ,
    ReductionSpec, ReferenceRuntime, ResidentExecutor, RW, Runtime, Session,
    WRITE, make_dataset, offset_stencil, point_stencil, star_stencil,
)


def heat_app(runtime, n, m, steps, halo=1):
    rng = np.random.RandomState(7)
    blk = Block("grid", (n, m))
    u = make_dataset(blk, "u", halo=halo, init=rng.rand(n, m).astype(np.float32))
    tmp = make_dataset(blk, "tmp", halo=halo)
    S = star_stencil(2, 1)
    Z = point_stencil(2)
    interior = ((1, n - 1), (1, m - 1))
    for s in range(steps):
        runtime.par_loop(
            f"avg{s}", blk, interior, [Arg(u, S, READ), Arg(tmp, Z, WRITE)],
            lambda acc: {"tmp": 0.25 * (acc("u", (1, 0)) + acc("u", (-1, 0))
                                         + acc("u", (0, 1)) + acc("u", (0, -1)))})
        runtime.par_loop(
            f"copy{s}", blk, interior, [Arg(tmp, Z, READ), Arg(u, Z, RW)],
            lambda acc: {"u": acc("tmp")})
    runtime.par_loop(
        "sum", blk, interior, [Arg(u, Z, READ)],
        lambda acc: {"total": jnp.sum(acc("u"))},
        reductions=[ReductionSpec("total", "sum")])
    total = runtime.reduction("total")
    return runtime.fetch(u), total


class TestEquivalence:
    @pytest.mark.parametrize("tiles,cyclic,prefetch", [
        (1, False, False), (3, False, False), (5, True, True), (7, True, False),
    ])
    def test_heat_matches_reference(self, tiles, cyclic, prefetch):
        ref_u, ref_t = heat_app(ReferenceRuntime(), 40, 24, 4)
        ex = OutOfCoreExecutor(OOCConfig(
            num_tiles=tiles, capacity_bytes=float("inf"),
            cyclic=cyclic, prefetch=prefetch))
        got_u, got_t = heat_app(Runtime(ex), 40, 24, 4)
        np.testing.assert_allclose(ref_u, got_u, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(ref_t, got_t, rtol=1e-4)

    def test_capacity_forces_tiling(self):
        ref_u, _ = heat_app(ReferenceRuntime(), 64, 16, 2)
        # capacity < 3 full-size slots -> executor must pick tiles > 1
        # (full footprint per slot here is 9072B; 3 slots need 27216B)
        ex = OutOfCoreExecutor(OOCConfig(capacity_bytes=24000))
        got_u, _ = heat_app(Runtime(ex), 64, 16, 2)
        assert ex.history[0].num_tiles > 1
        np.testing.assert_allclose(ref_u, got_u, rtol=1e-5, atol=1e-6)

    def test_resident_executor_raises_beyond_capacity(self):
        ex = ResidentExecutor(capacity_bytes=1024)  # absurdly small
        with pytest.raises(MemoryError):
            heat_app(Runtime(ex), 32, 16, 1)

    def test_transfer_elision_reduces_bytes(self):
        """cyclic ON must move strictly fewer bytes down, same result."""
        ex_off = OutOfCoreExecutor(OOCConfig(num_tiles=4, capacity_bytes=float("inf")))
        u_off, _ = heat_app(Runtime(ex_off), 40, 24, 4)
        ex_on = OutOfCoreExecutor(OOCConfig(num_tiles=4, capacity_bytes=float("inf"),
                                            cyclic=True))
        u_on, _ = heat_app(Runtime(ex_on), 40, 24, 4)
        np.testing.assert_allclose(u_off, u_on, rtol=1e-5, atol=1e-6)
        assert ex_on.history[0].downloaded < ex_off.history[0].downloaded

    def test_split_chain_preserves_cyclic_liveness(self):
        """A chain too long to fit splits on MemoryError; write-first dats of
        the first half that the second half still reads must be downloaded
        even under Cyclic (regression: stale-home re-upload read zeros)."""
        n, m, steps = 48, 10, 16
        ref_u, ref_t = heat_app(ReferenceRuntime(), n, m, steps)
        # capacity small enough that the 32-loop skewed chain cannot fit at
        # any tile count (skew span ~ chain length), forcing a split.
        ex = OutOfCoreExecutor(OOCConfig(capacity_bytes=4500, cyclic=True))
        got_u, got_t = heat_app(Runtime(ex), n, m, steps)
        assert len(ex.history) > 1  # the chain did split
        np.testing.assert_allclose(ref_u, got_u, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(ref_t, got_t, rtol=1e-4)

    def test_inc_mode(self):
        blk = Block("g", (16, 8))
        a = make_dataset(blk, "a", halo=0, init=np.ones((16, 8), np.float32))
        Z = point_stencil(2)
        rt_ref = ReferenceRuntime()
        rt_ref.par_loop("inc", blk, blk.full_range(), [Arg(a, Z, INC)],
                        lambda acc: {"a": jnp.full(acc.shape, 2.0)})
        ref = rt_ref.fetch(a)
        b = make_dataset(blk, "a", halo=0, init=np.ones((16, 8), np.float32))
        rt = Runtime(OutOfCoreExecutor(OOCConfig(num_tiles=3, capacity_bytes=float("inf"))))
        rt.par_loop("inc", blk, blk.full_range(), [Arg(b, Z, INC)],
                    lambda acc: {"a": jnp.full(acc.shape, 2.0)})
        got = rt.fetch(b)
        np.testing.assert_allclose(ref, got)
        assert float(ref[0, 0]) == 3.0


# -- property-based: random chains, random tiling == reference -------------------
if HAVE_HYPOTHESIS:
    @st.composite
    def random_chain_spec(draw):
        n = draw(st.integers(16, 48))
        m = draw(st.integers(6, 14))
        n_loops = draw(st.integers(1, 6))
        ops = draw(st.lists(
            st.sampled_from(["blur", "shift", "copyback", "scale"]),
            min_size=n_loops, max_size=n_loops))
        tiles = draw(st.integers(1, 7))
        seed = draw(st.integers(0, 2 ** 16))
        return n, m, ops, tiles, seed


def _build(ops, blk, u, tmp):
    S = star_stencil(2, 1)
    Z = point_stencil(2)
    n, m = blk.size
    interior = ((1, n - 1), (1, m - 1))
    loops = []
    for i, kind in enumerate(ops):
        if kind == "blur":
            loops.append((f"blur{i}", interior,
                          [Arg(u, S, READ), Arg(tmp, Z, WRITE)],
                          lambda acc: {"tmp": 0.2 * (acc("u") + acc("u", (1, 0))
                                                     + acc("u", (-1, 0)) + acc("u", (0, 1))
                                                     + acc("u", (0, -1)))}))
        elif kind == "shift":
            loops.append((f"shift{i}", interior,
                          [Arg(u, offset_stencil((0, 0), (1, 1)), READ),
                           Arg(tmp, Z, WRITE)],
                          lambda acc: {"tmp": acc("u", (1, 1)) * 0.5 + acc("u")}))
        elif kind == "copyback":
            loops.append((f"cb{i}", interior,
                          [Arg(tmp, Z, READ), Arg(u, Z, RW)],
                          lambda acc: {"u": acc("tmp") + 0.1 * acc("u")}))
        else:
            loops.append((f"scale{i}", interior,
                          [Arg(u, Z, RW)], lambda acc: {"u": acc("u") * 0.9}))
    return loops


def _random_chain_body(spec):
    n, m, ops, tiles, seed = spec
    rng = np.random.RandomState(seed)
    init = rng.rand(n, m).astype(np.float32)

    results = []
    for runtime_kind in ("ref", "ooc"):
        blk = Block("g", (n, m))
        u = make_dataset(blk, "u", halo=1, init=init)
        tmp = make_dataset(blk, "tmp", halo=1)
        rt = (ReferenceRuntime() if runtime_kind == "ref"
              else Runtime(OutOfCoreExecutor(OOCConfig(
                  num_tiles=tiles, capacity_bytes=float("inf")))))
        for name, rng_, args, kern in _build(ops, blk, u, tmp):
            rt.par_loop(name, blk, rng_, args, kern)
        results.append(rt.fetch(u))
    np.testing.assert_allclose(results[0], results[1], rtol=1e-5, atol=1e-6)


if HAVE_HYPOTHESIS:
    @given(random_chain_spec())
    @settings(max_examples=15, deadline=None)
    def test_random_chains_match_reference(spec):
        _random_chain_body(spec)
else:
    @pytest.mark.parametrize("spec", [
        (32, 10, ["blur", "copyback", "scale"], 3, 7),
        (48, 14, ["shift", "copyback", "blur", "copyback"], 5, 123),
        (16, 6, ["scale"], 1, 999),
    ])
    def test_random_chains_match_reference(spec):
        """Fixed-seed fallback when hypothesis is not installed."""
        _random_chain_body(spec)


# -- loop scalars passed as data (gbls) --------------------------------------------


def _advect_steps(sess, dts, n=32, m=12):
    """A two-loop chain per step whose first loop reads the step's ``dt``
    as a gbl; the field after each step."""
    blk = Block("g", (n, m))
    u = make_dataset(blk, "u", halo=1, init=np.random.RandomState(3).rand(
        n, m).astype(np.float32))
    t = make_dataset(blk, "t", halo=1)
    rng = ((1, n - 1), (0, m))

    def advect(acc):
        dt = acc.gbl("dt")
        return {"t": acc("u") - dt * (acc("u", (1, 0)) - acc("u", (-1, 0)))}

    out = []
    for dt in dts:
        sess.par_loop("advect", blk, rng, [u, t], advect,
                      gbls={"dt": np.float32(dt)})
        sess.par_loop("commit", blk, rng, [t, u], lambda acc: {"u": acc("t")})
        out.append(sess.fetch(u))
    return out


class TestGbls:
    def test_cached_plan_computes_the_new_value(self):
        """The second dt replays the first dt's plan and programs, and the
        tiles read the second dt, not the one the plan was built with."""
        sess = Session("ooc", num_tiles=2, capacity_bytes=float("inf"))
        got = _advect_steps(sess, (0.25, 0.125))
        want = _advect_steps(Session("reference"), (0.25, 0.125))
        assert sess.plan_stats()["plan_misses"] == 1
        assert sess.plan_stats()["plan_hits"] == 1
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_adopted_shared_plan_takes_the_adopters_values(self):
        """A plan another executor built (the serving layer's shared cache)
        runs with the adopting chain's gbl values."""
        from repro.serve import SharedPlanCache

        cache = SharedPlanCache()
        cfg = OOCConfig(num_tiles=2, capacity_bytes=float("inf"))
        first, second = (Session(backend=OutOfCoreExecutor(
            cfg, shared_plans=cache)) for _ in range(2))
        _advect_steps(first, (0.25,))
        got = _advect_steps(second, (0.125,))
        assert cache.hits == 1 and second.plan_stats()["plan_misses"] == 0
        np.testing.assert_array_equal(
            got[0], _advect_steps(Session("reference"), (0.125,))[0])

    @pytest.mark.parametrize("backend,kw", [
        ("reference", {}),
        ("resident", {}),
        ("ooc", dict(num_tiles=3, capacity_bytes=float("inf"))),
        ("ooc-cyclic", dict(num_tiles=3, capacity_bytes=float("inf"),
                            prefetch=True)),
        ("ooc-sharded", dict(mesh="sim:2", num_tiles=2,
                             capacity_bytes=float("inf"))),
    ])
    def test_gbl_loop_bit_identical_across_backends(self, backend, kw):
        dts = (0.25, 0.125, 0.0625)
        got = _advect_steps(Session(backend, **kw), dts)
        want = _advect_steps(Session("reference"), dts)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
