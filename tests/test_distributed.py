"""Multi-device behaviour (subprocess with 8 forced host devices, so the
main pytest process keeps its single real device): halo exchange vs periodic
reference, and the int8 compressed all-reduce vs exact mean."""
import subprocess
import sys
import textwrap

import pytest

_SCRIPT_HALO = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.core.distributed import exchange_halos, chain_halo_depth

    mesh = jax.make_mesh((8,), ("x",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    N, M, halo = 16, 64, 2
    per = M // 8
    rng = np.random.RandomState(0)
    g = rng.rand(N, M).astype(np.float32)
    ref = g.copy()
    for _ in range(2):
        ref = 0.5 * ref + 0.25 * (np.roll(ref, 1, 1) + np.roll(ref, -1, 1))
    locs = []
    for r in range(8):
        lo = (r * per - halo) % M
        idx = [(lo + i) % M for i in range(per + 2 * halo)]
        locs.append(g[:, idx])
    garr = jax.device_put(np.concatenate(locs, 1), NamedSharding(mesh, P(None, "x")))

    def local(arrays):
        # np.roll reference == periodic boundaries: ask for the wrap.
        arrays = exchange_halos(arrays, halo, "x", dim=1, periodic=True)
        u = arrays["u"]
        for _ in range(2):
            u = 0.5 * u + 0.25 * (jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1))
        return {"u": u}

    fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(None, "x"),
                           out_specs=P(None, "x"), check_vma=False))
    res = np.asarray(fn({"u": garr})["u"])
    outs = [res[:, r * (per + 2 * halo) + halo: r * (per + 2 * halo) + halo + per]
            for r in range(8)]
    got = np.concatenate(outs, 1)
    assert np.allclose(got, ref, atol=1e-6), np.abs(got - ref).max()
    assert chain_halo_depth([], dim=1) == 0
    print("HALO_OK")
""")

_SCRIPT_COMPRESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.distributed.compression import compressed_allreduce_mean

    mesh = jax.make_mesh((8,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.RandomState(1)
    per_dev = rng.randn(8, 1000).astype(np.float32)
    x = jax.device_put(per_dev, NamedSharding(mesh, P("pod", None)))

    fn = jax.jit(jax.shard_map(
        lambda g: compressed_allreduce_mean(g[0], "pod")[None],
        mesh=mesh, in_specs=P("pod", None), out_specs=P("pod", None),
        check_vma=False))
    out = np.asarray(fn(x))
    exact = per_dev.mean(axis=0)
    for r in range(8):
        rel = np.abs(out[r] - exact).max() / (np.abs(exact).max() + 1e-9)
        assert rel < 0.05, rel
    # all shards agree (it IS an all-reduce)
    assert np.allclose(out, out[0][None], atol=1e-6)
    print("COMPRESS_OK")
""")


def test_depth0_exchange_skips_collective():
    """A 0-depth chain (no reads along the decomposed dim) must skip the
    halo collective entirely.  Regression: the fast path needs no axis
    context, so calling it OUTSIDE shard_map must work — the old code
    always issued ``axis_index``/``ppermute`` and would raise here."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import Block, make_dataset, point_stencil, Arg, RW
    from repro.core.distributed import chain_halo_depth, exchange_halos
    from repro.core.loop import ParallelLoop

    arrays = {"u": jnp.arange(12.0).reshape(3, 4),
              "v": jnp.ones((3, 4))}
    out = exchange_halos(arrays, 0, "nonexistent-axis", dim=1)
    assert set(out) == {"u", "v"}
    for k in arrays:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(arrays[k]))

    # A pointwise chain really does have accumulated halo depth 0.
    blk = Block("g", (8, 8))
    a = make_dataset(blk, "a", halo=1)
    Z = point_stencil(2)
    loops = [
        ParallelLoop("scale", blk, blk.full_range(), (Arg(a, Z, RW),),
                     lambda acc: {"a": acc("a") * 2.0}),
        ParallelLoop("damp", blk, blk.full_range(), (Arg(a, Z, RW),),
                     lambda acc: {"a": acc("a") * 0.5}),
    ]
    assert chain_halo_depth(loops, dim=1) == 0


def _make_mesh(n):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} XLA devices (conftest forces 8)")
    return Mesh(np.asarray(jax.devices()[:n]), ("x",))


def test_exchange_halos_nonperiodic_keeps_edge_halos():
    """Regression (2-device mesh): with the default non-periodic semantics
    the edge ranks must NOT receive wrapped-around data — their outer halo
    slots keep the caller's boundary values, while the interior boundary
    still exchanges.  The old periodic-ring behaviour handed rank 0 the
    opposite edge's interior."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distributed import exchange_halos

    mesh = _make_mesh(2)
    depth, per, nrows = 2, 6, 4
    w = per + 2 * depth
    rng = np.random.RandomState(3)
    local = rng.rand(2, nrows, w).astype(np.float32)  # [rank, rows, cols]
    stacked = np.concatenate([local[0], local[1]], axis=1)
    garr = jax.device_put(stacked, NamedSharding(mesh, P(None, "x")))

    def run(periodic):
        fn = jax.jit(jax.shard_map(
            lambda a: exchange_halos({"u": a}, depth, "x", dim=1,
                                     periodic=periodic)["u"],
            mesh=mesh, in_specs=P(None, "x"), out_specs=P(None, "x"),
            check_vma=False))
        out = np.asarray(fn(garr))
        return out[:, :w], out[:, w:]

    r0, r1 = run(False)
    # Edge halos untouched; interiors untouched.
    np.testing.assert_array_equal(r0[:, :depth], local[0][:, :depth])
    np.testing.assert_array_equal(r1[:, -depth:], local[1][:, -depth:])
    np.testing.assert_array_equal(r0[:, depth:-depth],
                                  local[0][:, depth:-depth])
    # Interior boundary exchanged: r0's high halo = r1's low interior etc.
    np.testing.assert_array_equal(r0[:, -depth:],
                                  local[1][:, depth:2 * depth])
    np.testing.assert_array_equal(r1[:, :depth],
                                  local[0][:, -2 * depth:-depth])
    # periodic=True restores the wrap for grids that want it.
    p0, p1 = run(True)
    np.testing.assert_array_equal(p0[:, :depth],
                                  local[1][:, -2 * depth:-depth])
    np.testing.assert_array_equal(p1[:, -depth:],
                                  local[0][:, depth:2 * depth])


class TestShardedChainStep:
    """make_sharded_chain_step: correctness vs the reference runtime and the
    §5.2 per-chain vs per-loop message accounting (previously untested)."""

    N, M, DEPTH = 8, 32, 2  # two loops x stencil extent 1 -> chain depth 2

    def _loops(self):
        """A 2-loop ping-pong smoothing chain on the repro.core DSL."""
        import numpy as np

        from repro.core import Arg, Block, READ, WRITE, make_dataset
        from repro.core import point_stencil, star_stencil
        from repro.core.loop import ParallelLoop

        blk = Block("g", (self.N, self.M))
        rng = np.random.RandomState(7)
        u0 = rng.rand(self.N, self.M).astype(np.float32)
        u = make_dataset(blk, "u", halo=self.DEPTH, init=u0)
        v = make_dataset(blk, "v", halo=self.DEPTH)
        S = star_stencil(2, 1)
        Z = point_stencil(2)

        def k_uv(acc):
            return {"v": 0.5 * acc("u") + 0.25 * (acc("u", (0, -1))
                                                  + acc("u", (0, 1)))}

        def k_vu(acc):
            return {"u": 0.5 * acc("v") + 0.25 * (acc("v", (0, -1))
                                                  + acc("v", (0, 1)))}

        rng_box = ((0, self.N), (0, self.M))
        loops = [
            ParallelLoop("uv", blk, rng_box,
                         (Arg(u, S, READ), Arg(v, Z, WRITE)), k_uv),
            ParallelLoop("vu", blk, rng_box,
                         (Arg(v, S, READ), Arg(u, Z, WRITE)), k_vu),
        ]
        return u0, u, v, loops

    def _sharded_step(self, n_ranks, per_loop):
        import jax.numpy as jnp
        from jax import lax

        from repro.core.distributed import make_sharded_chain_step

        mesh = _make_mesh(n_ranks)
        per = self.M // n_ranks
        D = self.DEPTH
        W = per + 2 * D

        def smooth(arr):
            return (0.5 * arr + 0.25 * (jnp.roll(arr, 1, 1)
                                        + jnp.roll(arr, -1, 1)))

        def masked(write_to, read_from):
            def fn(arrays):
                rank = lax.axis_index("x")
                cols = rank * per + jnp.arange(W) - D
                mask = ((cols >= 0) & (cols < self.M))[None, :]
                out = dict(arrays)
                out[write_to] = jnp.where(mask, smooth(arrays[read_from]),
                                          arrays[write_to])
                return out
            return fn

        loop_fns = [masked("v", "u"), masked("u", "v")]

        def chain(arrays):
            for fn in loop_fns:
                arrays = fn(arrays)
            return arrays

        # per_loop_depth must equal the buffers' halo padding: exchange_halos
        # indexes send/recv regions by depth, so a shallower exchange on a
        # deeper-padded buffer would move the wrong columns.
        return make_sharded_chain_step(
            chain, mesh, "x", depth=D, per_loop=per_loop,
            loop_fns=loop_fns, per_loop_depth=D, dim=1), per

    @pytest.mark.parametrize("n_ranks", [2, 8])
    @pytest.mark.parametrize("per_loop", [False, True])
    def test_matches_reference_runtime(self, n_ranks, per_loop):
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.core.reference import run_chain_reference

        u0, u, v, loops = self._loops()
        run_chain_reference(loops)
        expect = u.interior().copy()

        step, per = self._sharded_step(n_ranks, per_loop)
        D = self.DEPTH
        padded = np.zeros((self.N, self.M + 2 * D), np.float32)
        padded[:, D:-D] = u0
        locs = [padded[:, r * per: r * per + per + 2 * D]
                for r in range(n_ranks)]
        mesh = _make_mesh(n_ranks)
        garr = jax.device_put(np.concatenate(locs, 1),
                              NamedSharding(mesh, P(None, "x")))
        zeros = jax.device_put(np.zeros_like(np.concatenate(locs, 1)),
                               NamedSharding(mesh, P(None, "x")))
        res = np.asarray(step({"u": garr, "v": zeros})["u"])
        W = per + 2 * D
        got = np.concatenate(
            [res[:, r * W + D: r * W + D + per] for r in range(n_ranks)], 1)
        np.testing.assert_allclose(got, expect, rtol=1e-6, atol=1e-6)

    def test_message_count_accounting(self):
        """§5.2 policy trade-off, in numbers: the tiled policy's one deep
        exchange vs the untiled policy's per-loop shallow exchanges."""
        from repro.core.distributed import chain_message_count

        tiled, per = self._sharded_step(2, per_loop=False)
        untiled, _ = self._sharded_step(2, per_loop=True)
        assert tiled.exchanges == 1
        assert untiled.exchanges == 2
        assert tiled.messages_per_array == chain_message_count(2, 1) == 2
        assert untiled.messages_per_array == chain_message_count(
            2, 1, n_loops=2, per_loop=True) == 4
        assert untiled.messages_per_array > tiled.messages_per_array
        # periodic rings close the loop: 2 extra wrap messages per exchange
        assert chain_message_count(8, 3, periodic=True) == 48
        assert chain_message_count(8, 3) == 42


@pytest.mark.parametrize("script,token", [
    (_SCRIPT_HALO, "HALO_OK"),
    (_SCRIPT_COMPRESS, "COMPRESS_OK"),
])
def test_multidevice_subprocess(script, token):
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=300,
                       # JAX_PLATFORMS=cpu: the forced host-device count only
                       # exists on the CPU platform, and without it JAX may
                       # stall probing for accelerators (TPU metadata fetch).
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "HOME": "/root", "JAX_PLATFORMS": "cpu"},
                       cwd="/root/repo")
    assert r.returncode == 0, r.stderr[-3000:]
    assert token in r.stdout
