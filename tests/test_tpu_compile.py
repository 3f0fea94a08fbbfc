"""Ahead-of-time compiles for a described TPU v5e: the Pallas kernels and a
CloverLeaf 2D tile program at real widths.  Nothing runs — the TPU compiler
refuses here what it would refuse on the chip (block shapes off the (8, 128)
tiling, kernels over the scoped-VMEM limit, programs over HBM).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library, and every xdist
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

C2 = np.array([0.5, 0.125, 0.125], np.float32)
C3 = np.array([0.4, 0.1, 0.1, 0.1], np.float32)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent cache
    # but never read back without one; keep the cache off meanwhile.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("shape,coeffs,call,halo", [
    ((3842, 3842), C2, lambda x, c: ops.stencil2d(x, c, interpret=False), 1),
    ((258, 258, 258), C3,
     lambda x, c: ops.stencil3d(x, c, interpret=False), 1),
    ((3848, 3848), C2, lambda x, c: ops.chain2d(x, c, 4, interpret=False), 4),
], ids=["stencil2d", "stencil3d", "chain2d"])
def test_kernel_compiles_at_default_block(one_chip, shape, coeffs, call, halo):
    """Each public wrapper's default block choice compiles for v5e at the
    widths the chip smoke runs (3840² interiors, 256³, a 4-sweep chain)."""
    compiled = _compile(call, _spec(shape, one_chip),
                        _spec(coeffs.shape, one_chip))
    out = compiled.out_info
    assert out.shape == tuple(n - 2 * halo for n in shape)


def test_cloverleaf_tile_program_compiles(one_chip):
    """One CloverLeaf 2D timestep tile program at the clover_bm16 deck size
    (3840², fp32) with a third of the working set as capacity — the tile
    shape the out-of-core chip smoke streams — compiles for v5e."""
    from repro.apps import CloverLeaf2D
    from repro.core import Session

    app = CloverLeaf2D(3840, 3840)
    sess = Session("ooc", capacity_bytes=app.total_bytes() / 3)
    app._ideal_gas(sess, "density0", "energy0", "_dt")
    app._viscosity(sess)
    app._calc_dt(sess)
    cp = sess.backend.plan_chain(sess.queue)
    assert cp.sched.num_tiles > 1
    tile = cp.sched.tiles[cp.sched.num_tiles // 2]
    td = cp.info.tiled_dim
    slots = {}
    for name, ln in cp.sched.max_fp_len.items():
        shape = list(cp.info.datasets[name].padded_shape)
        shape[td] = ln
        slots[name] = _spec(tuple(shape), one_chip)
    scalar = _spec((), one_chip, jnp.int32)
    loop_args = {k: (scalar, {}) for k, box in enumerate(tile.loop_ranges)
                 if box is not None}
    origins = {name: scalar for name in slots}
    fn = cp.engine.program(tile)
    compiled = fn.lower(slots, loop_args, origins).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= sum(
        int(np.prod(s.shape)) * 4 for s in slots.values())
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
