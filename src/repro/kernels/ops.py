"""Public jit'd wrappers for the Pallas kernels: padding, block sizing, VMEM
budgeting, and interpret-mode selection (interpret on CPU, compiled on TPU).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import chain2d as _chain2d
from . import stencil2d as _stencil2d
from . import stencil3d as _stencil3d

# VMEM a kernel may hold: the Mosaic compiler's scoped-VMEM limit on v5e is
# 16 MiB (its out-of-VMEM error reports "limit 16.00M"; the chip has 128 MiB
# of VMEM in all).  Blocks are sized to 7/8 of it, leaving headroom for what
# the estimate below does not see.
_VMEM_LIMIT = 16 << 20
_VMEM_BUDGET = _VMEM_LIMIT * 7 // 8


def _default_interpret() -> bool:
    """Interpret mode on the CPU (tests), compiled on the TPU; any other
    backend has no Pallas path here and is an error, never a silent
    fallback to the interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r} — pass interpret= explicitly")


def _tile_bytes(shape) -> int:
    """f32 bytes of one VMEM buffer of ``shape`` after Mosaic's (8, 128)
    tiling pads the last two dimensions."""
    *lead, rows, lanes = shape
    return (int(np.prod(lead, dtype=np.int64)) * (-(-rows // 8) * 8)
            * (-(-lanes // 128) * 128) * 4)


def _pick_block_rows(window_bytes, live: int, smallest: int = 8) -> int:
    """Largest power-of-two block (``smallest``..512 rows or planes) whose
    kernel fits the VMEM budget: 8 rows at least for a 2-D sublane block,
    down to 1 plane for a 3-D z-slab.  ``window_bytes(bm)`` is one padded
    input window of a ``bm``-row block; ``live`` is how many window-sized
    buffers the kernel holds at once: the pipeline double-buffers the input
    and the output window (4), and the fused K-sweep chain keeps two f32
    intermediates of the window on top (6) — what the v5e compiler's
    smallest sufficient scoped-VMEM limit showed at the widths
    tests/test_tpu_compile.py compiles."""
    bm = 512
    while bm > smallest and live * window_bytes(bm) > _VMEM_BUDGET:
        bm //= 2
    return bm


_stencil2d_jit = jax.jit(_stencil2d.stencil2d_pallas,
                         static_argnames=("block_rows", "interpret"))
_stencil3d_jit = jax.jit(_stencil3d.stencil3d_pallas,
                         static_argnames=("block_z", "interpret"))
_chain2d_jit = jax.jit(_chain2d.chain2d_pallas,
                       static_argnames=("steps", "block_rows", "interpret"))


def stencil2d(x, coeffs, *, block_rows: Optional[int] = None,
              interpret: Optional[bool] = None):
    """5-point stencil sweep. x: (H+2, W+2) padded; returns (H, W)."""
    x = jnp.asarray(x)
    coeffs = jnp.asarray(coeffs, dtype=jnp.float32)
    H, Wp = x.shape[0] - 2, x.shape[1]
    if block_rows is None:
        block_rows = _pick_block_rows(
            lambda bm: _tile_bytes((_stencil2d.window_rows(bm, 1), Wp)), 4)
    if interpret is None:
        interpret = _default_interpret()
    return _stencil2d_jit(x, coeffs, block_rows=min(block_rows, H),
                          interpret=interpret)


def stencil3d(x, coeffs, *, block_z: Optional[int] = None,
              interpret: Optional[bool] = None):
    """7-point stencil sweep. x: (D+2, H+2, W+2) padded; returns (D, H, W)."""
    x = jnp.asarray(x)
    coeffs = jnp.asarray(coeffs, dtype=jnp.float32)
    D = x.shape[0] - 2
    if block_z is None:
        block_z = _pick_block_rows(
            lambda bz: _tile_bytes((bz + 2,) + x.shape[1:]), 4, smallest=1)
    if interpret is None:
        interpret = _default_interpret()
    return _stencil3d_jit(x, coeffs, block_z=min(block_z, D),
                          interpret=interpret)


def chain2d(x, coeffs, steps: int, *, block_rows: Optional[int] = None,
            interpret: Optional[bool] = None):
    """K fused 5-point sweeps. x: (H+2K, W+2K) padded; returns (H, W)."""
    x = jnp.asarray(x)
    coeffs = jnp.asarray(coeffs, dtype=jnp.float32)
    H, Wp = x.shape[0] - 2 * steps, x.shape[1]
    if block_rows is None:
        block_rows = _pick_block_rows(
            lambda bm: _tile_bytes((_stencil2d.window_rows(bm, steps), Wp)),
            4 if steps == 1 else 6)
    if interpret is None:
        interpret = _default_interpret()
    return _chain2d_jit(x, coeffs, steps=steps,
                        block_rows=min(block_rows, H), interpret=interpret)


# -- declarative star-sweep kernels (the "pallas" backend's fast path) -----------
#
# These build Accessor-kernels for the runtime DSL that also *declare* what
# they compute via a ``pallas_op`` tag: the pallas backend routes tagged loops
# through the Pallas kernels above; every other backend just executes the
# generic accessor formula.  Coefficients are baked in as Python floats so the
# kernel fingerprint (and hence the chain-plan cache) sees coefficient changes.


def star2d_kernel(src: str, dst: str, coeffs):
    """5-point star sweep kernel: dst = c0*src + cx*(±dim0) + cy*(±dim1)."""
    c0, cx, cy = (float(c) for c in coeffs)

    def kernel(acc):
        return {dst: c0 * acc(src)
                + cx * (acc(src, (1, 0)) + acc(src, (-1, 0)))
                + cy * (acc(src, (0, 1)) + acc(src, (0, -1)))}

    kernel.pallas_op = ("stencil2d", src, dst, (c0, cx, cy))
    return kernel


def star3d_kernel(src: str, dst: str, coeffs):
    """7-point star sweep kernel: dst = c0*src + cz/cx/cy * (±each dim)."""
    c0, cz, cx, cy = (float(c) for c in coeffs)

    def kernel(acc):
        return {dst: c0 * acc(src)
                + cz * (acc(src, (1, 0, 0)) + acc(src, (-1, 0, 0)))
                + cx * (acc(src, (0, 1, 0)) + acc(src, (0, -1, 0)))
                + cy * (acc(src, (0, 0, 1)) + acc(src, (0, 0, -1)))}

    kernel.pallas_op = ("stencil3d", src, dst, (c0, cz, cx, cy))
    return kernel
