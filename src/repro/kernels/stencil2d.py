"""2-D 5-point star stencil sweep as a Pallas TPU kernel.

The grid is cut into row-slabs; each slab (+1-cell halo) is staged into VMEM
by the Pallas pipeline (overlapping windows via per-dimension ``Element``
indexing) and the weighted star update runs on the VPU.  Lane dimension (W)
stays whole per block — stencil width is tiny compared to the 128-lane
register shape, so only the sublane (row) dimension is tiled.

u'[i,j] = c0*u[i,j] + cx*(u[i-1,j]+u[i+1,j]) + cy*(u[i,j-1]+u[i,j+1])
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def window_rows(block_rows: int, halo: int) -> int:
    """Rows of one input window: the block plus its halos, rounded up to the
    8-row sublane tile Mosaic requires of a block's second-minor dimension."""
    return -(-(block_rows + 2 * halo) // 8) * 8


def row_windows(x: jax.Array, block_rows: int, halo: int):
    """Pad ``x`` (H + 2*halo padded rows) so that H is a multiple of
    ``block_rows`` and the last window stays in bounds; returns the padded
    array, the padded interior row count and the window's row count."""
    H = x.shape[0] - 2 * halo
    Hb = -(-H // block_rows) * block_rows
    win = window_rows(block_rows, halo)
    pad = Hb - block_rows + win - x.shape[0]
    if pad > 0:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x, Hb, win


def _kernel(x_ref, c_ref, o_ref, *, rows: int):
    u = x_ref[0:rows + 2, :].astype(jnp.float32)
    c0 = c_ref[0]
    cx = c_ref[1]
    cy = c_ref[2]
    core = u[1:-1, 1:-1]
    up = u[:-2, 1:-1]
    dn = u[2:, 1:-1]
    lf = u[1:-1, :-2]
    rt = u[1:-1, 2:]
    o_ref[...] = (c0 * core + cx * (up + dn) + cy * (lf + rt)).astype(o_ref.dtype)


def stencil2d_pallas(
    x: jax.Array,
    coeffs: jax.Array,
    *,
    block_rows: int,
    interpret: bool,
) -> jax.Array:
    """Apply the 5-point stencil to ``x`` (padded by 1 halo cell per side).

    Args:
      x: (H+2, W+2) padded input.
      coeffs: (3,) [c0, cx, cy] float32.
    Returns:
      (H, W) updated interior.
    """
    H, Wp = x.shape[0] - 2, x.shape[1]
    W = Wp - 2
    bm = min(block_rows, H)
    xp, Hb, win = row_windows(x, bm, 1)
    out = pl.pallas_call(
        functools.partial(_kernel, rows=bm),
        out_shape=jax.ShapeDtypeStruct((Hb, W), x.dtype),
        grid=(Hb // bm,),
        in_specs=[
            pl.BlockSpec((pl.Element(win), pl.Element(Wp)),
                         lambda i: (i * bm, 0)),
            pl.BlockSpec((3,), lambda i: (0,)),  # coefficients, replicated
        ],
        out_specs=pl.BlockSpec((bm, W), lambda i: (i, 0)),
        interpret=interpret,
    )(xp, coeffs)
    return out[:H]
