"""3-D 7-point star stencil sweep as a Pallas TPU kernel.

Blocks are z-slabs: (bz + 2h, Hp, Wp) input windows -> (bz, H, W) outputs.
Within a slab the y/x plane stays whole (the lane/sublane dims map to x/y on
TPU; the stencil only needs ±1 neighbours so the 2-D plane arithmetic
vectorises on the VPU while z-neighbours come from adjacent VMEM rows).

u'[k,i,j] = c0*u[kij] + cz*(u[k±1]) + cx*(u[i±1]) + cy*(u[j±1])
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, c_ref, o_ref):
    u = x_ref[...].astype(jnp.float32)
    c0, cz, cx, cy = c_ref[0], c_ref[1], c_ref[2], c_ref[3]
    core = u[1:-1, 1:-1, 1:-1]
    zm = u[:-2, 1:-1, 1:-1]
    zp = u[2:, 1:-1, 1:-1]
    xm = u[1:-1, :-2, 1:-1]
    xp = u[1:-1, 2:, 1:-1]
    ym = u[1:-1, 1:-1, :-2]
    yp = u[1:-1, 1:-1, 2:]
    o_ref[...] = (
        c0 * core + cz * (zm + zp) + cx * (xm + xp) + cy * (ym + yp)
    ).astype(o_ref.dtype)


def stencil3d_pallas(
    x: jax.Array,
    coeffs: jax.Array,
    *,
    block_z: int,
    interpret: bool,
) -> jax.Array:
    """7-point stencil on ``x`` (padded by 1 per side); returns (D,H,W).

    The z window is a leading dimension, so it needs no sublane rounding;
    only the plane dims are tiled by Mosaic and they stay whole."""
    Dp, Hp, Wp = x.shape
    D, H, W = Dp - 2, Hp - 2, Wp - 2
    bz = min(block_z, D)
    Db = -(-D // bz) * bz
    if Db > D:
        x = jnp.pad(x, ((0, Db - D), (0, 0), (0, 0)))
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((Db, H, W), x.dtype),
        grid=(Db // bz,),
        in_specs=[
            pl.BlockSpec((pl.Element(bz + 2), pl.Element(Hp), pl.Element(Wp)),
                         lambda i: (i * bz, 0, 0)),
            pl.BlockSpec((4,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bz, H, W), lambda i: (i, 0, 0)),
        interpret=interpret,
    )(x, coeffs)
    return out[:D]
