"""Plain reference for CloverLeaf 2D: whole arrays, one jitted function per
timestep, no tiling, no staging, no runtime.

It follows the loop chain of the mini-app (``repro/apps/cloverleaf2d.py``)
written out again as array expressions: the same loops in the same order,
over the same ranges, with each expression's operations in the same order.
It imports nothing of the program.  Every array carries a halo of 2 on each
side; a loop over grid range ``((r0, r1), (c0, c1))`` reads
``a[r0 + o0 + 2 : r1 + o0 + 2, c0 + o1 + 2 : c1 + o1 + 2]`` at offset
``(o0, o1)`` and writes the box at offset 0 after computing every output
from the values before the loop.

``dtype`` is the storage and arithmetic type: float32 is the configuration,
bfloat16 the control (the same reference one precision lower).
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import seeded

GAMMA = 1.4
H = 2
DT_CAP = 1e-4
FIELDS = [
    "density0", "density1", "energy0", "energy1", "pressure", "viscosity",
    "soundspeed", "volume", "vol_flux_x", "vol_flux_y", "mass_flux_x",
    "mass_flux_y", "pre_vol", "post_vol", "pre_mass", "post_mass",
    "advec_vol", "post_ener", "ener_flux", "xarea", "yarea",
    "xvel0", "xvel1", "yvel0", "yvel1",
]


def _rd(a, rng, off=(0, 0)):
    return a[tuple(slice(lo + o + H, hi + o + H) for (lo, hi), o in zip(rng, off))]


def _wr(f, rng, vals: Dict[str, jax.Array]):
    idx = tuple(slice(lo + H, hi + H) for lo, hi in rng)
    out = dict(f)
    for name, v in vals.items():
        out[name] = f[name].at[idx].set(jnp.asarray(v, f[name].dtype))
    return out


class CloverLeaf2DReference:
    def __init__(self, nx: int, ny: int, dtype=jnp.float32):
        self.nx, self.ny = nx, ny
        self.dtype = jnp.dtype(dtype)
        self.interior = ((0, nx), (0, ny))
        self.inner = ((2, nx - 2), (2, ny - 2))

    # -- loops ------------------------------------------------------------
    def _ideal_gas(self, f, rho_name, e_name):
        rho, e = _rd(f[rho_name], self.interior), _rd(f[e_name], self.interior)
        p = (GAMMA - 1.0) * rho * e
        ss = jnp.sqrt(jnp.maximum(GAMMA * p / jnp.maximum(rho, 1e-10), 1e-10))
        return _wr(f, self.interior, {"pressure": p, "soundspeed": ss})

    def _viscosity(self, f):
        r = self.interior
        du = _rd(f["xvel0"], r, (1, 0)) - _rd(f["xvel0"], r)
        dv = _rd(f["yvel0"], r, (0, 1)) - _rd(f["yvel0"], r)
        div = du + dv
        visc = jnp.where(div < 0.0, 2.0 * _rd(f["density0"], r) * div * div, 0.0)
        return _wr(f, r, {"viscosity": visc})

    def _calc_dt(self, f):
        r = self.interior
        speed = (_rd(f["soundspeed"], r) + jnp.abs(_rd(f["xvel0"], r))
                 + jnp.abs(_rd(f["yvel0"], r)))
        dt_local = 0.5 / jnp.maximum(speed, 1e-6) / max(self.nx, self.ny)
        return jnp.min(dt_local)

    def _pdv(self, f, dt):
        r = self.interior
        x0, y0 = f["xvel0"], f["yvel0"]
        div = ((_rd(x0, r, (1, 0)) - _rd(x0, r))
               + (_rd(y0, r, (0, 1)) - _rd(y0, r)))
        vol_change = 1.0 + dt * div
        d0, e0 = _rd(f["density0"], r), _rd(f["energy0"], r)
        rho = d0 / jnp.maximum(vol_change, 0.1)
        e = e0 - dt * _rd(f["pressure"], r) * div / jnp.maximum(d0, 1e-10)
        return _wr(f, r, {"density1": rho, "energy1": e})

    def _revert(self, f):
        r = self.interior
        return _wr(f, r, {"density1": _rd(f["density0"], r),
                          "energy1": _rd(f["energy0"], r)})

    def _accelerate(self, f, dt):
        r = ((1, self.nx), (1, self.ny))
        d0, p, v = f["density0"], f["pressure"], f["viscosity"]
        nodal_mass = 0.25 * (_rd(d0, r) + _rd(d0, r, (-1, 0))
                             + _rd(d0, r, (0, -1)) + _rd(d0, r, (-1, -1)))
        px = (_rd(p, r) - _rd(p, r, (-1, 0)) + _rd(v, r) - _rd(v, r, (-1, 0)))
        py = (_rd(p, r) - _rd(p, r, (0, -1)) + _rd(v, r) - _rd(v, r, (0, -1)))
        xv = _rd(f["xvel0"], r) - dt * px / jnp.maximum(nodal_mass, 1e-10)
        yv = _rd(f["yvel0"], r) - dt * py / jnp.maximum(nodal_mass, 1e-10)
        return _wr(f, r, {"xvel1": xv, "yvel1": yv})

    def _flux_calc(self, f, dt):
        r = self.interior
        x1, y1 = f["xvel1"], f["yvel1"]
        fx = 0.5 * dt * (_rd(x1, r) + _rd(x1, r, (0, 1))) * _rd(f["xarea"], r)
        fy = 0.5 * dt * (_rd(y1, r) + _rd(y1, r, (1, 0))) * _rd(f["yarea"], r)
        return _wr(f, r, {"vol_flux_x": fx, "vol_flux_y": fy})

    def _advec_cell(self, f, sweep):
        r = self.inner
        flux = f"vol_flux_{sweep}"
        off = (1, 0) if sweep == "x" else (0, 1)
        moff = (-1, 0) if sweep == "x" else (0, -1)
        fl = f[flux]
        vol = _rd(f["volume"], r)
        f = _wr(f, r, {"pre_vol": vol + (_rd(fl, r, off) - _rd(fl, r)),
                       "post_vol": vol})
        fv = _rd(fl, r)
        d1, e1 = f["density1"], f["energy1"]
        donor_rho = jnp.where(fv > 0, _rd(d1, r, moff), _rd(d1, r))
        donor_e = jnp.where(fv > 0, _rd(e1, r, moff), _rd(e1, r))
        f = _wr(f, r, {
            "pre_mass": donor_rho * jnp.abs(fv),
            "ener_flux": donor_rho * donor_e * jnp.abs(fv) * jnp.sign(fv)})
        fp = _rd(fl, r, off)
        pm = f["pre_mass"]
        mflux_in = jnp.where(fv > 0, _rd(pm, r), -_rd(pm, r))
        mflux_out = jnp.where(fp > 0, _rd(pm, r, off), -_rd(pm, r, off))
        pre_mass = _rd(f["density1"], r) * _rd(f["pre_vol"], r)
        post_mass = pre_mass + mflux_in - mflux_out
        rho = post_mass / jnp.maximum(_rd(f["post_vol"], r), 1e-10)
        ef = f["ener_flux"]
        post_e = ((pre_mass * _rd(f["energy1"], r) + _rd(ef, r) - _rd(ef, r, off))
                  / jnp.maximum(post_mass, 1e-10))
        return _wr(f, r, {"density1": rho, "energy1": post_e,
                          "post_mass": post_mass})

    def _advec_mom(self, f, sweep, vel):
        r = self.inner
        flux, vflux = f"mass_flux_{sweep}", f"vol_flux_{sweep}"
        off = (1, 0) if sweep == "x" else (0, 1)
        moff = (-off[0], -off[1])
        v1 = f"{vel}1"
        d1 = f["density1"]
        f = _wr(f, r, {flux: _rd(f[vflux], r) * 0.5
                       * (_rd(d1, r) + _rd(d1, r, off))})
        fv = _rd(f[flux], r)
        donor = jnp.where(fv > 0, _rd(f[v1], r, moff), _rd(f[v1], r))
        f = _wr(f, r, {"advec_vol": fv * donor})
        node_mass = jnp.maximum(_rd(f["post_mass"], r), 1e-10)
        mom = f["advec_vol"]
        return _wr(f, r, {v1: _rd(f[v1], r)
                          + (_rd(mom, r) - _rd(mom, r, off)) / node_mass})

    def _update_halo(self, f, fields, depth=2):
        nx, ny = self.nx, self.ny
        sites = []
        for k in range(depth):
            sites.append((((-k - 1, -k), (0, ny)), (2 * k + 1, 0)))
            sites.append((((nx + k, nx + k + 1), (0, ny)), (-2 * k - 1, 0)))
        for k in range(depth):
            sites.append((((-depth, nx + depth), (-k - 1, -k)), (0, 2 * k + 1)))
            sites.append((((-depth, nx + depth), (ny + k, ny + k + 1)),
                          (0, -2 * k - 1)))
        for rng, off in sites:
            f = _wr(f, rng, {n: _rd(f[n], rng, off) for n in fields})
        return f

    def _reset_field(self, f):
        r = self.interior
        return _wr(f, r, {"density0": _rd(f["density1"], r),
                          "energy0": _rd(f["energy1"], r),
                          "xvel0": _rd(f["xvel1"], r),
                          "yvel0": _rd(f["yvel1"], r)})

    # -- chains -----------------------------------------------------------
    def init(self, nz):
        """The app's initial state, then the seeded perturbation read from
        the noise array ``nz`` (padded shape)."""
        nx, ny = self.nx, self.ny
        f = {n: jnp.zeros(nz.shape, self.dtype) for n in FIELDS}
        c = self.dtype
        hx, hy = 2 * np.pi / nx, 2 * np.pi / ny
        ix = jnp.broadcast_to(jnp.arange(nx, dtype=jnp.int32)[:, None], (nx, ny))
        iy = jnp.broadcast_to(jnp.arange(ny, dtype=jnp.int32)[None, :], (nx, ny))
        x = ix.astype(c) * hx
        y = iy.astype(c) * hy
        one = jnp.ones((nx, ny), c)
        f = _wr(f, self.interior, {
            "density0": 1.0 + 0.2 * jnp.sin(x) * jnp.cos(y),
            "energy0": 2.5 + 0.5 * jnp.cos(x),
            "volume": one, "xarea": one, "yarea": one,
            "xvel0": 0.1 * jnp.sin(x), "yvel0": -0.1 * jnp.cos(y)})
        nz = nz.astype(self.dtype)
        r = self.interior
        vals = seeded.clover_perturb(
            _rd(nz, r), _rd(nz, r, (1, 0)), _rd(nz, r, (0, 1)),
            _rd(nz, r, (1, 1)), _rd(f["density0"], r), _rd(f["energy0"], r),
            _rd(f["xvel0"], r), _rd(f["yvel0"], r))
        return _wr(f, r, vals)

    def dt_chain(self, f):
        f = self._ideal_gas(f, "density0", "energy0")
        f = self._viscosity(f)
        return f, self._calc_dt(f)

    def timestep(self, f, dt, first: str):
        f = self._ideal_gas(f, "density0", "energy0")
        f = self._viscosity(f)
        f = self._update_halo(f, ["pressure", "viscosity", "soundspeed"])
        f = self._pdv(f, dt * 0.5)
        f = self._ideal_gas(f, "density1", "energy1")
        f = self._revert(f)
        f = self._accelerate(f, dt)
        f = self._pdv(f, dt)
        f = self._flux_calc(f, dt)
        f = self._update_halo(f, ["vol_flux_x", "vol_flux_y", "xvel1", "yvel1"])
        second = "y" if first == "x" else "x"
        for sweep in (first, second):
            f = self._advec_cell(f, sweep)
            f = self._advec_mom(f, sweep, "xvel")
            f = self._advec_mom(f, sweep, "yvel")
            if sweep == first:
                f = self._update_halo(f, ["density1", "energy1"])
        return self._reset_field(f)

    def summary(self, f):
        r = self.interior
        rho, e = _rd(f["density0"], r), _rd(f["energy0"], r)
        u, v = _rd(f["xvel0"], r), _rd(f["yvel0"], r)
        vol = _rd(f["volume"], r)
        ke = 0.5 * rho * (u * u + v * v)
        return {"sum_mass": jnp.sum(rho * vol), "sum_ie": jnp.sum(rho * e * vol),
                "sum_ke": jnp.sum(ke * vol),
                "max_p": jnp.max(_rd(f["pressure"], r)),
                "min_rho": jnp.min(rho)}


@functools.partial(jax.jit, static_argnums=0)
def _init(key, nz):
    return CloverLeaf2DReference(*key).init(nz)


@functools.partial(jax.jit, static_argnums=(0, 2), donate_argnums=(1,))
def _step(key, f, first: str):
    """The ``calc_dt`` chain, then the timestep it sets the dt of."""
    ref = CloverLeaf2DReference(*key)
    f, dt_red = ref.dt_chain(f)
    dt = jnp.minimum(DT_CAP, dt_red)
    return ref.timestep(f, dt, first), dt_red


@functools.partial(jax.jit, static_argnums=0)
def _summary(key, f):
    return CloverLeaf2DReference(*key).summary(f)


def run(cfg: dict, seed: int, steps: int, dtype=jnp.float32,
        fields: List[str] = ()) -> Tuple[Dict[str, np.ndarray], Dict[str, float]]:
    """``steps`` timesteps from the seeded initial state.  Returns the
    interior of each of ``fields`` and the reductions: ``dt.<k>`` for each
    step's ``calc_dt`` and the field summary after the last step."""
    nx, ny = cfg["grid"]
    key = (nx, ny, jnp.dtype(dtype).name)
    f = _init(key, seeded.noise(seed, (nx + 2 * H, ny + 2 * H)))
    reds: Dict[str, float] = {}
    for k in range(steps):
        f, dt_red = _step(key, f, "x" if k % 2 == 0 else "y")
        reds[f"dt.{k}"] = float(dt_red)
    reds.update({n: float(v) for n, v in _summary(key, f).items()})
    out = {n: np.asarray(f[n][H:-H, H:-H].astype(jnp.float32)) for n in fields}
    return out, reds
