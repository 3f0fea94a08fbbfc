"""Plain reference for OpenSBLI's Taylor-Green vortex: whole arrays, one
jitted function per timestep, no tiling, no staging, no runtime.

It follows the mini-app's loop chain (``repro/apps/opensbli.py``) written out
again as array expressions: three low-storage Runge-Kutta stages of
primitives, shear, five residuals and the update, over the same ranges and
with each expression's operations in the same order.  It imports nothing of
the program.  Every array carries a halo of 2 on each side; a loop over grid
range ``r`` reads ``a[lo + o + 2 : hi + o + 2]`` per axis at offset ``o``.

``dtype`` is the storage and arithmetic type: float32 is the configuration,
bfloat16 the control.
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
DT = 5e-4
RK_A = (0.0, -5.0 / 9.0, -153.0 / 128.0)
RK_B = (1.0 / 3.0, 15.0 / 16.0, 8.0 / 15.0)
AXES = {"x": (1, 0, 0), "y": (0, 1, 0), "z": (0, 0, 1)}
CONS = ("rho", "rhou", "rhov", "rhow", "rhoE")
FIELDS = (list(CONS) + [f"{c}_w" for c in CONS] + [f"{c}_r" for c in CONS]
          + ["u", "v", "w", "p", "T", "sxx", "syy", "szz", "sxy", "sxz", "syz",
             "detJ", "mu", "kappa"])


def _rd(a, rng, off=(0, 0, 0)):
    return a[tuple(slice(lo + o + H, hi + o + H) for (lo, hi), o in zip(rng, off))]


def _wr(f, rng, vals: Dict[str, jax.Array]):
    idx = tuple(slice(lo + H, hi + H) for lo, hi in rng)
    out = dict(f)
    for name, v in vals.items():
        out[name] = f[name].at[idx].set(jnp.asarray(v, f[name].dtype))
    return out


def _neg(o):
    return tuple(-x for x in o)


class OpenSBLIReference:
    def __init__(self, n: int, dtype=jnp.float32):
        self.n = n
        self.dtype = jnp.dtype(dtype)
        self.h = 2 * np.pi / n
        self.full = ((0, n),) * 3
        self.interior = ((2, n - 2),) * 3

    def init(self, nz):
        n, c = self.n, self.dtype
        f = {name: jnp.zeros(nz.shape, c) for name in FIELDS}
        h = 2 * np.pi / n
        ar = jnp.arange(n, dtype=jnp.int32)
        ix = jnp.broadcast_to(ar[:, None, None], (n, n, n))
        iy = jnp.broadcast_to(ar[None, :, None], (n, n, n))
        iz = jnp.broadcast_to(ar[None, None, :], (n, n, n))
        X, Y, Z = ix.astype(c) * h, iy.astype(c) * h, iz.astype(c) * h
        u = jnp.sin(X) * jnp.cos(Y) * jnp.cos(Z)
        v = -jnp.cos(X) * jnp.sin(Y) * jnp.cos(Z)
        w = jnp.zeros_like(u)
        p = 10.0 + ((jnp.cos(2 * X) + jnp.cos(2 * Y)) * (jnp.cos(2 * Z) + 2.0)) / 16.0
        rho = jnp.ones_like(p)
        E = p / ((GAMMA - 1.0) * rho) + 0.5 * (u * u + v * v + w * w)
        f = _wr(f, self.full, {
            "rho": rho, "rhou": rho * u, "rhov": rho * v, "rhow": rho * w,
            "rhoE": rho * E, "detJ": jnp.ones_like(u),
            "mu": jnp.full_like(u, 1e-3), "kappa": jnp.full_like(u, 1e-3)})
        nz = nz.astype(c)
        r = self.full
        vals = seeded.sbli_perturb(
            _rd(nz, r), _rd(nz, r, (1, 0, 0)), _rd(nz, r, (0, 1, 0)),
            _rd(nz, r, (0, 0, 1)), _rd(f["rho"], r), _rd(f["rhou"], r),
            _rd(f["rhov"], r), _rd(f["rhow"], r))
        return _wr(f, r, vals)

    def _primitives(self, f):
        r = self.full
        rho = jnp.maximum(_rd(f["rho"], r), 1e-3)
        u = _rd(f["rhou"], r) / rho
        v = _rd(f["rhov"], r) / rho
        w = _rd(f["rhow"], r) / rho
        p = (GAMMA - 1.0) * (_rd(f["rhoE"], r) - 0.5 * rho * (u * u + v * v + w * w))
        T = p / rho
        return _wr(f, r, {"u": u, "v": v, "w": w, "p": p, "T": T})

    def _dc(self, f, name, a):
        o = AXES[a]
        r = self.interior
        return (_rd(f[name], r, o) - _rd(f[name], r, _neg(o))) * (0.5 / self.h)

    def _lap(self, f, name):
        r = self.interior
        ih2 = 1.0 / (self.h * self.h)
        out = 0.0
        for a in "xyz":
            o = AXES[a]
            out = out + (_rd(f[name], r, o) - 2.0 * _rd(f[name], r)
                         + _rd(f[name], r, _neg(o))) * ih2
        return out

    def _shear(self, f):
        dc = functools.partial(self._dc, f)
        return _wr(f, self.interior, {
            "sxx": dc("u", "x"), "syy": dc("v", "y"), "szz": dc("w", "z"),
            "sxy": 0.5 * (dc("u", "y") + dc("v", "x")),
            "sxz": 0.5 * (dc("u", "z") + dc("w", "x")),
            "syz": 0.5 * (dc("v", "z") + dc("w", "y"))})

    def _residual(self, f, eq):
        r = self.interior
        g = lambda name: _rd(f[name], r)  # noqa: E731
        dc = functools.partial(self._dc, f)
        conv = dc(eq, "x") * g("u") + dc(eq, "y") * g("v") + dc(eq, "z") * g("w")
        if eq == "rho":
            res = -(g("rho") * (g("sxx") + g("syy") + g("szz")) + conv)
        elif eq in ("rhou", "rhov", "rhow"):
            a = {"rhou": "x", "rhov": "y", "rhow": "z"}[eq]
            vel = {"rhou": "u", "rhov": "v", "rhow": "w"}[eq]
            res = -(conv + dc("p", a)) + g("mu") * self._lap(f, vel)
        else:
            work = dc("p", "x") * g("u") + dc("p", "y") * g("v") + dc("p", "z") * g("w")
            visc = g("mu") * (g("sxx") ** 2 + g("syy") ** 2 + g("szz") ** 2
                              + 2 * (g("sxy") ** 2 + g("sxz") ** 2 + g("syz") ** 2))
            res = -(conv + work) + g("kappa") * self._lap(f, "T") + visc
        return _wr(f, r, {f"{eq}_r": res})

    def _rk_update(self, f, stage):
        r = self.interior
        a_c, b_c = RK_A[stage], RK_B[stage]
        out = {}
        for c in CONS:
            wrk = a_c * _rd(f[f"{c}_w"], r) + DT * _rd(f[f"{c}_r"], r)
            out[f"{c}_w"] = wrk
            out[c] = _rd(f[c], r) + b_c * wrk
        return _wr(f, r, out)

    def timestep(self, f):
        for stage in range(3):
            f = self._primitives(f)
            f = self._shear(f)
            for eq in CONS:
                f = self._residual(f, eq)
            f = self._rk_update(f, stage)
        return f

    def summary(self, f):
        r = self.interior
        rho = _rd(f["rho"], r)
        ke = 0.5 * (_rd(f["rhou"], r) ** 2 + _rd(f["rhov"], r) ** 2
                    + _rd(f["rhow"], r) ** 2) / jnp.maximum(rho, 1e-3)
        return {"sum_mass": jnp.sum(rho), "sum_ke": jnp.sum(ke),
                "max_rho": jnp.max(rho)}


@functools.partial(jax.jit, static_argnums=0)
def _init(key, nz):
    return OpenSBLIReference(*key).init(nz)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=(1,))
def _step(key, f):
    return OpenSBLIReference(*key).timestep(f)


@functools.partial(jax.jit, static_argnums=0)
def _summary(key, f):
    return OpenSBLIReference(*key).summary(f)


def run(cfg: dict, seed: int, steps: int, dtype=jnp.float32,
        fields: List[str] = ()) -> Tuple[Dict[str, np.ndarray], Dict[str, float]]:
    """``steps`` timesteps from the seeded initial state; returns the
    interior of each of ``fields`` and the TGV summary after the last step."""
    (n, _, _) = cfg["grid"]
    key = (n, jnp.dtype(dtype).name)
    f = _init(key, seeded.noise(seed, (n + 2 * H,) * 3))
    for _ in range(steps):
        f = _step(key, f)
    reds = {k: float(v) for k, v in _summary(key, f).items()}
    out = {name: np.asarray(f[name][H:-H, H:-H, H:-H].astype(jnp.float32))
           for name in fields}
    return out, reds
