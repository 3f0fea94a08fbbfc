"""Inputs drawn from ``--seed``: one noise array and the perturbations built
from it.

The program and the plain reference both start from an app's smooth initial
state and then apply the same seeded perturbation, so every seed gives other
fields with the same sizes and the same work.  The noise is made on the
device in one jitted call that takes the seed as data (one compile serves
every seed); the perturbation formulas are plain functions of arrays, called
by the program's recorded loop and by the reference alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def _key_words(seed: int) -> np.ndarray:
    """The seed as two 32-bit words (seeds beyond 32 bits stay distinct)."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


@functools.partial(jax.jit, static_argnums=(1,))
def _uniform(words, shape):
    key = jax.random.wrap_key_data(words, impl="threefry2x32")
    return jax.random.uniform(key, shape, jnp.float32)


def noise(seed: int, shape) -> jax.Array:
    """Uniform [0, 1) float32 noise of ``shape`` on the default device."""
    return _uniform(_key_words(seed), tuple(int(s) for s in shape))


def clover_perturb(n, n_x, n_y, n_xy, density, energy, xvel, yvel):
    """CloverLeaf 2D: +-5% on density and energy and +-0.01 on both
    velocities.  ``n*`` are the noise at offsets (0, 0),
    (1, 0), (0, 1) and (1, 1)."""
    return {
        "density0": density * (1.0 + 0.05 * (2.0 * n - 1.0)),
        "energy0": energy * (1.0 + 0.05 * (2.0 * n_xy - 1.0)),
        "xvel0": xvel + 0.01 * (2.0 * n_x - 1.0),
        "yvel0": yvel + 0.01 * (2.0 * n_y - 1.0),
    }


def sbli_perturb(n, n_x, n_y, n_z, rho, rhou, rhov, rhow):
    """OpenSBLI TGV: +-1% on density and +-0.01 on each momentum component.
    ``n*`` are the noise at offsets (0,0,0), (1,0,0), (0,1,0), (0,0,1)."""
    return {
        "rho": rho * (1.0 + 0.01 * (2.0 * n - 1.0)),
        "rhou": rhou + 0.01 * (2.0 * n_x - 1.0),
        "rhov": rhov + 0.01 * (2.0 * n_y - 1.0),
        "rhow": rhow + 0.01 * (2.0 * n_z - 1.0),
    }
