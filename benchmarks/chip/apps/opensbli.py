"""OpenSBLI's Taylor-Green vortex driven through ``repro.core.Session`` as
``OpenSBLI.run`` drives it: timesteps recorded back to back, a flush every
``chain_steps`` of them, and the TGV summary after the last."""
from __future__ import annotations

import numpy as np

from .. import seeded
from ..xtrace import annotate

# A residual array: every timestep writes it before reading it, so the noise
# it holds before the first step feeds only the perturbation loop.
NOISE_FIELD = "rho_r"


def _perturb(acc):
    n = NOISE_FIELD
    return seeded.sbli_perturb(
        acc(n), acc(n, (1, 0, 0)), acc(n, (0, 1, 0)), acc(n, (0, 0, 1)),
        acc("rho"), acc("rhou"), acc("rhov"), acc("rhow"))


class Driver:
    def __init__(self, cfg: dict, mix: dict):
        from repro.apps import OpenSBLI

        n, _, _ = cfg["grid"]
        self.app = OpenSBLI(n, dtype=np.dtype(cfg["dtype"]),
                            chain_steps=int(mix.get("chain_steps", 1)))
        self.steps = 0
        self.reductions = {}

    def total_bytes(self) -> int:
        return self.app.total_bytes()

    def init(self, sess, seed: int, cyclic: bool) -> None:
        app = self.app
        app.record_init(sess)
        sess.flush()
        nz = app.d(NOISE_FIELD)
        nz.write_region((slice(None),) * 3,
                        np.asarray(seeded.noise(seed, nz.padded_shape)))
        full = ((0, app.n),) * 3
        sess.par_loop("bench_perturb", app.block, full,
                      [nz] + [app.d(c) for c in ("rho", "rhou", "rhov", "rhow")],
                      _perturb)
        sess.flush()
        sess.cyclic = cyclic

    def record_step(self, sess) -> None:
        """One timestep's loops, recorded and not run."""
        self.app.record_timestep(sess)

    def step(self, sess) -> bool:
        """Record one timestep; True where it ended in a flush."""
        with annotate("record"):
            self.app.record_timestep(sess)
        self.steps += 1
        synced = self.steps % self.app.chain_steps == 0
        if synced:
            with annotate("flush"):
                sess.flush()
        return synced

    def finish(self, sess) -> dict:
        sess.flush()
        for name in self.app.record_summary(sess):
            self.reductions[name] = float(sess.reduction(name))
        return self.reductions

    def fields(self, names) -> dict:
        return {n: self.app.d(n).interior() for n in names}
