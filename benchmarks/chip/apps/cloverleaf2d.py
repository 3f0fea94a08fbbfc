"""CloverLeaf 2D driven through ``repro.core.Session`` as
``CloverLeaf2D.run`` drives it: per timestep the ``calc_dt`` chain, a host
read of its reduction (which flushes the previous timestep's loops with it),
then the next timestep's loops; the field summary closes the last chain."""
from __future__ import annotations

import numpy as np

from .. import seeded
from ..xtrace import annotate

NOISE_FIELD = "post_ener"   # no CloverLeaf 2D loop reads or writes it
DT_CAP = 1e-4               # CloverLeaf2D.run's cap on the CFL time step


def _perturb(acc):
    n = NOISE_FIELD
    return seeded.clover_perturb(
        acc(n), acc(n, (1, 0)), acc(n, (0, 1)), acc(n, (1, 1)),
        acc("density0"), acc("energy0"), acc("xvel0"), acc("yvel0"))


class Driver:
    def __init__(self, cfg: dict, mix: dict):
        from repro.apps import CloverLeaf2D

        nx, ny = cfg["grid"]
        self.app = CloverLeaf2D(nx, ny, dtype=np.dtype(cfg["dtype"]),
                                summary_every=0)
        self.dt_every = int(mix.get("dt_read_every", 1))
        self.summary_every = int(mix.get("summary_every", 0))
        self.steps = 0
        self.reductions = {}

    def total_bytes(self) -> int:
        return self.app.total_bytes()

    def init(self, sess, seed: int, cyclic: bool) -> None:
        app = self.app
        app.record_init(sess)
        sess.flush()
        nz = app.d(NOISE_FIELD)
        nz.write_region((slice(None),) * 2,
                        np.asarray(seeded.noise(seed, nz.padded_shape)))
        sess.par_loop("bench_perturb", app.block, app._interior(),
                      [nz, app.d("density0"), app.d("energy0"),
                       app.d("xvel0"), app.d("yvel0")], _perturb)
        sess.flush()
        sess.cyclic = cyclic

    def record_step(self, sess) -> None:
        """One step's loops, recorded and not run (the compulsory-bytes
        count reads them)."""
        app = self.app
        app._ideal_gas(sess, "density0", "energy0", "_dt")
        app._viscosity(sess)
        app._calc_dt(sess)
        app.record_timestep(sess)

    def step(self, sess) -> bool:
        """Record one timestep; True where it ended in a host read."""
        app, k = self.app, self.steps
        app._ideal_gas(sess, "density0", "energy0", "_dt")
        app._viscosity(sess)
        app._calc_dt(sess)
        synced = k % self.dt_every == 0
        if synced:
            with annotate("calc_dt_read"):
                dt = sess.reduction("dt")
            self.reductions[f"dt.{k}"] = float(dt)
            app.dt = float(min(DT_CAP, dt))
        with annotate("record"):
            app.record_timestep(sess)
        if self.summary_every and (k + 1) % self.summary_every == 0:
            with annotate("summary"):
                for name in app.record_summary(sess):
                    sess.reduction(name)
        self.steps += 1
        return synced

    def finish(self, sess) -> dict:
        """Run what is queued, with the field summary in the same chain."""
        for name in self.app.record_summary(sess):
            self.reductions[name] = float(sess.reduction(name))
        return self.reductions

    def fields(self, names) -> dict:
        return {n: self.app.d(n).interior() for n in names}
