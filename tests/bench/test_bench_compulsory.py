"""The benchmark's compulsory-bytes count (the numerator of
``tile_fn_roofline``) against hand counts, and the same whichever backend
records the loops."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import compulsory, harness  # noqa: E402
from repro.apps import CloverLeaf2D, OpenSBLI  # noqa: E402
from repro.core import Block, Session, make_dataset  # noqa: E402
from repro.core.dependency import chain_live_set  # noqa: E402


def _toy_chain(sess):
    """Two loops: b = a(-1) + a(+1); c = c + b.  Input: a and c (c read
    before written); c is also written, so the next step reads it again;
    b is a temporary."""
    blk = Block("toy", (10, 6))
    a = make_dataset(blk, "a", halo=1, init=np.ones((10, 6)))
    b = make_dataset(blk, "b", halo=1)
    c = make_dataset(blk, "c", halo=1)
    rng = ((1, 9), (0, 6))
    sess.par_loop("spread", blk, rng, [a, b],
                  lambda acc: {"b": acc("a", (-1, 0)) + acc("a", (1, 0))})
    sess.par_loop("accum", blk, rng, [b, c],
                  lambda acc: {"c": acc("c") + acc("b")})
    loops = list(sess.queue)
    sess.queue.clear()
    return loops


@pytest.mark.parametrize("backend", ["ooc", "resident", "reference"])
def test_toy_chain_hand_count(backend):
    loops = _toy_chain(Session(backend))
    assert compulsory.live_in(loops) == ["a", "c"]
    assert compulsory.step_fields(loops) == ["a", "c", "c"]
    # 3 fields x 10 x 6 interior cells x 4 bytes (float32)
    assert compulsory.step_bytes(loops, (10, 6), "float32") == 3 * 60 * 4


def test_cloverleaf_and_opensbli_steps():
    sess = Session("reference")
    app = CloverLeaf2D(16, 16)
    app._ideal_gas(sess, "density0", "energy0", "_dt")
    app._viscosity(sess)
    app._calc_dt(sess)
    app.record_timestep(sess)
    # in: density0 energy0 xvel0 yvel0 xarea yarea volume; out: the first 4
    assert len(compulsory.step_fields(sess.queue)) == 11
    assert set(compulsory.live_in(sess.queue)) == set(chain_live_set(sess.queue))
    sess.queue.clear()
    OpenSBLI(16).record_timestep(sess)
    # in: 5 conserved, 5 RK registers (read, times 0, at stage 0), mu, kappa;
    # out: the 10 written of them
    assert len(compulsory.step_fields(sess.queue)) == 22


@pytest.mark.parametrize("name", ["clover2d-bm16-ooc3x", "opensbli-tgv256-ooc3x"])
def test_harness_count_at_full_size(name):
    cell = harness.load_cell(name)
    cfg = cell["config"]
    per_field = compulsory.field_bytes(cfg["grid"], cfg["dtype"])
    n = {"cloverleaf2d": 11, "opensbli": 22}[cfg["app"]]
    assert harness.compulsory_bytes(cfg, cell["mix"]) == n * per_field


@pytest.mark.parametrize("name", ["clover2d-bm16-ooc3x", "opensbli-tgv256-ooc3x"])
def test_compared_fields_are_carried(name):
    """Under cyclic execution only the fields a step reads first keep a
    defined home copy; the comparison must stay within them."""
    cfg = harness.load_cell(name)["config"]
    small = dict(cfg, grid=[16] * len(cfg["grid"]))
    sess = Session("reference")
    harness.driver_module(cfg).Driver(small, {}).record_step(sess)
    assert set(cfg["compare"]) <= chain_live_set(sess.queue)
