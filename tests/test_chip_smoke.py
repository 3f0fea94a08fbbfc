"""chip_smoke.py's phases at a tiny grid on the CPU: the CloverLeaf
ooc/resident/reference comparison, the Pallas phase in interpret mode, the
four-shard mesh phase on forced host devices (each shard on its own
device), and the refusal to run anywhere but on a TPU."""
import importlib.util
from pathlib import Path

import jax
import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as e:
        smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_cloverleaf_phase_tiny(smoke):
    log = smoke.CompileLog()
    out = smoke.phase_cloverleaf(n=32, steps=2, log=log)
    for run in ("ooc", "ooc (2nd, same config)", "resident"):
        assert out[run]["max_drho0"] < smoke.DENSITY_TOL
        assert len(out[run]["step_walls"]) == 2
        assert out[run]["compiles"] >= 0
    assert out["ooc"]["tiles"] > 1
    assert out["resident"]["tiles"] == 1


def test_pallas_phase_interpret(smoke):
    out = smoke.phase_pallas(n2=40, n3=12, interpret=True)
    assert out["2d"]["max_err"] < smoke.KERNEL_ATOL
    assert out["3d"]["max_err"] < smoke.KERNEL_ATOL


def test_mesh_phase_four_host_devices(smoke):
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 XLA devices (conftest forces 8)")
    out = smoke.phase_mesh(n=32, steps=2, chips=4)
    assert out["shard_devices"] == [d.id for d in jax.devices()[:4]]
    assert len(set(out["shard_devices"])) == 4
