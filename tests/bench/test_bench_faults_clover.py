"""The planted faults of ``test_bench_control.py`` on the CloverLeaf
out-of-core cell, whose tiny run compiles the most (a file of its own, so
the test workers share the time)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from test_bench_control import FAULTS, run_with_fault  # noqa: E402


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_fault_is_not_correct_clover_ooc(monkeypatch, fault):
    line = run_with_fault(monkeypatch, "clover2d-bm16-ooc3x", fault)
    assert line["correct"] is False and line["failed"] == line["attempted"]
