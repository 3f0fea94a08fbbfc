"""Compulsory bytes of a timestep: the least that any implementation must
move through HBM to take one step.

That is every field the step reads before it writes it (its input), counted
once, plus every field the step writes that the next step reads, counted
once.  Temporaries written and read inside the step are left out: a fused
implementation need not store them.  The count is taken from the loops a
step records (each argument's dataset name and whether it reads or writes),
so it is the same whichever backend the loops are recorded on.
"""
from __future__ import annotations

from math import prod
from typing import Iterable, List

import numpy as np


def live_in(loops: Iterable) -> List[str]:
    """Fields read before any loop of ``loops`` writes them, in first-read
    order.  A loop that reads and writes a field reads it first."""
    written, out = set(), []
    for lp in loops:
        for a in lp.args:
            name = a.dat.name
            if a.mode.reads and name not in written and name not in out:
                out.append(name)
        written.update(a.dat.name for a in lp.args if a.mode.writes)
    return out


def step_fields(loops: Iterable) -> List[str]:
    """The fields counted: the step's input, then those of them it writes
    (the next step of a cyclic chain reads the same input)."""
    loops = list(loops)
    inputs = live_in(loops)
    writes = {a.dat.name for lp in loops for a in lp.args if a.mode.writes}
    return inputs + [n for n in inputs if n in writes]


def field_bytes(grid, dtype) -> int:
    """Bytes of one field's interior at the configuration's size and type."""
    return prod(int(g) for g in grid) * np.dtype(dtype).itemsize


def step_bytes(loops: Iterable, grid, dtype) -> int:
    return len(step_fields(loops)) * field_bytes(grid, dtype)
