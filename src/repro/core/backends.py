"""String-keyed backend registry: how a :class:`~repro.core.program.Session`
turns an :class:`~repro.core.program.ExecutionConfig` into something that can
run loop chains.

A backend is any object with ``run_chain(loops) -> {reduction: value}``;
optional attributes the session surfaces when present: ``history`` (per-chain
:class:`~repro.core.executor.ChainStats`), ``cfg`` (for the cyclic flag), and
``plan_hits``/``plan_misses``/``plan_time_s`` (chain-plan cache counters).

Built-ins:

==============  ===============================================================
``reference``   eager NumPy oracle, program order, no tiling (tests)
``resident``    paper baseline: everything in fast memory, raises beyond it
``ooc``         3-slot out-of-core streaming executor (Algorithm 1)
``ooc-async``   ``ooc`` with the threaded transfer engine: staging on
                background workers overlapping compute (bit-identical output)
``ooc-cyclic``  ``ooc`` with the §4.1 unsafe-temporaries elision pre-enabled
``sim``         ``ooc`` without the data plane: the same Plan IR stream,
                interpreted by the ledger interpreter only (modelled runs)
``ooc-sharded`` device-mesh execution: the grid decomposed along
                ``shard_dim`` over ``config.mesh`` (``"sim:N"`` virtual or
                ``"jax:N"`` real devices), every shard running the full
                out-of-core machinery with one accumulated-depth halo
                exchange per chain (paper §5.2)
``pallas``      eager backend routing tagged star-sweep loops through the
                Pallas TPU kernels in :mod:`repro.kernels` (fast path), with
                the reference path for everything else
==============  ===============================================================

Any ``ooc``-family backend given a multi-device ``mesh=`` transparently
routes through the sharded executor — the mesh is an orthogonal axis of the
config, not a separate code path.

The ``ooc``-family backends (including ``sim`` and ``resident``'s inner
executor) all lower chains to the typed instruction stream of
:mod:`repro.core.plan` and execute it through the shared interpreters in
:mod:`repro.core.interp` — ``Session.plan()``/``explain()``/``tune()`` work
on any of them.

Register your own with::

    @register_backend("my-backend")
    def _build(config: ExecutionConfig):
        return MyExecutor(...)
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .loop import AccessMode, ParallelLoop
from .reference import (
    merge_loop_reductions,
    run_chain_reference,
    run_loop_reference,
)

_REGISTRY: Dict[str, Callable] = {}


def register_backend(name: str):
    """Decorator registering ``factory(config) -> backend`` under ``name``."""
    def deco(factory):
        _REGISTRY[name] = factory
        return factory
    return deco


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def make_backend(config):
    """Instantiate the backend ``config.backend`` names."""
    factory = _REGISTRY.get(config.backend)
    if factory is None:
        raise ValueError(
            f"unknown backend {config.backend!r}; "
            f"available: {', '.join(available_backends())}")
    return factory(config)


# -- built-in backends ------------------------------------------------------------


class ReferenceBackend:
    """Eager NumPy oracle (what :class:`ReferenceRuntime` used to be)."""

    def __init__(self):
        self.history: List = []

    def run_chain(self, loops: Sequence[ParallelLoop]):
        return run_chain_reference(loops)


class PallasBackend:
    """Eager backend with a Pallas fast path for tagged star-sweep loops.

    Loops whose kernel carries a ``pallas_op`` tag (built by
    :func:`repro.kernels.star2d_kernel` / ``star3d_kernel``) execute through
    the Pallas TPU kernels (``stencil2d``/``stencil3d``); untagged loops, and
    loops that declare gbls (the tag's coefficients are constants), fall
    back to the reference path, so arbitrary chains still run correctly.
    ``fallback_loops`` counts those.  ``interpret`` is passed to the kernels
    (None: compiled on a TPU, interpreted on the CPU).
    """

    def __init__(self, interpret: Optional[bool] = None):
        self.history: List = []
        self.interpret = interpret
        self.pallas_loops = 0
        self.fallback_loops = 0

    def run_chain(self, loops: Sequence[ParallelLoop]):
        merged: Dict[str, np.ndarray] = {}
        for lp in loops:
            op = getattr(lp.kernel, "pallas_op", None)
            if op is not None and not lp.gbls and self._try_pallas(lp, op):
                self.pallas_loops += 1
                continue
            self.fallback_loops += 1
            merge_loop_reductions(merged, lp, run_loop_reference(lp))
        return merged

    def _try_pallas(self, lp: ParallelLoop, op) -> bool:
        kind, src, dst, coeffs = op
        if lp.reductions or kind not in ("stencil2d", "stencil3d"):
            return False
        dats = {a.dat.name: a.dat for a in lp.args}
        if src not in dats or dst not in dats:
            return False
        src_dat, dst_dat = dats[src], dats[dst]
        # The fast path overwrites exactly dst from src: any other write arg,
        # an INC dst, or src==dst must take the general path.
        write_args = [a for a in lp.args if a.mode.writes]
        if (src == dst or len(write_args) != 1
                or write_args[0].dat.name != dst
                or write_args[0].mode is AccessMode.INC):
            return False
        box = lp.range_
        halo_box = tuple((a - 1, b + 1) for a, b in box)
        for d, (lo, hi) in enumerate(halo_box):
            blo, bhi = src_dat.bounds(d)
            if lo < blo or hi > bhi:
                return False
        from .. import kernels  # lazy: pulls in jax.experimental.pallas

        fn = kernels.stencil2d if kind == "stencil2d" else kernels.stencil3d
        padded = np.ascontiguousarray(src_dat.read(halo_box))
        out = fn(padded, np.asarray(coeffs, np.float32),
                 interpret=self.interpret)
        dst_dat.write(box, np.asarray(out, dtype=dst_dat.dtype))
        return True


@register_backend("reference")
def _reference(config):
    return ReferenceBackend()


@register_backend("pallas")
def _pallas(config):
    return PallasBackend()


@register_backend("resident")
def _resident(config):
    from .executor import ResidentExecutor

    return ResidentExecutor(hw=config.hw, capacity_bytes=config.capacity_bytes)


def _ooc_executor(config, shared_plans=None, **overrides):
    """The shared ooc-family builder: a plain executor, or — when the config
    carries a multi-device mesh — the sharded one wrapping a per-device
    executor per mesh entry.  ``shared_plans`` (a serving-layer
    :class:`~repro.serve.SharedPlanCache`) attaches a cross-executor plan
    cache to unsharded executors; sharded executors plan per-device and keep
    their caches private."""
    from .executor import OutOfCoreExecutor
    from .sharded import ShardedOutOfCoreExecutor

    ooc_cfg = config.ooc_config(**overrides)
    mesh = getattr(config, "mesh", None)
    if mesh is not None and mesh.num_devices > 1:
        return ShardedOutOfCoreExecutor(
            ooc_cfg, mesh=mesh, shard_dim=config.shard_dim,
            halo_depth=config.halo_depth)
    return OutOfCoreExecutor(ooc_cfg, shared_plans=shared_plans)


@register_backend("ooc")
def _ooc(config):
    return _ooc_executor(config)


@register_backend("ooc-cyclic")
def _ooc_cyclic(config):
    return _ooc_executor(config, cyclic=True)


@register_backend("ooc-async")
def _ooc_async(config):
    """``ooc`` with the threaded transfer engine pre-enabled: uploads and
    downloads stage on background workers and genuinely overlap compute.
    Bit-identical to ``ooc`` (tasks touch disjoint regions; functional
    updates commute) — threading changes wall-clock behaviour only."""
    return _ooc_executor(config, transfer="threaded")


@register_backend("sim")
def _sim(config):
    return _ooc_executor(config, simulate_only=True)


@register_backend("ooc-sharded")
def _ooc_sharded(config):
    """Device-mesh execution, explicitly: always the sharded executor, even
    on a 1-device mesh (where it is bit-identical to ``ooc`` and simply
    skips decomposition and exchange)."""
    from .mesh import DeviceMesh
    from .sharded import ShardedOutOfCoreExecutor

    mesh = getattr(config, "mesh", None) or DeviceMesh.sim(1)
    return ShardedOutOfCoreExecutor(
        config.ooc_config(), mesh=mesh, shard_dim=config.shard_dim,
        halo_depth=config.halo_depth)
