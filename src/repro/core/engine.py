"""Tile execution engine: runs a chain's loops over slot-resident arrays.

Loops execute under one ``jax.jit`` per *tile signature* (the pattern of
active loops and their static box sizes).  Interior tiles share a signature,
so a chain compiles O(3) times regardless of tile count: tiled-dim starts and
slot origins enter as traced int32 scalars and all slices are
``lax.dynamic_slice`` / ``lax.dynamic_update_slice``.  A loop's declared
gbls enter beside its start, as traced 0-d arrays, so a new value
(CloverLeaf's dt every step) reuses the compiled program; a loop without
gbls adds no argument, so its programs are those of a chain without them.

This is the moral equivalent of Algorithm 1 line 8 ("adjust base pointers of
datasets for virtual position"): the kernel addresses global grid
coordinates; the engine rebases them into slot-local offsets.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .dependency import ChainInfo
from .loop import AccessMode, Accessor, ParallelLoop, read_gbl
from .tiling import TilePlan, TileSchedule
from ..obs.tracer import AnyTracer, NULL_TRACER


class _SliceAccessor(Accessor):
    """Accessor over slot arrays for one loop's iteration box."""

    def __init__(self, loop, box_sizes, td, start_td, origins, slots, halos,
                 gbls):
        self._loop = loop
        self._sizes = box_sizes
        self.shape = tuple(box_sizes)
        self._td = td
        self._start_td = start_td          # traced: box start in grid coords
        self._origins = origins            # traced: per-dat slot origin
        self._slots = slots
        self._halos = halos                # per-dat halo_lo tuple
        self._gbls = gbls                  # traced: the loop's gbls
        self._args = {a.dat.name: a for a in loop.args}

    def gbl(self, name: str):
        return read_gbl(self._gbls, name, self._loop.name)

    def coords(self):
        """Global grid coordinates over the box, broadcast to full box shape."""
        lp = self._loop
        nd = lp.block.ndim
        out = []
        for d in range(nd):
            start = self._start_td if d == self._td else lp.range_[d][0]
            ar = start + jnp.arange(self._sizes[d], dtype=jnp.int32)
            shape = [1] * nd
            shape[d] = self._sizes[d]
            out.append(jnp.broadcast_to(ar.reshape(shape), self.shape))
        return tuple(out)

    def __call__(self, name: str, offset: Tuple[int, ...] = None):
        lp = self._loop
        nd = lp.block.ndim
        if offset is None:
            offset = (0,) * nd
        arr = self._slots[name]
        halo_lo = self._halos[name]
        idx = []
        for d in range(nd):
            if d == self._td:
                idx.append(self._start_td + offset[d] - self._origins[name])
            else:
                idx.append(lp.range_[d][0] + offset[d] + halo_lo[d])
        return lax.dynamic_slice(arr, tuple(idx), self._sizes)


class TileEngine:
    """Compiles & caches tile functions for one chain."""

    def __init__(self, chain: ChainInfo):
        self.chain = chain
        self.td = chain.tiled_dim
        self.halos = {
            name: tuple(h[0] for h in dat.halo) for name, dat in chain.datasets.items()
        }
        self._cache: Dict[Tuple, callable] = {}

    # -- signature ----------------------------------------------------------
    def _signature(self, tile: TilePlan) -> Tuple:
        sig = []
        for box in tile.loop_ranges:
            if box is None:
                sig.append(None)
            else:
                sig.append(tuple(b - a for a, b in box))
        return tuple(sig)

    # -- tile function construction ------------------------------------------
    def _build(self, sig: Tuple):
        chain, td, halos = self.chain, self.td, self.halos

        def tile_fn(slots, loop_args, origins):
            reds = {}
            slots = dict(slots)
            for k, lp in enumerate(chain.loops):
                sizes = sig[k]
                if sizes is None:
                    continue
                start, gbls = loop_args[k]
                acc = _SliceAccessor(lp, sizes, td, start, origins, slots,
                                     halos, gbls)
                out = lp.kernel(acc)
                if not isinstance(out, dict):
                    raise TypeError(f"kernel of {lp.name!r} must return a dict")
                for arg in lp.args:
                    if not arg.mode.writes:
                        continue
                    name = arg.dat.name
                    if name not in out:
                        raise KeyError(f"kernel of {lp.name!r} did not produce {name!r}")
                    vals = jnp.asarray(out[name], dtype=arg.dat.dtype)
                    if vals.shape != sizes:
                        raise ValueError(
                            f"kernel of {lp.name!r}: {name!r} shape {vals.shape} "
                            f"!= box {sizes}"
                        )
                    idx = []
                    for d in range(lp.block.ndim):
                        if d == td:
                            idx.append(start - origins[name])
                        else:
                            idx.append(lp.range_[d][0] + halos[name][d])
                    if arg.mode is AccessMode.INC:
                        cur = lax.dynamic_slice(slots[name], tuple(idx), sizes)
                        vals = cur + vals
                    slots[name] = lax.dynamic_update_slice(slots[name], vals, tuple(idx))
                for rspec in lp.reductions:
                    if rspec.name not in out:
                        raise KeyError(
                            f"kernel of {lp.name!r} did not produce reduction "
                            f"{rspec.name!r}"
                        )
                    contrib = out[rspec.name]
                    if rspec.name in reds:
                        reds[rspec.name] = rspec.combine(reds[rspec.name], contrib)
                    else:
                        reds[rspec.name] = contrib
            return slots, reds

        return jax.jit(tile_fn)

    def program(self, tile: TilePlan):
        """The jitted tile function for ``tile``'s signature (built on first
        use, then cached), called as ``fn(slots, loop_args, origins)``:
        ``loop_args[k]`` is active loop ``k``'s ``(start, gbls)``, its box's
        start along the tiled dim and its gbl values."""
        sig = self._signature(tile)
        fn = self._cache.get(sig)
        if fn is None:
            fn = self._build(sig)
            self._cache[sig] = fn
        return fn

    @staticmethod
    def tile_gbls(tile: TilePlan, loop_gbls: Sequence[Mapping[str, Any]]
                  ) -> Dict[int, Mapping[str, Any]]:
        """The gbl values of ``tile``'s active loops, by loop index, from
        ``loop_gbls`` (one mapping per loop of the chain being run).  Loops
        that declare none are left out, so a chain without gbls passes an
        empty dict and its programs are those of a chain without them."""
        return {k: loop_gbls[k] for k, box in enumerate(tile.loop_ranges)
                if box is not None and loop_gbls[k]}

    def run_tile(
        self,
        tile: TilePlan,
        slots: Dict[str, jax.Array],
        origins: Dict[str, int],
        gbls: Dict[int, Mapping[str, Any]],
        *,
        tracer: AnyTracer = NULL_TRACER,
        track: str = "compute",
        args: Optional[Dict] = None,
    ) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
        """Launch ``tile``'s program.  ``gbls`` are the gbl values of the
        tile's active loops (:meth:`tile_gbls`), taken from the chain being
        run: a cached plan's ``chain.loops`` belong to the chain that first
        built it.  Traced, a signature seen for the first time is lowered
        and compiled inside a ``tile_compile`` span (``args`` plus ``sig``,
        the signature's index in this engine) before the call, which then
        finds it compiled."""
        n_programs = len(self._cache)
        fn = self.program(tile)
        loop_args = {
            k: (jnp.int32(box[self.td][0]), gbls.get(k, {}))
            for k, box in enumerate(tile.loop_ranges)
            if box is not None
        }
        origins_t = {name: jnp.int32(v) for name, v in origins.items()}
        if tracer.enabled and len(self._cache) > n_programs:
            with tracer.span("tile_compile", cat="compile", track=track,
                             args={**(args or {}), "sig": n_programs}):
                fn.lower(slots, loop_args, origins_t).compile()
        return fn(slots, loop_args, origins_t)
