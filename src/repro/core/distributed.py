"""Distributed (multi-device) stencil execution: halo exchange per chain.

The paper (§5.2) notes tiling's second benefit: instead of exchanging halos
per-loop, OPS computes the accumulated halo depth of the whole loop chain and
exchanges once per chain — fewer, larger messages.  This module implements
both policies on a device mesh with ``shard_map`` + ``collective_permute``
so the trade-off is measurable and the schedule is visible in dry-run HLO.

Grids are decomposed along one axis (default: the *non*-tiled dim 1, so
out-of-core slab tiling along dim 0 composes with MPI-style decomposition
along dim 1, mirroring the paper's 4-process KNL runs).

The chain's accumulated halo depth for left-to-right execution is
``n_loops × σ`` per neighbour side (σ = max stencil extent): loop k may read
σ cells beyond what loop k-1 wrote, so a chain of n loops consumes up to n·σ
remote cells before requiring fresh data.  After the exchange, every rank
runs the whole chain redundantly on its extended region (halo-deep compute),
which is exactly the "compute tiles that do not depend on halo data first"
follow-up the paper sketches in its conclusion, minus the overlap.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .dependency import analyze_chain
from .loop import ParallelLoop


@dataclass
class HaloExchangeStats:
    messages: int = 0
    bytes: int = 0


def exchange_halos(arrays: Dict[str, jax.Array], depth: int, axis_name: str,
                   dim: int = 1, periodic: bool = False) -> Dict[str, jax.Array]:
    """One bidirectional halo exchange of ``depth`` cells along ``dim``.

    ``arrays`` are the per-device local shards *including* halo padding of at
    least ``depth`` on each side of ``dim``.  Neighbour interiors are pushed
    into our halo slots with two ``ppermute`` rings (up and down).

    Boundary semantics: by default the grid is NOT periodic — the edge ranks
    (first and last along the mesh axis) keep their outer halo slots
    *unchanged*, so whatever physical boundary data the caller placed there
    (mirrored cells, global-halo rows) survives the exchange.  The previous
    behaviour wrapped the ``ppermute`` ring around, silently handing edge
    ranks the opposite edge's interior even for non-periodic grids; pass
    ``periodic=True`` to request that wrap explicitly.

    Depth 0 is a fast path: a chain with no reads along ``dim`` (pointwise
    chains, sweeps along other axes) needs no neighbour data at all, so the
    collectives are skipped entirely — no ``ppermute``, no axis context
    required.
    """
    if depth <= 0:
        return dict(arrays)
    n = lax.axis_size(axis_name)
    if periodic:
        fwd = [(i, (i + 1) % n) for i in range(n)]
        bwd = [(i, (i - 1) % n) for i in range(n)]
    else:
        # Open chain: the wrap pairs are dropped, so the edge ranks receive
        # zeros from ppermute — masked back to their original halo below.
        fwd = [(i, i + 1) for i in range(n - 1)]
        bwd = [(i, i - 1) for i in range(1, n)]
    rank = lax.axis_index(axis_name)
    out = {}
    for name, arr in arrays.items():
        size = arr.shape[dim]

        def take(lo, hi):
            sl = [slice(None)] * arr.ndim
            sl[dim] = slice(lo, hi)
            return arr[tuple(sl)]

        # our top interior -> neighbour's bottom halo, and vice versa
        send_up = take(size - 2 * depth, size - depth)
        send_dn = take(depth, 2 * depth)
        recv_dn = lax.ppermute(send_up, axis_name, fwd)   # from rank-1
        recv_up = lax.ppermute(send_dn, axis_name, bwd)   # from rank+1
        lo_sl = [slice(None)] * arr.ndim
        lo_sl[dim] = slice(0, depth)
        hi_sl = [slice(None)] * arr.ndim
        hi_sl[dim] = slice(size - depth, size)
        if not periodic:
            # Edge ranks: no neighbour on that side — keep the existing halo.
            recv_dn = jnp.where(rank == 0, arr[tuple(lo_sl)], recv_dn)
            recv_up = jnp.where(rank == n - 1, arr[tuple(hi_sl)], recv_up)
        arr = arr.at[tuple(lo_sl)].set(recv_dn)
        arr = arr.at[tuple(hi_sl)].set(recv_up)
        out[name] = arr
    return out


def exchange_message_count(n_ranks: int, n_arrays: int = 1,
                           periodic: bool = False) -> int:
    """Messages one halo exchange sends: 2 directions per neighbour pair per
    array — ``2·n`` pairs on a periodic ring, ``2·(n-1)`` on an open chain."""
    if n_ranks <= 1:
        return 0
    pairs = n_ranks if periodic else n_ranks - 1
    return 2 * pairs * n_arrays


def chain_message_count(n_ranks: int, n_arrays: int, n_loops: int = 1,
                        per_loop: bool = False, periodic: bool = False) -> int:
    """Total messages a chain moves under either exchange policy: the tiled
    policy exchanges once per chain (deep); the untiled policy exchanges
    before every loop (``n_loops`` shallow exchanges) — the §5.2 trade-off."""
    exchanges = n_loops if per_loop else 1
    return exchanges * exchange_message_count(n_ranks, n_arrays, periodic)


def chain_halo_depth(loops: Sequence[ParallelLoop], dim: int = 1) -> int:
    """Accumulated halo depth a whole chain needs along ``dim``."""
    sigma = 0
    for lp in loops:
        for arg in lp.args:
            if arg.mode.reads:
                sigma = max(sigma, arg.stencil.max_abs_extent(dim))
    return sigma * len(loops)


def make_sharded_chain_step(
    chain_fn: Callable[[Dict[str, jax.Array]], Dict[str, jax.Array]],
    mesh: Mesh,
    axis_name: str,
    depth: int,
    per_loop: bool = False,
    loop_fns: Sequence[Callable] = (),
    per_loop_depth: int = 1,
    dim: int = 1,
    periodic: bool = False,
):
    """Build a jitted sharded step: halo exchange(s) + local chain execution.

    ``per_loop=False`` (tiled policy): ONE deep exchange then the whole chain
    locally (each rank computes a ``depth``-wide skirt redundantly).
    ``per_loop=True`` (untiled policy): exchange before every loop —
    ``len(loop_fns)`` shallow messages, no redundant compute.

    Migration note: this low-level builder is superseded by the
    ``ooc-sharded`` backend (``Session("ooc-sharded", mesh="sim:4")`` /
    ``mesh="jax:4"``), which runs the same one-exchange-per-chain policy
    *composed with* out-of-core tiling, with halo ops in the Plan IR and
    modelled per-device makespans.  It remains for raw jitted-step use.

    The returned function carries message accounting for the §5.2 policy
    trade-off: ``fn.exchanges`` (exchange events per step) and
    ``fn.messages_per_array`` (ppermute messages per step per array).
    """
    n_ranks = int(mesh.shape[axis_name])

    def local(arrays: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        if per_loop:
            for fn in loop_fns:
                arrays = exchange_halos(arrays, per_loop_depth, axis_name,
                                        dim, periodic)
                arrays = fn(arrays)
            return arrays
        arrays = exchange_halos(arrays, depth, axis_name, dim, periodic)
        return chain_fn(arrays)

    spec = P(*[None if d != dim else axis_name for d in range(2)])
    # A single PartitionSpec broadcasts over the dict-of-arrays pytree.
    shard_fn = jax.shard_map(
        local, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )
    jitted = jax.jit(shard_fn)

    # Thin wrapper: jitted callables reject attribute assignment on some JAX
    # versions, and the accounting must ride along with the step.
    def step(arrays: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
        return jitted(arrays)

    step.exchanges = len(loop_fns) if per_loop else 1
    step.messages_per_array = chain_message_count(
        n_ranks, 1, n_loops=len(loop_fns), per_loop=per_loop,
        periodic=periodic)
    return step
