"""Send/Recv (paper §6.1): inter-node data movement as jax collectives.

The paper's Send operator 'segments data such that all alike values are
sent to the same node, so each node computes full results independently' --
that is exactly an all_to_all resegmentation under shard_map. Broadcast
(replicating a small build side) is an all_gather. The optimizer picks
between co-located (no exchange), resegment, and broadcast (planner/cost).

These run on whatever mesh the caller provides -- tests use an 8-device CPU
mesh; the training stack reuses the same pattern for MoE expert dispatch
(models/moe.py 'a2a' mode).
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _slots(n_shards: int, per: int, dest_l: jax.Array):
    """Slot of each local row within its destination bucket, whether it
    fits (``slot < per``), and the rows per destination that did not.

    One running count per destination over flat (n_local,) vectors -- an
    (n_local, n_shards) one-hot would pad its minor dim to 128 lanes on
    TPU.  Overflowing rows get the scratch slot ``per``, sliced off by
    :func:`_move` -- writing them to per-1 would clobber the legitimate
    last slot and silently drop one MORE tuple than reported."""
    pos = jnp.zeros(dest_l.shape, jnp.int32)
    for d in range(n_shards):
        hit = (dest_l == d).astype(jnp.int32)
        pos = jnp.where(dest_l == d, jnp.cumsum(hit) - hit, pos)
    keep = pos < per
    dropped = jnp.stack([((dest_l == d) & ~keep).sum(dtype=jnp.int32)
                         for d in range(n_shards)])
    return jnp.where(keep, pos, per), keep, dropped


def _move(axis: str, n_shards: int, per: int, dest_l: jax.Array,
          slot: jax.Array, keep: jax.Array, v: jax.Array) -> jax.Array:
    """One column to its destination slots, then across the mesh."""
    buf = jnp.zeros((n_shards, per + 1), v.dtype)
    buf = buf.at[dest_l, slot].set(jnp.where(keep, v, 0))[:, :per]
    return jax.lax.all_to_all(buf, axis, 0, 0, tiled=False).reshape(-1)


def resegment_local(axis: str, n_shards: int, per: int, dest_l: jax.Array,
                    vals: Tuple[jax.Array, ...]
                    ) -> Tuple[Tuple[jax.Array, ...], jax.Array, jax.Array]:
    """Per-shard body of :func:`resegment`, callable from INSIDE another
    ``shard_map``'d program (the segmented executor fuses this with the
    join + pre-aggregation stage so a multi-join query dispatches one
    program per stage instead of blocking on a host get between the
    exchange and the join).  ``dest_l`` is the (n_local,) destination
    shard per local row; returns (moved value tuple, valid, overflow),
    each moved value flat with ``n_shards * per`` slots."""
    slot, keep, dropped = _slots(n_shards, per, dest_l)
    # global per-destination overflow is the psum of what each source
    # wanted to send but could not fit
    overflow = jax.lax.psum(dropped, axis)
    outs = tuple(_move(axis, n_shards, per, dest_l, slot, keep, v)
                 for v in vals)
    vr = _move(axis, n_shards, per, dest_l, slot, keep, keep)
    return outs, vr, overflow


@lru_cache(maxsize=32)
def _resegment_programs(mesh: Mesh, axis: str, per: int):
    """(slots, move) programs for :func:`resegment`.  The move program is
    per column, so its compiled executable is shared by every column and
    every call of one dtype and row count -- at tens of millions of rows
    each distinct program costs tens of seconds to compile for TPU."""
    n_shards = mesh.shape[axis]

    def slots(dest_l):
        slot, keep, dropped = _slots(n_shards, per, dest_l)
        return slot, keep, jax.lax.psum(dropped, axis)

    slots_fn = jax.jit(shard_map(slots, mesh=mesh, in_specs=P(axis),
                                 out_specs=(P(axis), P(axis), P())))
    move_fn = jax.jit(shard_map(partial(_move, axis, n_shards, per),
                                mesh=mesh, in_specs=(P(axis),) * 4,
                                out_specs=P(axis)))
    return slots_fn, move_fn


def resegment(mesh: Mesh, axis: str, cols: Dict[str, jax.Array],
              dest: jax.Array, capacity: int
              ) -> Tuple[Dict[str, jax.Array], jax.Array, jax.Array]:
    """Move each row to the shard ``dest[i]`` (hash-segmentation target).

    Returns (columns, valid, overflow) with per-shard static capacity.
    ``overflow`` is an (n_shards,) int32 count of tuples destined to each
    shard that did NOT fit in ``capacity // n_shards`` slots and were
    dropped -- callers MUST check it (``overflow.sum() == 0``) and either
    retry with a larger capacity or fail loudly; silent truncation is a
    wrong answer, not a slow one.  One all_to_all per column -- each tuple
    crosses the wire exactly once."""
    slots_fn, move_fn = _resegment_programs(
        mesh, axis, capacity // mesh.shape[axis])
    slot, keep, overflow = slots_fn(dest)
    out = {c: move_fn(dest, slot, keep, v) for c, v in cols.items()}
    # validity rides as bytes, sharing the int8 program with byte columns
    valid = move_fn(dest, slot, keep, keep.astype(jnp.int8)) != 0
    return out, valid, overflow


def broadcast_build_side(mesh: Mesh, axis: str,
                         cols: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Replicate a (small) build side to every shard: all_gather."""
    def local(*vals):
        return tuple(jax.lax.all_gather(v, axis, tiled=True) for v in vals)

    names = list(cols)
    # check_vma=False: all_gather(tiled) output IS replicated, but the
    # out_specs of P() assert it without the checker's proof
    fn = shard_map(local, mesh=mesh,
                   in_specs=(P(axis),) * len(names),
                   out_specs=(P(),) * len(names), check_vma=False)
    return dict(zip(names, fn(*[cols[c] for c in names])))
