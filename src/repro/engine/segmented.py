"""Segmented multi-node query execution on a jax device mesh.

This is the scale-out half of the paper made real in the engine: a
projection's ``SegmentationSpec`` (§3.6) decides which *device shard* owns
each tuple, Send/Recv (§6.1) runs as ``exchange.resegment`` /
``exchange.broadcast_build_side`` collectives, and buddy projections
(§5.2) keep every segment scannable when a node is down -- the planner's
``plan.sources`` routing already walks buddies, so ``fail_node()``
failover is transparent here too.

Execution shape (one query):

  0. **RLE-direct routes**: a count-only GroupBy on the RLE-encoded sort
     leader (or a scalar COUNT with a sort-leader range predicate)
     aggregates straight off each node's encoded runs -- per-node
     metadata work with a trivial host merge (§6.1 "operate on encoded
     data"); no slab, no collective.
  1. **Device slab build** (cold only, cached ``KIND_SEG``): the decoded
     device blocks of every source container are concatenated on device
     (``executor.snapshot_scan_device``), ring-hashed with the device
     twins ``hash_columns_jnp`` / ``shard_of_jnp``, moved to their owning
     shard by one ``exchange.resegment`` all_to_all sized from an exact
     on-device destination histogram, then compacted (valid rows first)
     and annotated with per-512-row-block min/max/count SMAs -- the
     columns never round-trip through the host.  Trickle-loaded WOS rows
     live in separate per-store device buffers (``KIND_WOS``) built at
     commit time (``prewarm_wos_buffer``) and keyed by ``WOS.version``;
     a query only uploads the per-row visibility mask for its epoch and
     appends them shard-locally.
  2. **Slab-block pruning** (per query, device gather): predicate bounds
     against the slab's block SMAs select the surviving 512-row blocks;
     each shard gathers just those blocks into a power-of-two-sized view.
     Conservative and exact: pruned rows cannot pass the predicate, and
     inner joins only ever drop rows.
  3. **Fused stage programs** (one shard_map'd jitted executable per
     resegment stage): ``exchange.resegment_local`` (Send/Recv) fused
     with the stage's hash joins, and -- in the final stage -- derived
     exprs, the deferred predicate, mixed-radix key packing and the
     shard-local pre-aggregation (``kernels.seg_preagg``: Pallas scatter
     on TPU, XLA scatter elsewhere; sort-based partials past the dense
     limit).  Exchange overflow reports are collected and checked once
     after the final dispatch, so no host sync splits a stage chain.
  4. **Final merge** (host, small): partial counts/sums add, min/max
     combine, avg = merged sum / merged count; packed keys unpack.

The plan-cache signature includes the mesh identity, the projection's
segmentation, the per-join exchange ops and the pack radices -- two mesh
shapes (or a re-segmented projection) can never share an executable.
Static exchange capacities are memoized INSIDE each cached entry (a
factory keyed by the per-stage slot counts), so data growth retraces
without invalidating the plan.

Falls back to the single-node pipeline (returns None) for shapes outside
the segmented subset: plain selects, non-inner joins, derived group keys,
group domains past the device integer width, or an empty snapshot.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.block_cache import KIND_SEG, KIND_WOS
from ..core.database import VerticaDB
from ..core.faults import fire_with_retries
from ..core.segmentation import (hash_columns, hash_columns_jnp, shard_of,
                                 shard_of_jnp)
from ..kernels.seg_preagg import interpreted as preagg_interpreted
from ..kernels.seg_preagg import seg_preagg
from ..planner import cost as cost_mod
from . import exchange
from . import executor as fused_exec
from . import operators as ops
from .executor import PLAN_CACHE
from .logical import LogicalQuery

_PACK_LIMIT = 1 << 31         # packed keys live in device int32
_PAD_MULTIPLE = 8
_SLAB_BLOCK = 512             # rows per slab SMA block (pruning granule)


def _round_up(n: int, m: int = _PAD_MULTIPLE) -> int:
    return -(-max(int(n), 1) // m) * m


def _pow2_at_least(n: int) -> int:
    k = 1
    while k < n:
        k <<= 1
    return k


# ---------------------------------------------------------------------------
# 1. Partitioned scan slabs (device-built, cached)
# ---------------------------------------------------------------------------

def _canon_np(v: np.ndarray) -> np.ndarray:
    """Match the single-node path's device canonicalization (jax default
    32-bit runtime) so both execution models aggregate identical dtypes."""
    if jax.config.jax_enable_x64:
        return v
    if v.dtype.kind in "iu" and v.dtype.itemsize > 4:
        return v.astype(np.int32)
    if v.dtype.kind == "f" and v.dtype.itemsize > 4:
        return v.astype(np.float32)
    return v


def _source_sig(db: VerticaDB, plan, need, reseg_keys, eff: int,
                mesh, axis: str) -> tuple:
    """Identity of a cached ROS slab: *effective* snapshot epoch (the
    query's as-of clamped to the sources' ROS epoch ceiling -- trickle
    commits that only touched the WOS advance the cluster epoch without
    changing ROS visibility, so warm slabs survive them), mesh identity,
    needed columns, resegment keys, and the exact physical container set
    (the tuple mover retires containers by replacing ids, so a mergeout
    or moveout naturally misses -- and ``ProjectionStore.
    invalidate_seg_slabs`` evicts precisely those entries)."""
    items = []
    for host, owner in plan.sources:
        store = db.nodes[host].stores[owner]
        items.append((host, owner,
                      tuple(c.id for c in store.containers)))
    return (tuple(items), tuple(need), tuple(reseg_keys), int(eff),
            _mesh_sig(mesh, axis))


def _slab_positions(shard: np.ndarray, n_shards: int):
    """Stable within-shard slot assignment shared by row and build-side
    packing: returns (order, sorted_shard, pos, counts) such that source
    row ``order[i]`` belongs in slab slot ``[sorted_shard[i], pos[i]]``."""
    counts = np.bincount(shard, minlength=n_shards)
    order = np.argsort(shard, kind="stable")
    ss = shard[order]
    starts = np.zeros(n_shards, np.int64)
    starts[1:] = np.cumsum(counts)[:-1]
    pos = np.arange(len(shard)) - starts[ss]
    return order, ss, pos, counts


# own-shard index columns for exchange pad slots, cached per (mesh, width)
# so warm resegment queries skip the host build + upload
_SHARD_IDX_CACHE: Dict[tuple, jax.Array] = {}
_SHARD_IDX_CAP = 64


def _shard_index_col(mesh, axis: str, n_shards: int,
                     per_local: int) -> jax.Array:
    key = (_mesh_sig(mesh, axis), per_local)
    v = _SHARD_IDX_CACHE.get(key)
    if v is None:
        # evict oldest-first down to the cap (dict preserves insertion
        # order); wholesale clearing would also throw away the hot
        # (mesh, width) pairs of every OTHER live query shape
        while len(_SHARD_IDX_CACHE) >= _SHARD_IDX_CAP:
            _SHARD_IDX_CACHE.pop(next(iter(_SHARD_IDX_CACHE)))
        v = jax.device_put(
            np.repeat(np.arange(n_shards, dtype=np.int32), per_local),
            NamedSharding(mesh, P(axis)))
        _SHARD_IDX_CACHE[key] = v
    return v


def _slab_bytes(slab: dict) -> int:
    n = 0
    for v in slab["cols"].values():
        n += int(v.size) * v.dtype.itemsize
    for v in slab["dests"].values():
        n += int(v.size) * v.dtype.itemsize
    n += int(slab["valid"].size)
    return n


def _shard_assignment(proj, cols_np: Dict[str, np.ndarray], n: int,
                      n_shards: int, ring: Optional[np.ndarray] = None,
                      base: int = 0) -> np.ndarray:
    """Device shard per row: ring hash of the segmentation columns,
    OFFSET-FREE (core/segmentation.shard_of) -- the same logical row must
    land on the same shard whether the primary or the ring-offset buddy
    store served it.  Trickle-loaded WOS rows arrive with their ring
    value already stamped at commit (``ring``), so no re-hash.
    Replicated projections have no ring: spread rows round-robin."""
    seg = proj.segmentation
    if seg.replicated:
        return ((base + np.arange(n, dtype=np.int64))
                % n_shards).astype(np.int32)
    if ring is None:
        ring = hash_columns(*[cols_np[c] for c in seg.columns])
    return shard_of(ring, n_shards)


def _partition_to_slab(cols_np: Dict[str, np.ndarray], shard: np.ndarray,
                       reseg_keys: Sequence[str], n_shards: int, mesh,
                       axis: str, keep_layout: bool = False
                       ) -> Optional[dict]:
    """Pack host rows (already canonicalized) into a static
    ``(n_shards, per)`` device slab from each row's shard assignment.
    Used for the commit-time WOS buffers (the ROS slab builds on device,
    ``_build_ros_slab_device``).  Zero rows return the empty-slab
    sentinel ``None`` -- computing ``v.min()`` bounds on an empty column
    used to raise out of the whole segmented path.  ``keep_layout``
    additionally records the (order, shard, slot) map so a caller can
    scatter per-row host data (e.g. an epoch visibility mask) into slab
    slots later without repartitioning."""
    n = len(shard)
    if n == 0:
        return None
    dests = {k: shard_of(hash_columns(cols_np[k]), n_shards)
             for k in reseg_keys}

    # observed per-column bounds: static pack radices for the shard
    # program (exact, tighter than SMA estimates)
    bounds = {}
    for c, v in cols_np.items():
        bounds[c] = (int(v.min()), int(v.max())) \
            if v.dtype.kind in "iub" else None

    order, ss, pos, counts = _slab_positions(shard, n_shards)
    per = _round_up(counts.max())

    sharding = NamedSharding(mesh, P(axis))
    out_cols = {}
    for c, v in cols_np.items():
        buf = np.zeros((n_shards, per), v.dtype)
        buf[ss, pos] = v[order]
        out_cols[c] = jax.device_put(buf.reshape(-1), sharding)
    vbuf = np.zeros((n_shards, per), bool)
    vbuf[ss, pos] = True
    out_valid = jax.device_put(vbuf.reshape(-1), sharding)
    out_dests = {}
    for k, d in dests.items():
        # pad slots point at their own shard so an exchange leaves them
        # in place instead of piling them all onto shard 0
        dbuf = np.repeat(np.arange(n_shards, dtype=np.int32)[:, None],
                         per, axis=1)
        dbuf[ss, pos] = d[order]
        out_dests[k] = jax.device_put(dbuf.reshape(-1), sharding)

    out = {"cols": out_cols, "valid": out_valid, "per": int(per),
           "n_rows": n, "dests": out_dests,
           "real": {k: np.bincount(d, minlength=n_shards)
                    for k, d in dests.items()},
           "r0": counts, "bounds": bounds}
    if keep_layout:
        out["layout"] = (order, ss, pos)
    return out


# ------------------------------------------------- device ROS slab build --

def _build_dest_program(mesh, axis: str, n_shards: int,
                        seg_cols: Tuple[str, ...],
                        reseg_keys: Tuple[str, ...], replicated: bool):
    """Build phase B1: per-row shard ownership and resegment destinations
    from the DEVICE hash twins, plus the exact histograms that size the
    build exchange -- per-source bucket counts over ALL rows (invalid
    rows stay on their own shard, so they can never overflow a bucket),
    and global per-destination counts of the valid rows."""

    def local_fn(valid_l, segd, resegd):
        me = jax.lax.axis_index(axis).astype(jnp.int32)
        n_local = valid_l.shape[0]
        if replicated:
            dest_v = ((me * n_local
                       + jnp.arange(n_local, dtype=jnp.int32))
                      % n_shards)
        else:
            ring = hash_columns_jnp(*[segd[c] for c in seg_cols])
            dest_v = shard_of_jnp(ring, n_shards)
        dest0 = jnp.where(valid_l, dest_v, me).astype(jnp.int32)
        oh = jax.nn.one_hot(dest0, n_shards, dtype=jnp.int32)
        bucket = oh.sum(axis=0)                    # ALL rows, this source
        vi = valid_l.astype(jnp.int32)
        r0 = jax.lax.psum((oh * vi[:, None]).sum(axis=0), axis)
        dests, reals = {}, {}
        for k in reseg_keys:
            dk = shard_of_jnp(hash_columns_jnp(resegd[k]), n_shards)
            dests[k] = dk
            ohk = jax.nn.one_hot(dk, n_shards, dtype=jnp.int32)
            reals[k] = jax.lax.psum((ohk * vi[:, None]).sum(axis=0), axis)
        return dest0, dests, bucket, r0, reals

    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=(P(axis), P(axis), P(axis)),
                   out_specs=(P(axis), P(axis), P(axis), P(), P()))
    return jax.jit(fn)


def _build_take_program(mesh, axis: str, per_out: int, sb: int):
    """Build phase B2a: per shard, the rows to keep -- valid rows first
    (stable, preserving source container order: at one shard the slab
    keeps the exact single-node scan order, so its block SMAs prune at
    least as tightly), sliced to the padded row budget -- and the valid
    count per block.  Independent of the columns, so one compiled sort
    serves every query's slab of the same size."""
    nb = per_out // sb

    def local_fn(valid):
        n_local = valid.shape[0]
        # stable valid-first order without argsort-kind kwargs: invalid
        # rows rank after every valid row, ties broken by position
        rank = (jnp.where(valid, 0, 1) * n_local
                + jnp.arange(n_local, dtype=jnp.int32))
        take = jnp.argsort(rank)[:per_out]
        valid_c = valid[take]
        bcount = valid_c.reshape(nb, sb).sum(axis=1).astype(jnp.int32)
        return take, valid_c, bcount

    fn = shard_map(local_fn, mesh=mesh, in_specs=P(axis),
                   out_specs=(P(axis),) * 3)
    return jax.jit(fn)


def _build_gather_program(mesh, axis: str, per_out: int, sb: int):
    """Build phase B2b: one column through the kept rows, with its
    per-block min/max SMAs over the surviving layout.  Per column, so
    its compiled executable is shared by every column of one dtype."""
    nb = per_out // sb

    def local_fn(v, take, valid_c):
        out = v[take]
        arr = out.reshape(nb, sb)
        v2 = valid_c.reshape(nb, sb)
        if arr.dtype.kind == "f":
            hi, lo = jnp.inf, -jnp.inf
        else:
            hi = jnp.iinfo(arr.dtype).max
            lo = jnp.iinfo(arr.dtype).min
        return (out, jnp.where(v2, arr, hi).min(axis=1),
                jnp.where(v2, arr, lo).max(axis=1))

    fn = shard_map(local_fn, mesh=mesh, in_specs=(P(axis),) * 3,
                   out_specs=(P(axis),) * 3)
    return jax.jit(fn)


def _build_ros_slab_device(db: VerticaDB, proj, plan, need: Sequence[str],
                           reseg_keys: Sequence[str], eff: int, mesh,
                           axis: str, n_shards: int, stats
                           ) -> Optional[dict]:
    """Device-side ROS slab build: already-cached decoded device blocks ->
    ring hash + destination histograms (B1) -> one all_to_all resegment ->
    compaction + block SMAs (B2).  The only host traffic is the
    visibility mask going up and the small histograms/SMA stats coming
    back -- never the columns."""
    got = fused_exec.snapshot_scan_device(db, plan, need, eff, stats)
    if got is None:
        return None
    cols_dev, valid_np = got
    if not bool(valid_np.any()):
        return None
    n_total = int(valid_np.shape[0])
    n_vis = int(valid_np.sum())
    per_src = -(-n_total // n_shards)
    pad = n_shards * per_src - n_total
    sharding = NamedSharding(mesh, P(axis))
    cols_p = {}
    for c in need:
        v = cols_dev[c]
        if pad:
            v = jnp.pad(v, (0, pad))
        cols_p[c] = jax.device_put(v, sharding)
    vp = np.pad(valid_np, (0, pad)) if pad else valid_np
    valid_p = jax.device_put(np.ascontiguousarray(vp), sharding)

    seg = proj.segmentation
    seg_cols = () if seg.replicated else tuple(seg.columns)
    reseg_keys = tuple(reseg_keys)
    fn, _ = PLAN_CACHE.get_or_build(
        ("seg-dest", _mesh_sig(mesh, axis), seg_cols, reseg_keys,
         seg.replicated, n_shards),
        lambda: _build_dest_program(mesh, axis, n_shards, seg_cols,
                                    reseg_keys, seg.replicated))
    dest0, dests_raw, bucket, r0, reals = fn(
        valid_p, {c: cols_p[c] for c in seg_cols},
        {k: cols_p[k] for k in reseg_keys})
    bucket_np = np.asarray(bucket).reshape(n_shards, n_shards)
    r0_np = np.asarray(r0).astype(np.int64)
    real_np = {k: np.asarray(v).astype(np.int64)
               for k, v in reals.items()}

    # capacity from the exact per-source histogram: overflow-free by
    # construction.  Block-multiple so the compacted layout reshapes.
    per_b = _round_up(int(bucket_np.max()), _SLAB_BLOCK)
    payload = dict(cols_p)
    payload["__v"] = valid_p.astype(jnp.int8)   # bools ride as bytes
    for k in reseg_keys:
        payload["__d:" + k] = dests_raw[k]
    moved, slot_valid, overflow = exchange.resegment(
        mesh, axis, payload, dest0, per_b * n_shards)
    if int(np.asarray(overflow).sum()):
        return None                             # defensive; cannot happen
    valid2 = (moved["__v"] != 0) & slot_valid
    # invalid slots (pads AND rows deleted at this epoch) must point at
    # their own shard so every later exchange leaves them in place --
    # that invariant is what makes the staged capacity math exact
    shard_idx = _shard_index_col(mesh, axis, n_shards, n_shards * per_b)
    dests2 = {k: jnp.where(valid2, moved["__d:" + k], shard_idx)
              for k in reseg_keys}

    per_out = _round_up(max(int(r0_np.max()), 1), _SLAB_BLOCK)
    take_fn, _ = PLAN_CACHE.get_or_build(
        ("seg-take", _mesh_sig(mesh, axis), per_out, _SLAB_BLOCK),
        lambda: _build_take_program(mesh, axis, per_out, _SLAB_BLOCK))
    gather_fn, _ = PLAN_CACHE.get_or_build(
        ("seg-gather", _mesh_sig(mesh, axis), per_out, _SLAB_BLOCK),
        lambda: _build_gather_program(mesh, axis, per_out, _SLAB_BLOCK))
    take, valid_c, bcount = take_fn(valid2)
    cols_c, bmins, bmaxs = {}, {}, {}
    for c in sorted(need):
        cols_c[c], bmins[c], bmaxs[c] = gather_fn(moved[c], take, valid_c)
    dests_c = {k: gather_fn(d, take, valid_c)[0] for k, d in dests2.items()}

    nb = per_out // _SLAB_BLOCK
    bcount_np = np.asarray(bcount).reshape(n_shards, nb)
    bmins_np = {c: np.asarray(v).reshape(n_shards, nb)
                for c, v in bmins.items()}
    bmaxs_np = {c: np.asarray(v).reshape(n_shards, nb)
                for c, v in bmaxs.items()}
    bounds = {}
    for c in need:
        if cols_p[c].dtype.kind in "iub":
            sel = bcount_np > 0
            bounds[c] = (int(bmins_np[c][sel].min()),
                         int(bmaxs_np[c][sel].max()))
        else:
            bounds[c] = None
    return {"cols": cols_c, "valid": valid_c, "dests": dests_c,
            "per": per_out, "n_rows": n_vis, "r0": r0_np,
            "real": real_np, "bounds": bounds, "sb": _SLAB_BLOCK,
            "bstats": (bcount_np, bmins_np, bmaxs_np)}


# -------------------------------------------- commit-time WOS buffers --

def _wos_buffer_key(store, mesh, axis: str) -> tuple:
    return ("wos", store.wos.version, _mesh_sig(mesh, axis))


def _build_wos_buffer(store, n_shards: int, mesh, axis: str
                      ) -> Optional[dict]:
    """Per-store device WOS buffer: EVERY projection column (plus a
    resegment-destination column per column), partitioned by the
    commit-stamped ring values.  Query-shape independent, so it can be
    built eagerly at commit time; a query subsets the columns it needs
    and uploads only its epoch's visibility mask."""
    proj = store.proj
    data, eps, _segs = store.wos.snapshot()
    n = len(eps)
    if n == 0:
        return None
    cols_np = {c: _canon_np(np.asarray(data[c])) for c in proj.columns}
    ring = store.wos.ring_snapshot()
    shard = _shard_assignment(proj, cols_np, n, n_shards, ring=ring)
    return _partition_to_slab(cols_np, shard, tuple(proj.columns),
                              n_shards, mesh, axis, keep_layout=True)


def _get_wos_buffer(db: VerticaDB, host: int, owner: str, mesh, axis: str,
                    n_shards: int) -> Optional[dict]:
    store = db.nodes[host].stores[owner]
    if store.wos.n_rows == 0:
        return None
    cache = getattr(db, "block_cache", None)
    if cache is None:
        return _build_wos_buffer(store, n_shards, mesh, axis)
    primary = store.proj.buddy_of or store.proj.name
    return cache.get_or_put(
        f"seg:{primary}", (_wos_buffer_key(store, mesh, axis), host, owner),
        KIND_WOS, lambda: _build_wos_buffer(store, n_shards, mesh, axis),
        _slab_bytes)


def prewarm_wos_buffer(db: VerticaDB, host: int, owner: str) -> None:
    """Commit-time hook (core/database.commit): stream the just-appended
    WOS batch into its per-shard device buffer while the commit is still
    holding the rows hot, so the next query's trickle delta is already
    resident.  Keyed by ``WOS.version`` -- a later append/delete/clear
    simply strands this entry for the LRU."""
    mesh = getattr(db, "mesh", None)
    axis = getattr(db, "mesh_axis", None)
    if mesh is None or getattr(db, "block_cache", None) is None:
        return
    node = db.nodes[host]
    if not node.up or owner not in node.stores:
        return
    _get_wos_buffer(db, host, owner, mesh, axis, int(mesh.shape[axis]))


def _wos_parts(db: VerticaDB, plan, need: Sequence[str],
               reseg_keys: Sequence[str], as_of: int, mesh, axis: str,
               n_shards: int) -> List[dict]:
    """Per-source WOS slab views at this query's snapshot: the cached
    device buffer's columns subset to ``need``, with ONLY the epoch
    visibility mask built host-side and uploaded (one small bool array).
    Capacity accounting (``r0``/``real``) counts ALL buffered rows --
    rows invisible at this epoch still occupy slots whose destinations
    are their real ring targets, so undercounting them could overflow a
    later exchange."""
    parts = []
    for host, owner in plan.sources:
        store = db.nodes[host].stores[owner]
        buf = _get_wos_buffer(db, host, owner, mesh, axis, n_shards)
        if buf is None:
            continue
        w = fused_exec.wos_visible(store, as_of)
        if w is None:
            continue
        vis = np.asarray(w[1], bool)
        if not vis.any():
            continue
        order, ss, pos = buf["layout"]
        vbuf = np.zeros((n_shards, buf["per"]), bool)
        vbuf[ss, pos] = vis[order]
        valid = jax.device_put(vbuf.reshape(-1),
                               NamedSharding(mesh, P(axis)))
        parts.append({
            "cols": {c: buf["cols"][c] for c in need},
            "valid": valid,
            "dests": {k: buf["dests"][k] for k in reseg_keys},
            "per": buf["per"], "n_rows": int(vis.sum()),
            "r0": buf["r0"],
            "real": {k: buf["real"][k] for k in reseg_keys},
            "bounds": {c: buf["bounds"][c] for c in need}})
    return parts


# ------------------------------------------------- slab concatenation --

def _build_concat_program(mesh, axis: str):
    """Append one slab to another shard-locally (both are already
    partitioned by the same ring map, so this is pure local
    concatenation -- no collective)."""

    def local_fn(a_cols, a_valid, a_dests, b_cols, b_valid, b_dests):
        cols = {c: jnp.concatenate([a_cols[c], b_cols[c]])
                for c in a_cols}
        valid = jnp.concatenate([a_valid, b_valid])
        dests = {k: jnp.concatenate([a_dests[k], b_dests[k]])
                 for k in a_dests}
        return cols, valid, dests

    fn = shard_map(local_fn, mesh=mesh, in_specs=(P(axis),) * 6,
                   out_specs=(P(axis),) * 3)
    return jax.jit(fn)


def _merge_bounds(a: Optional[tuple], b: Optional[tuple]
                  ) -> Optional[tuple]:
    if a is None or b is None:
        return None
    return (min(a[0], b[0]), max(a[1], b[1]))


def _concat_slabs(ros: dict, wos: dict, mesh, axis: str) -> dict:
    fn, _ = PLAN_CACHE.get_or_build(
        ("seg-concat", _mesh_sig(mesh, axis),
         tuple(sorted(ros["cols"])), tuple(sorted(ros["dests"]))),
        lambda: _build_concat_program(mesh, axis))
    cols, valid, dests = fn(ros["cols"], ros["valid"], ros["dests"],
                            wos["cols"], wos["valid"], wos["dests"])
    return {"cols": cols, "valid": valid, "dests": dests,
            "per": ros["per"] + wos["per"],
            "n_rows": ros["n_rows"] + wos["n_rows"],
            "real": {k: ros["real"][k] + wos["real"][k]
                     for k in ros["real"]},
            "r0": ros["r0"] + wos["r0"],
            "bounds": {c: _merge_bounds(ros["bounds"][c],
                                        wos["bounds"][c])
                       for c in ros["bounds"]}}


# ------------------------------------------------ slab-block pruning --

def _build_prune_program(mesh, axis: str, n_shards: int,
                         names: Tuple[str, ...], dkeys: Tuple[str, ...],
                         per_in: int, k2: int, sb: int):
    nb = per_in // sb

    def local_fn(cols, valid, dests, idx, live):
        me = jax.lax.axis_index(axis).astype(jnp.int32)
        liv = jnp.repeat(live, sb)
        out_cols = {c: v.reshape(nb, sb)[idx].reshape(-1)
                    for c, v in cols.items()}
        valid_g = valid.reshape(nb, sb)[idx].reshape(-1) & liv
        # gathered pad blocks replay block 0's destinations: re-point
        # them at their own shard or they would travel on the next
        # exchange and break the capacity accounting
        out_dests = {k: jnp.where(liv,
                                  d.reshape(nb, sb)[idx].reshape(-1), me)
                     for k, d in dests.items()}
        # EXACT per-destination histograms over the surviving rows: the
        # staged capacity proof needs ``real`` to count precisely the
        # rows occupying slots (a pre-prune overestimate could undersize
        # a SECOND resegment stage's own-shard pad accounting)
        vi = valid_g.astype(jnp.int32)
        reals = {k: jax.lax.psum(
            (jax.nn.one_hot(d, n_shards, dtype=jnp.int32)
             * vi[:, None]).sum(axis=0), axis)
            for k, d in out_dests.items()}
        return out_cols, valid_g, out_dests, reals

    fn = shard_map(local_fn, mesh=mesh, in_specs=(P(axis),) * 5,
                   out_specs=(P(axis), P(axis), P(axis), P()))
    return jax.jit(fn)


def _prune_slab(q: LogicalQuery, slab: dict, mesh, axis: str,
                n_shards: int, stats) -> dict:
    """Per-query slab-block pruning: the predicate's column bounds
    against the slab's per-block SMAs (device-computed at build time)
    select the surviving ``sb``-row blocks; each shard gathers just
    those.  Conservative by construction -- a pruned block contains no
    row satisfying the predicate, and the segmented subset only runs
    inner joins, which never resurrect rows."""
    if "bstats" not in slab:
        return slab
    bcounts, bmins, bmaxs = slab["bstats"]
    total = int(bcounts.size)
    stats.blocks_total += total
    if q.predicate is None:
        return slab
    pbounds = q.predicate.bounds()
    keep = bcounts > 0
    applied = False
    for c, (lo, hi) in pbounds.items():
        if c not in bmins:
            continue
        lo = -np.inf if lo is None else lo
        hi = np.inf if hi is None else hi
        keep &= (bmaxs[c] >= lo) & (bmins[c] <= hi)
        applied = True
    if not applied:
        return slab
    kept = int(keep.sum())
    stats.blocks_pruned += total - kept
    if kept == total:
        return slab
    sb = slab["sb"]
    nb = slab["per"] // sb
    # static gather width: max surviving blocks on any shard, bucketed
    # to a power of two so repeat queries reuse a handful of traces.
    # kept == 0 keeps one all-dead block -- the program runs with every
    # row invalid and yields exactly the empty aggregation a predicate
    # matching nothing produces
    k2 = min(_pow2_at_least(max(int(keep.sum(axis=1).max()), 1)), nb)
    idx = np.zeros((n_shards, k2), np.int32)
    live = np.zeros((n_shards, k2), bool)
    for s in range(n_shards):
        ki = np.flatnonzero(keep[s])[:k2]
        idx[s, :len(ki)] = ki
        live[s, :len(ki)] = True
    sharding = NamedSharding(mesh, P(axis))
    idx_d = jax.device_put(idx.reshape(-1), sharding)
    live_d = jax.device_put(live.reshape(-1), sharding)
    names = tuple(sorted(slab["cols"]))
    dkeys = tuple(sorted(slab["dests"]))
    fn, _ = PLAN_CACHE.get_or_build(
        ("seg-prune", _mesh_sig(mesh, axis), names, dkeys,
         slab["per"], k2, sb),
        lambda: _build_prune_program(mesh, axis, n_shards, names, dkeys,
                                     slab["per"], k2, sb))
    cols, valid, dests, reals = fn(slab["cols"], slab["valid"],
                                   slab["dests"], idx_d, live_d)
    r0_kept = np.array([int(bcounts[s][keep[s]].sum())
                        for s in range(n_shards)], np.int64)
    out = dict(slab)
    out.update(cols=cols, valid=valid, dests=dests, per=k2 * sb,
               r0=r0_kept,
               real={k: np.asarray(v).astype(np.int64)
                     for k, v in reals.items()})
    out.pop("bstats", None)
    return out


def _sharded_scan(db: VerticaDB, proj, plan, q: LogicalQuery, need,
                  reseg_keys, as_of: int, mesh, axis: str, n_shards: int,
                  stats) -> Optional[dict]:
    """Partitioned scan: the device-built ROS slab is cached (keyed by
    the effective epoch + exact container set, invalidated precisely by
    the tuple mover), pruned per query against its block SMAs, then the
    per-store WOS buffer views are appended shard-locally -- a
    trickle-load commit therefore costs one small WOS visibility upload,
    never a whole-projection repartition."""
    # injection points: one per source store feeding the slab.  A crash
    # here fails the host node and escalates to query-level failover (the
    # retry replans onto buddy stores); transients retry in place.
    for host, owner in plan.sources:
        point = "segmented.buddy_read" \
            if db.catalog.projections[owner].buddy_of is not None \
            else "segmented.slab_build"
        fire_with_retries(db, point, stats=stats, node=host,
                          projection=owner)
    cache = getattr(db, "block_cache", None)
    ros = None
    if cache is None:
        ros = _build_ros_slab_device(db, proj, plan, need, reseg_keys,
                                     as_of, mesh, axis, n_shards, stats)
        stats.seg_slab = "nocache"
    else:
        ceil = max((db.nodes[h].stores[o].epoch_ceiling(include_wos=False)
                    for h, o in plan.sources), default=0)
        eff = min(as_of, ceil)
        sig = _source_sig(db, plan, need, reseg_keys, eff, mesh, axis)
        ids = frozenset(i for item in sig[0] for i in item[2])
        key = ("slab", ids, sig)
        cid = f"seg:{plan.projection}"
        ros = cache.get(cid, key, KIND_SEG)
        stats.seg_slab = "hit" if ros is not None else "miss"
        if ros is None:
            ros = _build_ros_slab_device(db, proj, plan, need, reseg_keys,
                                         eff, mesh, axis, n_shards, stats)
            if ros is not None:
                cache.put(cid, key, KIND_SEG, ros, _slab_bytes(ros))
    wos_parts = _wos_parts(db, plan, need, reseg_keys, as_of, mesh, axis,
                           n_shards)
    if wos_parts:
        stats.seg_slab += "+wos"
    if ros is None and not wos_parts:
        return None
    stats.rows_scanned = (0 if ros is None else ros["n_rows"]) \
        + sum(p["n_rows"] for p in wos_parts)
    if ros is not None:
        ros = _prune_slab(q, ros, mesh, axis, n_shards, stats)
    parts = ([] if ros is None else [ros]) + wos_parts
    slab = parts[0]
    for p in parts[1:]:
        slab = _concat_slabs(slab, p, mesh, axis)
    return slab


# ---------------------------------------------------------------------------
# 2. Build-side placement per exchange strategy
# ---------------------------------------------------------------------------

def _partition_build(bnp: Dict[str, np.ndarray], shard: np.ndarray,
                     n_shards: int, mesh, axis: str
                     ) -> Dict[str, jax.Array]:
    """Place dimension rows onto shards by hash(dim_key), padded per shard
    with copies of row 0.  A pad copy is harmless: a probe key equal to
    the pad's key hashes to the pad's home shard, so on any other shard no
    probe row can match it, and on its home shard the duplicate carries
    identical values."""
    sharding = NamedSharding(mesh, P(axis))
    n = len(shard)
    if n == 0:
        return {c: jax.device_put(np.zeros(0, _canon_np(v).dtype), sharding)
                for c, v in bnp.items()}
    order, ss, pos, counts = _slab_positions(shard, n_shards)
    per = max(int(counts.max()), 1)
    out = {}
    for c, v in bnp.items():
        v = _canon_np(v)
        buf = np.full((n_shards, per), v[0], v.dtype)
        buf[ss, pos] = v[order]
        out[c] = jax.device_put(buf.reshape(-1), sharding)
    return out


def _broadcast_build(bnp: Dict[str, np.ndarray], n_shards: int, mesh,
                     axis: str) -> Dict[str, jax.Array]:
    """Split the build side contiguously across shards, then replicate it
    with a real all_gather (exchange.broadcast_build_side)."""
    sharding = NamedSharding(mesh, P(axis))
    n = len(next(iter(bnp.values())))
    per = -(-n // n_shards) if n else 0
    cols = {}
    for c, v in bnp.items():
        v = _canon_np(v)
        if n == 0:
            buf = np.zeros(0, v.dtype)
        else:
            buf = np.full(n_shards * per, v[0], v.dtype)
            buf[:n] = v
        cols[c] = jax.device_put(buf, sharding)
    if n == 0:
        return cols               # nothing to gather
    return exchange.broadcast_build_side(mesh, axis, cols)


def _place_one_build(db: VerticaDB, spec, exch: str,
                     build: Dict[str, jax.Array], mesh, axis: str,
                     n_shards: int, replicated_dim: bool
                     ) -> Tuple[Dict[str, jax.Array], Dict]:
    """(placed device arrays, per-column host bounds) for one join."""
    bnp = {c: np.asarray(v) for c, v in build.items()}
    bounds = {}
    for c, v in bnp.items():
        if not v.size:
            bounds[c] = (0, 0)
        elif v.dtype.kind in "iub":
            bounds[c] = (int(v.min()), int(v.max()))
        else:
            bounds[c] = None
    if exch == "broadcast":
        return _broadcast_build(bnp, n_shards, mesh, axis), bounds
    if exch == "local" and replicated_dim:
        return {c: jax.device_put(
            jnp.asarray(_canon_np(v)), NamedSharding(mesh, P()))
            for c, v in bnp.items()}, bounds
    # co-located (probe placed by the join key) or the dim side of a
    # resegment: place rows by hash(dim_key) on the same offset-free
    # ring map the probe side uses
    shard = shard_of(hash_columns(bnp[spec.dim_key]), n_shards)
    return _partition_build(bnp, shard, n_shards, mesh, axis), bounds


def _place_builds(db: VerticaDB, q: LogicalQuery, plan, as_of: int, mesh,
                  axis: str, n_shards: int, stats=None
                  ) -> Tuple[List[Dict[str, jax.Array]], List, List[Dict]]:
    """Returns (placed build dicts, per-join shard_map specs, per-join
    dim-column bounds).  Placed builds are cached device-side keyed by
    (dim table, join signature, exchange op, mesh identity, snapshot
    epoch) -- MVCC makes the fixed-epoch read immutable, so a warm
    repeat skips the host round-trip, re-partition AND (for broadcast
    joins) the all_gather; drop_partition invalidates the dim's entries."""
    builds_dev, _ = fused_exec.build_join_sides(db, q, as_of)
    cache = getattr(db, "block_cache", None)
    mh = hash(_mesh_sig(mesh, axis)) & 0xFFFFFFFFFFFFFFFF
    placed, specs, bounds = [], [], []
    for spec, exch, build in zip(q.joins, plan.join_exchanges, builds_dev):
        replicated_dim = db.catalog.super_of(
            spec.dim_table).segmentation.replicated
        specs.append(P() if exch == "broadcast"
                     or (exch == "local" and replicated_dim) else P(axis))
        if exch == "broadcast":
            # the all_gather of the small build side is a collective too:
            # a crash/transient here follows the same taxonomy
            fire_with_retries(db, "exchange.broadcast", stats=stats,
                              join=spec.dim_table)

        def make(spec=spec, exch=exch, build=build,
                 replicated_dim=replicated_dim):
            return _place_one_build(db, spec, exch, build, mesh, axis,
                                    n_shards, replicated_dim)
        if cache is None:
            pb = make()
        else:
            pb = cache.get_or_put(
                f"dim:{spec.dim_table}",
                f"seg|{spec.signature()}|{exch}|{mh:016x}@{as_of}",
                fused_exec.KIND_BUILD, make,
                lambda v: sum(int(a.size) * a.dtype.itemsize
                              for a in v[0].values()))
        placed.append(pb[0])
        bounds.append(pb[1])
    return placed, specs, bounds


# ---------------------------------------------------------------------------
# 3. Fused stage programs (plan-cached factories)
# ---------------------------------------------------------------------------

def _mesh_sig(mesh, axis: str) -> tuple:
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat), axis)


def _build_stage_factory(mesh, axis: str, n_shards: int, specs: Sequence,
                         build_specs: Sequence,
                         reseg_key: Optional[str], final_cfg):
    """One exchange->join(->pre-agg) stage as a SINGLE shard_map'd jitted
    program: ``exchange.resegment_local`` (when the stage opens with a
    Send/Recv), the stage's hash joins, and -- for the final stage --
    derived exprs, deferred predicate, key packing and the shard-local
    pre-aggregation.  The per-shard exchange OVERFLOW report is returned
    as an output instead of being checked inline, so a multi-stage query
    dispatches its whole chain without a host sync in the middle.

    Returns a factory memoizing the jitted program per static
    (input slots, exchange capacity) pair: the plan cache keys the
    factory by plan/mesh signature alone, and data-size changes retrace
    inside the entry without demoting it to a miss."""
    reseg = reseg_key is not None

    if final_cfg is not None:
        (ir, algo, domains, lows, domain, local_aggs, values_cols,
         packed) = final_cfg

    def build(per_new: int, check_vma: bool):
        def local_fn(cols, valid, dests, shard_idx, builds):
            cols = dict(cols)
            dests = dict(dests)
            if reseg:
                dest_l = dests.pop(reseg_key)
                names = sorted(cols)
                dkeys = sorted(dests)
                vals = (tuple(cols[c] for c in names)
                        + tuple(dests[k] for k in dkeys)
                        + (valid.astype(jnp.int8),))
                outs, vr, overflow = exchange.resegment_local(
                    axis, n_shards, per_new, dest_l, vals)
                nn = len(names)
                cols = dict(zip(names, outs[:nn]))
                # empty slots point at their own shard so the NEXT
                # exchange leaves them in place; occupied slots keep
                # their moved destination (a join-invalidated row's
                # destination is still counted by the build histogram)
                dests = {k: jnp.where(vr, outs[nn + i], shard_idx)
                         for i, k in enumerate(dkeys)}
                valid = (outs[-1] != 0) & vr
            else:
                overflow = jnp.zeros((n_shards,), jnp.int32)
            for spec, bld in zip(specs, builds):
                cols, valid = ops.hash_join(bld, spec.dim_key, cols,
                                            spec.fact_key, valid,
                                            how=spec.how)
            if final_cfg is None:
                out = dict(cols)
                out["__valid"] = valid
                for k, d in dests.items():
                    out["__d:" + k] = d
                return out, overflow
            for name, e in ir.derived:
                cols[name] = e(cols)
            if ir.predicate is not None:
                valid = valid & jnp.asarray(ir.predicate(cols), bool)
            values = {c: cols[c] for c in values_cols}
            if not ir.group_by:
                keys = jnp.zeros(valid.shape[0], jnp.int32)
                out = seg_preagg(keys, valid, values, 1, local_aggs)
                return ({k: v.reshape(-1) for k, v in out.items()},
                        overflow)
            keys = ops.pack_keys([cols[g] for g in ir.group_by],
                                 domains, lows) \
                if packed else cols[ir.group_by[0]]
            if algo == "dense":
                out = seg_preagg(keys.astype(jnp.int32), valid, values,
                                 domain, local_aggs)
            else:
                out = ops.groupby_sort(keys, valid, values, domain,
                                       local_aggs)
            return ({k: jnp.reshape(v, (-1,)) for k, v in out.items()},
                    overflow)

        fn = shard_map(local_fn, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis), P(axis),
                                 tuple(build_specs)),
                       out_specs=(P(axis), P()), check_vma=check_vma)
        return jax.jit(fn)

    progs: Dict[Tuple[int, bool], object] = {}

    def get(per_new: int):
        # the varying-axes check is off only around an interpret-mode
        # seg_preagg (see kernels/seg_preagg.interpreted)
        key = (per_new, not preagg_interpreted())
        fn = progs.get(key)
        if fn is None:
            fn = progs[key] = build(*key)
        return fn

    return get


# ---------------------------------------------------------------------------
# 4. Final merge (host-side, over small partials)
# ---------------------------------------------------------------------------

def _merge_scalar(aggs, res, n_shards: int) -> Dict[str, np.ndarray]:
    counts = np.asarray(res["group_count"]).reshape(n_shards)
    total = int(counts.sum())
    out = {"group_count": np.asarray([total])}
    for name, _, kind in aggs:
        v = np.asarray(res[name]).reshape(n_shards)
        if kind in ("sum", "count"):
            out[name] = np.asarray([v.sum()])
        elif kind == "avg":
            out[name] = np.asarray([v.sum() / max(total, 1)])
        elif kind == "min":
            out[name] = np.asarray([v.min()])
        else:
            out[name] = np.asarray([v.max()])
    return out


def _merge_dense(aggs, res, n_shards: int, domain: int
                 ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    counts = np.asarray(res["group_count"]).reshape(n_shards, domain)
    counts = counts.sum(0)
    sel = counts > 0
    gkeys = np.flatnonzero(sel)
    out = {"group_count": counts[sel]}
    for name, _, kind in aggs:
        v = np.asarray(res[name]).reshape(n_shards, domain)
        if kind in ("sum", "count"):
            m = v.sum(0)
        elif kind == "avg":
            m = v.sum(0) / np.maximum(counts, 1)
        elif kind == "min":
            m = v.min(0)
        else:
            m = v.max(0)
        out[name] = m[sel]
    return gkeys, out


def _merge_sorted(aggs, res, n_shards: int, max_groups: int
                  ) -> Optional[Tuple[np.ndarray, Dict[str, np.ndarray]]]:
    ngs = np.asarray(res["n_groups"]).reshape(n_shards)
    if (ngs > max_groups).any():
        return None               # local sort cap exceeded: fall back
    gk = np.asarray(res["group_keys"]).reshape(n_shards, max_groups)
    gc = np.asarray(res["group_count"]).reshape(n_shards, max_groups)
    keys = np.concatenate([gk[s, :ngs[s]] for s in range(n_shards)])
    cnts = np.concatenate([gc[s, :ngs[s]] for s in range(n_shards)])
    if keys.size == 0:
        return np.zeros(0, np.int64), {
            "group_count": np.zeros(0, np.int64),
            **{name: np.zeros(0) for name, _, _ in aggs}}
    uniq, inv = np.unique(keys, return_inverse=True)
    ng = len(uniq)
    counts = np.bincount(inv, weights=cnts, minlength=ng).astype(np.int64)
    out = {"group_count": counts}
    for name, _, kind in aggs:
        pv = np.asarray(res[name])
        v = np.concatenate([pv.reshape(
            n_shards, max_groups)[s, :ngs[s]] for s in range(n_shards)])
        if kind in ("sum", "count", "avg"):
            acc = np.bincount(inv, weights=v, minlength=ng)
            if kind == "avg":
                acc = acc / np.maximum(counts, 1)
        elif kind == "min":
            acc = np.full(ng, np.inf)
            np.minimum.at(acc, inv, v)
        else:
            acc = np.full(ng, -np.inf)
            np.maximum.at(acc, inv, v)
        # integer partials stay integral (the single-node path returns
        # int sums/mins/maxes for int columns; only avg is a ratio)
        if kind != "avg" and pv.dtype.kind in "iub":
            acc = acc.astype(np.int64)
        out[name] = acc
    return uniq, out


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def execute_segmented(db: VerticaDB, q: LogicalQuery, plan, as_of: int,
                      mesh, axis: str, stats
                      ) -> Optional[Dict[str, np.ndarray]]:
    """Run an aggregate query segmented across the mesh.  Returns the
    merged (pre-HAVING/ORDER/LIMIT) result columns, or None to fall back
    to the single-node pipeline."""
    if not (q.aggs or q.group_by):
        return None               # plain selects stay single-node
    if any(j.how != "inner" for j in q.joins):
        return None
    derived_names = {n for n, _ in q.derived}
    if any(g in derived_names for g in q.group_by):
        return None               # no static pack bounds for derived keys

    n_shards = int(mesh.shape[axis])

    # ---- RLE-direct routes: aggregate each node's encoded runs on the
    # host and merge -- the paper's "operate directly on encoded data"
    # beats shipping 2M decoded rows through slabs for count-only
    # GroupBys on the sort leader (no predicate/joins/WOS/deletes; the
    # helpers return None otherwise and the slab path runs) ----
    from . import pipeline as _pipe
    if plan.scalar_rle:
        res = _pipe._rle_scalar_count(db, q, plan, as_of)
        if res is not None:
            stats.segmented = True
            stats.n_shards = n_shards
            stats.exchange = ";".join(plan.join_exchanges)
            stats.groupby_algorithm = "rle-scalar (segmented)"
            return res
    if _pipe.rle_direct_eligible(q, plan):
        res = _pipe._rle_groupby(db, q, plan, as_of)
        if res is not None:
            stats.segmented = True
            stats.n_shards = n_shards
            stats.exchange = ";".join(plan.join_exchanges)
            stats.groupby_algorithm = "rle (segmented)"
            return res

    proj = db.catalog.projections[plan.projection]
    reseg_keys = tuple(spec.fact_key for spec, e
                       in zip(q.joins, plan.join_exchanges)
                       if e == "resegment")
    need = set(q.scan_columns(proj))
    if not proj.segmentation.replicated:
        need |= set(proj.segmentation.columns)
    need |= set(reseg_keys)
    need = sorted(need & set(proj.columns))

    slab = _sharded_scan(db, proj, plan, q, need, reseg_keys, as_of, mesh,
                         axis, n_shards, stats)
    if slab is None:
        return None               # empty snapshot: pipeline shapes it

    builds, build_specs, build_bounds = _place_builds(
        db, q, plan, as_of, mesh, axis, n_shards, stats)

    # ---- static pack radices for the group keys (exact host bounds) ----
    aggs = tuple(q.aggs)
    lows: Tuple[int, ...] = ()
    domains: Tuple[int, ...] = ()
    algo, domain = "dense", 1
    if q.group_by:
        los, doms = [], []
        for g in q.group_by:
            b = slab["bounds"].get(g)
            if b is None:
                for spec, bnds in zip(q.joins, build_bounds):
                    if g in spec.dim_columns:
                        b = bnds.get(g)
                        break
            if b is None:
                return None       # non-integral / unlocatable group key
            lo, hi = b
            lo = min(lo, 0)
            los.append(lo)
            doms.append(hi - lo + 1)
        total = 1
        for d in doms:
            total *= d
        if total >= _PACK_LIMIT:
            return None           # packed key overflows device int32
        lows, domains = tuple(los), tuple(doms)
        algo = "dense" if total <= plan.dense_domain_limit else "sort"
        domain = total if algo == "dense" else plan.max_groups

    values_cols = tuple(sorted({c for _, c, kind in aggs
                                if kind != "count" and c != "*"}))
    local_aggs = tuple((name, c, "sum" if kind == "avg" else kind)
                       for name, c, kind in aggs)
    packed = len(q.group_by) > 1 or (bool(lows) and lows[0] != 0)
    final_cfg = (q, algo, domains, lows, domain, local_aggs, values_cols,
                 packed)

    # ---- staged execution: joins run in plan order, with a resegment
    # exchange (Send/Recv) opening the stage of the join that needs it --
    # an up-front exchange would destroy the placement an earlier
    # co-located join depends on.  Each stage is ONE fused program ----
    stage_joins: List[List[int]] = [[]]
    for ji, exch in enumerate(plan.join_exchanges):
        if exch == "resegment":
            stage_joins.append([])
        stage_joins[-1].append(ji)

    mesh_sig = _mesh_sig(mesh, axis)
    hit_all = True

    def run_stages(mult: int):
        nonlocal hit_all
        cols, valid = dict(slab["cols"]), slab["valid"]
        dest_cols = dict(slab["dests"])
        per_prev, real_prev = slab["per"], slab["r0"]
        overflows = []
        res = None
        for si, stage in enumerate(stage_joins):
            final = si == len(stage_joins) - 1
            reseg_key = None
            per_new = 0
            if si > 0:
                spec0 = q.joins[stage[0]]
                reseg_key = spec0.fact_key
                if reseg_key not in dest_cols:
                    return None   # no destination column: fall back
                real_k = slab["real"][reseg_key]
                # exact destination occupancy: arriving rows + slots
                # that stay (pads and earlier arrivals not moving again)
                filled = real_k + per_prev - real_prev
                per_new = cost_mod.resegment_capacity(
                    filled, n_shards) // n_shards * mult
                fire_with_retries(db, "exchange.resegment", stats=stats,
                                  join=spec0.dim_table)
            elif not final and not stage:
                continue          # leading resegment: nothing local yet
            specs = tuple(q.joins[ji] for ji in stage)
            sb = tuple(builds[ji] for ji in stage)
            sbs = tuple(build_specs[ji] for ji in stage)
            if final:
                sig = ("seg2", q.exec_signature(), plan.projection,
                       proj.segmentation.kind,
                       tuple(proj.segmentation.columns), mesh_sig,
                       plan.join_exchanges,
                       tuple(bs == P() for bs in build_specs),
                       algo, int(domain), domains, lows, reseg_key)
                cfg = final_cfg
            else:
                sig = ("seg-stage2",
                       tuple(s.signature() for s in specs),
                       tuple(bs == P() for bs in sbs), mesh_sig,
                       reseg_key)
                cfg = None
            factory, hit = PLAN_CACHE.get_or_build(
                sig, lambda: _build_stage_factory(mesh, axis, n_shards,
                                                  specs, sbs, reseg_key,
                                                  cfg))
            hit_all &= hit
            fn = factory(per_new)
            sidx = _shard_index_col(
                mesh, axis, n_shards,
                n_shards * per_new if reseg_key else 1)
            out, overflow = fn(cols, valid, dest_cols, sidx, sb)
            if reseg_key is not None:
                overflows.append(overflow)
                per_prev, real_prev = n_shards * per_new, real_k
            if final:
                # the final fused stage ends in the shard-local scatter
                # pre-aggregation (kernels/seg_preagg)
                res = out
            else:
                valid = out.pop("__valid")
                dest_cols = {k[4:]: v for k, v in out.items()
                             if k.startswith("__d:")}
                cols = {c: v for c, v in out.items()
                        if not c.startswith("__")}
        return res, overflows

    # overflow is checked ONCE, after the final dispatch: capacities come
    # from exact histograms so a nonzero report is defensive -- record,
    # double every stage's capacity, retry the whole chain, then fall back
    res = None
    for mult in (1, 2):
        r = run_stages(mult)
        if r is None:
            return None
        res0, overflows = r
        ov = sum(int(np.asarray(o).sum()) for o in overflows)
        if ov == 0:
            res = res0
            break
        stats.reseg_overflow += ov
    if res is None:
        return None
    stats.plan_cache = "hit" if hit_all else "miss"

    # ---- final merge ----
    if not q.group_by:
        out = _merge_scalar(aggs, res, n_shards)
    else:
        merged = _merge_dense(aggs, res, n_shards, domain) \
            if algo == "dense" else _merge_sorted(aggs, res, n_shards,
                                                  domain)
        if merged is None:
            return None
        gkeys, out = merged
        key_cols = ops.unpack_keys(gkeys, domains, lows) if packed \
            else [np.asarray(gkeys).astype(np.int64)]
        for g, kv in zip(q.group_by, key_cols):
            out[g] = kv
    stats.segmented = True
    stats.n_shards = n_shards
    stats.exchange = ";".join(plan.join_exchanges)
    # placed builds are hash-partitioned or gathered: binary search
    stats.join_probe = ";".join("search" for _ in q.joins)
    stats.groupby_algorithm = f"{algo} (segmented)"
    return out
