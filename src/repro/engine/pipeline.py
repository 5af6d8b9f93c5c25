"""Query pipeline: logical IR -> chosen plan -> execution (paper §6).

The front-end is the logical-plan IR (engine/logical.py): ``LogicalQuery``
carries scan/filter/a *list* of joins/derived projections/multi-column
group-by/HAVING/multi-key sort/limit.  The planner (planner/planner.py)
picks the projection, per-join strategy, SIP filters and the GroupBy
algorithm; this module runs the physical plan over a VerticaDB's live
nodes and returns numpy results.

Composite group-by keys are packed into one dense integer domain
(operators.pack_keys) so the single-key GroupBy machinery -- dense
scatter, sort-based, the fused plan-cached executor and the Pallas
kernels -- applies unchanged; keys unpack on the (small) output.

Runtime algorithm switching (§6.1): the GroupBy starts on the planner's
choice but falls back from dense-hash to sort-based when the observed key
domain exceeds the table budget -- the paper's hash->sort-merge switch --
and to a host-side unique-based GroupBy when even packed keys would
overflow the device integer width.

DEPRECATED SHIMS: ``Query`` and ``JoinSpec`` predate the IR (one join,
one group-by column).  They remain importable from ``repro.engine`` as
thin constructors that lower via ``Query.to_ir()``; new code should use
``db.query(...)`` (engine/builder.py) or LogicalQuery directly.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.database import VerticaDB
from ..core.encodings import Encoding
from .expr import Col, Expr
from .logical import LogicalJoin, LogicalQuery, as_ir
from . import executor as fused_exec
from . import operators as ops
from .sip import sip_filter

# DEPRECATED back-compat alias: JoinSpec always matched the IR's join
# shape field-for-field, so the shim IS LogicalJoin.  New code should
# spell it ``LogicalJoin`` (engine/logical.py) or -- better -- use the
# fluent ``db.query(...).join(...)`` builder (engine/builder.py).
JoinSpec = LogicalJoin

_PACK_LIMIT = 1 << 31   # packed keys live in device int32 by default

# per-process query number: the ``qid`` of a query's spans
_query_ids = itertools.count(1)

_shim_warned = False


@dataclasses.dataclass(frozen=True, eq=False)
class Query:
    """DEPRECATED pre-IR front-end (single join, single group-by column),
    frozen at its PR-1 feature set.  Kept only as a thin shim for old
    call sites: ``to_ir()`` lowers to the ``LogicalQuery`` consumed
    everywhere, and ``execute``/``plan_query`` accept it transparently
    (emitting one ``DeprecationWarning`` per process).  New code should
    use the fluent builder -- ``db.query("t").where(...).join(...)
    .group_by(...).agg(...).collect()`` (engine/builder.py) -- or build
    ``LogicalQuery`` directly; both support multi-join, multi-column
    GROUP BY, derived columns, HAVING and multi-key ORDER BY, which this
    shim never will."""
    table: str
    columns: Tuple[str, ...] = ()
    predicate: Optional[Expr] = None
    join: Optional[LogicalJoin] = None
    group_by: Optional[str] = None
    aggs: Tuple[Tuple[str, str, str], ...] = ()   # (out, col, kind)
    order_by: Optional[str] = None
    descending: bool = False
    limit: Optional[int] = None

    def to_ir(self) -> LogicalQuery:
        global _shim_warned
        if not _shim_warned:
            _shim_warned = True
            import warnings
            warnings.warn(
                "repro.engine.Query is a deprecated shim; use "
                "db.query(...) (engine/builder.py) or LogicalQuery",
                DeprecationWarning, stacklevel=2)
        return LogicalQuery(
            table=self.table, columns=tuple(self.columns),
            predicate=self.predicate,
            joins=(self.join,) if self.join is not None else (),
            group_by=(self.group_by,) if self.group_by else (),
            aggs=tuple(self.aggs),
            order_by=((self.order_by, self.descending),)
            if self.order_by else (),
            limit=self.limit).validate()

    def needed_columns(self) -> set:
        return self.to_ir().needed_columns()


@dataclasses.dataclass
class ExecStats:
    projection: str = ""
    groupby_algorithm: str = ""
    join_strategy: str = ""
    containers_scanned: int = 0
    blocks_pruned: int = 0
    blocks_total: int = 0
    rows_scanned: int = 0
    sip_applied: bool = False
    wall_s: float = 0.0
    frontend_s: float = 0.0         # lowering + planning time
    query_id: int = 0               # the ``qid`` of this query's spans
    # warm-path telemetry (engine/executor.py)
    fused: bool = False
    plan_cache: str = ""            # "hit" / "miss" / "" (not attempted)
    block_cache_hits: int = 0
    block_cache_misses: int = 0
    # compressed-domain execution telemetry (engine/compressed.py)
    compressed_scan: bool = False   # code-domain scan + late materialization
    # segmented-execution telemetry (engine/segmented.py)
    segmented: bool = False
    n_shards: int = 0
    exchange: str = ""              # ";"-joined per-join exchange ops
    join_probe: str = ""            # ";"-joined per-join probe: "dense"
    #                                 (position index) or "search"
    reseg_overflow: int = 0         # tuples that hit a full exchange slot
    seg_slab: str = ""              # ROS slab "hit"/"miss", "+wos" when a
    #                                 trickle-load delta slab was appended
    snapshot_epoch: int = 0         # pinned cluster snapshot this query read
    # fault/failover telemetry (core/faults.py): failovers = mid-query
    # node crashes absorbed by replanning onto buddies at the pinned
    # epoch; fault_retries = transient-fault attempt retries; injected =
    # fault actions fired while this query ran
    failovers: int = 0
    fault_retries: int = 0
    faults_injected: int = 0


def execute(db: VerticaDB, q, *, as_of: Optional[int] = None,
            plan=None, mesh=None,
            mesh_axis: str = "data"
            ) -> Tuple[Dict[str, np.ndarray], ExecStats]:
    """Run a logical plan (LogicalQuery, node tree, builder, or the legacy
    Query shim).  ``plan`` (from planner.plan_query) may be supplied;
    otherwise the planner is invoked.

    When a ``mesh`` is passed -- or the database has one attached
    (``db.attach_mesh()``) -- aggregate queries route through the
    segmented multi-device executor (engine/segmented.py) and fall back
    here for shapes outside its subset."""
    qid = next(_query_ids)
    with obs.span("exec.query", qid=qid):
        return _execute(db, q, qid, as_of, plan, mesh, mesh_axis)


def _execute(db: VerticaDB, q, qid: int, as_of: Optional[int], plan,
             mesh, mesh_axis: str
             ) -> Tuple[Dict[str, np.ndarray], ExecStats]:
    from ..planner.planner import plan_query

    t0 = time.perf_counter()
    with obs.span("exec.plan"):
        q = as_ir(q)
        if plan is None:
            plan = plan_query(db, q)
        if mesh is None:
            mesh = getattr(db, "mesh", None)
            mesh_axis = getattr(db, "mesh_axis", mesh_axis)
    frontend_s = time.perf_counter() - t0
    from ..core.database import QueryRejectedError
    from ..core.faults import NodeCrashError, TransientFaultError

    stats = ExecStats(projection=plan.projection,
                      groupby_algorithm=plan.groupby_algorithm,
                      join_strategy=plan.join_strategy,
                      frontend_s=frontend_s, query_id=qid)
    faults = getattr(db, "faults", None)
    f0 = faults.total_fired if faults is not None else 0
    # pin the cluster snapshot epoch for the query's lifetime (§5):
    # trickle-load commits advancing the epoch concurrently cannot shift
    # what this query sees, and the AHM cannot purge the history it
    # reads.  EVERYTHING past the pin -- including failover replans --
    # runs inside the try so no failure path can leak a pin and freeze
    # the AHM forever.
    as_of = db.epochs.pin(as_of)
    try:
        stats.snapshot_epoch = as_of
        bc = db.block_cache.stats
        bc_h0, bc_m0 = bc.hits, bc.misses

        def _finish(out, *, final: bool = True):
            if final:
                with obs.span("exec.finalize"):
                    out = _finalize(q, out)
            stats.block_cache_hits = bc.hits - bc_h0
            stats.block_cache_misses = bc.misses - bc_m0
            if faults is not None:
                stats.faults_injected = faults.total_fired - f0
            stats.wall_s = time.perf_counter() - t0
            return out, stats

        retries_left = int(getattr(db, "max_failover_retries", 2))
        while True:
            try:
                return _execute_attempt(db, q, plan, as_of, mesh,
                                        mesh_axis, stats, _finish)
            except NodeCrashError as e:
                # mid-query node failure: bounded query-level failover.
                # Replan at the SAME pinned epoch -- the planner routes
                # the dead node's segments to buddies (identical rows at
                # as_of, §4.3), so the retried query reads the identical
                # snapshot; exhausted redundancy surfaces the planner's
                # SegmentUnavailableError instead.
                stats.failovers += 1
                if retries_left <= 0:
                    raise QueryRejectedError(
                        f"failover budget exhausted (node {e.node} "
                        f"crashed at {e.point})",
                        epoch=as_of, attempts=stats.failovers) from e
                retries_left -= 1
                plan = plan_query(db, q)
                stats.projection = plan.projection
                stats.groupby_algorithm = plan.groupby_algorithm
                stats.join_strategy = plan.join_strategy
            except TransientFaultError as e:
                # per-point retry budgets already ran (faults.with_retries)
                raise QueryRejectedError(
                    f"transient retry budget exhausted: {e}",
                    epoch=as_of, attempts=stats.failovers) from e
    finally:
        db.epochs.unpin(as_of)


def _execute_attempt(db: VerticaDB, q: LogicalQuery, plan, as_of: int,
                     mesh, mesh_axis: str, stats: ExecStats, _finish):
    """One execution attempt of a pinned-epoch query (the body of
    ``execute``'s failover retry loop)."""
    try:
        # --- segmented multi-device path (explicit opt-in via mesh) ---
        if mesh is not None:
            from . import segmented
            res = segmented.execute_segmented(db, q, plan, as_of, mesh,
                                              mesh_axis, stats)
            if res is not None:
                return _finish(res)

        # --- scalar COUNT directly on RLE runs (predicate on sort leader) ---
        if plan.scalar_rle:
            with obs.span("exec.rle"):
                res = _rle_scalar_count(db, q, plan, as_of)
            if res is not None:
                stats.groupby_algorithm = "rle-scalar"
                return _finish(res)

        # --- RLE-direct fast path: aggregate on encoded data, zero decode ---
        if rle_direct_eligible(q, plan):
            with obs.span("exec.rle"):
                res = _rle_groupby(db, q, plan, as_of)
            if res is not None:
                return _finish(res)
            stats.groupby_algorithm = "sort (rle fallback)"
            plan = dataclasses.replace(plan, groupby_algorithm="sort")

        # --- warm path: cached fused scan->join->predicate->aggregate ---
        res = fused_exec.execute_fused(db, q, plan, as_of, stats)
        if res is not None:
            stats.fused = True
            return _finish(res)

        # --- build sides + SIP (§6.1), one per join in plan order ---
        with obs.span("exec.join_build"):
            builds, dense = fused_exec.build_join_sides(db, q, as_of,
                                                         stats)
        sips: List[Callable] = []
        for ji, spec in enumerate(q.joins):
            if plan.sip_joins and plan.sip_joins[ji]:
                sips.append(sip_filter(builds[ji][spec.dim_key],
                                       spec.fact_key))
                stats.sip_applied = True
        sip = _combine_sips(sips)

        # --- scan (SMA pruning + predicate + SIP pushed down) ---
        proj = db.catalog.projections[plan.projection]
        need = q.scan_columns(proj)
        # predicates over join outputs / derived columns defer past the scan
        scan_pred = q.scan_predicate(proj.columns)
        scans = []
        # ROS containers: one batched device-cached scan over every source
        # (engine/executor.py), replacing the per-container Python loop
        ros = fused_exec.scan_stores_batched(db, plan, sorted(need),
                                             scan_pred, sip, as_of, stats)
        if ros is not None:
            scans.append(ros)
        with obs.span("exec.scan"):
            scans.extend(wos_scan_results(db, plan, need, scan_pred, sip,
                                          as_of))
        merged = ops.concat_scans(scans)
        if merged is None:
            return _finish(_empty_result(q))
        stats.blocks_pruned = merged.pruned_blocks
        stats.blocks_total = merged.total_blocks
        cols, valid = dict(merged.columns), merged.valid
        stats.rows_scanned = int(cols[next(iter(cols))].shape[0])

        # --- joins (in plan order; later probes may use earlier outputs) ---
        for spec, build, idx in zip(q.joins, builds, dense):
            cols, valid = ops.hash_join(build, spec.dim_key, cols,
                                        spec.fact_key, valid, how=spec.how,
                                        dense=idx)

        # --- derived projections, then any deferred predicate ---
        for name, e in q.derived:
            cols[name] = e(cols)
        if scan_pred is None and q.predicate is not None:
            valid = valid & jnp.asarray(q.predicate(cols), bool)

        # --- groupby / aggregate / plain select ---
        if q.group_by or q.aggs:
            out = _run_groupby(q, plan, cols, valid, stats)
        else:
            keep = set(q.columns) | {n for n, _ in q.derived}
            cols = {c: v for c, v in cols.items()
                    if (c in keep) or (not keep and c != "_matched")}
            with obs.span("exec.result_sync"):
                mask, cols = jax.device_get((valid, cols))
            out = {c: np.asarray(v)[mask] for c, v in cols.items()}
        return _finish(out)
    finally:
        # per-attempt bookkeeping only; the epoch pin is released by
        # ``execute`` (one pin covers every failover attempt, so the
        # retried query replans at the identical snapshot)
        pass


def wos_scan_results(db: VerticaDB, plan, need, scan_pred, sip,
                     as_of: int) -> List[ops.ScanResult]:
    """Unencoded side-scans of every pending WOS behind ``plan.sources``
    (rows the tuple mover hasn't drained yet participate in queries
    immediately).  Shared by the single-query pipeline and the serving
    shared-scan path (engine/serving.py) so trickle-loaded rows are
    byte-identically visible to both."""
    scans: List[ops.ScanResult] = []
    for host, owner in plan.sources:
        store = db.nodes[host].stores[owner]
        wos = fused_exec.wos_visible(store, as_of)
        if wos is not None:
            data, vis = wos
            cols = {c: jnp.asarray(data[c]) for c in need}
            valid = jnp.asarray(vis)
            if scan_pred is not None:
                valid = valid & jnp.asarray(scan_pred(cols), bool)
            if sip is not None:
                valid = valid & sip(cols)
            scans.append(ops.ScanResult(cols, valid))
    return scans


# ---------------------------------------------------------------------------
# result shaping shared by every path (incl. the fused executor)
# ---------------------------------------------------------------------------

def _finalize(q: LogicalQuery, out: Dict[str, np.ndarray]
              ) -> Dict[str, np.ndarray]:
    """HAVING -> ORDER BY (multi-key, per-key direction) -> LIMIT, on the
    (small) host-side result."""
    if q.having is not None and out:
        n = len(next(iter(out.values())))
        if n:
            m = np.asarray(q.having(out), bool)
            out = {c: np.asarray(v)[m] for c, v in out.items()}
    if q.order_by and out:
        n = len(next(iter(out.values())))
        if n:
            keys = []
            for c, desc in reversed(q.order_by):
                k = np.asarray(out[c])
                if desc:
                    # descending without precision loss: bit-complement
                    # for ints/bools (= -k-1, never overflows), negate
                    # floats
                    k = ~k if k.dtype.kind in "bui" else -k
                keys.append(k)
            order = np.lexsort(keys)       # last key = primary
            out = {c: np.asarray(v)[order] for c, v in out.items()}
    if q.limit is not None:
        out = {c: v[: q.limit] for c, v in out.items()}
    return out


def _empty_result(q: LogicalQuery) -> Dict[str, np.ndarray]:
    """Structured empty output for a fully pruned / empty scan (same key
    set as the non-empty path)."""
    out = {c: np.zeros(0, np.int64) for c in q.columns}
    for name, _ in q.derived:
        out[name] = np.zeros(0)
    for g in q.group_by:
        out[g] = np.zeros(0, np.int64)
    if q.group_by:
        out["group_count"] = np.zeros(0, np.int64)
    for name, _, kind in q.aggs:
        out[name] = np.zeros(1) if not q.group_by else np.zeros(0)
    return out


def _combine_sips(sips: List[Callable]) -> Optional[Callable]:
    if not sips:
        return None
    if len(sips) == 1:
        return sips[0]

    def apply(cols):
        m = sips[0](cols)
        for s in sips[1:]:
            m = m & s(cols)
        return m

    return apply


# ---------------------------------------------------------------------------
# RLE-direct paths (single-column group keys on encoded data)
# ---------------------------------------------------------------------------

def rle_direct_eligible(q: LogicalQuery, plan) -> bool:
    """Shape test for the RLE-direct GroupBy route, shared by the
    single-node dispatch below and the segmented executor (which routes
    the same queries per node and merges, instead of slabbing 2M decoded
    rows across the mesh only to count runs it already had encoded)."""
    return plan.groupby_algorithm == "rle" and not q.joins \
        and q.predicate is None


def _rle_scalar_count(db: VerticaDB, q: LogicalQuery, plan, as_of: int
                      ) -> Optional[Dict[str, np.ndarray]]:
    """COUNT(*) with a range predicate on the RLE-encoded sort leader:
    sum run lengths whose value passes -- O(runs), no decode (§6.1; the
    Pallas twin is kernels/rle_scan_agg.py)."""
    from .expr import exact_int_interval

    proj = db.catalog.projections[plan.projection]
    leader = proj.sort_order[0]
    if q.predicate is not None:
        iv = exact_int_interval(q.predicate)
        if iv is None or iv[0] != leader:
            return None
        _, lo, hi = iv
    else:
        lo = hi = None
    lo = -np.inf if lo is None else lo
    hi = np.inf if hi is None else hi
    total = 0
    for host, owner in plan.sources:
        store = db.nodes[host].stores[owner]
        if store.wos.n_rows:
            return None
        for c in store.containers:
            if store.delete_vectors.get(c.id) or (c.epochs > as_of).any():
                return None
            colenc = c.columns[leader]
            if colenc.encoding != Encoding.RLE:
                return None
            rv = colenc.arrays["run_values"].reshape(-1)
            rl = colenc.arrays["run_lengths"].reshape(-1)
            m = (rv >= lo) & (rv <= hi) & (rl > 0)
            cnt = int(rl[m].sum())
            pad = colenc.n_blocks * colenc.block_rows - c.n_rows
            if pad and c.n_rows:
                last = rv[np.flatnonzero(rl)[-1]]
                if lo <= last <= hi:
                    cnt -= pad
            total += cnt
    out = {}
    for name, _, _ in q.aggs:
        out[name] = np.asarray([total])
    return out


def _rle_groupby(db: VerticaDB, q: LogicalQuery, plan, as_of: int
                 ) -> Optional[Dict[str, np.ndarray]]:
    """COUNT GROUP BY key straight off RLE runs (§6.1 'operate directly on
    encoded data'). Requires no pending deletes and fully-committed
    containers; otherwise returns None and the caller decodes."""
    from ..planner.planner import _domain_estimate

    group = q.group_by[0]
    proj = db.catalog.projections[plan.projection]
    dom = _domain_estimate(db, proj, group)
    if dom is None or dom > plan.dense_domain_limit:
        return None
    parts, pads = [], []
    for host, owner in plan.sources:
        store = db.nodes[host].stores[owner]
        if store.wos.n_rows:
            return None
        for c in store.containers:
            if store.delete_vectors.get(c.id) or (c.epochs > as_of).any():
                return None
            if c.columns[group].encoding != Encoding.RLE:
                return None
            parts.append(ops.groupby_rle(c.columns[group],
                                         c.smas[group].counts,
                                         dom)["group_count"])
            # subtract tail-block padding (pad value = last value)
            pad = c.columns[group].n_blocks * \
                c.columns[group].block_rows - c.n_rows
            if pad and c.n_rows:
                pads.append((int(c.decode_column(group)[-1]), pad))
    with obs.span("exec.result_sync"):
        parts = jax.device_get(parts)
    total = np.zeros(dom, np.int64)
    for counts in parts:
        total += np.asarray(counts)
    for last, pad in pads:
        total[last] -= pad
    sel = total > 0
    out = {group: np.flatnonzero(sel), "group_count": total[sel]}
    for name, _, kind in q.aggs:
        if kind == "count":
            out[name] = total[sel]
    return out


# ---------------------------------------------------------------------------
# generic GroupBy over (possibly composite) keys
# ---------------------------------------------------------------------------

def _run_groupby(q: LogicalQuery, plan, cols, valid, stats
                 ) -> Dict[str, np.ndarray]:
    aggs = tuple(q.aggs)
    values = {c: cols[c] for _, c, kind in aggs
              if kind != "count" and c != "*"}
    if not q.group_by:
        # scalar aggregate: single group
        keys = jnp.zeros(valid.shape[0], jnp.int32)
        res = ops.groupby_dense(keys, valid, values, 1, aggs)
        with obs.span("exec.result_sync"):
            res = jax.device_get(res)
        return {name: np.asarray(v)[:1] for name, v in res.items()}

    if not bool(valid.any()):
        out = {g: np.zeros(0, np.int64) for g in q.group_by}
        out["group_count"] = np.zeros(0, np.int64)
        for name, _, _ in aggs:
            out[name] = np.zeros(0)
        return out

    algo = plan.groupby_algorithm
    if algo == "rle":
        algo = "sort"

    key_cols = [cols[g] for g in q.group_by]
    packed, lows, domains = key_cols[0], None, None
    if len(key_cols) > 1 or algo == "dense":
        # observed per-key bounds for packing / the dense domain (tighter
        # than SMA estimates; one host sync each -- this is the cold
        # path).  A single-key sort GroupBy needs none of this.
        lows, domains = [], []
        for k in key_cols:
            big = int(jnp.iinfo(k.dtype).max) if k.dtype.kind == "i" \
                else 2**30
            lo = int(jnp.where(valid, k, big).min())
            hi = int(jnp.where(valid, k, -big).max())
            lows.append(min(lo, 0))
            domains.append(hi - lows[-1] + 1)
        total = 1
        for d in domains:
            total *= d
        if total >= _PACK_LIMIT:
            # packed keys would overflow device int32: host fallback
            stats.groupby_algorithm = "host-unique (domain overflow)"
            return _groupby_host(q, cols, valid, values, aggs)
        if algo == "dense" and total > plan.dense_domain_limit:
            algo = "sort"   # runtime switch (§6.1)
            stats.groupby_algorithm = "sort (runtime switch)"
        if len(key_cols) > 1 or lows[0] != 0:
            packed = ops.pack_keys(key_cols, domains, lows)
        else:
            lows = domains = None    # raw single key: no unpack needed

    if algo == "dense":
        res = ops.groupby_dense(packed.astype(jnp.int32), valid, values,
                                total, aggs)
        with obs.span("exec.result_sync"):
            res = jax.device_get(res)
        counts = np.asarray(res["group_count"])
        sel = counts > 0
        gkeys = np.flatnonzero(sel)
        out = {"group_count": counts[sel]}
        for name, _, _ in aggs:
            out[name] = np.asarray(res[name])[sel]
    else:
        res = ops.groupby_sort(packed, valid, values, plan.max_groups, aggs)
        with obs.span("exec.result_sync"):
            res = jax.device_get(res)
        n = int(res["n_groups"])
        if n > plan.max_groups:
            # more distinct groups than the sort cap: groupby_sort would
            # silently merge the tail -- host fallback keeps it exact
            stats.groupby_algorithm = "host-unique (group overflow)"
            return _groupby_host(q, cols, valid, values, aggs)
        gkeys = np.asarray(res["group_keys"])[:n]
        out = {"group_count": np.asarray(res["group_count"])[:n]}
        for name, _, _ in aggs:
            out[name] = np.asarray(res[name])[:n]
    unpacked = [gkeys] if domains is None \
        else ops.unpack_keys(gkeys, domains, lows)
    for g, kv in zip(q.group_by, unpacked):
        out[g] = kv
    return out


def _groupby_host(q: LogicalQuery, cols, valid, values, aggs
                  ) -> Dict[str, np.ndarray]:
    """numpy unique-based GroupBy for key domains too wide to pack into
    the device integer width.  Small-result assumption holds (grouped
    outputs are aggregated), only the scan stays device-side."""
    mask = np.asarray(valid)
    keys2d = np.stack([np.asarray(cols[g])[mask] for g in q.group_by], 1)
    uniq, inv = np.unique(keys2d, axis=0, return_inverse=True)
    n_groups = len(uniq)
    counts = np.bincount(inv, minlength=n_groups)
    out = {g: uniq[:, i] for i, g in enumerate(q.group_by)}
    out["group_count"] = counts
    for name, c, kind in aggs:
        if kind == "count":
            out[name] = counts
            continue
        v = np.asarray(values[c])[mask]
        if kind in ("sum", "avg"):
            acc = np.bincount(inv, weights=v, minlength=n_groups)
            out[name] = acc / np.maximum(counts, 1) if kind == "avg" \
                else acc
        elif kind == "min":
            acc = np.full(n_groups, np.inf)
            np.minimum.at(acc, inv, v)
            out[name] = acc
        else:
            acc = np.full(n_groups, -np.inf)
            np.maximum.at(acc, inv, v)
            out[name] = acc
    return out
