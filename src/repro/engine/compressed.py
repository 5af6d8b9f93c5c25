"""Compressed-domain execution: code-space predicates + late materialization.

The paper's EE "operates directly on encoded data" (§6.1): predicates on
dictionary-encoded columns are evaluated against the *codes*, GROUP BY keys
stay in code space, and only the rows that survive are ever decoded.  This
module is our analog for the fused aggregate path (engine/executor.py):

1.  **Plan-time rewrite** -- ``plan_compressed_scan`` decomposes the scan
    predicate into per-column integer intervals (expr.interval_decompose).
    For a BLOCK_DICT column the interval [lo, hi] becomes a per-block code
    range via binary search of the block dictionary: codes are assigned in
    sorted value order, so ``searchsorted(dict, lo/hi)`` brackets exactly
    the codes whose values fall inside the interval.  No value
    materialization happens to evaluate the predicate.

2.  **Code-domain GROUP BY** -- when every container encodes a group-by
    column as BLOCK_DICT, its container-global dictionaries are unioned
    and the per-block ``code_map`` composed into a block-code -> union-code
    remap.  The fused program then groups on union codes directly (a dense
    domain of exactly ``len(union)``), and the finish step translates codes
    back to values with one host-side take.  Because the union is sorted,
    code order == value order and the result rows come out byte-identical
    to the value-domain plan.

3.  **Late materialization** -- non-predicate payload columns are gathered
    for *surviving rows only*: randomly-accessible encodings (PLAIN,
    DELTA_VALUE, BLOCK_DICT, FLOAT_SCALED over those) gather straight out
    of the packed device payload (``gather_decode_jnp`` /
    ``gather_unpack``); sequential encodings (RLE, DELTA_RANGE,
    COMMON_DELTA) decode their SMA-surviving blocks into per-query
    temporaries that die with the scan -- the block cache only ever holds
    the packed payloads, which is what makes a constrained cache budget go
    2x+ further (BENCH_cstore.json "compression" row).

Eligibility is strict because the differential guarantee is byte-identity,
not allclose: integer intervals on INT columns only, conjunctions only;
anything else falls back to the decoded scan.  ``db.exec_mode`` picks the
policy ("auto" uses the compressed scan only when the decoded working set
is neither device-resident nor able to fit the cache budget comfortably,
so unconstrained workloads keep the exact legacy fast path -- cold and
warm, same plan signature).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.block_cache import KIND_DECODED, KIND_ENCODED
from ..core.encodings import (Encoding, EncodedColumn, _packed_width,
                              decode_jnp, device_bytes, gather_decode_jnp,
                              random_access_jnp, upload_jnp)
from ..core.types import SQLType
from ..kernels import ops as kops
from . import operators as ops
from .expr import Expr, interval_decompose

Interval = Tuple[Optional[int], Optional[int]]

# Jitted-closure cache for the scan's device programs.  The eager path
# costs ~1k python dispatches per query (decode + gather + mask ops per
# container), which dwarfs the actual device work; each (site, container,
# column) pair compiles once per shape instead.  Containers are immutable
# and their ids never reused, so closure staleness cannot occur; entries
# for retired containers are just dead weight (bounded by container
# count, tiny vs the arrays they produced).
_JIT_CACHE: Dict[tuple, object] = {}


def _jitted(key: tuple, fn):
    cached = _JIT_CACHE.get(key)
    if cached is None:
        cached = _JIT_CACHE[key] = jax.jit(fn)
    return cached


@dataclasses.dataclass
class CompressedScanPlan:
    """A plan-time rewrite of one fused scan into the code domain."""

    intervals: Dict[str, Interval]          # col -> inclusive int bounds
    containers: List[tuple]                 # [(store, ROSContainer), ...]
    need: List[str]                         # scan columns, sorted
    # group col -> sorted union dictionary (values); present only when the
    # column groups in code space
    group_dicts: Dict[str, np.ndarray]
    # (container id, group col) -> (n_blocks, dict_size) block-code ->
    # union-code remap
    union_maps: Dict[Tuple[int, str], np.ndarray]
    as_of: int
    # plan-cache identity: symbol widths of every packed stream touched +
    # union dictionary sizes (dictionary growth must miss the plan cache)
    sig_suffix: tuple

    # ------------------------------------------------------------ params --

    def key_domains(self, q, plan) -> Optional[Tuple[Optional[int], ...]]:
        """Per-key domains with dict-grouped columns overridden by their
        union dictionary size (codes are a dense [0, len(union)) domain)."""
        if not q.group_by:
            return None
        base = plan.key_domains or (None,) * len(q.group_by)
        return tuple(len(self.group_dicts[g]) if g in self.group_dicts
                     else base[i] for i, g in enumerate(q.group_by))

    # -------------------------------------------------------------- scan --

    def scan(self, db, predicate: Optional[Expr], sip,
             stats) -> Optional[ops.ScanResult]:
        """Code-domain scan: predicate in code/value space over packed
        payloads, ONE host sync for the survivor set, then late-materialize
        ``need`` columns for survivors only."""
        cache = getattr(db, "block_cache", None)

        def enc_of(c, name):
            col = c.columns[name]
            if cache is None:
                return col, upload_jnp(col)
            return col, cache.get_or_put(c.id, name, KIND_ENCODED,
                                         lambda: upload_jnp(col),
                                         device_bytes)

        from .executor import cached_valid

        pruned = total = 0
        # (container, kept_idx, device mask, block_rows, encs, tmps)
        segs = []
        with obs.span("exec.cd_mask"):
            for store, c in self.containers:
                first = c.columns[self.need[0]]
                nb, br = first.n_blocks, first.block_rows
                total += nb
                # identical SMA pruning to scan_stores_batched (stats parity)
                keep = np.ones(nb, dtype=bool)
                if predicate is not None:
                    for colname, (lo, hi) in predicate.bounds().items():
                        if colname in c.smas:
                            keep &= c.smas[colname].prune_blocks(lo, hi)
                kept = np.flatnonzero(keep)
                pruned += nb - kept.size
                if kept.size == 0:
                    continue
                stats.containers_scanned += 1
                counts = c.smas[self.need[0]].counts
                vblocks = cached_valid(cache, store, c, self.as_of, counts)

                # ONE jitted mask program per container: validity slice plus
                # every interval predicate (code range or decoded temporary)
                encs = {name: enc_of(c, name)[1] for name in self.need}
                meta = {name: c.columns[name] for name in self.need}
                bounds: Dict[str, object] = {}
                shape_key = []
                for name, (lo, hi) in sorted(self.intervals.items()):
                    col = meta[name]
                    if col.encoding == Encoding.BLOCK_DICT \
                            and "codes_packed" in col.arrays:
                        clo, chi = _code_range(col, lo, hi)
                        bounds[name] = jnp.asarray(
                            np.stack([clo[kept], chi[kept]]))
                        shape_key.append((name, "dict"))
                    else:
                        # literals ride in as device scalars so a new literal
                        # reuses the compiled program
                        bounds[name] = tuple(
                            None if b is None else jnp.asarray(b)
                            for b in (lo, hi))
                        shape_key.append((name, lo is None, hi is None))
                fn = _jitted(("cmask", c.id, tuple(shape_key)),
                             _make_mask_fn(meta, dict(self.intervals)))
                mask, tmps = fn(vblocks, jnp.asarray(kept), encs, bounds)
                segs.append((c, kept, mask, br, encs, tmps))
            stats.blocks_pruned, stats.blocks_total = pruned, total
            if not segs:
                return None

            flat = segs[0][2].reshape(-1) if len(segs) == 1 else \
                jnp.concatenate([m.reshape(-1) for _, _, m, _, _, _ in segs])
        # the single host sync of the scan: survivor positions
        with obs.span("exec.cd_sync"):
            surv = np.flatnonzero(np.asarray(flat))
        n = int(surv.size)
        stats.rows_scanned = int(flat.shape[0])
        with obs.span("exec.cd_gather"):
            # pad to the next pow2 so survivor-count jitter reuses programs
            bucket = max(1, 1 << (n - 1).bit_length()) if n else 1

            parts: Dict[str, List[jax.Array]] = {name: []
                                                 for name in self.need}
            off = 0
            for c, kept, mask, br, encs, tmps in segs:
                lo_off, off = off, off + kept.size * br
                s = surv[(surv >= lo_off) & (surv < off)] - lo_off
                if s.size == 0:
                    continue
                lb, r = np.divmod(s, br)             # local kept-block, row
                # one upload: (global block, local kept-block, row) rows
                idx = jnp.asarray(np.stack([kept[lb], lb, r]))
                meta = {name: c.columns[name] for name in self.need}
                umaps = {g: self.union_maps[(c.id, g)]
                         for g in self.group_dicts if g in self.need}
                # ONE jitted gather program per container: every need column
                # (union codes / random-access gather / temp fancy-index)
                fn = _jitted(("cgat", c.id, tuple(self.need),
                              tuple(sorted(umaps)), tuple(sorted(tmps))),
                             _make_gather_fn(meta, umaps, tuple(self.need)))
                out = fn(encs, tmps, idx)
                for name in self.need:
                    parts[name].append(out[name])

            cols: Dict[str, jax.Array] = {}
            any_c = self.containers[0][1]
            for name in self.need:
                ps = parts[name]
                if not ps:                           # zero survivors
                    dt = self._empty_dtype(name)
                    cols[name] = jnp.zeros(bucket, dt)
                else:
                    # concat + dtype canonicalization (the exact dtypes the
                    # decoded scan would produce) + zero-pad to the bucket
                    fin = _jitted(("fin", len(ps), bucket),
                                  _make_finish_fn(bucket))
                    cols[name] = fin(tuple(ps))
                col = any_c.columns[name]
                if col.encoding == Encoding.FLOAT_SCALED \
                        and name not in self.group_dicts:
                    # the gather program returned the INNER integer lanes;
                    # apply the scale division eagerly so its rounding is
                    # bit-identical to the eager decode_jnp path
                    cols[name] = cols[name].astype(jnp.float32) / col.scale
            valid = jnp.arange(bucket) < n
            if sip is not None:
                valid = valid & sip(cols)
        return ops.ScanResult(cols, valid, pruned, total)

    def _empty_dtype(self, name):
        if name in self.group_dicts:
            return jnp.int32
        col = self.containers[0][1].columns[name]
        return jnp.float64 if col.sql_type == SQLType.FLOAT else jnp.int64

    # ------------------------------------------------------------ finish --

    def translate(self, out: Optional[Dict[str, np.ndarray]]
                  ) -> Optional[Dict[str, np.ndarray]]:
        """Union codes -> values on the (small) host-side result."""
        if out is None:
            return None
        for g, union in self.group_dicts.items():
            if g in out:
                out[g] = union[np.asarray(out[g], dtype=np.int64)]
        return out


def _make_mask_fn(meta: Dict[str, EncodedColumn],
                  intervals: Dict[str, Interval]):
    """Build the per-container mask program (traced once per shape set):
    validity slice + every interval predicate.  Dict columns compare
    unpacked codes against per-block code ranges; other columns decode to
    a temporary (returned for reuse by the gather program).  The inner
    function's name names its program in a profile (``jit_cd_mask``), as
    the gather's and the finish's do theirs."""
    @jax.named_scope("cd_decode")
    def cd_mask(vblocks, kept, encs, bounds):
        mask = vblocks[kept]
        tmps = {}
        for name in sorted(intervals):
            col = meta[name]
            if col.encoding == Encoding.BLOCK_DICT \
                    and "codes_packed" in col.arrays:
                w = _packed_width(col.arrays, "codes_packed",
                                  col.block_rows)
                codes = kops.bitunpack(encs[name]["codes_packed"][kept],
                                       w, col.block_rows)
                b = bounds[name]
                mask = mask & (codes >= b[0][:, None]) \
                    & (codes <= b[1][:, None])
            else:
                dec = decode_jnp(col, encs[name])[kept]
                tmps[name] = dec
                lo, hi = bounds[name]
                if lo is not None:
                    mask = mask & (dec >= lo)
                if hi is not None:
                    mask = mask & (dec <= hi)
        return mask, tmps
    return cd_mask


def _make_gather_fn(meta: Dict[str, EncodedColumn],
                    umaps: Dict[str, np.ndarray], need: tuple):
    """Build the per-container late-materialization program: every need
    column gathered for survivor rows only.  ``idx`` rows are (global
    block, local kept-block, row-in-block)."""
    from ..kernels.bitunpack import gather_unpack

    @jax.named_scope("cd_gather")
    def cd_gather(encs, tmps, idx):
        b, lb, r = idx[0], idx[1], idx[2]
        out = {}
        for name in need:
            col = meta[name]
            # FLOAT_SCALED: gather the INNER integer lanes here and leave
            # the `/scale` division to the eager finish step -- inside jit
            # XLA rewrites division-by-constant into multiply-by-
            # reciprocal (1 ULP off), which would break byte-identity with
            # the eagerly-decoded scan
            if col.encoding == Encoding.FLOAT_SCALED:
                col = col.inner
            if name in umaps:
                # group col: gather union CODES, never the values
                w = _packed_width(col.arrays, "codes_packed",
                                  col.block_rows)
                codes = gather_unpack(encs[name]["codes_packed"], w, b, r)
                out[name] = jnp.asarray(umaps[name])[b, codes]
            elif name in tmps:
                # already decoded (kept-sliced) by the mask program
                out[name] = tmps[name][lb, r]
            elif random_access_jnp(col):
                out[name] = gather_decode_jnp(col, encs[name], b, r)
            else:
                # sequential encoding: decode, then fancy-index survivors
                out[name] = decode_jnp(col, encs[name])[b, r]
        return out
    return cd_gather


def _make_finish_fn(bucket: int):
    """Concat survivor parts, canonicalize dtype exactly like the decoded
    scan, zero-pad to the pow2 bucket."""
    def cd_finish(ps):
        v = ps[0] if len(ps) == 1 else jnp.concatenate(ps)
        v = v.astype(jnp.float64 if v.dtype.kind == "f" else jnp.int64)
        if bucket > v.shape[0]:
            v = jnp.concatenate([v, jnp.zeros(bucket - v.shape[0],
                                              v.dtype)])
        return v
    return cd_finish


def _code_range(col: EncodedColumn, lo: Optional[int], hi: Optional[int]
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-block inclusive code range [clo, chi] matching value interval
    [lo, hi].  Blocks with no matching value get clo > chi (empty)."""
    dv, dn = col.arrays["dict_values"], col.arrays["dict_n"]
    nb = dv.shape[0]
    clo = np.zeros(nb, np.int64)
    chi = np.zeros(nb, np.int64)
    for i in range(nb):
        u = dv[i, : int(dn[i])]
        clo[i] = 0 if lo is None else np.searchsorted(u, lo, side="left")
        chi[i] = (int(dn[i]) if hi is None
                  else int(np.searchsorted(u, hi, side="right"))) - 1
    return clo.astype(np.int32), chi.astype(np.int32)


def plan_compressed_scan(db, q, plan, need, scan_pred: Optional[Expr],
                         as_of: int) -> Optional[CompressedScanPlan]:
    """Rewrite an eligible fused scan into the code domain, or None.

    Eligible: exec_mode allows it, the scan predicate decomposes into
    per-column integer intervals, and every interval column is INT-typed in
    every container (interval semantics are exact only for integers).  In
    "auto" mode the rewrite additionally requires that the decoded working
    set is NOT already device-resident and does NOT comfortably fit the
    cache budget -- a warm decoded scan is strictly faster than
    re-gathering, so unconstrained workloads keep the exact legacy path
    (same plan signature, cold and warm) and the compressed scan engages
    only when decoded residency is unattainable."""
    mode = getattr(db, "exec_mode", "auto")
    if mode == "decoded" or scan_pred is None:
        return None
    intervals = interval_decompose(scan_pred)
    if not intervals:
        return None
    need = sorted(set(need) | set(intervals))

    pairs = []
    for host, owner in plan.sources:
        store = db.nodes[host].stores[owner]
        for c in store.containers:
            pairs.append((store, c))
    if not pairs:
        return None
    for name in intervals:
        for _, c in pairs:
            col = c.columns.get(name)
            if col is None or col.sql_type != SQLType.INT:
                return None
    if mode != "compressed":
        cache = getattr(db, "block_cache", None)
        if cache is None:
            return None
        if all((c.id, name, KIND_DECODED) in cache
               for _, c in pairs for name in need):
            return None
        # budget comfortably fits the decoded working set: let the legacy
        # path decode-and-cache (identical plan signature cold and warm,
        # so repeats stay plan-cache hits); compressed is for budgets
        # where decoded residency is unattainable
        dec_bytes = sum(c.columns[nm].n_blocks * c.columns[nm].block_rows
                        * 4 for _, c in pairs for nm in need
                        if nm in c.columns)
        if cache.budget_bytes >= 2 * dec_bytes:
            return None

    # code-domain GROUP BY: a group col groups on union codes only when it
    # carries no other role in the program (agg input, join key, derived
    # input) -- those need the real values inside the fused program
    used_as_value = {c for _, c, kind in q.aggs
                     if kind != "count" and c != "*"}
    for j in q.joins:
        used_as_value.add(j.fact_key)
    for _, e in q.derived:
        used_as_value |= e.columns()
    group_dicts: Dict[str, np.ndarray] = {}
    union_maps: Dict[Tuple[int, str], np.ndarray] = {}
    for g in q.group_by:
        if g in used_as_value:
            continue
        encs = [c.columns.get(g) for _, c in pairs]
        if not all(e is not None and e.encoding == Encoding.BLOCK_DICT
                   and "codes_packed" in e.arrays for e in encs):
            continue
        union = np.unique(np.concatenate([e.arrays["global_dict"]
                                          for e in encs]))
        for (_, c), e in zip(pairs, encs):
            umap = np.searchsorted(union, e.arrays["global_dict"]) \
                .astype(np.int32)[e.arrays["code_map"]]
            union_maps[(c.id, g)] = np.ascontiguousarray(umap)
        group_dicts[g] = union

    sig_suffix = (
        "cdom",
        tuple(sorted((c.id, name) + c.columns[name].width_signature()
                     for _, c in pairs for name in need
                     if name in c.columns)),
        tuple(sorted((g, len(u)) for g, u in group_dicts.items())),
    )
    return CompressedScanPlan(dict(intervals), pairs, list(need),
                              group_dicts, union_maps, as_of, sig_suffix)
