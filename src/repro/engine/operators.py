"""Vectorized execution-engine operators (paper §6.1), jnp-based.

Adaptation (DESIGN.md): the pull-model multi-threaded pipeline becomes XLA
programs over block-structured columns; intra-node thread parallelism
becomes SPMD/grid parallelism. The operator *algebra* is the paper's:

  Scan (SMA pruning + predicate + SIP), GroupBy (dense-hash / sort /
  pipelined-on-sorted / RLE-direct / prepass), Join (lookup a.k.a. hash,
  merge on sorted), Sort, TopK, Analytic, ExprEval.

'Operate directly on encoded data': groupby_rle aggregates straight from
(run_value, run_length) pairs without decoding -- the flagship C-Store
move; kernels/rle_scan_agg.py is its Pallas twin for real TPUs.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.encodings import EncodedColumn, Encoding, decode_jnp
from ..core.sma import ColumnSMA
from ..core.storage import ROSContainer
from .expr import Expr

AGGS = ("sum", "count", "min", "max", "avg")


# ---------------------------------------------------------------------------
# Scan: container -> (columns dict, valid mask), with SMA pruning
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ScanResult:
    columns: Dict[str, jax.Array]   # flat (n,) device arrays
    valid: jax.Array                # (n,) bool
    pruned_blocks: int = 0
    total_blocks: int = 0


def scan_container(c: ROSContainer, columns: Sequence[str],
                   predicate: Optional[Expr] = None,
                   deleted: Optional[np.ndarray] = None,
                   sip: Optional[Callable] = None) -> Optional[ScanResult]:
    """Scan one ROS container: SMA-prune blocks, decode survivors on
    device, apply the predicate (and any SIP filter) as a mask."""
    need = set(columns) | (predicate.columns() if predicate else set())
    first = c.columns[next(iter(need))]
    nb, br = first.n_blocks, first.block_rows

    # --- container/block pruning from predicate bounds (paper §3.5) ---
    keep = np.ones(nb, dtype=bool)
    if predicate is not None:
        for colname, (lo, hi) in predicate.bounds().items():
            if colname in c.smas:
                keep &= c.smas[colname].prune_blocks(lo, hi)
    if not keep.any():
        return None
    kept_idx = np.flatnonzero(keep)

    cols = {}
    for name in need:
        blocks = decode_jnp(c.columns[name])            # (nb, br)
        cols[name] = blocks[kept_idx].reshape(-1)
    n = kept_idx.size * br
    # row validity: inside n_rows, not deleted
    counts = c.smas[next(iter(need))].counts
    pos_in_block = np.arange(br)[None, :]
    valid_np = pos_in_block < counts[kept_idx][:, None]
    if deleted is not None:
        # deleted is positional over the container; spread over padded blocks
        flat = np.zeros(nb * br, bool)
        flat[np.flatnonzero(deleted)] = True
        valid_np &= ~flat.reshape(nb, br)[kept_idx]
    valid = jnp.asarray(valid_np.reshape(-1))
    if predicate is not None:
        valid = valid & jnp.asarray(predicate(cols), bool)
    if sip is not None:
        valid = valid & sip(cols)
    return ScanResult({k: v for k, v in cols.items() if k in columns},
                      valid, int(nb - kept_idx.size), int(nb))


def concat_scans(results: List[ScanResult]) -> Optional[ScanResult]:
    results = [r for r in results if r is not None]
    if not results:
        return None
    cols = {k: jnp.concatenate([r.columns[k] for r in results])
            for k in results[0].columns}
    valid = jnp.concatenate([r.valid for r in results])
    return ScanResult(cols, valid,
                      sum(r.pruned_blocks for r in results),
                      sum(r.total_blocks for r in results))


# ---------------------------------------------------------------------------
# GroupBy
# ---------------------------------------------------------------------------

# Composite group-by keys (logical IR: group_by is a tuple of columns) are
# key-packed into one dense non-negative domain -- mixed-radix, last column
# fastest -- so every single-key path below (dense scatter, sort-based,
# the fused executor program, and on TPU the Pallas rle_grouped_agg /
# onehot kernels) applies unchanged to multi-column grouping.

def pack_keys(key_cols: Sequence[jax.Array],
              domains: Sequence[int],
              lows: Optional[Sequence[int]] = None) -> jax.Array:
    """Mix-radix pack: keys k_i in [lo_i, lo_i + d_i) -> one int key in
    [0, prod(d_i)).  Values outside their domain are clipped (callers
    guarantee domains via SMAs or a runtime min/max pass)."""
    lows = lows or (0,) * len(domains)
    packed = None
    for k, d, lo in zip(key_cols, domains, lows):
        k = jnp.clip(k.astype(_int_dtype()) - lo, 0, d - 1)
        packed = k if packed is None else packed * d + k
    return packed


def unpack_keys(packed: np.ndarray, domains: Sequence[int],
                lows: Optional[Sequence[int]] = None) -> List[np.ndarray]:
    """Host-side inverse of pack_keys over the (small) group-key output."""
    lows = lows or (0,) * len(domains)
    packed = np.asarray(packed).astype(np.int64)
    out: List[np.ndarray] = []
    for d, lo in zip(reversed(domains), reversed(lows)):
        out.append(packed % d + lo)
        packed = packed // d
    out.reverse()
    return out

# device dtypes: jax runs 32-bit by default; counts/sums accumulate in
# i32/f32 on device (benchmark-scale exact for counts; sums compared with
# tolerance), 64-bit when the caller enables jax_enable_x64.
def _int_dtype():
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


def _float_dtype():
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def _sentinel(dt, hi: bool):
    if jnp.issubdtype(dt, jnp.integer):
        info = jnp.iinfo(dt)
        return info.max if hi else info.min
    return jnp.inf if hi else -jnp.inf


def _prep_agg(values: jax.Array, valid: jax.Array, agg: str):
    v = values.astype(_float_dtype()) if values.dtype.kind == "f" \
        else values.astype(_int_dtype())
    if agg == "count":
        return valid.astype(_int_dtype())
    if agg == "min":
        return jnp.where(valid, v, _sentinel(v.dtype, True))
    if agg == "max":
        return jnp.where(valid, v, _sentinel(v.dtype, False))
    return jnp.where(valid, v, 0)   # sum / avg


_COMBINE = {"sum": "add", "count": "add", "avg": "add",
            "min": "min", "max": "max"}


@partial(jax.jit, static_argnames=("domain", "aggs"))
def groupby_dense(keys: jax.Array, valid: jax.Array,
                  values: Dict[str, jax.Array],
                  domain: int, aggs: Tuple[Tuple[str, str, str], ...]):
    """Dense-hash GroupBy: keys are small non-negative ints (the paper's
    'few-valued' case / dictionary-encoded); one scatter per aggregate.

    aggs: (out_name, in_col, agg_kind). Returns dict with per-key results
    over [0, domain) plus 'group_count'."""
    k = jnp.clip(keys, 0, domain - 1)
    out = {}
    counts = jnp.zeros(domain, _int_dtype()).at[k].add(
        valid.astype(_int_dtype()))
    out["group_count"] = counts
    for name, col_, agg in aggs:
        src = _prep_agg(values[col_] if agg != "count" else keys,
                        valid, agg)
        if _COMBINE[agg] == "add":
            acc = jnp.zeros(domain, src.dtype).at[k].add(src)
        elif _COMBINE[agg] == "min":
            acc = jnp.full(domain, _sentinel(src.dtype, True),
                           src.dtype).at[k].min(src)
        else:
            acc = jnp.full(domain, _sentinel(src.dtype, False),
                           src.dtype).at[k].max(src)
        if agg == "avg":
            acc = acc / jnp.maximum(counts, 1)
        out[name] = acc
    return out


@partial(jax.jit, static_argnames=("max_groups", "aggs"))
def groupby_sort(keys: jax.Array, valid: jax.Array,
                 values: Dict[str, jax.Array],
                 max_groups: int, aggs: Tuple[Tuple[str, str, str], ...]):
    """Sort-based GroupBy for arbitrary int keys (the paper's runtime
    fallback when the hash table would not fit). Returns padded
    (keys, aggs, n_groups)."""
    big = jnp.asarray(jnp.iinfo(_int_dtype()).max, _int_dtype())
    k = jnp.where(valid, keys.astype(_int_dtype()), big)
    order = jnp.argsort(k)
    ks = k[order]
    is_new = jnp.concatenate([jnp.ones(1, bool), ks[1:] != ks[:-1]])
    is_new &= ks != big
    gid = jnp.cumsum(is_new) - 1                      # (n,) group index
    gid = jnp.where(ks == big, max_groups - 1, jnp.clip(gid, 0,
                                                        max_groups - 1))
    n_groups = is_new.sum()
    uniq = jnp.full(max_groups, big).at[gid].min(ks)
    out = {"group_keys": uniq, "n_groups": n_groups}
    vsort = {c: v[order] for c, v in values.items()}
    valid_s = valid[order]
    counts = jnp.zeros(max_groups, _int_dtype()).at[gid].add(
        valid_s.astype(_int_dtype()))
    out["group_count"] = counts
    for name, col_, agg in aggs:
        src = _prep_agg(vsort[col_] if agg != "count" else ks, valid_s, agg)
        if _COMBINE[agg] == "add":
            acc = jnp.zeros(max_groups, src.dtype).at[gid].add(src)
        elif _COMBINE[agg] == "min":
            acc = jnp.full(max_groups, _sentinel(src.dtype, True),
                           src.dtype).at[gid].min(src)
        else:
            acc = jnp.full(max_groups, _sentinel(src.dtype, False),
                           src.dtype).at[gid].max(src)
        if agg == "avg":
            acc = acc / jnp.maximum(counts, 1)
        out[name] = acc
    return out


def groupby_rle(key_col: EncodedColumn, valid_counts: np.ndarray,
                domain: int) -> Dict[str, jax.Array]:
    """COUNT(*) GROUP BY key directly on RLE-encoded data: each run
    contributes (value, length) without decoding a single row. This is the
    §6.1 'operate directly on encoded data' fast path (Pallas twin:
    kernels/rle_scan_agg.py)."""
    assert key_col.encoding == Encoding.RLE
    from ..kernels import ops as kops
    from ..kernels.rle_scan_agg import DOMAIN_CAP
    if kops.on_tpu() and domain <= DOMAIN_CAP:
        # fused Pallas path: per-key count straight off the runs (grouped
        # twin of the scalar kernel; CPU stays on the XLA scatter below
        # because interpret-mode Pallas is row-at-a-time Python)
        out = kops.rle_grouped_agg(
            jnp.asarray(key_col.arrays["run_values"]),
            jnp.asarray(key_col.arrays["run_lengths"]), domain=domain)
        return {"group_count": out["count"].astype(_int_dtype())}
    rv = jnp.asarray(key_col.arrays["run_values"]).reshape(-1)
    rl = jnp.asarray(key_col.arrays["run_lengths"]).reshape(-1)
    # clamp tail-block padding runs: total rows cap
    k = jnp.clip(rv, 0, domain - 1).astype(jnp.int32)
    counts = jnp.zeros(domain, _int_dtype()).at[k].add(
        rl.astype(_int_dtype()))
    return {"group_count": counts}


def groupby_prepass(keys: jax.Array, valid: jax.Array,
                    values: Dict[str, jax.Array], domain: int,
                    aggs: Tuple[Tuple[str, str, str], ...],
                    block: int = 4096):
    """Two-stage GroupBy mirroring the paper's prepass operators: partial
    per-block aggregation (the 'L1-sized hash table', VMEM-sized on TPU),
    then a final combine. Numerically identical to groupby_dense."""
    n = keys.shape[0]
    nb = -(-n // block)
    pad = nb * block - n
    kp = jnp.pad(keys, (0, pad))
    vp = jnp.pad(valid, (0, pad))
    vals = {c: jnp.pad(v, (0, pad)) for c, v in values.items()}
    kb = kp.reshape(nb, block)
    vb = vp.reshape(nb, block)

    # avg does not distribute over blocks: aggregate partial SUMs instead
    # and divide by the combined counts at the end.
    part_aggs = tuple((name, col_, "sum" if agg == "avg" else agg)
                      for name, col_, agg in aggs)

    def per_block(kb1, vb1, vals1):
        return groupby_dense(kb1, vb1, vals1, domain, part_aggs)

    partials = jax.vmap(per_block)(kb, vb,
                                   {c: v.reshape(nb, block)
                                    for c, v in vals.items()})
    out = {}
    for name, v in partials.items():
        if name == "group_count" or _COMBINE.get(
                _agg_kind(name, part_aggs), "add") == "add":
            out[name] = v.sum(axis=0)
        elif _COMBINE[_agg_kind(name, part_aggs)] == "min":
            out[name] = v.min(axis=0)
        else:
            out[name] = v.max(axis=0)
    for name, col_, agg in aggs:
        if agg == "avg":
            out[name] = out[name] / jnp.maximum(out["group_count"], 1)
    return out


def _agg_kind(name, aggs):
    for n, _, a in aggs:
        if n == name:
            return a
    return "sum"


# ---------------------------------------------------------------------------
# Join (N:1 lookup = hash join; same primitive is a merge join on sorted)
# ---------------------------------------------------------------------------

@jax.jit
def join_lookup(build_keys: jax.Array, probe_keys: jax.Array):
    """Returns (idx, matched): for each probe key, the position of the
    matching build key (build keys unique, pre-sorted by caller)."""
    idx = jnp.searchsorted(build_keys, probe_keys)
    idx = jnp.clip(idx, 0, build_keys.shape[0] - 1)
    matched = build_keys[idx] == probe_keys
    return idx, matched


def hash_join(build: Dict[str, jax.Array], build_key: str,
              probe: Dict[str, jax.Array], probe_key: str,
              probe_valid: jax.Array,
              how: str = "inner") -> Tuple[Dict[str, jax.Array], jax.Array]:
    """N:1 join: probe each fact row against the (small) build side.
    Build side is sorted once ('building the hash table'); the probe is one
    vectorized lookup. Returns (joined columns, valid mask)."""
    if build[build_key].shape[0] == 0:
        # empty build side (dim predicate filtered everything, or the
        # dimension was truncated): no probe row can match
        n = probe[probe_key].shape[0]
        out = dict(probe)
        for c, v in build.items():
            if c != build_key:
                out[c] = jnp.full((n,) + v.shape[1:], -1, v.dtype)
        matched = jnp.zeros(n, bool)
        if how == "inner":
            return out, probe_valid & matched
        if how == "left":
            out["_matched"] = matched
            return out, probe_valid
        raise ValueError(how)
    order = jnp.argsort(build[build_key])
    bk = build[build_key][order]
    idx, matched = join_lookup(bk, probe[probe_key])
    out = dict(probe)
    for c, v in build.items():
        if c == build_key:
            continue
        joined = v[order][idx]
        if how == "left":
            # unmatched rows carry the NULL sentinel (-1), the engine's
            # NULL analog, instead of an arbitrary clipped build row
            joined = jnp.where(matched, joined,
                               jnp.asarray(-1, joined.dtype))
        out[f"{c}"] = joined
    if how == "inner":
        valid = probe_valid & matched
    elif how == "left":
        valid = probe_valid
        out["_matched"] = matched
    else:
        raise ValueError(how)
    return out, valid


# ---------------------------------------------------------------------------
# Sort / TopK / Analytic
# ---------------------------------------------------------------------------

def sort_rows(cols: Dict[str, jax.Array], valid: jax.Array,
              by: Sequence[str], descending: bool = False):
    key = cols[by[0]].astype(jnp.float64)
    big = jnp.inf if not descending else -jnp.inf
    key = jnp.where(valid, key, big)
    order = jnp.argsort(-key if descending else key)
    return {c: v[order] for c, v in cols.items()}, valid[order]


def top_k(cols: Dict[str, jax.Array], valid: jax.Array, by: str, k: int):
    key = jnp.where(valid, cols[by].astype(jnp.float32), -jnp.inf)
    _, idx = jax.lax.top_k(key, k)
    return {c: v[idx] for c, v in cols.items()}


@jax.jit
def analytic_running_sum(values: jax.Array, partition_ids: jax.Array):
    """SQL-99 windowed SUM() OVER (PARTITION BY p ORDER BY input order):
    segmented cumulative sum (input pre-sorted by partition)."""
    n = values.shape[0]
    csum = jnp.cumsum(values)
    is_new = jnp.concatenate([jnp.ones(1, bool),
                              partition_ids[1:] != partition_ids[:-1]])
    gid = jnp.cumsum(is_new) - 1
    # each group has exactly one start; record csum-before-start per group
    base_per_gid = jnp.zeros(n, csum.dtype).at[gid].add(
        jnp.where(is_new, csum - values, 0))
    return csum - base_per_gid[gid]
