"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def rle_filter_agg_ref(run_values: jax.Array, run_lengths: jax.Array,
                       lo: float, hi: float) -> jax.Array:
    """Per-block (count, sum, max) of rows with lo <= value <= hi, computed
    on RLE runs: a run contributes len rows and len*value sum.
    run_values/run_lengths: (nb, R). Returns (nb, 3) f32."""
    rv = run_values.astype(jnp.float32)
    rl = run_lengths.astype(jnp.float32)
    m = ((rv >= lo) & (rv <= hi) & (rl > 0)).astype(jnp.float32)
    cnt = (rl * m).sum(axis=1)
    s = (rv * rl * m).sum(axis=1)
    mx = jnp.where(m > 0, rv, -jnp.inf).max(axis=1)
    return jnp.stack([cnt, s, mx], axis=1)


def rle_grouped_agg_ref(run_values: jax.Array, run_lengths: jax.Array,
                        values: jax.Array, domain: int,
                        lo: float, hi: float) -> dict:
    """Per-key count / sum / min / max over a dense domain, from RLE runs:
    a run of key k and length L contributes L rows of its value.  Runs
    with key outside [lo, hi] (or [0, domain)) or zero length drop out.
    Returns {"count": (domain,) int32, "sum"/"min"/"max": (domain,) f32};
    empty keys: count 0, sum 0, min/max at the +-3.4e38 sentinels
    (matching the Pallas kernel)."""
    rv = run_values.astype(jnp.float32).reshape(-1)
    rl = run_lengths.astype(jnp.int32).reshape(-1)
    val = values.astype(jnp.float32).reshape(-1)
    m = (rv >= lo) & (rv <= hi) & (rl > 0) & (rv >= 0) & (rv < domain)
    k = jnp.clip(run_values.astype(jnp.int32).reshape(-1), 0, domain - 1)
    cnt = jnp.zeros(domain, jnp.int32).at[k].add(jnp.where(m, rl, 0))
    s = jnp.zeros(domain, jnp.float32).at[k].add(
        jnp.where(m, val * rl.astype(jnp.float32), 0.0))
    pos, neg = jnp.float32(3.4e38), jnp.float32(-3.4e38)
    mn = jnp.full(domain, pos).at[k].min(jnp.where(m, val, pos))
    mx = jnp.full(domain, neg).at[k].max(jnp.where(m, val, neg))
    return {"count": cnt, "sum": s, "min": mn, "max": mx}


def seg_preagg_ref(keys: jax.Array, valid: jax.Array, values,
                   domain: int, aggs) -> dict:
    """Oracle for the segmented executor's packed-domain pre-aggregation
    scatter (kernels/seg_preagg.py): same contract as
    ``operators.groupby_dense`` -- keys clip into [0, domain) (negative
    keys merge into group 0 exactly like the scatter path), counts
    accumulate in int32, int sums in int32 (wrapping, exact), float sums
    in f32 (summation-order tolerance), min/max start from the dtype's
    sentinels.  ``aggs`` is the
    (out_name, in_col, kind) tuple the engine passes; ``values`` maps
    column name -> (n,) array."""
    k = jnp.clip(keys.astype(jnp.int32), 0, domain - 1)
    vi = valid.astype(jnp.int32)
    counts = jnp.zeros(domain, jnp.int32).at[k].add(vi)
    out = {"group_count": counts}
    for name, col, kind in aggs:
        if kind == "count":
            out[name] = counts
            continue
        v = values[col]
        v = v.astype(jnp.float32) if v.dtype.kind == "f" \
            else v.astype(jnp.int32)
        if kind in ("sum", "avg"):
            acc = jnp.zeros(domain, v.dtype).at[k].add(
                jnp.where(valid, v, 0))
            if kind == "avg":
                acc = acc / jnp.maximum(counts, 1)
        elif kind == "min":
            sent = jnp.iinfo(v.dtype).max if v.dtype.kind == "i" \
                else jnp.inf
            acc = jnp.full(domain, sent, v.dtype).at[k].min(
                jnp.where(valid, v, sent))
        else:
            sent = jnp.iinfo(v.dtype).min if v.dtype.kind == "i" \
                else -jnp.inf
            acc = jnp.full(domain, sent, v.dtype).at[k].max(
                jnp.where(valid, v, sent))
        out[name] = acc
    return out


def onehot_groupby_ref(keys: jax.Array, values: jax.Array,
                       domain: int) -> jax.Array:
    """Per-block dense partial GroupBy (count+sum) via one-hot contraction.
    keys (nb, B) int32, values (nb, B) f32 -> (nb, domain, 2) f32."""
    onehot = jax.nn.one_hot(keys, domain, dtype=jnp.float32)  # (nb,B,dom)
    cnt = onehot.sum(axis=1)
    # HIGHEST: on TPU a default-precision f32 einsum rounds to bf16
    s = jnp.einsum("nbd,nb->nd", onehot, values.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    return jnp.stack([cnt, s], axis=-1)


def bitunpack_ref(words, width: int, block_rows: int,
                  base=None) -> jax.Array:
    """Bit-by-bit oracle for the packed word-stream format
    (kernels/bitunpack.py): symbol j of a block lives in group j//32 slot
    j%32, starting at bit (j%32)*width of the group's width words.  Slow on
    purpose -- an independent reimplementation, not shared shift tables."""
    w = np.asarray(words, dtype=np.uint32)
    nb, nw = w.shape
    ng = nw // width
    out = np.zeros((nb, block_rows), dtype=np.int64)
    for b in range(nb):
        for j in range(min(block_rows, ng * 32)):
            g, s = divmod(j, 32)
            v = 0
            for i in range(width):
                bit = s * width + i
                word = int(w[b, g * width + bit // 32])
                v |= ((word >> (bit % 32)) & 1) << i
            out[b, j] = v
    if base is not None:
        out = out + np.asarray(base).astype(np.int64)[:, None]
    return jnp.asarray(out.astype(np.int32))


def delta_decode_ref(first: jax.Array, deltas: jax.Array) -> jax.Array:
    """DELTA_RANGE block decode: first (nb, 1), deltas (nb, B) ->
    values (nb, B) where v[0]=first, v[i]=v[i-1]+deltas[i]."""
    d = deltas.astype(jnp.float32)
    return first.astype(jnp.float32) + jnp.cumsum(d, axis=1) - d[:, :1]


def semijoin_probe_ref(keys: jax.Array, build: jax.Array) -> jax.Array:
    """Exact semi-join membership: keys (nb, B) int32 vs build (S,) int32
    (padded with -1) -> bool (nb, B)."""
    eq = keys[..., None] == build[None, None, :]
    return eq.any(axis=-1)


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True) -> jax.Array:
    """q (S, d), k/v (T, d) -> (S, d); fp32 softmax."""
    d = q.shape[-1]
    hp = jax.lax.Precision.HIGHEST   # f32 on TPU too, not bf16 passes
    s = jnp.matmul(q.astype(jnp.float32), k.astype(jnp.float32).T,
                   precision=hp) / np.sqrt(d)
    if causal:
        S, T = s.shape
        mask = jnp.arange(S)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.matmul(p, v.astype(jnp.float32),
                      precision=hp).astype(q.dtype)
