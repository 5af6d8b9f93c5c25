"""Packed-domain pre-aggregation scatter as a Pallas grid kernel.

The segmented executor's shard-local GroupBy (engine/segmented.py) packs
the group keys into one dense domain and scatters each aggregate into a
per-shard partial vector -- ``operators.groupby_dense``.  On TPU the XLA
scatter serializes; this kernel re-expresses it as VPU compare/select
work: rows stream through VMEM as (rows, 128) tiles, each 128-row slice is
compared against a sublane iota of the packed keys, and every aggregate
folds into a (domain, 128) partial that stays resident across grid steps.
The wrapper reduces the 128 lanes.

Contract: matches ``operators.groupby_dense`` under the default 32-bit
runtime -- keys clip into [0, domain) (negative keys merge into group 0),
counts and int sums accumulate in int32 (wrapping, exact), float sums in
f32 (to summation-order tolerance), min/max start from the dtype's
sentinels.  The oracle is
``kernels.ref.seg_preagg_ref``; ``tests/test_kernels_seg_preagg.py``
checks kernel == oracle == groupby_dense.

Dispatch (``seg_preagg``): the kernel runs when compiled for TPU (or
forced via ``REPRO_SEG_PREAGG=pallas``, interpreted elsewhere; the
variable never turns the kernel off on TPU) and the packed domain fits
the VMEM budget; every other shape keeps the XLA scatter, so CPU
differential tests exercise byte-identical code by default.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref
from .ops import on_tpu, pallas_enabled
from .rle_scan_agg import DOMAIN_CAP

_ROWS = 64               # (64, 128) rows per grid step
_BLOCK = _ROWS * 128


def _use_kernel(domain: int, kinds: Tuple[str, ...]) -> bool:
    """Kernel eligibility: small packed domain, plain aggregates, 32-bit
    runtime, and a backend that wants it (TPU, or the env override)."""
    if domain > DOMAIN_CAP or jax.config.jax_enable_x64:
        return False
    if not all(k in ("count", "sum", "min", "max") for k in kinds):
        return False
    return pallas_enabled("REPRO_SEG_PREAGG")


def interpreted() -> bool:
    """The kernel runs in interpret mode (forced off TPU).  Its loop
    discharge drops the refs' varying axes, so a shard_map around it
    must then run without ``check_vma``."""
    return pallas_enabled("REPRO_SEG_PREAGG") and not on_tpu()


def _sentinel(dt, hi: bool):
    if jnp.issubdtype(dt, jnp.integer):
        info = jnp.iinfo(dt)
        return info.max if hi else info.min
    return jnp.inf if hi else -jnp.inf


def _make_kernel(dpad: int, domain: int, kinds: Tuple[str, ...]):
    """Refs: keys, mask, one value tile per non-count aggregate, then the
    count partial and one partial per non-count aggregate."""
    n_vals = len(kinds)

    def kernel(*refs):
        keys_ref, mask_ref = refs[0], refs[1]
        vrefs = refs[2:2 + n_vals]
        cref = refs[2 + n_vals]
        orefs = refs[3 + n_vals:]

        @pl.when(pl.program_id(0) == 0)
        def _init():
            cref[...] = jnp.zeros(cref.shape, jnp.int32)
            for kind, vref, oref in zip(kinds, vrefs, orefs):
                init = 0 if kind == "sum" else \
                    _sentinel(vref.dtype, kind == "min")
                oref[...] = jnp.full(oref.shape, init, oref.dtype)

        slots = jax.lax.broadcasted_iota(jnp.int32, (dpad, 128), 0)

        def fold_row(j, carry):
            k = jnp.clip(keys_ref[pl.ds(j, 1), :], 0, domain - 1)
            m = mask_ref[pl.ds(j, 1), :] != 0
            hit = slots == jnp.where(m, k, -1)          # (dpad, 128)
            cref[...] += hit.astype(jnp.int32)
            for kind, vref, oref in zip(kinds, vrefs, orefs):
                v = vref[pl.ds(j, 1), :]
                if kind == "sum":
                    oref[...] += jnp.where(hit, v, jnp.zeros((), v.dtype))
                elif kind == "min":
                    oref[...] = jnp.minimum(oref[...], jnp.where(
                        hit, v, _sentinel(v.dtype, True)))
                else:
                    oref[...] = jnp.maximum(oref[...], jnp.where(
                        hit, v, _sentinel(v.dtype, False)))
            return carry

        jax.lax.fori_loop(0, _ROWS, fold_row, 0)

    return kernel


@functools.partial(jax.jit, static_argnames=("domain", "kinds",
                                             "interpret"))
def _preagg_call(keys, mask, vals, domain: int, kinds: Tuple[str, ...],
                 interpret: bool):
    """keys/mask (n,) padded to a _BLOCK multiple by the caller; vals is
    one prepared (n,) array per non-count aggregate, aligned with
    ``kinds``.  Returns the (domain,) count and one (domain,) partial per
    aggregate."""
    rows = keys.shape[0] // 128
    dpad = -(-domain // 8) * 8
    row_spec = pl.BlockSpec((_ROWS, 128), lambda i: (i, 0))
    acc_spec = pl.BlockSpec((dpad, 128), lambda i: (0, 0))
    # inside the segmented executor's shard_map the partials vary over
    # the mesh axes the keys vary over
    vma = jax.typeof(keys).vma
    out_shape = [jax.ShapeDtypeStruct((dpad, 128), dt, vma=vma)
                 for dt in (jnp.int32,) + tuple(v.dtype for v in vals)]
    outs = pl.pallas_call(
        _make_kernel(dpad, domain, kinds),
        grid=(rows // _ROWS,),
        in_specs=[row_spec] * (2 + len(vals)),
        out_specs=[acc_spec] * len(out_shape),
        out_shape=out_shape,
        interpret=interpret,
        name="seg_preagg",
    )(keys.reshape(rows, 128), mask.reshape(rows, 128),
      *(v.reshape(rows, 128) for v in vals))
    reduce = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}
    counts = outs[0].sum(axis=1)[:domain]
    return counts, tuple(reduce[kind](o, axis=1)[:domain]
                         for kind, o in zip(kinds, outs[1:]))


def seg_preagg_pallas(keys, valid, values: Dict[str, jax.Array],
                      domain: int, aggs, *, interpret: bool = True):
    """Run the kernel unconditionally (tests drive this in interpret
    mode); same signature and outputs as ``operators.groupby_dense``."""
    n = keys.shape[0]
    pad = (-n) % _BLOCK
    k = jnp.pad(keys.astype(jnp.int32), (0, pad))
    m = jnp.pad(valid.astype(jnp.int32), (0, pad))
    kinds, vals = [], []
    for _name, col, kind in aggs:
        if kind == "count":
            continue
        v = values[col]
        v = v.astype(jnp.float32) if v.dtype.kind == "f" \
            else v.astype(jnp.int32)
        kinds.append(kind)
        vals.append(jnp.pad(v, (0, pad)))
    counts, partials = _preagg_call(k, m, tuple(vals), int(domain),
                                    tuple(kinds), bool(interpret))
    res = {"group_count": counts}
    it = iter(partials)
    for name, _col, kind in aggs:
        res[name] = counts if kind == "count" else next(it)
    return res


def seg_preagg(keys, valid, values: Dict[str, jax.Array], domain: int,
               aggs, *, force_ref: bool = False):
    """Drop-in for ``operators.groupby_dense`` inside the segmented
    executor's fused shard program: Pallas kernel when eligible
    (``_use_kernel``), XLA scatter otherwise, jnp oracle on demand."""
    if force_ref:
        return ref.seg_preagg_ref(keys, valid, values, domain, aggs)
    kinds = tuple(a[2] for a in aggs)
    if _use_kernel(int(domain), kinds):
        return seg_preagg_pallas(keys, valid, values, int(domain), aggs,
                                 interpret=not on_tpu())
    from ..engine import operators as ops
    return ops.groupby_dense(keys.astype(jnp.int32), valid, values,
                             int(domain), aggs)
