"""Fused RLE scan->filter->aggregate Pallas kernels.

The paper's flagship 'operate directly on encoded data' (§6.1): a scan over
an RLE column evaluates the predicate per RUN and aggregates len-weighted
contributions -- O(runs) work and O(runs) HBM bytes instead of O(rows).
On TPU this turns the encoding ratio directly into memory-roofline headroom
(DESIGN.md hardware-adaptation table).

Two kernels:

  * ``rle_filter_agg``  -- per-block [count, sum, max] of rows in [lo, hi]
                           (Q1-shaped: filtered scalar aggregate).  Eight
                           blocks per grid step; the three results land in
                           lanes 0..2 of a (8, 128) output tile.
  * ``rle_grouped_agg`` -- per-key count (int32, exact) and f32 sum / min /
                           max over a dense key domain (Q2/Q3-shaped:
                           filtered GROUP BY).  Runs stream through VMEM as
                           (8, 128) tiles; each 128-run row is compared
                           against a sublane iota of keys and folded into
                           (domain, 128) accumulators that stay resident
                           across grid steps (every step maps to block
                           (0, 0)); the wrapper reduces the 128 lanes.

No contraction runs on the MXU, so nothing is rounded to bf16: counts
accumulate in int32 whatever the run lengths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_ROWS = 8          # sublanes per grid step
# (domain, 128) accumulators must sit in VMEM; seg_preagg shares the cap
DOMAIN_CAP = 1024


def _kernel(rv_ref, rl_ref, out_ref, *, lo: float, hi: float):
    rv = rv_ref[...].astype(jnp.float32)          # (8, R)
    rl = rl_ref[...].astype(jnp.float32)
    m = (rv >= lo) & (rv <= hi) & (rl > 0)
    cnt = jnp.where(m, rl, 0.0).sum(axis=1, keepdims=True)
    s = jnp.where(m, rv * rl, 0.0).sum(axis=1, keepdims=True)
    mx = jnp.where(m, rv, -jnp.inf).max(axis=1, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out_ref[...] = jnp.where(lane == 0, cnt,
                             jnp.where(lane == 1, s,
                                       jnp.where(lane == 2, mx, 0.0)))


@functools.partial(jax.jit, static_argnames=("lo", "hi", "interpret"))
def rle_filter_agg(run_values: jax.Array, run_lengths: jax.Array, *,
                   lo: float, hi: float,
                   interpret: bool = False) -> jax.Array:
    """(nb, R) runs -> (nb, 3) [count, sum, max] of rows in [lo, hi]."""
    nb, R = run_values.shape
    padb = (-nb) % _ROWS
    padr = (-R) % 128
    run_values = jnp.pad(run_values, ((0, padb), (0, padr)))
    run_lengths = jnp.pad(run_lengths, ((0, padb), (0, padr)))
    nbp, Rp = run_values.shape
    out = pl.pallas_call(
        functools.partial(_kernel, lo=lo, hi=hi),
        grid=(nbp // _ROWS,),
        in_specs=[
            pl.BlockSpec((_ROWS, Rp), lambda i: (i, 0)),
            pl.BlockSpec((_ROWS, Rp), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp, 128), jnp.float32),
        interpret=interpret,
        name="rle_filter_agg",
    )(run_values, run_lengths)
    return out[:nb, :3]


_NEG = -3.4e38   # min/max sentinels (finite, so an empty key reads as a
_POS = 3.4e38    # number); python floats so the kernel closes over static
                 # constants, not traced arrays


def _grouped_kernel(rv_ref, rl_ref, val_ref, cnt_ref, sum_ref, mn_ref,
                    mx_ref, *, dpad: int, valid_domain: int, lo: float,
                    hi: float):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        cnt_ref[...] = jnp.zeros((dpad, 128), jnp.int32)
        sum_ref[...] = jnp.zeros((dpad, 128), jnp.float32)
        mn_ref[...] = jnp.full((dpad, 128), _POS, jnp.float32)
        mx_ref[...] = jnp.full((dpad, 128), _NEG, jnp.float32)

    keys = jax.lax.broadcasted_iota(jnp.int32, (dpad, 128), 0)

    def fold_row(j, carry):
        rv = rv_ref[pl.ds(j, 1), :]                 # (1, 128) key per run
        rl = rl_ref[pl.ds(j, 1), :]                 # rows per run
        val = val_ref[pl.ds(j, 1), :]               # value per run
        rvf = rv.astype(jnp.float32)
        m = (rvf >= lo) & (rvf <= hi) & (rl > 0)    # predicate per RUN
        m &= (rv >= 0) & (rv < valid_domain)        # keys must be in-domain
        hit = keys == jnp.where(m, rv, -1)          # (dpad, 128)
        cnt_ref[...] += jnp.where(hit, rl, 0)
        sum_ref[...] += jnp.where(hit, val * rl.astype(jnp.float32), 0.0)
        mn_ref[...] = jnp.minimum(mn_ref[...], jnp.where(hit, val, _POS))
        mx_ref[...] = jnp.maximum(mx_ref[...], jnp.where(hit, val, _NEG))
        return carry

    jax.lax.fori_loop(0, _ROWS, fold_row, 0)


@functools.partial(jax.jit, static_argnames=("domain", "lo", "hi",
                                             "interpret"))
def rle_grouped_agg(run_values: jax.Array, run_lengths: jax.Array,
                    values: jax.Array = None, *, domain: int,
                    lo: float = -3.0e38, hi: float = 3.0e38,
                    interpret: bool = False):
    """(nb, R) runs -> {"count": (domain,) int32, "sum"/"min"/"max":
    (domain,) f32} per key.

    ``run_values`` carries the (dense, non-negative) group key per run;
    ``values`` the per-run aggregate value (every row of a run contributes
    that value -- defaults to the key itself, the C-Store 'aggregate the
    sort leader' case).  Runs whose key falls outside [lo, hi] or with
    zero length are dropped, so padding runs never contribute.  Keys with
    no surviving rows report count 0, sum 0, min +BIG, max -BIG.
    """
    assert domain <= DOMAIN_CAP, "accumulators must fit VMEM"
    if values is None:
        values = run_values

    def tiles(a, dt):
        a = a.astype(dt).reshape(-1)
        rows = -(-a.shape[0] // (128 * _ROWS)) * _ROWS
        return jnp.pad(a, (0, rows * 128 - a.shape[0])).reshape(rows, 128)

    rv = tiles(run_values, jnp.int32)
    rl = tiles(run_lengths, jnp.int32)      # zero-length padding runs
    val = tiles(values, jnp.float32)
    dpad = -(-domain // 8) * 8
    row_spec = pl.BlockSpec((_ROWS, 128), lambda i: (i, 0))
    acc_spec = pl.BlockSpec((dpad, 128), lambda i: (0, 0))
    cnt, s, mn, mx = pl.pallas_call(
        functools.partial(_grouped_kernel, dpad=dpad, valid_domain=domain,
                          lo=lo, hi=hi),
        grid=(rv.shape[0] // _ROWS,),
        in_specs=[row_spec] * 3,
        out_specs=[acc_spec] * 4,
        out_shape=[jax.ShapeDtypeStruct((dpad, 128), jnp.int32)]
        + [jax.ShapeDtypeStruct((dpad, 128), jnp.float32)] * 3,
        interpret=interpret,
        name="rle_grouped_agg",
    )(rv, rl, val)
    return {"count": cnt.sum(axis=1)[:domain],
            "sum": s.sum(axis=1)[:domain],
            "min": mn.min(axis=1)[:domain],
            "max": mx.max(axis=1)[:domain]}
