"""Prepass GroupBy Pallas kernel: the paper's 'L1-cache-sized hash table'
pre-aggregation (§6.1), rethought for the MXU.

TPU adaptation (DESIGN.md): instead of a chasing-pointers hash table, the
VMEM-resident dense table is built by a ONE-HOT CONTRACTION -- the (B x
domain) one-hot of the keys hits the systolic array as a matmul, producing
per-block (count, sum) partials that a cheap tree-combine finishes.  The
block streams through the contraction 512 keys at a time, so the one-hot
tile stays (domain, 512) whatever the block size. Domain
is capped so the table tiles VMEM (<= 1024 here), exactly mirroring the
paper's 'when the table fills, emit partials and start afresh'.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(keys_ref, vals_ref, out_ref, *, domain: int, chunk: int):
    B = keys_ref.shape[2]
    contract = (((1,), (1,)), ((), ()))                # sum over the lanes
    hp = jax.lax.Precision.HIGHEST                     # no bf16 rounding
    # transposed one-hot via a sublane iota compare: (domain, chunk)
    ids = jax.lax.broadcasted_iota(jnp.int32, (domain, chunk), 0)

    def fold(c, carry):
        cnt, s = carry
        k = keys_ref[0, :, pl.ds(c * chunk, chunk)]    # (1, chunk) int32
        v = vals_ref[0, :, pl.ds(c * chunk, chunk)].astype(jnp.float32)
        onehot = (ids == k).astype(jnp.float32)
        cnt += jax.lax.dot_general(jnp.ones((1, chunk), jnp.float32),
                                   onehot, contract, precision=hp)
        s += jax.lax.dot_general(v, onehot, contract, precision=hp)
        return cnt, s

    zero = jnp.zeros((1, domain), jnp.float32)
    cnt, s = jax.lax.fori_loop(0, B // chunk, fold, (zero, zero))
    out_ref[0, 0:1, :] = cnt                           # (1, domain)  MXU
    out_ref[0, 1:2, :] = s


@functools.partial(jax.jit, static_argnames=("domain", "interpret"))
def onehot_groupby(keys: jax.Array, values: jax.Array, *, domain: int,
                   interpret: bool = False) -> jax.Array:
    """keys/values (nb, B) -> per-block partials (nb, domain, 2)."""
    assert domain <= 1024, "prepass table must fit VMEM; combine upstream"
    nb, B = keys.shape
    dpad = -(-domain // 128) * 128
    chunk = next(c for c in (512, 128, B) if B % c == 0)
    out = pl.pallas_call(
        functools.partial(_kernel, domain=dpad, chunk=chunk),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 1, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, B), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 2, dpad), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 2, dpad), jnp.float32),
        interpret=interpret,
        name="onehot_groupby",
    )(keys.astype(jnp.int32).reshape(nb, 1, B), values.reshape(nb, 1, B))
    return out[:, :, :domain].transpose(0, 2, 1)
