"""Bit-unpack Pallas kernel: packed uint32 word streams -> int32 symbol lanes.

Storage packs w-bit symbols (w = 1..32) into little-endian uint32 words with
a group structure of 32 symbols per 32*w bits (core/encodings.py §9 format):
symbol s of a group starts at bit s*w, i.e. word (s*w)//32 bit (s*w)%32,
possibly straddling one word boundary.  Because a group is exactly w words,
every slot's (word, shift) pair is a compile-time constant per width -- the
kernel is 32 unrolled shift/mask lanes with static indices, no gather.

Three implementations, dispatched by kernels/ops.py like seg_preagg:

* ``bitunpack_pallas`` -- grid kernel over word-major tiles (below); the
  per-block base-offset add of the delta reconstruction (DELTA_VALUE base /
  DELTA_RANGE delta_min) fuses into the transpose that restores row order.
* ``bitunpack_xla``    -- shift/mask reference path, byte-identical on CPU.
* ``gather_unpack``    -- random access: decode only (block, row) positions,
  the late-materialization gather for surviving rows.

TPU layout: the kernel never reshapes in VMEM.  The wrapper transposes the
packed stream word-major, ``(width, groups)`` -- row k holds word k of
every 32-symbol group -- and tiles the groups as ``(rows, 128)`` lanes.
Slot s of every group is then two static sublane-row reads, shifts and a
mask over full ``(8k, 128)`` tiles, written to output row s; the wrapper
transposes the ``(32, groups)`` result back to row order.  Symbols wider
than 32 bits never reach here -- encodings fall back to byte-wide storage.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

def _mask32(width: int) -> int:
    return (1 << width) - 1 if width < 32 else 0xFFFFFFFF


def _slot_tables(width: int):
    """Static per-slot (of 32) word index / shift tables for one width."""
    slot = np.arange(32)
    bit = slot * width
    lo = bit // 32
    sh = bit % 32
    straddle = sh + width > 32
    hi = np.minimum(lo + 1, width - 1)   # clipped: only read when straddling
    hi_shift = (32 - sh) % 32
    return lo, sh, hi, hi_shift, straddle


def bitunpack_xla(words: jax.Array, width: int, block_rows: int,
                  base: Optional[jax.Array] = None) -> jax.Array:
    """XLA shift/mask unpack: words (nb, ng*width) uint32 ->
    (nb, block_rows) int32; ``base`` (nb,) is added per block when given."""
    nb, nw = words.shape
    ng = nw // width
    lo, sh, hi, hi_shift, straddle = _slot_tables(width)
    g = words.reshape(nb, ng, width)
    v = g[:, :, lo] >> jnp.asarray(sh, jnp.uint32)
    hi_part = jnp.where(jnp.asarray(straddle),
                        g[:, :, hi] << jnp.asarray(hi_shift, jnp.uint32),
                        jnp.uint32(0))
    v = (v | hi_part) & jnp.uint32(_mask32(width))
    out = v.reshape(nb, ng * 32)[:, :block_rows].astype(jnp.int32)
    if base is not None:
        out = out + base[:, None].astype(jnp.int32)
    return out


_TILE_SUBLANES = 32      # (32, 128) groups = 131072 symbols per grid step


def _kernel(words_ref, out_ref, *, width):
    """words (width, ts, 128): word k of each group in row k ->
    out (32, ts, 128): slot s of each group in row s, unrolled."""
    lo, sh, hi, hi_shift, straddle = _slot_tables(width)
    mask = jnp.uint32(_mask32(width))
    for s in range(32):
        v = words_ref[int(lo[s])] >> jnp.uint32(sh[s])
        if straddle[s]:
            v = v | (words_ref[int(hi[s])] << jnp.uint32(hi_shift[s]))
        if width < 32:
            v = v & mask
        out_ref[s] = v.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("width", "block_rows", "interpret"))
def bitunpack_pallas(words: jax.Array, width: int, block_rows: int,
                     base: Optional[jax.Array] = None, *,
                     interpret: bool = False) -> jax.Array:
    """Pallas unpack: words (nb, ng*width) uint32 -> (nb, block_rows)
    int32; ``base`` (nb,) is added per block when given."""
    nb, nw = words.shape
    ng = nw // width
    n_groups = nb * ng
    rows = -(-n_groups // 128)
    ts = min(_TILE_SUBLANES, -(-rows // 8) * 8)
    rows_p = -(-rows // ts) * ts
    wt = words.reshape(nb, ng, width).transpose(2, 0, 1) \
        .reshape(width, n_groups)
    wt = jnp.pad(wt, ((0, 0), (0, rows_p * 128 - n_groups))) \
        .reshape(width, rows_p, 128)
    out = pl.pallas_call(
        functools.partial(_kernel, width=width),
        grid=(rows_p // ts,),
        in_specs=[pl.BlockSpec((width, ts, 128), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((32, ts, 128), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((32, rows_p, 128), jnp.int32),
        interpret=interpret,
        name="bitunpack",
    )(wt)
    out = out.reshape(32, rows_p * 128)[:, :n_groups] \
        .reshape(32, nb, ng).transpose(1, 2, 0) \
        .reshape(nb, ng * 32)[:, :block_rows]
    if base is not None:
        out = out + base[:, None].astype(jnp.int32)
    return out


def gather_unpack(words: jax.Array, width: int, b_idx: jax.Array,
                  r_idx: jax.Array) -> jax.Array:
    """Random-access unpack of symbols (b_idx[i], r_idx[i]) -> int32.

    The late-materialization path: per-element dynamic word index + shift,
    so survivor rows decode without touching the rest of the block."""
    nw = words.shape[1]
    r = r_idx.astype(jnp.uint32)
    s = r % 32
    bit = s * jnp.uint32(width)
    lo = (r // 32) * jnp.uint32(width) + bit // 32
    sh = bit % 32
    w_lo = words[b_idx, lo]
    w_hi = words[b_idx, jnp.minimum(lo + 1, jnp.uint32(nw - 1))]
    straddle = (sh + jnp.uint32(width)) > 32
    hi_shift = (jnp.uint32(32) - sh) % 32
    v = (w_lo >> sh) | jnp.where(straddle, w_hi << hi_shift, jnp.uint32(0))
    return (v & jnp.uint32(_mask32(width))).astype(jnp.int32)
