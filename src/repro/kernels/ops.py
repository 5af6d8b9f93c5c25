"""Public jit'd wrappers for the Pallas kernels.

Dispatch policy (honest about the runtime, per DESIGN.md):
  * on TPU           -> compiled Pallas kernels, whatever the environment
                        says (no interpret mode, no XLA fallback)
  * on CPU (tests)   -> interpret=True (kernel body executed in Python,
                        validating the kernel logic itself); the decode
                        kernels keep their XLA path unless ``REPRO_*=pallas``
                        opts into interpret mode
  * ``force_ref=True`` -> the pure-jnp oracle (kernels/ref.py)
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from . import ref
from .bitunpack import bitunpack_pallas, bitunpack_xla
from .delta_decode import delta_decode as _delta_decode
from .flash_attention import flash_attention as _flash_attention
from .hash_groupby import onehot_groupby as _onehot_groupby
from .rle_scan_agg import (rle_filter_agg as _rle_filter_agg,
                           rle_grouped_agg as _rle_grouped_agg)
from .sip_probe import semijoin_probe as _semijoin_probe


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_enabled(env: str) -> bool:
    """Single kernel gate for the decode kernels and seg_preagg: always
    the compiled kernel on TPU; elsewhere the XLA path unless ``env`` is
    set to ``pallas`` (interpret mode)."""
    return on_tpu() or os.environ.get(env, "") == "pallas"


def rle_filter_agg(run_values, run_lengths, *, lo, hi, force_ref=False):
    if force_ref:
        return ref.rle_filter_agg_ref(run_values, run_lengths, lo, hi)
    return _rle_filter_agg(run_values, run_lengths, lo=lo, hi=hi,
                           interpret=not on_tpu())


def rle_grouped_agg(run_values, run_lengths, values=None, *, domain,
                    lo=-3.0e38, hi=3.0e38, force_ref=False):
    """Per-key {count, sum, min, max} over a dense domain, straight from
    RLE runs (the §6.1 grouped 'operate on encoded data' path)."""
    if force_ref:
        return ref.rle_grouped_agg_ref(
            run_values, run_lengths,
            run_values if values is None else values, domain, lo, hi)
    return _rle_grouped_agg(run_values, run_lengths, values,
                            domain=domain, lo=lo, hi=hi,
                            interpret=not on_tpu())


def onehot_groupby(keys, values, *, domain, force_ref=False):
    if force_ref:
        return ref.onehot_groupby_ref(keys, values, domain)
    return _onehot_groupby(keys, values, domain=domain,
                           interpret=not on_tpu())


def bitunpack(words, width, block_rows, base=None, *, force_ref=False):
    """Unpack w-bit symbols from packed uint32 words -> (nb, block_rows)
    int32, optionally fused with a per-block base add (delta/dict
    reconstruction).  See kernels/bitunpack.py for the word format."""
    if force_ref:
        return ref.bitunpack_ref(words, width, block_rows, base)
    if pallas_enabled("REPRO_BITUNPACK"):
        return bitunpack_pallas(words, width, block_rows, base,
                                interpret=not on_tpu())
    return bitunpack_xla(words, width, block_rows, base)


def delta_decode(first, deltas, *, force_ref=False):
    if force_ref:
        return ref.delta_decode_ref(first, deltas)
    if pallas_enabled("REPRO_DELTA_DECODE"):
        return _delta_decode(first, deltas, interpret=not on_tpu())
    # XLA path (same math as the kernel body, byte-identical on CPU)
    d = deltas.astype(jnp.float32)
    return first.astype(jnp.float32) + jnp.cumsum(d, axis=1) - d[:, :1]


def semijoin_probe(keys, build, *, force_ref=False):
    if force_ref:
        return ref.semijoin_probe_ref(keys, build)
    return _semijoin_probe(keys, build, interpret=not on_tpu())


def flash_attention(q, k, v, *, causal=True, bq=128, bk=128,
                    force_ref=False):
    """Batched/multi-head wrapper: q (..., S, d), k/v (..., T, d)."""
    if force_ref:
        fn = functools.partial(ref.flash_attention_ref, causal=causal)
    else:
        fn = functools.partial(_flash_attention, causal=causal, bq=bq,
                               bk=bk, interpret=not on_tpu())
    for _ in range(q.ndim - 2):
        fn = jax.vmap(fn)
    return fn(q, k, v)
