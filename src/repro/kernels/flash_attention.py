"""Blocked causal attention (flash) Pallas kernel -- the LM stack's prefill
hot spot.

Grid: (q_blocks,) outer; the kernel loops KV blocks with an online softmax
(running max / normalizer in fp32), touching O(Bq*Bk) VMEM instead of the
O(S*T) scores matrix. Causal blocks above the diagonal are skipped by
masking; dims are multiples of 128 for MXU alignment.

The XLA-path twin used by the dry-run is models/attention.py:
attend_chunked (same contraction order); this kernel swaps in through
kernels/ops.py on real TPUs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, causal: bool):
    qi = pl.program_id(0)
    q = q_ref[...].astype(jnp.float32)                 # (bq, d)
    d = q.shape[-1]
    T = k_ref.shape[0]
    n_kv = T // bk
    scale = 1.0 / np.sqrt(d)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)   # (bk, d)
        v = v_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = (q @ k.T) * scale                          # (bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * alpha + p.sum(axis=1)
        acc_new = acc * alpha[:, None] + p @ v
        return acc_new, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    # causal: kv blocks beyond this q block's diagonal contribute nothing
    upper = (qi + 1) * bq if causal else T
    n_iter = (upper + bk - 1) // bk if causal else n_kv
    acc, m, l = jax.lax.fori_loop(0, n_iter, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bk", "causal", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    bq: int = 128, bk: int = 128, causal: bool = True,
                    interpret: bool = False) -> jax.Array:
    """Single-head attention: q (S, d), k/v (T, d) -> (S, d).
    Heads/batch are vmapped by the caller (ops.py)."""
    S, d = q.shape
    T = k.shape[0]
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, causal=causal),
        grid=(S // bq,),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i: (i, 0)),
            pl.BlockSpec((T, d), lambda i: (0, 0)),
            pl.BlockSpec((T, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bq, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((S, d), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
