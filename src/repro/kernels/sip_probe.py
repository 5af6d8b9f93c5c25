"""SIP semi-join probe Pallas kernel (§6.1 Sideways Information Passing).

The join build side (small dimension keys, padded to a lane multiple) sits
in VMEM as one column; each grid step tests one block of probe keys, 128
lanes at a time, against all of it with a broadcast compare + any-reduce over
sublanes. Exact (not Bloom): on TPU the
build side fits VMEM wholesale, so the approximate filter is unnecessary --
an intentional deviation recorded in DESIGN.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

MAX_BUILD = 4096   # keys; 16KB of VMEM


def _kernel(keys_ref, build_ref, out_ref, *, chunk: int):
    b = build_ref[...]                                 # (S, 1)

    def probe(c, carry):
        k = keys_ref[0, :, pl.ds(c * chunk, chunk)]    # (1, chunk)
        eq = (b == k).astype(jnp.int32)                # (S, chunk)
        out_ref[0, :, pl.ds(c * chunk, chunk)] = eq.max(axis=0,
                                                        keepdims=True)
        return carry

    jax.lax.fori_loop(0, keys_ref.shape[2] // chunk, probe, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def semijoin_probe(keys: jax.Array, build: jax.Array, *,
                   interpret: bool = False) -> jax.Array:
    """keys (nb, B) int32, build (S,) int32 (pad with -1) -> bool (nb, B)."""
    nb, B = keys.shape
    S = build.shape[0]
    assert S <= MAX_BUILD, "chunk the build side upstream"
    pad = (-S) % 128
    if pad:
        build = jnp.pad(build, (0, pad), constant_values=-1)
        S += pad
    chunk = 128 if B % 128 == 0 else B
    out = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 1, B), lambda i: (i, 0, 0)),
            pl.BlockSpec((S, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, B), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, B), jnp.int32),
        interpret=interpret,
        name="semijoin_probe",
    )(keys.astype(jnp.int32).reshape(nb, 1, B),
      build.astype(jnp.int32).reshape(S, 1))
    return out.reshape(nb, B) != 0
