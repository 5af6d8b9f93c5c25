"""DELTA_RANGE block decode Pallas kernel: in-VMEM prefix scan.

Decode is fused into consumers on real pipelines; standalone it shows the
structure: eight block strips per grid step, an inclusive prefix sum along
the lane axis as log2(B) lane rotations (Mosaic has no cumsum lowering).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 8


def _kernel(first_ref, deltas_ref, out_ref):
    d = deltas_ref[...].astype(jnp.float32)            # (8, B)
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    acc, shift = d, 1
    while shift < d.shape[1]:
        acc = acc + jnp.where(lane >= shift,
                              pltpu.roll(acc, shift, axis=1), 0.0)
        shift *= 2
    first = first_ref[...].astype(jnp.float32)         # (8, 1)
    out_ref[...] = first + acc - d[:, :1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_decode(first: jax.Array, deltas: jax.Array, *,
                 interpret: bool = False) -> jax.Array:
    """first (nb, 1), deltas (nb, B) -> values (nb, B) f32."""
    nb, B = deltas.shape
    pad = (-nb) % _ROWS
    first = jnp.pad(first, ((0, pad), (0, 0)))
    deltas = jnp.pad(deltas, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _kernel,
        grid=((nb + pad) // _ROWS,),
        in_specs=[
            pl.BlockSpec((_ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((_ROWS, B), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS, B), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb + pad, B), jnp.float32),
        interpret=interpret,
        name="delta_decode",
    )(first, deltas)
    return out[:nb]
