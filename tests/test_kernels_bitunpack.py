"""Bit-unpack kernel oracle tests: XLA path, Pallas path (interpret on CPU),
and the random-access gather, all against the bit-by-bit ref.bitunpack_ref
-- byte-identical, not allclose.  Also the delta_decode oracle coverage the
kernel previously lacked (every ops.py decode dispatch now shares one gate).
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.encodings import pack_words, unpack_words
from repro.kernels import ops, ref
from repro.kernels.bitunpack import bitunpack_pallas, bitunpack_xla, \
    gather_unpack

RNG = np.random.default_rng(3)


def _symbols(nb, br, width):
    return RNG.integers(0, 1 << width, (nb, br), dtype=np.uint64) \
        .astype(np.int64)


@pytest.mark.parametrize("width", [1, 2, 3, 5, 7, 8, 11, 16, 21, 31, 32])
@pytest.mark.parametrize("nb,br", [(1, 32), (3, 64), (2, 100)])
def test_xla_unpack_matches_bit_oracle(width, nb, br):
    syms = _symbols(nb, br, width)
    words = pack_words(syms, width)
    want = ref.bitunpack_ref(words, width, br)
    got = bitunpack_xla(jnp.asarray(words), width, br)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the oracle itself round-trips the host packer
    np.testing.assert_array_equal(
        np.asarray(want).astype(np.uint32).astype(np.int64),
        syms.astype(np.uint32).astype(np.int64))


@pytest.mark.parametrize("width", [1, 4, 6, 8, 13, 17, 24, 32])
@pytest.mark.parametrize("nb,br", [(2, 64), (1, 512), (3, 1024), (2, 96)])
def test_pallas_unpack_matches_bit_oracle(width, nb, br):
    syms = _symbols(nb, br, width)
    words = pack_words(syms, width)
    want = ref.bitunpack_ref(words, width, br)
    got = bitunpack_pallas(jnp.asarray(words), width, br, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("width", [5, 8, 19, 32])
def test_fused_base_add(width):
    nb, br = 3, 128
    syms = _symbols(nb, br, min(width, 20))
    base = RNG.integers(-1000, 1000, nb).astype(np.int64)
    words = pack_words(syms, width)
    want = ref.bitunpack_ref(words, width, br, base)
    got_xla = bitunpack_xla(jnp.asarray(words), width, br, jnp.asarray(base))
    got_pl = bitunpack_pallas(jnp.asarray(words), width, br,
                              jnp.asarray(base), interpret=True)
    np.testing.assert_array_equal(np.asarray(got_xla), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_pl), np.asarray(want))


@pytest.mark.parametrize("width", [1, 3, 8, 12, 27, 32])
def test_gather_unpack_random_positions(width):
    nb, br = 4, 256
    syms = _symbols(nb, br, width)
    words = jnp.asarray(pack_words(syms, width))
    n = 300
    b = RNG.integers(0, nb, n)
    r = RNG.integers(0, br, n)
    got = gather_unpack(words, width, jnp.asarray(b), jnp.asarray(r))
    want = syms[b, r].astype(np.uint32).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_ops_dispatch_env_gate(monkeypatch):
    syms = _symbols(2, 64, 9)
    words = jnp.asarray(pack_words(syms, 9))
    want = np.asarray(ops.bitunpack(words, 9, 64, force_ref=True))
    monkeypatch.delenv("REPRO_BITUNPACK", raising=False)
    np.testing.assert_array_equal(np.asarray(ops.bitunpack(words, 9, 64)),
                                  want)
    monkeypatch.setenv("REPRO_BITUNPACK", "pallas")
    np.testing.assert_array_equal(np.asarray(ops.bitunpack(words, 9, 64)),
                                  want)


def test_host_unpack_words_inverse():
    for width in (1, 2, 9, 15, 22, 30, 32):
        syms = _symbols(3, 70, width)
        np.testing.assert_array_equal(
            unpack_words(pack_words(syms, width), width, 70), syms)


@pytest.mark.parametrize("nb,B", [(1, 128), (4, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("env", ["", "pallas"])
def test_delta_decode_oracle_both_paths(nb, B, dtype, env, monkeypatch):
    if env:
        monkeypatch.setenv("REPRO_DELTA_DECODE", env)
    else:
        monkeypatch.delenv("REPRO_DELTA_DECODE", raising=False)
    first = jnp.asarray(RNG.integers(0, 1000, (nb, 1)), dtype)
    deltas = jnp.asarray(RNG.integers(-5, 6, (nb, B)), dtype)
    got = ops.delta_decode(first, deltas)
    want = ref.delta_decode_ref(first, deltas)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("env", ["", "xla", "pallas"])
def test_tpu_dispatch_ignores_env(env, monkeypatch):
    """On a TPU the decode kernels and seg_preagg always take the compiled
    kernel: no REPRO_* value selects interpret mode or the XLA path."""
    from repro.kernels import seg_preagg
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    for var in ("REPRO_BITUNPACK", "REPRO_DELTA_DECODE",
                "REPRO_SEG_PREAGG"):
        monkeypatch.setenv(var, env)
        assert ops.pallas_enabled(var)
    assert seg_preagg._use_kernel(64, ("count", "sum"))
