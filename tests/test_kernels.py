"""Per-kernel shape/dtype sweeps: Pallas (interpret on CPU) vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("nb,R", [(1, 128), (4, 128), (3, 384), (8, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_rle_filter_agg(nb, R, dtype):
    rv = jnp.asarray(RNG.integers(0, 100, (nb, R)), dtype)
    rl = jnp.asarray(RNG.integers(0, 20, (nb, R)), dtype)
    got = ops.rle_filter_agg(rv, rl, lo=25.0, hi=75.0)
    pad = (-R) % 128
    rvp = jnp.pad(rv, ((0, 0), (0, pad)))
    rlp = jnp.pad(rl, ((0, 0), (0, pad)))
    want = ref.rle_filter_agg_ref(rvp, rlp, 25.0, 75.0)
    np.testing.assert_allclose(got, want, rtol=1e-5)


@pytest.mark.parametrize("nb,R,domain", [(1, 128, 16), (4, 130, 64),
                                         (2, 384, 1000), (3, 40, 7)])
@pytest.mark.parametrize("bounded", [True, False])
def test_rle_grouped_agg(nb, R, domain, bounded):
    # keys partly OUT of [0, domain): must be dropped, not clipped in
    rv = jnp.asarray(RNG.integers(0, domain + 3, (nb, R)), jnp.int32)
    rl = jnp.asarray(RNG.integers(0, 20, (nb, R)), jnp.int32)
    val = jnp.asarray(RNG.normal(size=(nb, R)), jnp.float32)
    lo, hi = (2.0, float(domain)) if bounded else (-3.0e38, 3.0e38)
    got = ops.rle_grouped_agg(rv, rl, val, domain=domain, lo=lo, hi=hi)
    want = ref.rle_grouped_agg_ref(rv, rl, val, domain, lo, hi)
    assert got["count"].shape == (domain,)
    assert got["count"].dtype == jnp.int32
    np.testing.assert_array_equal(got["count"], want["count"])
    for k in ("sum", "min", "max"):
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4, atol=1e-4)


def test_rle_grouped_agg_default_values_is_key():
    rv = jnp.asarray(RNG.integers(0, 8, (2, 128)), jnp.int32)
    rl = jnp.asarray(RNG.integers(0, 5, (2, 128)), jnp.int32)
    got = ops.rle_grouped_agg(rv, rl, domain=8)
    want = ref.rle_grouped_agg_ref(rv, rl, rv, 8, -3.0e38, 3.0e38)
    for k in ("count", "sum", "min", "max"):
        np.testing.assert_allclose(got[k], want[k], rtol=1e-4, atol=1e-4)
    # count of key k == total run length with that key
    flat_rv, flat_rl = np.asarray(rv).ravel(), np.asarray(rl).ravel()
    for k in range(8):
        assert got["count"][k] == flat_rl[flat_rv == k].sum()


def test_rle_grouped_agg_long_runs_exact():
    """Counts stay exact for run lengths far above bf16's 256-integer
    range (a block-long run of one key is the common sorted case)."""
    rv = jnp.asarray(RNG.integers(0, 365, (64, 128)), jnp.int32)
    rl = jnp.asarray(RNG.integers(4000, 4097, (64, 128)), jnp.int32)
    got = ops.rle_grouped_agg(rv, rl, domain=365)
    want = np.bincount(np.asarray(rv).ravel(),
                       weights=np.asarray(rl).ravel(), minlength=365)
    np.testing.assert_array_equal(np.asarray(got["count"]),
                                  want.astype(np.int64))


@pytest.mark.parametrize("nb,B,domain", [(1, 128, 16), (4, 256, 64),
                                         (2, 512, 128), (3, 128, 1000)])
def test_onehot_groupby(nb, B, domain):
    k = jnp.asarray(RNG.integers(0, domain, (nb, B)), jnp.int32)
    v = jnp.asarray(RNG.normal(size=(nb, B)), jnp.float32)
    got = ops.onehot_groupby(k, v, domain=domain)
    want = ref.onehot_groupby_ref(k, v, domain)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("nb,B", [(1, 128), (5, 256), (2, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_delta_decode(nb, B, dtype):
    first = jnp.asarray(RNG.integers(0, 1000, (nb, 1)), dtype)
    deltas = jnp.asarray(RNG.integers(-5, 6, (nb, B)), dtype)
    got = ops.delta_decode(first, deltas)
    want = ref.delta_decode_ref(first, deltas)
    np.testing.assert_allclose(got, want)


@pytest.mark.parametrize("nb,B,S", [(1, 128, 100), (3, 256, 128),
                                    (2, 512, 1000)])
def test_semijoin_probe(nb, B, S):
    keys = jnp.asarray(RNG.integers(0, 2000, (nb, B)), jnp.int32)
    build = jnp.asarray(RNG.choice(2000, S, replace=False), jnp.int32)
    got = ops.semijoin_probe(keys, build)
    pad = (-S) % 128
    want = ref.semijoin_probe_ref(
        keys, jnp.pad(build, (0, pad), constant_values=-1))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("S,T,d", [(128, 128, 64), (256, 256, 128),
                                   (128, 384, 64)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(S, T, d, causal, dtype):
    if causal and S != T:
        pytest.skip("causal requires square here")
    q = jnp.asarray(RNG.normal(size=(S, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(T, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(T, d)), dtype)
    got = ops.flash_attention(q, k, v, causal=causal)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_batched():
    q = jnp.asarray(RNG.normal(size=(2, 3, 128, 64)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 3, 128, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 3, 128, 64)), jnp.float32)
    got = ops.flash_attention(q, k, v)
    want = ops.flash_attention(q, k, v, force_ref=True)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
