"""Pallas packed-domain pre-aggregation kernel vs its jnp oracle.

Three-way agreement: the Pallas grid kernel (interpret mode on CPU), the
scatter-based oracle (kernels/ref.seg_preagg_ref) and the engine's XLA
path (operators.groupby_dense).  Comparison policy mirrors the kernel
contract: int32 outputs (counts, int sums, int min/max) are exact; float
sums differ only by summation order (rtol 1e-5)."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.engine import operators as ops
from repro.kernels import seg_preagg as sp
from repro.kernels.ref import seg_preagg_ref

AGGS = (("n", "*", "count"), ("sq", "qty", "sum"), ("mq", "qty", "min"),
        ("xp", "price", "max"), ("sp", "price", "sum"))


def _mkdata(rng, n, domain, key_lo=0):
    keys = jnp.asarray(rng.integers(key_lo, domain, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.8)
    values = {
        "qty": jnp.asarray(rng.integers(-50, 50, n), jnp.int32),
        "price": jnp.asarray(
            np.round(rng.normal(100, 10, n), 2), jnp.float32)}
    return keys, valid, values


def _assert_agree(a, b, label):
    assert set(a) == set(b)
    for name in a:
        av, bv = np.asarray(a[name]), np.asarray(b[name])
        assert av.shape == bv.shape, (label, name)
        if av.dtype.kind in "iub":
            np.testing.assert_array_equal(av, bv, err_msg=f"{label}:{name}")
        else:
            np.testing.assert_allclose(av, bv, rtol=1e-5,
                                       err_msg=f"{label}:{name}")


@pytest.mark.parametrize("n,domain", [(1000, 37), (4096, 256), (77, 1),
                                      (513, 1000)])
def test_kernel_matches_oracle_and_engine(n, domain):
    rng = np.random.default_rng(n + domain)
    keys, valid, values = _mkdata(rng, n, domain)
    got = sp.seg_preagg_pallas(keys, valid, values, domain, AGGS,
                               interpret=True)
    want = seg_preagg_ref(keys, valid, values, domain, AGGS)
    engine = ops.groupby_dense(keys, valid, values, domain, AGGS)
    _assert_agree(got, want, "kernel-vs-ref")
    _assert_agree(got, engine, "kernel-vs-engine")


def test_negative_keys_clip_to_group_zero():
    """Negative packed keys (a group key below the planner's assumed
    low bound) clip into group 0 on every path -- never out-of-bounds."""
    rng = np.random.default_rng(3)
    domain = 16
    keys, valid, values = _mkdata(rng, 500, domain, key_lo=-8)
    got = sp.seg_preagg_pallas(keys, valid, values, domain, AGGS,
                               interpret=True)
    want = seg_preagg_ref(keys, valid, values, domain, AGGS)
    _assert_agree(got, want, "negative-keys")
    # the clipped mass really lands in group 0
    kn = np.asarray(keys)
    vn = np.asarray(valid)
    assert int(np.asarray(got["group_count"])[0]) \
        == int((vn & (kn <= 0)).sum())


def test_all_invalid_rows_yield_sentinels():
    rng = np.random.default_rng(4)
    keys, _, values = _mkdata(rng, 256, 8)
    valid = jnp.zeros(256, bool)
    got = sp.seg_preagg_pallas(keys, valid, values, 8, AGGS,
                               interpret=True)
    want = seg_preagg_ref(keys, valid, values, 8, AGGS)
    _assert_agree(got, want, "all-invalid")
    assert int(np.asarray(got["group_count"]).sum()) == 0


def test_dispatch_declines_large_domains_and_cpu():
    # near-int32 packed domains can never fit the kernel's VMEM budget
    assert not sp._use_kernel(2**31 - 10, ("count", "sum"))
    assert not sp._use_kernel(sp.DOMAIN_CAP + 1, ("count",))
    # CPU without the env override keeps the XLA scatter
    assert not sp._use_kernel(64, ("count", "sum"))
    # unsupported aggregate kinds always decline
    assert not sp._use_kernel(64, ("count", "median"))


def test_seg_preagg_dispatcher_env_forced(monkeypatch):
    """REPRO_SEG_PREAGG=pallas forces the kernel on CPU (interpret mode);
    the dispatcher's three routes agree on the same inputs."""
    rng = np.random.default_rng(11)
    keys, valid, values = _mkdata(rng, 700, 64)
    baseline = sp.seg_preagg(keys, valid, values, 64, AGGS)  # XLA scatter
    monkeypatch.setenv("REPRO_SEG_PREAGG", "pallas")
    assert sp._use_kernel(64, tuple(a[2] for a in AGGS))
    forced = sp.seg_preagg(keys, valid, values, 64, AGGS)    # kernel
    oracle = sp.seg_preagg(keys, valid, values, 64, AGGS, force_ref=True)
    _assert_agree(forced, baseline, "forced-vs-xla")
    _assert_agree(forced, oracle, "forced-vs-oracle")


def test_large_domain_falls_back_and_matches():
    """Past the VMEM domain cap the dispatcher must keep the XLA scatter
    and still produce groupby_dense's exact outputs."""
    rng = np.random.default_rng(12)
    domain = sp.DOMAIN_CAP * 4
    keys, valid, values = _mkdata(rng, 2048, domain)
    got = sp.seg_preagg(keys, valid, values, domain, AGGS)
    want = ops.groupby_dense(keys, valid, values, domain, AGGS)
    _assert_agree(got, want, "large-domain")
