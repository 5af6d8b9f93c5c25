"""Compile the Pallas kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jax; it compiles for a chip that is
described (``topologies.get_topology_desc``) and not attached.  It refuses
what interpret mode accepts: blocks not aligned to the (8, 128) tiling,
in-kernel reshapes Mosaic cannot lay out, ops without a Mosaic lowering.
Each test lowers a kernel at the shapes the query path produces and
asserts the compiled program holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import (bitunpack, delta_decode, flash_attention,
                           hash_groupby, rle_scan_agg, seg_preagg, sip_probe)

BLOCK_ROWS = 4096          # the harness's block size
N_BLOCKS = 2930            # one 12M-row container


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import compilation_cache, topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                        # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", saved)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", [1, 3, 7, 13, 17, 31, 32])
@pytest.mark.parametrize("with_base", [False, True])
def test_bitunpack_compiles(one_chip, width, with_base):
    words = _spec((N_BLOCKS, BLOCK_ROWS // 32 * width), jnp.uint32, one_chip)
    base = _spec((N_BLOCKS,), jnp.int32, one_chip) if with_base else None
    _assert_kernel(bitunpack.bitunpack_pallas.lower(
        words, width=width, block_rows=BLOCK_ROWS, base=base))


@pytest.mark.parametrize("domain", [7, 365, rle_scan_agg.DOMAIN_CAP])
def test_rle_grouped_agg_compiles(one_chip, domain):
    runs = _spec((N_BLOCKS, 128), jnp.int32, one_chip)
    vals = _spec((N_BLOCKS, 128), jnp.float32, one_chip)
    _assert_kernel(rle_scan_agg.rle_grouped_agg.lower(
        runs, runs, vals, domain=domain))


@pytest.mark.parametrize("domain,kinds,dtypes", [
    (100, ("sum",), (jnp.int32,)),
    (365, ("sum", "min", "max"), (jnp.float32,) * 3),
    (seg_preagg.DOMAIN_CAP, ("sum", "min", "max", "sum"),
     (jnp.int32, jnp.int32, jnp.float32, jnp.float32)),
])
def test_seg_preagg_compiles(one_chip, domain, kinds, dtypes):
    n = 12_000_000 + (-12_000_000) % seg_preagg._BLOCK
    keys = _spec((n,), jnp.int32, one_chip)
    vals = tuple(_spec((n,), dt, one_chip) for dt in dtypes)
    _assert_kernel(seg_preagg._preagg_call.lower(
        keys, keys, vals, domain=domain, kinds=kinds, interpret=False))


@pytest.mark.parametrize("n_chips", [1, 4])
@pytest.mark.parametrize("check_vma", [True, False])
def test_seg_preagg_compiles_under_shard_map(topo, n_chips, check_vma):
    """The segmented executor calls the kernel per shard inside its
    shard_map'd stage program, on a 1-chip mesh or across 4 chips."""
    mesh = Mesh(np.asarray(topo.devices[:n_chips]), ("data",))
    aggs = (("n", "*", "count"), ("s", "q", "sum"), ("a", "p", "sum"))

    def local(keys, valid, q, p):
        out = seg_preagg.seg_preagg_pallas(
            keys, valid, {"q": q, "p": p}, 100, aggs, interpret=False)
        return tuple(jax.lax.psum(out[k], "data") for k in ("n", "s", "a"))

    fn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P("data"),) * 4,
                               out_specs=(P(),) * 3, check_vma=check_vma))
    rows = NamedSharding(mesh, P("data"))
    n = 3_000_000 * n_chips
    args = [_spec((n,), dt, rows) for dt in (jnp.int32, jnp.bool_,
                                              jnp.int32, jnp.float32)]
    _assert_kernel(fn.lower(*args))


@pytest.mark.parametrize("n_chips", [1, 4])
def test_resegment_fits_the_chip(topo, n_chips):
    """The slab build's exchange keeps O(rows) scratch: an (n, n_shards)
    int32 array pads its minor dim to 128 lanes on TPU, 512 bytes a row
    (24.6 GB at the smoke test's 48M rows on one chip)."""
    from repro.engine import exchange
    mesh = Mesh(np.asarray(topo.devices[:n_chips]), ("data",))
    rows = NamedSharding(mesh, P("data"))
    n = 1 << 21
    cols = {c: _spec((n,), jnp.int32, rows) for c in "abcd"}

    def move(dest, cols):
        return exchange.resegment(mesh, "data", cols, dest, n + 512 * n_chips)

    compiled = jax.jit(move).lower(_spec((n,), jnp.int32, rows),
                                   cols).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * n


def test_off_main_path_kernels_compile(one_chip):
    """rle_filter_agg, onehot_groupby, semijoin_probe, delta_decode and
    flash_attention have no caller on the query path, but ops.py
    dispatches them to the compiled kernel on TPU, so they must compile
    too."""
    runs = _spec((N_BLOCKS, 128), jnp.int32, one_chip)
    rows = _spec((64, BLOCK_ROWS), jnp.int32, one_chip)
    _assert_kernel(rle_scan_agg.rle_filter_agg.lower(
        runs, runs, lo=25.0, hi=75.0))
    _assert_kernel(hash_groupby.onehot_groupby.lower(
        rows, _spec((64, BLOCK_ROWS), jnp.float32, one_chip), domain=1000))
    _assert_kernel(sip_probe.semijoin_probe.lower(
        rows, _spec((sip_probe.MAX_BUILD,), jnp.int32, one_chip)))
    _assert_kernel(delta_decode.delta_decode.lower(
        _spec((64, 1), jnp.float32, one_chip),
        _spec((64, BLOCK_ROWS), jnp.float32, one_chip)))
    qkv = _spec((512, 128), jnp.bfloat16, one_chip)
    _assert_kernel(flash_attention.flash_attention.lower(qkv, qkv, qkv))
