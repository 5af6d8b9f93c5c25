"""Property-style tests for the segmentation primitives that the
segmented executor's correctness rests on: ring hashing, buddy-offset
placement, mixed-radix key packing, and elastic rebalance coverage.
"""
import numpy as np
import pytest
from hypothesis import given, settings
import hypothesis.strategies as st

import jax.numpy as jnp

from repro.core.segmentation import (SegmentationSpec, hash_columns,
                                     rebalance_plan, shard_of)
from repro.core.types import C_MAX
from repro.engine import operators as ops

I64_MIN, I64_MAX = -(2 ** 63), 2 ** 63 - 1


# ---------------------------------------------------------------------------
# hash_columns: deterministic, full-range safe, ring-bounded
# ---------------------------------------------------------------------------

@settings(max_examples=30)
@given(st.lists(st.integers(I64_MIN, I64_MAX), min_size=1, max_size=100))
def test_hash_columns_deterministic_and_in_range(xs):
    a = np.asarray(xs, dtype=np.int64)
    h1, h2 = hash_columns(a), hash_columns(a)
    assert h1.dtype == np.uint64
    assert (h1 == h2).all()                      # deterministic
    assert (h1 < np.uint64(C_MAX)).all()         # on the ring


@settings(max_examples=20)
@given(st.lists(st.integers(-10 ** 9, 10 ** 9), min_size=1, max_size=50),
       st.integers(0, 10 ** 6))
def test_hash_columns_multi_column_order_sensitivity(xs, shift):
    """Multi-column hashes mix every column: shifting one column while
    holding the other changes the hash for (almost) every row, and the
    hash of (a, b) is reproducible."""
    a = np.asarray(xs, dtype=np.int64)
    b = a + shift + 1
    h = hash_columns(a, b)
    assert (h == hash_columns(a, b)).all()
    assert (h < np.uint64(C_MAX)).all()


@settings(max_examples=20)
@given(st.lists(st.integers(-10 ** 9, 10 ** 9), min_size=1, max_size=200),
       st.integers(2, 16))
def test_node_of_buddy_offset_disjoint(xs, n_nodes):
    """Paper §5.2: a K=1 buddy's ring offset guarantees that NO row's
    buddy copy lives on the same node as its primary copy."""
    ring = hash_columns(np.asarray(xs, dtype=np.int64))
    primary = SegmentationSpec("hash", ("k",), offset=0)
    buddy = SegmentationSpec("hash", ("k",), offset=1)
    a = primary.node_of(ring, n_nodes)
    b = buddy.node_of(ring, n_nodes)
    assert ((0 <= a) & (a < n_nodes)).all()
    assert ((0 <= b) & (b < n_nodes)).all()
    assert (a != b).all()
    # and the buddy is exactly the primary shifted one ring slot
    assert ((a + 1) % n_nodes == b).all()


@settings(max_examples=20)
@given(st.lists(st.integers(I64_MIN, I64_MAX), min_size=1, max_size=100),
       st.integers(1, 16))
def test_shard_of_is_offset_free_node_of(xs, n):
    """Device shard placement (engine/segmented.py) must agree with the
    offset-0 node map so primary- and buddy-served rows coincide."""
    ring = hash_columns(np.asarray(xs, dtype=np.int64))
    s = shard_of(ring, n)
    assert ((0 <= s) & (s < n)).all()
    spec = SegmentationSpec("hash", ("k",), offset=0)
    assert (s == spec.node_of(ring, n)).all()


# ---------------------------------------------------------------------------
# pack_keys / unpack_keys: mixed-radix round trip incl. negative domains
# ---------------------------------------------------------------------------

@settings(max_examples=25)
@given(st.integers(0, 10 ** 6), st.integers(1, 3))
def test_pack_unpack_roundtrip(seed, ncols):
    rng = np.random.default_rng(seed)
    lows = [int(v) for v in rng.integers(-(2 ** 16), 2 ** 16, ncols)]
    domains = [int(v) for v in rng.integers(1, 1024, ncols)]
    keys = [rng.integers(lo, lo + d, 64).astype(np.int32)
            for lo, d in zip(lows, domains)]
    packed = ops.pack_keys([jnp.asarray(k) for k in keys],
                           tuple(domains), tuple(lows))
    total = 1
    for d in domains:
        total *= d
    p = np.asarray(packed)
    assert (0 <= p).all() and (p < total).all()
    unpacked = ops.unpack_keys(p, domains, lows)
    for orig, rec in zip(keys, unpacked):
        assert (orig == np.asarray(rec)).all()


def test_pack_unpack_near_int32_limit():
    """Product within a hair of 2^31 (the device pack limit) with negative
    lows: the packed intermediate must not overflow int32."""
    domains = (1 << 15, 1 << 15)                 # product = 2^30
    lows = (-(1 << 14), -(1 << 14))
    rng = np.random.default_rng(3)
    keys = [rng.integers(lo, lo + d, 256).astype(np.int32)
            for lo, d in zip(lows, domains)]
    # include the exact corners
    keys[0][:2] = [lows[0], lows[0] + domains[0] - 1]
    keys[1][:2] = [lows[1], lows[1] + domains[1] - 1]
    packed = np.asarray(ops.pack_keys([jnp.asarray(k) for k in keys],
                                      domains, lows))
    assert packed.max() < (1 << 30)
    assert packed.min() >= 0
    unpacked = ops.unpack_keys(packed, domains, lows)
    for orig, rec in zip(keys, unpacked):
        assert (orig == np.asarray(rec)).all()


def test_pack_clips_out_of_domain_values():
    """Out-of-domain values clip to the domain edge (callers bound the
    domain; clipping keeps the scatter in range rather than corrupting a
    neighbor's bucket)."""
    packed = np.asarray(ops.pack_keys(
        [jnp.asarray(np.array([-5, 0, 9, 42], np.int32))], (10,), (0,)))
    assert packed.tolist() == [0, 0, 9, 9]


# ---------------------------------------------------------------------------
# rebalance_plan: moved segments exactly cover the ranges that changed owner
# ---------------------------------------------------------------------------

def _center_owner(node: int, seg: int, n_old: int, n_local: int,
                  n_new: int) -> int:
    """Independent owner-of-center check via the ring map itself."""
    width = float(C_MAX) / n_old
    point = node * width + (seg + 0.5) * width / n_local
    ring = np.asarray([min(point, float(C_MAX) - 1)])
    return int(shard_of(ring, n_new)[0])


@pytest.mark.parametrize("n_old,n_new", [
    (4, 8), (8, 4),          # double / halve
    (3, 5), (5, 3),          # coprime grow / shrink
    (6, 2), (2, 6), (2, 3), (7, 8),
])
@pytest.mark.parametrize("n_local", [1, 3, 4])
def test_rebalance_moves_exactly_changed_ranges(n_old, n_new, n_local):
    moves = rebalance_plan(n_old, n_new, n_local)
    moved = {}
    for old_node, seg, new_node in moves:
        assert (old_node, seg) not in moved, "duplicate move"
        moved[(old_node, seg)] = new_node
    for node in range(n_old):
        for seg in range(n_local):
            owner = _center_owner(node, seg, n_old, n_local, n_new)
            if owner != node:
                # range changed owner: must move, and to that owner
                assert moved.get((node, seg)) == owner, \
                    (node, seg, owner, moves)
            else:
                assert (node, seg) not in moved, (node, seg)


def test_rebalance_identity_topology_moves_nothing():
    for n in (1, 2, 4, 7):
        assert rebalance_plan(n, n, 3) == []


def test_rebalance_grow_shrink_roundtrip_is_consistent():
    """A segment's ring center owned by node i under the old topology is
    owned by i again after growing and shrinking back -- whole-segment
    moves are invertible."""
    n_local = 3
    for node in range(4):
        for seg in range(n_local):
            width4 = float(C_MAX) / 4
            point = node * width4 + (seg + 0.5) * width4 / n_local
            assert int(shard_of(np.asarray([point]), 4)[0]) == node
