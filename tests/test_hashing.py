"""Property tests for the weight-sharing hash constructions."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import hashing


@given(
    vocab=st.integers(10, 100_000),
    collision=st.integers(2, 512),
)
@settings(max_examples=50, deadline=None)
def test_qr_spec_counts(vocab, collision):
    spec = hashing.QRSpec(vocab=vocab, collision=collision, dim=16)
    assert spec.q_rows == -(-vocab // collision)
    assert spec.r_rows == collision
    # capacity shrinks whenever the table is meaningfully bigger than c^2
    if vocab >= 4 * collision * collision:
        assert spec.compression > 1.0


@given(
    vocab=st.integers(8, 50_000),
    collision=st.integers(2, 128),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=30, deadline=None)
def test_qr_complementary_partition(vocab, collision, seed):
    """(q, r) is unique per logical index — the complementarity property."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, vocab, size=min(vocab, 512)).astype(np.int32)
    q, r = hashing.qr_decompose(jnp.asarray(idx), collision)
    recon = np.asarray(q) * collision + np.asarray(r)
    np.testing.assert_array_equal(recon, idx)


@given(buckets=st.integers(1, 10_000), seed=st.integers(0, 5))
@settings(max_examples=30, deadline=None)
def test_universal_hash_range(buckets, seed):
    idx = jnp.arange(256, dtype=jnp.int32)
    h = hashing.universal_hash(idx, buckets, seed=seed)
    assert h.dtype == jnp.int32
    assert int(h.min()) >= 0 and int(h.max()) < buckets
    # deterministic
    h2 = hashing.universal_hash(idx, buckets, seed=seed)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h2))


def test_k_ary_hash_shape():
    idx = jnp.arange(17, dtype=jnp.int32)
    hs = hashing.k_ary_hash(idx, 97, 3)
    assert hs.shape == (17, 3)
    # different seeds give different hash functions (overwhelmingly likely)
    assert not np.array_equal(np.asarray(hs[:, 0]), np.asarray(hs[:, 1]))


@given(
    rows=st.integers(1, 100_000),
    shards=st.sampled_from([1, 2, 4, 8, 16, 64]),
    seed=st.integers(0, 1000),
)
@settings(max_examples=40, deadline=None)
def test_row_owner_local_consistency(rows, shards, seed):
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, rows, size=64).astype(np.int32))
    owner = hashing.row_owner(idx, rows, shards)
    local = hashing.local_row(idx, rows, shards)
    rps = -(-rows // shards)
    np.testing.assert_array_equal(
        np.asarray(owner) * rps + np.asarray(local), np.asarray(idx)
    )
    assert int(owner.max()) < shards
    assert hashing.padded_rows(rows, shards) % shards == 0
    assert hashing.padded_rows(rows, shards) >= rows


def test_qr_shard_owner_matches_decompose():
    idx = jnp.arange(1000, dtype=jnp.int32)
    c, q_rows, nsh = 8, 125, 4
    owner = hashing.qr_shard_owner(idx, c, q_rows, nsh)
    q, _ = hashing.qr_decompose(idx, c)
    np.testing.assert_array_equal(
        np.asarray(owner), np.asarray(hashing.row_owner(q, q_rows, nsh))
    )
