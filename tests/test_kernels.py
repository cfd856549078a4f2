"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


def _tables(q_rows, c, dim, dtype, seed=0):
    kq, kr = jax.random.split(jax.random.PRNGKey(seed))
    q = jax.random.normal(kq, (q_rows, dim), dtype)
    r = jax.random.normal(kr, (c, dim), dtype)
    return q, r


@pytest.mark.parametrize("dim", [128, 256, 512, 640, 1024])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qr_lookup_sweep(dim, dtype):
    q, r = _tables(64, 8, dim, dtype)
    key = jax.random.PRNGKey(1)
    qi = jax.random.randint(key, (33,), 0, 64)
    ri = jax.random.randint(key, (33,), 0, 8)
    out = ops.qr_lookup(q, r, qi, ri)
    expect = ref.qr_lookup_ref(q, r, qi, ri)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=1e-5 if dtype == jnp.float32 else 2e-2,
    )


@pytest.mark.parametrize("lead", [(7,), (2, 5), (3, 2, 2)])
def test_qr_lookup_leading_shapes(lead):
    q, r = _tables(32, 4, 128, jnp.float32)
    key = jax.random.PRNGKey(2)
    qi = jax.random.randint(key, lead, 0, 32)
    ri = jax.random.randint(key, lead, 0, 4)
    out = ops.qr_lookup(q, r, qi, ri)
    assert out.shape == lead + (128,)
    np.testing.assert_allclose(out, ref.qr_lookup_ref(q, r, qi, ri), rtol=1e-6)


@pytest.mark.parametrize("dim", [128, 512])
@pytest.mark.parametrize("k", [1, 4, 32])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gnr_pooled_sweep(dim, k, dtype):
    q, r = _tables(128, 16, dim, dtype)
    key = jax.random.PRNGKey(3)
    qi = jax.random.randint(key, (6, k), 0, 128)
    ri = jax.random.randint(key, (6, k), 0, 16)
    out = ops.gnr_pooled(q, r, qi, ri)
    expect = ref.gnr_bag_ref(q, r, qi, ri)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=1e-5 if dtype == jnp.float32 else 3e-2, atol=1e-2,
    )


@pytest.mark.parametrize("dim", [128, 384])
def test_gnr_dense_sweep(dim):
    t, _ = _tables(64, 2, dim, jnp.float32)
    key = jax.random.PRNGKey(4)
    idx = jax.random.randint(key, (5, 9), 0, 64)
    out = ops.gnr_pooled_dense(t, idx)
    # atol covers fp32 accumulation-order differences between the interpret-
    # mode kernel and the XLA-fused reference (host-dependent).
    np.testing.assert_allclose(out, ref.dense_bag_ref(t, idx), rtol=1e-5, atol=1e-5)


def test_small_dim_fallback():
    """dims with no 8-aligned tile fall back to the jnp reference path."""
    q, r = _tables(16, 4, 12, jnp.float32)
    qi = jnp.array([0, 15], jnp.int32)
    ri = jnp.array([1, 3], jnp.int32)
    np.testing.assert_allclose(
        ops.qr_lookup(q, r, qi, ri), ref.qr_lookup_ref(q, r, qi, ri), rtol=1e-6
    )


@pytest.mark.parametrize("dim", [96, 200])
def test_non_128_dim_single_tile(dim):
    """8-aligned dims NOT divisible by 128 run the kernel as one wide tile
    (the explicit fallback): kernel == oracle, for lookup and pooled paths."""
    q, r = _tables(64, 8, dim, jnp.float32)
    key = jax.random.PRNGKey(7)
    qi = jax.random.randint(key, (5, 6), 0, 64)
    ri = jax.random.randint(key, (5, 6), 0, 8)
    np.testing.assert_allclose(
        np.asarray(ops.gnr_pooled(q, r, qi, ri)),
        np.asarray(ref.gnr_bag_ref(q, r, qi, ri)), rtol=1e-5, atol=1e-5,
    )
    np.testing.assert_allclose(
        np.asarray(ops.qr_lookup(q, r, qi[:, 0], ri[:, 0])),
        np.asarray(ref.qr_lookup_ref(q, r, qi[:, 0], ri[:, 0])), rtol=1e-5,
    )


def test_pick_dim_block_ladder():
    """The dim-block choice is now an explicit tuner knob
    (``repro.tune.knobs``): the heuristic default reproduces the historical
    ladder (largest of 512/256/128 dividing dim; whole dim when 8-aligned;
    None = jnp reference), and the valid-block enumeration bounds what a
    tuned plan may pass."""
    from repro.tune import knobs as K

    for d, want in ((128, 128), (256, 256), (512, 512), (640, 128),
                    (384, 128)):
        assert ops._pick_dim_block(d) == want == K.default_dim_block(d)
    # 8-aligned, non-128 dims: single wide tile (96, 200)
    assert ops._pick_dim_block(96) == 96
    assert ops._pick_dim_block(200) == 200
    assert K.valid_dim_blocks(96) == (96,)
    assert K.valid_dim_blocks(200) == (200,)
    # no 8-aligned tile at all: jnp reference only
    assert ops._pick_dim_block(13) is None
    assert K.valid_dim_blocks(13) == ()


@pytest.mark.parametrize("dim,block", [(96, 96), (200, 200), (256, 128)])
def test_explicit_dim_block_matches_oracle(dim, block):
    """A tuner-chosen ``dim_block`` threads through the public wrappers and
    produces oracle-identical results."""
    q, r = _tables(64, 8, dim, jnp.float32)
    key = jax.random.PRNGKey(11)
    qi = jax.random.randint(key, (4, 6), 0, 64)
    ri = jax.random.randint(key, (4, 6), 0, 8)
    np.testing.assert_allclose(
        np.asarray(ops.gnr_pooled(q, r, qi, ri, dim_block=block)),
        np.asarray(ref.gnr_bag_ref(q, r, qi, ri)), rtol=1e-5, atol=1e-5,
    )


def test_invalid_dim_block_rejected():
    """An explicit block that is illegal for the dim is an error, and a dim
    with no valid block rejects every explicit block."""
    q, r = _tables(64, 8, 96, jnp.float32)
    qi = jnp.zeros((2, 3), jnp.int32)
    ri = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(ValueError, match="not valid for dim 96"):
        ops.gnr_pooled(q, r, qi, ri, dim_block=128)
    q13, r13 = _tables(64, 8, 13, jnp.float32)
    with pytest.raises(ValueError, match="not valid for dim 13"):
        ops.gnr_pooled(q13, r13, qi, ri, dim_block=13)


@given(
    n=st.integers(1, 64),
    q_rows=st.integers(1, 200),
    c=st.integers(1, 32),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=20, deadline=None)
def test_qr_lookup_property(n, q_rows, c, seed):
    """Kernel == oracle for arbitrary index distributions (dim fixed 128)."""
    q, r = _tables(q_rows, c, 128, jnp.float32, seed=seed % 97)
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    qi = jax.random.randint(k1, (n,), 0, q_rows)
    ri = jax.random.randint(k2, (n,), 0, c)
    np.testing.assert_allclose(
        ops.qr_lookup(q, r, qi, ri), ref.qr_lookup_ref(q, r, qi, ri), rtol=1e-6
    )


def test_gnr_accumulates_fp32():
    """bf16 tables with many repeated adds must not lose precision (the
    kernel's fp32 VMEM accumulator — 'MAC-unit accuracy')."""
    dim, k = 128, 256
    q = jnp.full((4, dim), 1.001, jnp.bfloat16)
    r = jnp.zeros((2, dim), jnp.bfloat16)
    qi = jnp.zeros((1, k), jnp.int32)
    ri = jnp.zeros((1, k), jnp.int32)
    out = ops.gnr_pooled(q, r, qi, ri)
    expect = float(jnp.bfloat16(1.001)) * k
    assert abs(float(out[0, 0]) - expect) / expect < 1e-2


# ---------------------------------------------------------------------------
# Pallas flash attention (VMEM-resident tiles)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [
    (2, 4, 2, 256, 256, True),    # GQA causal
    (1, 4, 4, 128, 384, False),   # MHA cross-length
    (2, 8, 2, 512, 512, True),
])
def test_flash_fused_vs_oracle(shape):
    from repro.kernels.flash_attention import flash_fwd

    b, h, kh, sq, skv, causal = shape
    key = jax.random.PRNGKey(0)
    q_ = jax.random.normal(jax.random.fold_in(key, 1), (b, h, sq, 128))
    k_ = jax.random.normal(jax.random.fold_in(key, 2), (b, kh, skv, 128))
    v_ = jax.random.normal(jax.random.fold_in(key, 3), (b, kh, skv, 128))
    out = flash_fwd(q_, k_, v_, causal=causal, q_block=128, kv_block=128,
                    interpret=True)
    expect = ref.flash_attention_ref(q_, k_, v_, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_fused_dtypes(dtype):
    from repro.kernels.flash_attention import flash_fwd

    key = jax.random.PRNGKey(1)
    q_ = jax.random.normal(key, (1, 2, 128, 128), dtype)
    k_ = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 128, 128), dtype)
    v_ = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 128, 128), dtype)
    out = flash_fwd(q_, k_, v_, causal=True, interpret=True)
    expect = ref.flash_attention_ref(
        q_.astype(jnp.float32), k_.astype(jnp.float32), v_.astype(jnp.float32),
        causal=True)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect),
                               rtol=tol, atol=tol)


def test_flash_fused_grad_matches_reference():
    from repro.kernels.flash_attention import flash_mha
    from repro.models.layers import flash_attention

    key = jax.random.PRNGKey(2)
    q_ = jax.random.normal(key, (1, 2, 128, 128))
    k_ = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 128, 128))
    v_ = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 128, 128))
    g1 = jax.grad(lambda a: flash_mha(a, k_, v_, True, True).sum())(q_)
    g2 = jax.grad(lambda a: flash_attention(a, k_, v_, causal=True).sum())(q_)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("rows,n", [(64, 5), (3000, 512), (3000, 1300)])
def test_stage_rows_is_the_gather(rows, n):
    """The cache-staging kernel copies each named row of the row view."""
    from repro.kernels import blocks

    table = jax.random.normal(jax.random.PRNGKey(rows), (rows, 1, 128))
    idx = jnp.asarray(np.random.default_rng(n).integers(0, rows, n), jnp.int32)
    got = blocks.stage_rows(table, idx, interpret=True)
    assert got.shape == (n, 128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(table[idx, 0]))
    via_ops = ops.packed_cache_block(table, idx, kind="qr", exec_mode="kernel")
    np.testing.assert_array_equal(np.asarray(via_ops), np.asarray(got))
