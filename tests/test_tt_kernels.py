"""Fused Pallas TT gather-contract kernel vs the pure-jnp oracle
(interpret=True on CPU), plus integration with the tt_embedding module."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qr_embedding as QE, tt_embedding as TT
from repro.core.qr_embedding import EmbeddingConfig
from repro.kernels import ops, ref


def _cores(v1, v2, v3, dims, dtype, seed=0):
    d1, d2, d3, r = dims
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    g1 = jax.random.normal(k1, (v1, d1 * r), dtype)
    g2 = jax.random.normal(k2, (v2, r * d2 * r), dtype)
    g3 = jax.random.normal(k3, (v3, r * d3), dtype)
    return g1, g2, g3


def _indices(key, shape, v1, v2, v3):
    return (
        jax.random.randint(jax.random.fold_in(key, 1), shape, 0, v1),
        jax.random.randint(jax.random.fold_in(key, 2), shape, 0, v2),
        jax.random.randint(jax.random.fold_in(key, 3), shape, 0, v3),
    )


@pytest.mark.parametrize("dims", [(4, 8, 4, 4), (4, 8, 4, 16), (2, 4, 2, 8), (4, 4, 2, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tt_bag_sweep(dims, dtype):
    v1, v2, v3 = 8, 64, 8
    g1, g2, g3 = _cores(v1, v2, v3, dims, dtype)
    i1, i2, i3 = _indices(jax.random.PRNGKey(1), (6, 5), v1, v2, v3)
    out = ops.tt_pooled(g1, g2, g3, i1, i2, i3, dims=dims)
    expect = ref.tt_bag_ref(g1, g2, g3, i1, i2, i3, dims=dims)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=1e-5 if dtype == jnp.float32 else 3e-2, atol=1e-2,
    )


@pytest.mark.parametrize("k", [1, 4, 32])
def test_tt_bag_pooling_sizes(k):
    dims = (4, 8, 4, 8)
    g1, g2, g3 = _cores(16, 128, 16, dims, jnp.float32)
    i1, i2, i3 = _indices(jax.random.PRNGKey(2), (5, k), 16, 128, 16)
    out = ops.tt_pooled(g1, g2, g3, i1, i2, i3, dims=dims)
    expect = ref.tt_bag_ref(g1, g2, g3, i1, i2, i3, dims=dims)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lead", [(7,), (2, 5), (3, 2, 2)])
def test_tt_lookup_leading_shapes(lead):
    dims = (4, 4, 2, 4)
    g1, g2, g3 = _cores(8, 32, 8, dims, jnp.float32)
    i1, i2, i3 = _indices(jax.random.PRNGKey(3), lead, 8, 32, 8)
    out = ops.tt_lookup(g1, g2, g3, i1, i2, i3, dims=dims)
    assert out.shape == lead + (32,)
    expect = ref.tt_row_ref(g1, g2, g3, i1, i2, i3, dims=dims)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5, atol=1e-6)


def test_tt_small_dim_fallback():
    """dims with no 8-aligned output tile fall back to the jnp reference."""
    dims = (2, 3, 2, 2)                     # dim 12: not 8-aligned
    g1, g2, g3 = _cores(4, 8, 4, dims, jnp.float32)
    i1, i2, i3 = _indices(jax.random.PRNGKey(4), (3, 2), 4, 8, 4)
    out = ops.tt_pooled(g1, g2, g3, i1, i2, i3, dims=dims)
    expect = ref.tt_bag_ref(g1, g2, g3, i1, i2, i3, dims=dims)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-6)


def test_tt_bag_accumulates_fp32():
    """bf16 cores with many repeated adds must not lose precision (the fp32
    VMEM accumulator — 'MAC-unit accuracy')."""
    dims = (1, 1, 1, 1)
    k = 256
    g1 = jnp.full((2, 1), 1.0, jnp.bfloat16)
    g2 = jnp.full((2, 1), jnp.bfloat16(1.001), jnp.bfloat16)
    g3 = jnp.full((2, 1), 1.0, jnp.bfloat16)
    zeros = jnp.zeros((1, k), jnp.int32)
    out = ops.tt_pooled(g1, g2, g3, zeros, zeros, zeros, dims=dims)
    expect = float(jnp.bfloat16(1.001)) * k
    assert abs(float(out[0, 0]) - expect) / expect < 1e-2


def test_tt_kernel_matches_module_lookup():
    """The fused kernel reproduces tt_embedding.lookup numerics end to end:
    kind='tt' serving can swap the jnp path for the kernel transparently."""
    cfg = EmbeddingConfig(
        vocab=4096, dim=32, kind="tt", tt_rank=4,
        param_dtype=jnp.float32, compute_dtype=jnp.float32,
    )
    spec = cfg.tt_spec
    params = QE.init(jax.random.PRNGKey(5), cfg)
    idx = jax.random.randint(jax.random.PRNGKey(6), (17,), 0, cfg.vocab)
    i1, i2, i3 = TT.tt_decompose(idx, spec)
    out = ops.tt_lookup(
        params["g1"], params["g2"], params["g3"], i1, i2, i3,
        dims=(spec.d1, spec.d2, spec.d3, spec.rank),
    )
    expect = QE.lookup(params, idx, cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5, atol=1e-6)


def test_tt_bag_matches_pooled_module_bag():
    """Kernel bag == module-level pooled bag (the DLRM GnR contract)."""
    from repro.core.embedding_bag import BagConfig, bag_lookup

    cfg = EmbeddingConfig(
        vocab=4096, dim=32, kind="tt", tt_rank=4,
        param_dtype=jnp.float32, compute_dtype=jnp.float32,
    )
    spec = cfg.tt_spec
    params = QE.init(jax.random.PRNGKey(7), cfg)
    idx = jax.random.randint(jax.random.PRNGKey(8), (9, 8), 0, cfg.vocab)
    i1, i2, i3 = TT.tt_decompose(idx, spec)
    out = ops.tt_pooled(
        params["g1"], params["g2"], params["g3"], i1, i2, i3,
        dims=(spec.d1, spec.d2, spec.d3, spec.rank),
    )
    expect = bag_lookup(params, idx, BagConfig(emb=cfg, pooling=8))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# the packed TT megakernel's block path (32-bit rows, ``blocks.run_tt_blocks``)
# ---------------------------------------------------------------------------

def _packed_tt_case(dims, bags, k, slots, seed, *, hits, ragged=False,
                    tables=2, vocab=(5, 40, 5), dtype=jnp.float32):
    """Packed cores of ``tables`` tables (G2 with its trailing zero row), a
    cache block, and (bags, k) global streams: ``hits`` is "miss", "hit" or
    "mixed"; ``ragged`` routes positions past a random length to the zero
    row, as ``packed_tables.pack_indices`` does."""
    v1, v2, v3 = vocab
    g1, g2, g3 = _cores(tables * v1, tables * v2, tables * v3, dims, dtype, seed)
    g2 = jnp.concatenate([g2, jnp.zeros((1, g2.shape[1]), dtype)])
    cache = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (slots, g2.shape[1])).astype(dtype)
    key = jax.random.PRNGKey(seed + 2)
    i1, i2, i3 = _indices(key, (bags, k), tables * v1, tables * v2, tables * v3)
    slot = jax.random.randint(jax.random.fold_in(key, 4), (bags, k), 0, slots)
    if hits == "miss":
        slot = jnp.full_like(slot, -1)
    elif hits == "mixed":
        hit = jax.random.uniform(jax.random.fold_in(key, 5), (bags, k)) < 0.4
        slot = jnp.where(hit, slot, -1)
    if ragged:
        lengths = jax.random.randint(jax.random.fold_in(key, 6), (bags, 1), 0, k + 1)
        keep = jnp.arange(k)[None, :] < lengths
        i2 = jnp.where(keep, i2, g2.shape[0] - 1)
        slot = jnp.where(keep, slot, -1)
    return (g1, g2, g3, cache, i1, i2, i3, slot)


# (13, 5): one block of 8 and one with 3 pad bags; (300, 4) at the published
# dim factors holds a few hundred G2 rows.
@pytest.mark.parametrize("dims,bags,k,hits,ragged", [
    ((4, 4, 2, 4), 13, 5, "miss", False),
    ((4, 4, 2, 4), 13, 5, "hit", False),
    ((4, 4, 2, 4), 13, 5, "mixed", False),
    ((4, 4, 2, 4), 13, 5, "mixed", True),
    ((4, 8, 4, 16), 21, 4, "mixed", False),
    ((4, 8, 4, 16), 11, 6, "miss", True),
    ((4, 8, 4, 16), 9, 3, "hit", False),
])
def test_packed_tt_bag_blocks_match_reference(dims, bags, k, hits, ragged):
    from repro.kernels import packed_gather

    vocab = (5, 150, 5) if dims[3] == 16 else (5, 40, 5)
    args = _packed_tt_case(dims, bags, k, 24, bags + k, hits=hits,
                           ragged=ragged, vocab=vocab)
    out = packed_gather.packed_tt_bag(*args, dims=dims, interpret=True)
    expect = ref.packed_tt_bag_ref(*args, dims=dims)
    assert out.shape == (bags, dims[0] * dims[1] * dims[2])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_packed_tt_bag_blocks_over_several_chunks(monkeypatch):
    """Streams that overflow one chunk's SMEM budget run as several
    ``pallas_call`` chunks, the last one partly pad bags."""
    from repro.kernels import blocks, packed_gather

    dims, bags, k = (4, 4, 2, 4), 40, 3
    row_bytes = 4 * 4 * 4 * 4                   # r * d2 * r f32
    # blocks of 8 bags, chunks of 16: 40 bags -> 3 chunks x 2 blocks
    monkeypatch.setattr(blocks, "TT_BLOCK_SCRATCH_BYTES", 2 * k * row_bytes * 8)
    monkeypatch.setattr(blocks, "SMEM_STREAM_BYTES", 4 * k * 4 * 16)
    assert blocks.tt_block_grid(bags, k, 4, row_bytes) == (16, 3, 6)
    args = _packed_tt_case(dims, bags, k, 8, 3, hits="mixed")
    out = packed_gather.packed_tt_bag.__wrapped__(*args, dims=dims, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.packed_tt_bag_ref(*args, dims=dims)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype,runner", [
    (jnp.float32, "run_tt_blocks"), (jnp.bfloat16, "run_bags"),
])
def test_packed_tt_bag_runner_follows_row_width(monkeypatch, dtype, runner):
    """32-bit TT rows take the block runner; 16-bit rows keep the row-a-step
    runner, and both match the reference."""
    from repro.kernels import packed_gather

    calls = []
    for name in ("run_tt_blocks", "run_bags"):
        fn = getattr(packed_gather, name)
        monkeypatch.setattr(packed_gather, name,
                            lambda *a, _fn=fn, _n=name, **kw: calls.append(_n) or _fn(*a, **kw))
    dims = (4, 4, 2, 4)
    args = _packed_tt_case(dims, 10, 4, 16, 7, hits="mixed", dtype=dtype)
    out = packed_gather.packed_tt_bag.__wrapped__(*args, dims=dims, interpret=True)
    assert calls == [runner]
    expect = ref.packed_tt_bag_ref(*args, dims=dims)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), rtol=tol, atol=tol)
