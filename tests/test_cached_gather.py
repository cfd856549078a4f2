"""Cached-gather Pallas kernel vs the jnp oracle (interpret=True on CPU)
across a size/skew sweep, plus integration with the serving lookup path.

f32 tables run a block of bags per grid step (``blocks.run_bag_blocks``),
bf16 tables a row per step (``blocks.run_bags``); the sweeps cover both."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine as E
from repro.cache.sram_cache import PrefetchScheduler
from repro.core.embedding_bag import BagConfig
from repro.core.qr_embedding import EmbeddingConfig
from repro.data.synthetic import zipf_trace
from repro.engine import EngineSpec
from repro.kernels import cached_gather, ops, ref


def _setup(rows, slots, dim, bk, dtype=jnp.float32, seed=0, hit_p=0.5):
    """Table + cache block + indices with a controlled hit fraction."""
    k = jax.random.PRNGKey(seed)
    table = jax.random.normal(jax.random.fold_in(k, 0), (rows, dim), dtype)
    cache = jax.random.normal(jax.random.fold_in(k, 1), (slots, dim), dtype)
    b, kk = bk
    idx = jax.random.randint(jax.random.fold_in(k, 2), (b, kk), 0, rows)
    slot = jnp.where(
        jax.random.uniform(jax.random.fold_in(k, 3), (b, kk)) < hit_p,
        jax.random.randint(jax.random.fold_in(k, 4), (b, kk), 0, slots),
        -1,
    )
    return table, cache, idx, slot


@pytest.mark.parametrize("dim", [8, 32, 128, 256, 64])
@pytest.mark.parametrize("hit_p", [0.0, 0.5, 1.0])
def test_cached_bag_size_hit_sweep(dim, hit_p):
    table, cache, idx, slot = _setup(64, 8, dim, (5, 7), hit_p=hit_p)
    out = ops.cached_pooled(table, cache, idx, slot)
    expect = ref.cached_bag_ref(table, cache, idx, slot)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


# (bags, K) at dim 32, or (bags, K, dim, dim_block).  With f32 rows:
# (21, 3) is one block with 3 pad bags; (2000, 33) two chunks of 23 blocks
# of 56 bags, pads in the last block of the last chunk; the last two split
# the row into lane tiles, each tile its own chain of blocks.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bk", [(1, 1), (3, 16), (8, 4), (21, 3), (2000, 33),
                                (70, 1, 64, 16), (9, 33, 256, 128)])
def test_cached_qr_bag_sweep(dtype, bk):
    (b, k), (dim, bd) = bk[:2], bk[2:] or (32, None)
    table, cache, idx, slot = _setup(96, 16, dim, (b, k), dtype=dtype)
    r_lut = jax.random.normal(jax.random.PRNGKey(9), (8, dim), dtype)
    r_idx = jax.random.randint(jax.random.PRNGKey(10), (b, k), 0, 8)
    if bd is None:
        out = ops.cached_qr_pooled(table, cache, r_lut, idx, slot, r_idx)
    else:       # a lane tile that ``ops`` would not pick for this dim
        out = cached_gather.cached_qr_bag(table, cache, r_lut, idx, slot, r_idx,
                                          dim_block=bd, interpret=True)
    expect = ref.cached_qr_bag_ref(table, cache, r_lut, idx, slot, r_idx)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=1e-5 if dtype == jnp.float32 else 3e-2, atol=1e-2,
    )


def test_cached_bag_zipf_skew_with_scheduler():
    """End-to-end skew case: slots staged by the real prefetch scheduler on a
    Zipf trace; kernel must agree with the oracle bit-for-bit in fp32."""
    rows, slots, dim, pooling = 512, 64, 32, 8
    table = jax.random.normal(jax.random.PRNGKey(0), (rows, dim))
    trace = zipf_trace(rows, 64 * pooling, alpha=1.05, seed=2).reshape(-1, pooling)
    sched = PrefetchScheduler(rows, slots)
    sched.prefetch(trace)
    slot = sched.slots_for(trace)
    assert (slot >= 0).any() and (slot < 0).any()   # genuinely mixed routing
    cache = table[jnp.asarray(sched.cache_rows())]
    out = ops.cached_pooled(table, cache, jnp.asarray(trace), jnp.asarray(slot))
    expect = ref.cached_bag_ref(table, cache, jnp.asarray(trace), jnp.asarray(slot))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    # routing consistency: staged cache rows equal the table rows they mirror,
    # so the cached result also equals a plain uncached bag
    plain = ref.dense_bag_ref(table, jnp.asarray(trace))
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain),
                               rtol=1e-5, atol=1e-5)


def test_cached_small_dim_fallback():
    """Dims with no 8-aligned tile fall back to the jnp reference."""
    table, cache, idx, slot = _setup(32, 4, 12, (3, 5))
    out = ops.cached_pooled(table, cache, idx, slot)
    expect = ref.cached_bag_ref(table, cache, idx, slot)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-6)


def test_cached_bag_lookup_matches_plain_bag():
    """The serving path (cache staged from the same table) must reproduce the
    uncached bag lookup exactly, for QR and dense kinds."""
    for kind in ("qr", "dense"):
        emb = EmbeddingConfig(
            vocab=1024, dim=32, kind=kind, collision=8,
            param_dtype=jnp.float32, compute_dtype=jnp.float32,
        )
        bag = BagConfig(emb=emb, pooling=8)
        from repro.core import embedding_bag

        params = embedding_bag.init_tables(jax.random.PRNGKey(0), [bag])[0]
        idx = jax.random.randint(jax.random.PRNGKey(1), (6, 8), 0, 1024)
        rows = np.asarray(idx) // emb.collision if kind == "qr" else np.asarray(idx)
        nrows = emb.qr_spec.q_rows if kind == "qr" else emb.vocab
        sched = PrefetchScheduler(nrows, 16)
        sched.prefetch(rows)
        slot = sched.slots_for(rows)
        eng = E.engine_for(EngineSpec.from_bags((bag,)))
        out = eng.cached_lookup(
            params, idx, 0,
            cache_rows=jnp.asarray(sched.cache_rows()), slot=jnp.asarray(slot),
        )
        expect = embedding_bag.bag_lookup(params, idx, bag)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32),
            rtol=1e-5, atol=1e-5,
        )


def test_cached_bag_lookup_tt_kernel_parity():
    """TT serving path: tt_exec='pallas' (oracle fallback on CPU) matches the
    jnp module lookup."""
    emb = EmbeddingConfig(
        vocab=2048, dim=32, kind="tt", tt_rank=4, tt_exec="pallas",
        param_dtype=jnp.float32, compute_dtype=jnp.float32,
    )
    bag = BagConfig(emb=emb, pooling=4)
    from repro.core import embedding_bag

    params = embedding_bag.init_tables(jax.random.PRNGKey(0), [bag])[0]
    idx = jax.random.randint(jax.random.PRNGKey(1), (5, 4), 0, 2048)
    eng = E.engine_for(EngineSpec.from_bags((bag,)))
    out = eng.cached_lookup(params, idx, 0, cache_rows=None, slot=None)
    import dataclasses

    plain = embedding_bag.bag_lookup(
        params, idx, BagConfig(emb=dataclasses.replace(emb, tt_exec="jnp"), pooling=4)
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(plain), rtol=1e-5, atol=1e-5
    )
