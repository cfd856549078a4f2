"""Packed-table megakernel layer: layout, oracle parity, ragged bags, slot
routing, gradients through the custom vjp, slot-budget waterfilling, and the
overlapped serving pipeline's parity with the sequential baseline."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import intra_gnr
from repro.cache.sram_cache import PrefetchScheduler
from repro.core import embedding_bag as EB
from repro.core import packed_tables as PT
from repro.core.embedding_bag import BagConfig
from repro.core.qr_embedding import EmbeddingConfig
from repro.kernels import ops, ref


def _bags(kind, num_tables=3, vocab=1024, dim=32, pooling=8, **kw):
    emb = EmbeddingConfig(
        vocab=vocab, dim=dim, kind=kind, param_dtype=jnp.float32,
        compute_dtype=jnp.float32, **kw,
    )
    return [BagConfig(emb=emb, pooling=pooling) for _ in range(num_tables)]


KINDS = [("dense", {}), ("qr", {"collision": 8}), ("tt", {"tt_rank": 4})]
# dense and QR at other row widths: the bag-block runner's lane tiles
WIDE = [("qr", {"collision": 8, "dim": 256}), ("dense", {"dim": 64})]


# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------

def test_layout_offsets_and_zero_rows():
    bags = _bags("qr", num_tables=3, collision=8)
    layout = PT.build_layout(bags, [4, 8, 2])
    assert layout.num_tables == 3
    assert layout.row_offsets == (0, 128, 256)       # q_rows=128, 128-padded
    assert layout.zero_row == layout.total_rows == 384
    assert layout.small_offsets == (0, 8, 16)        # R LUTs of collision 8
    assert layout.small_zero_row == 24
    assert layout.slot_offsets == (0, 4, 12) and layout.total_slots == 14
    tt = PT.build_layout(_bags("tt", num_tables=2))
    spec = _bags("tt")[0].emb.tt_spec
    assert tt.big_width == spec.g2_width
    assert tt.tt_vocab == spec.vocab_factors


def test_packable_rejects_non_uniform_and_unsupported():
    assert PT.packable(_bags("qr"))
    assert PT.packable(_bags("dense")) and PT.packable(_bags("tt"))
    hashed = _bags("dense")[:1] + [
        BagConfig(emb=dataclasses.replace(_bags("dense")[0].emb, kind="hashed"),
                  pooling=8)
    ]
    assert not PT.packable(hashed)
    mixed_dim = _bags("dense", dim=32)[:1] + _bags("dense", dim=64)[:1]
    assert not PT.packable(mixed_dim)
    # mixed vocab falls back too (hot-slot maps must stack on the mesh path)
    mixed_vocab = _bags("qr", vocab=1024)[:1] + _bags("qr", vocab=2048)[:1]
    assert not PT.packable(mixed_vocab)
    mul = [BagConfig(emb=dataclasses.replace(_bags("qr")[0].emb,
                                             reconstruction="mul"), pooling=8)]
    assert not PT.packable(mul)
    assert not PT.packable([])


# ---------------------------------------------------------------------------
# oracle parity (packed path vs the per-table loop, both exec modes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,kw", KINDS + WIDE)
@pytest.mark.parametrize("exec_mode", ["jnp", "kernel"])
def test_packed_multi_bag_parity(kind, kw, exec_mode):
    bags = _bags(kind, **kw)
    tables = EB.init_tables(jax.random.PRNGKey(0), bags)
    idx = jax.random.randint(jax.random.PRNGKey(1), (5, 3, 8), 0, 1024)
    oracle = EB.multi_bag_lookup(tables, idx, bags)
    out = PT.packed_multi_bag_lookup(
        tables, idx, bags, exec_mode=exec_mode,
        interpret=True if exec_mode == "kernel" else None,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind,kw", KINDS)
def test_packed_single_table_degenerate(kind, kw):
    """T=1 must reduce to the plain bag lookup (no packing artifacts)."""
    bags = _bags(kind, num_tables=1, **kw)
    tables = EB.init_tables(jax.random.PRNGKey(2), bags)
    idx = jax.random.randint(jax.random.PRNGKey(3), (4, 1, 8), 0, 1024)
    out = PT.packed_multi_bag_lookup(tables, idx, bags, exec_mode="kernel",
                                     interpret=True)
    oracle = EB.multi_bag_lookup(tables, idx, bags)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind,kw", KINDS + WIDE)
def test_packed_ragged_and_empty_bags(kind, kw):
    """Positions past a bag's length route to the zero row: a masked-oracle
    match, and an empty bag pools to exactly zero."""
    bags = _bags(kind, **kw)
    tables = EB.init_tables(jax.random.PRNGKey(4), bags)
    idx = jax.random.randint(jax.random.PRNGKey(5), (4, 3, 8), 0, 1024)
    lengths = jnp.array([[8, 3, 0]] * 4)
    out = PT.packed_multi_bag_lookup(tables, idx, bags, lengths=lengths,
                                     exec_mode="kernel", interpret=True)
    # masked oracle: zero out invalid positions before the per-table pool
    from repro.core import qr_embedding as QE

    emb = bags[0].emb
    rows = jnp.stack(
        [QE.lookup(tables[t], idx[:, t], emb) for t in range(3)], axis=1
    )                                                  # (B, T, K, dim)
    mask = (jnp.arange(8)[None, None, :] < lengths[..., None])[..., None]
    expect = (rows * mask).sum(axis=-2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
    assert bool(jnp.all(out[:, 2] == 0))               # empty bag


def test_packed_ragged_mean_divides_by_valid_length():
    """mean combiner on ragged bags divides by the VALID length, not K."""
    emb = EmbeddingConfig(vocab=256, dim=32, kind="dense",
                          param_dtype=jnp.float32, compute_dtype=jnp.float32)
    bags = [BagConfig(emb=emb, pooling=8, combiner="mean") for _ in range(2)]
    tables = EB.init_tables(jax.random.PRNGKey(0), bags)
    idx = jax.random.randint(jax.random.PRNGKey(1), (3, 2, 8), 0, 256)
    lengths = jnp.array([[3, 8]] * 3)
    out = PT.packed_multi_bag_lookup(tables, idx, bags, lengths=lengths)
    expect0 = tables[0]["table"][idx[:, 0, :3]].mean(axis=-2)   # mean of 3
    expect1 = tables[1]["table"][idx[:, 1]].mean(axis=-2)       # full bag
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(expect0),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[:, 1]), np.asarray(expect1),
                               rtol=1e-5, atol=1e-5)


def test_vmem_resident_budget_guard():
    """Oversized packed cache blocks fail loudly at trace time, not as a
    Mosaic VMEM OOM on hardware."""
    from repro.kernels import packed_gather as PG

    table = jnp.zeros((64, 128))
    too_big = PG.VMEM_RESIDENT_BUDGET // (128 * 4) + 1
    cache = jnp.zeros((too_big, 128))
    idx = jnp.zeros((2, 4), jnp.int32)
    with pytest.raises(AssertionError, match="VMEM-resident"):
        PG.packed_bag(table, cache, idx, idx, interpret=True)


# ---------------------------------------------------------------------------
# cache-slot routing through the packed block (megakernel x scheduler)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,kw", KINDS)
def test_packed_cache_routing_matches_uncached(kind, kw):
    """Slots staged by real per-table schedulers, translated to the packed
    cache block: hits must reproduce the uncached result bit-for-bit."""
    from repro.launch import serve_rec

    bags = _bags(kind, **kw)
    emb = bags[0].emb
    tables = EB.init_tables(jax.random.PRNGKey(6), bags)
    _name, rows = serve_rec.big_subtable(emb)
    idx = np.asarray(
        jax.random.randint(jax.random.PRNGKey(7), (6, 3, 8), 0, 1024))
    scheds = [PrefetchScheduler(rows, 16) for _ in range(3)]
    slot = []
    for t in range(3):
        r = serve_rec.big_rows(idx[:, t], emb)
        scheds[t].prefetch(r)
        slot.append(scheds[t].slots_for(r))
    slot = np.stack(slot, axis=1)
    assert (slot >= 0).any()

    layout = PT.build_layout(bags, [s.num_slots for s in scheds])
    packed = PT.pack_params(tables, layout)
    cache_rows = PT.packed_cache_rows([s.cache_rows() for s in scheds], layout)
    packed["cache"] = packed[PT.big_key(kind)][jnp.asarray(cache_rows)]
    streams = PT.pack_indices(jnp.asarray(idx), layout)
    streams["slot"] = PT.global_slots(jnp.asarray(slot), layout)
    out = ops.packed_multi_pooled(
        packed, streams, kind=layout.kind, dims=layout.tt_dims,
        exec_mode="kernel", interpret=True,
    )
    oracle = EB.multi_bag_lookup(tables, jnp.asarray(idx), bags)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# gradients through the reference-recompute vjp
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,kw", KINDS + WIDE)
def test_packed_kernel_grads_match_oracle(kind, kw):
    """The megakernel path must be training-safe: grads w.r.t. every table
    leaf equal the pure-jnp packed oracle's."""
    bags = _bags(kind, num_tables=2, **kw)
    tables = EB.init_tables(jax.random.PRNGKey(8), bags)
    idx = jax.random.randint(jax.random.PRNGKey(9), (3, 2, 4), 0, 1024)

    def loss(tabs, exec_mode, interpret):
        out = PT.packed_multi_bag_lookup(
            tabs, idx, bags, exec_mode=exec_mode, interpret=interpret)
        return (out.astype(jnp.float32) ** 2).sum()

    gk = jax.grad(lambda t: loss(t, "kernel", True))(tables)
    gr = jax.grad(lambda t: loss(t, "jnp", None))(tables)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    assert any(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(gk))


# ---------------------------------------------------------------------------
# adaptive slot budgets (waterfilling by prefetch value)
# ---------------------------------------------------------------------------

def test_split_slot_budget_waterfills_by_value():
    hot = np.zeros(100)
    hot[:50] = 10.0                        # table 0: 50 valuable rows
    cold = np.zeros(100)
    cold[:5] = 1.0                         # table 1: 5 mildly valuable rows
    budgets = intra_gnr.split_slot_budget([hot, cold], 40)
    assert sum(budgets) == 40
    assert budgets[0] > budgets[1] >= 1    # value skew drives the split
    # marginal-value exactness: table 1 keeps exactly its 5 valuable rows + base
    assert budgets[1] <= 6


def test_split_slot_budget_min_and_caps():
    vals = [np.ones(4), np.zeros(1000)]
    budgets = intra_gnr.split_slot_budget(vals, 100)
    assert budgets[0] >= 1 and budgets[1] >= 1
    assert budgets[0] <= 4                 # never more slots than rows
    assert sum(budgets) <= 100
    # degenerate inputs are explicit errors, not silent empty plans
    with pytest.raises(ValueError, match="empty table list"):
        intra_gnr.split_slot_budget([], 10)
    with pytest.raises(ValueError, match="positive slot budget"):
        intra_gnr.split_slot_budget([np.ones(4)], 0)
    with pytest.raises(ValueError, match="positive slot budget"):
        intra_gnr.split_slot_budget([np.ones(4)], -3)
    with pytest.raises(ValueError, match="min_slots"):
        intra_gnr.split_slot_budget([np.ones(4)], 10, min_slots=0)
    # starved budget still gives every table one slot
    tight = intra_gnr.split_slot_budget([np.ones(8)] * 3, 2)
    assert all(b >= 1 for b in tight)
    # the min_slots floor takes precedence over the total
    floored = intra_gnr.split_slot_budget([np.ones(8)] * 4, 7, min_slots=2)
    assert floored == [2, 2, 2, 2]
    # a rowless table gets zero slots
    assert intra_gnr.split_slot_budget([np.ones(4), np.empty(0)], 10)[1] == 0


def test_dup_plan_records_slot_budgets():
    from repro.cache import duplication
    from repro.core import placement
    from repro.data.synthetic import zipf_trace

    bags = _bags("qr", num_tables=2, collision=8)
    counts = placement.profile_counts(zipf_trace(1024, 10_000, seed=1), 1024)
    plan = duplication.plan_duplication(
        bags, [counts] * 2, num_shards=2, budget_bytes=4096,
        slot_budgets=[12, 20],
    )
    assert [t.cache_slots for t in plan.tables] == [12, 20]


# ---------------------------------------------------------------------------
# serving pipeline: batch overlap must not change the math
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["dlrm-qr-smoke", "dlrm-tt-smoke"])
def test_serve_pipeline_overlap_matches_sequential(arch):
    from repro.configs import registry
    from repro.launch import serve_rec
    from repro.models import dlrm

    cfg = registry.get_dlrm(arch)
    params, _ = dlrm.init_dlrm(jax.random.PRNGKey(0), cfg)
    res = {
        mode: serve_rec.run_pipeline(
            cfg, batch=4, batches=4, mode=mode, params=params)
        for mode in ("sequential", "overlap")
    }
    for a, b in zip(res["sequential"]["logits"], res["overlap"]["logits"]):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    assert res["overlap"]["qps"] > 0
    # adaptive budgets: one scheduler per table, waterfilled global budget
    assert len(res["overlap"]["slot_budgets"]) == cfg.num_tables
    assert sum(res["overlap"]["slot_budgets"]) <= cfg.cache_slots * cfg.num_tables
