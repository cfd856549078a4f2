"""ProactivePIM cache subsystem: intra-GnR analyzer, prefetch scheduler,
duplication planner, the plan-aware sharded GnR, and the serve_rec driver."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.cache import duplication, intra_gnr, sram_cache
from repro.core import placement
from repro.core.embedding_bag import BagConfig
from repro.core.qr_embedding import EmbeddingConfig
from repro.data.synthetic import zipf_trace


def _qr_cfg(vocab=4096, dim=32, collision=8):
    return EmbeddingConfig(vocab=vocab, dim=dim, kind="qr", collision=collision)


def _tt_cfg(vocab=4096, dim=32, rank=4):
    return EmbeddingConfig(vocab=vocab, dim=dim, kind="tt", tt_rank=rank)


def _bag_trace(vocab, bags, pooling, seed=0):
    return zipf_trace(vocab, bags * pooling, seed=seed).reshape(bags, pooling)


# ---------------------------------------------------------------------------
# intra-GnR locality analyzer
# ---------------------------------------------------------------------------

def test_analyzer_counts_and_bags():
    trace = np.array([[0, 0, 1], [1, 1, 1], [2, 0, 2]])
    loc = intra_gnr.analyze_bags(trace, rows=4)
    assert loc.touches.tolist() == [3, 4, 2, 0]
    assert loc.bags.tolist() == [2, 2, 1, 0]
    # row 1: 4 touches over 2 bags -> reuse 2.0
    assert loc.intra_reuse[1] == 2.0
    assert loc.num_bags == 3


def test_shared_subtables_have_structural_reuse():
    """R / outer-core reuse must exceed the big table's — the paper's premise."""
    trace = _bag_trace(4096, 300, pooling=8)
    qr = intra_gnr.analyze_table(trace, _qr_cfg())
    assert qr["r"].mean_intra_reuse > qr["q"].mean_intra_reuse
    tt = intra_gnr.analyze_table(trace, _tt_cfg())
    assert tt["g1"].mean_intra_reuse > tt["g2"].mean_intra_reuse
    assert tt["g3"].mean_intra_reuse > tt["g2"].mean_intra_reuse


def test_rank_prefetch_orders_by_saved_accesses():
    trace = _bag_trace(4096, 200, pooling=8)
    loc = intra_gnr.analyze_table(trace, _qr_cfg())["q"]
    rank = intra_gnr.rank_prefetch(loc)
    vals = loc.prefetch_value()[rank]
    assert np.all(np.diff(vals) <= 0)            # descending
    assert np.all(vals > 0)                      # never ranks untouched rows
    top3 = intra_gnr.rank_prefetch(loc, top=3)
    assert top3.tolist() == rank[:3].tolist()


def test_analyzer_empty_and_shape_checks():
    loc = intra_gnr.analyze_bags(np.empty((0, 4), dtype=np.int64), rows=8)
    assert loc.touches.sum() == 0 and loc.bags.sum() == 0
    with pytest.raises(ValueError):
        intra_gnr.analyze_bags(np.zeros(5, dtype=np.int64), rows=8)


# ---------------------------------------------------------------------------
# prefetch scheduler
# ---------------------------------------------------------------------------

def test_scheduler_double_buffer_accounting():
    sched = sram_cache.PrefetchScheduler(num_rows=64, num_slots=8)
    b0 = np.array([1, 2, 3, 1, 2, 1])
    sched.prefetch(b0)
    assert sched.stats.staged_rows == 3          # cold start: 3 distinct rows
    slots = sched.slots_for(b0)
    assert (slots >= 0).all()                    # staged exactly what b0 needs
    assert sched.stats.hit_rate == 1.0
    # next batch shares row 1 -> only the new rows are staged
    b1 = np.array([1, 9, 9, 10, 1, 1])
    sched.prefetch(b1)
    assert sched.stats.staged_rows == 3 + 2
    assert sched.stats.kept_rows >= 1
    slots = sched.slots_for(b1)
    assert (slots >= 0).all()
    # slot map and slot_rows stay mutually consistent
    for r, s in enumerate(sched.slot_map):
        if s >= 0:
            assert sched.slot_rows[s] == r


def test_scheduler_capacity_eviction():
    sched = sram_cache.PrefetchScheduler(num_rows=100, num_slots=4)
    batch = np.array([0, 0, 0, 1, 1, 2, 3, 4, 5])   # 6 distinct, 4 slots
    sched.prefetch(batch)
    slots = sched.slots_for(batch)
    hit_rows = set(int(r) for r, s in zip(batch, slots) if s >= 0)
    assert len(hit_rows) == 4
    assert {0, 1} <= hit_rows                    # highest-count rows win slots
    assert (sched.slot_rows >= 0).sum() == 4


def test_scheduler_zipf_hit_rate_and_traffic():
    """Acceptance-adjacent: double-buffered prefetch reaches a high hit rate
    on a Zipf(1.05) stream, and cached DRAM traffic beats the baseline."""
    q = zipf_trace(262_144, 24 * 2048, alpha=1.05, seed=3).reshape(24, -1) // 64
    stats = sram_cache.simulate([q[t] for t in range(24)], 4096, 1024)
    assert stats.hit_rate >= 0.8
    tr = stats.traffic_bytes(512)
    assert tr["cached"] < tr["baseline"]


def test_scheduler_value_tiebreak():
    """Analyzer value breaks ties between equal in-batch counts."""
    value = np.zeros(10)
    value[7] = 5.0
    sched = sram_cache.PrefetchScheduler(10, 1, value)
    sched.prefetch(np.array([3, 7]))             # tied counts; 7 has value
    assert sched.slot_rows[0] == 7


def test_scheduler_counts_evictions_exactly():
    sched = sram_cache.PrefetchScheduler(num_rows=16, num_slots=3)
    sched.prefetch(np.array([1, 1, 2, 3]))       # cold: 3 staged, none evicted
    assert (sched.stats.staged_rows, sched.stats.kept_rows,
            sched.stats.evicted_rows) == (3, 0, 0)
    want = sched.rank(np.array([3, 3, 3, 7, 7, 1]))
    assert want.tolist() == [3, 7, 1]            # by count, then by row id
    assert sched.update(want) == 1               # 7 staged; 2 evicted
    assert (sched.stats.staged_rows, sched.stats.kept_rows,
            sched.stats.evicted_rows) == (4, 2, 1)
    assert sched.update(sched.rank(np.array([9]))) == 1   # 3, 7, 1 all go
    assert sched.stats.evicted_rows == 4
    assert sorted(sched.slot_rows.tolist()) == [-1, -1, 9]


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), tables=st.integers(1, 4),
       slots=st.integers(1, 12), batches=st.integers(1, 6))
def test_phase_major_rank_update_matches_per_table_prefetch(
        seed, tables, slots, batches):
    """Every table ranked, then every table updated (the serving loop's
    order) leaves each scheduler exactly as table-by-table ``prefetch``."""
    rng = np.random.default_rng(seed)
    rows = 40
    values = [rng.random(rows) if t % 2 else None for t in range(tables)]
    ref = [sram_cache.PrefetchScheduler(rows, slots, v) for v in values]
    got = [sram_cache.PrefetchScheduler(rows, slots, v) for v in values]
    for _ in range(batches):
        batch = rng.zipf(1.3, size=(6, tables, 4)) % rows
        for t, s in enumerate(ref):
            s.prefetch(batch[:, t])
        want = [s.rank(batch[:, t]) for t, s in enumerate(got)]
        for s, w in zip(got, want):
            s.update(w)
        for a, b in zip(ref, got):
            assert np.array_equal(a.slot_map, b.slot_map)
            assert np.array_equal(a.slot_rows, b.slot_rows)
            assert a.stats == b.stats
            a.slots_for(batch[:, 0])
            b.slots_for(batch[:, 0])


def _full_vocab_rank(sched, idx):
    """The ranking as it was before only touched rows were ranked: a count
    and a stable sort over every row of the subtable."""
    flat = np.asarray(idx).reshape(-1)
    counts = np.bincount(flat, minlength=sched.num_rows)
    want = np.argsort(-(counts + sched.value), kind="stable")[: sched.num_slots]
    return want[counts[want] > 0]


def _rank_case(case, rows):
    rng = np.random.default_rng(7)
    value = rng.integers(0, 4, rows).astype(np.float64)
    if case == "zipf":
        return (rng.zipf(1.2, (256, 32)) - 1) % rows, 900, value
    if case == "uniform":
        return rng.integers(0, rows, (256, 32)), 900, value
    if case == "ties_no_value":
        return np.repeat(rng.permutation(rows)[:300], 4), 100, None
    if case == "ties_with_value":
        return np.repeat(rng.permutation(rows)[:300], 4), 100, value
    if case == "more_slots_than_touched":
        return rng.integers(0, rows, (4, 8)), 500, value
    assert case == "one_row"
    return np.full((16, 8), rows // 3), 50, value


# 3,000 rows: fewer than the 8,192 ids of a zipf batch, ranked by counting;
# 2,000,000 rows: ranked by sorting the touched rows
@pytest.mark.parametrize("rows", [3_000, 2_000_000])
@pytest.mark.parametrize("case", ["zipf", "uniform", "ties_no_value",
                                  "ties_with_value", "more_slots_than_touched",
                                  "one_row"])
def test_touched_row_rank_is_the_full_vocabulary_rank(case, rows):
    idx, slots, value = _rank_case(case, rows)
    sched = sram_cache.PrefetchScheduler(rows, slots, value)
    got = sched.rank(idx)
    assert np.array_equal(got, _full_vocab_rank(sched, idx))
    assert got.size == min(slots, np.unique(idx).size)


def test_sharded_scheduler_is_one_scheduler_a_shard():
    """A scheduler over rows split into shards keeps, for each shard, the
    cache block that a one-shard scheduler over that shard's rows keeps."""
    rng = np.random.default_rng(3)
    rows, per, shards, slots = 250, 64, 4, 9       # the last shard is short
    value = rng.random(rows)
    sharded = sram_cache.PrefetchScheduler(rows, slots, value, shards=shards,
                                           shard_rows=per)
    norm = value / (value.max() + 1.0)
    alone = []
    for k in range(shards):
        a = sram_cache.PrefetchScheduler(min(per, rows - k * per), slots)
        a.value = norm[k * per:(k + 1) * per]        # the same tiebreak
        alone.append(a)
    for _ in range(5):
        batch = rng.zipf(1.3, size=(12, 5)) % rows
        sharded.update(sharded.rank(batch))
        for k, a in enumerate(alone):
            mine = batch[(batch >= k * per) & (batch < (k + 1) * per)]
            a.update(a.rank(mine - k * per))
            block = sharded.slot_rows[k * slots:(k + 1) * slots]
            assert np.array_equal(np.where(block >= 0, block - k * per, -1),
                                  a.slot_rows)
        slot = sharded.slots_for(batch)
        owner, hit = batch // per, slot >= 0
        assert np.all(slot[hit] // slots == owner[hit])
        assert np.array_equal(sharded.cache_rows()[slot[hit]], batch[hit])
    empty = sharded.slot_rows < 0
    assert np.array_equal(sharded.cache_rows()[empty],
                          (np.flatnonzero(empty) // slots) * per)


def test_pinned_cache_has_the_scheduler_phases():
    from repro.adapt.replan import PinnedCache

    c = PinnedCache(16, 2, rows=np.array([1, 2]))
    assert c.rank(np.array([5, 5, 6])).size == 0
    assert c.update(np.array([5, 6])) == 0
    assert sorted(c.pinned_rows().tolist()) == [1, 2]
    c.pin(np.array([2, 9]))                      # 1 evicted, 2 kept
    assert (c.stats.staged_rows, c.stats.kept_rows,
            c.stats.evicted_rows) == (3, 1, 1)


# ---------------------------------------------------------------------------
# duplication planner
# ---------------------------------------------------------------------------

def _counts(vocab=4096, n=30_000, seed=1):
    return placement.profile_counts(zipf_trace(vocab, n, seed=seed), vocab)


def test_duplication_generous_budget_kills_communication():
    bags = [BagConfig(emb=_qr_cfg(), pooling=8) for _ in range(3)]
    plan = duplication.plan_duplication(
        bags, [_counts()] * 3, num_shards=4, budget_bytes=32 * 2**20
    )
    assert plan.comm_free
    assert all(t.local_share == 1.0 for t in plan.tables)
    ici = plan.ici_bytes_per_batch(256, 32)
    assert ici["duplicated"] == 0 and ici["saved"] == ici["baseline"] > 0


def test_duplication_budget_respected_and_prioritized():
    bags = [BagConfig(emb=_qr_cfg(), pooling=8)]
    budget = 8192
    plan = duplication.plan_duplication(
        bags, [_counts()], num_shards=4, budget_bytes=budget
    )
    assert plan.replicated_bytes <= budget
    assert not plan.comm_free
    t = plan.tables[0]
    by_name = {d.name: d for d in t.decisions}
    assert by_name["r"].replicated              # tiny LUT always wins first
    assert 0 < t.hot_plan.num_hot < 512         # leftover budget -> hot rows
    # hot tier holds the hottest rows
    folded = duplication._fold_quotient(_counts(), 8, 512)
    assert folded[t.hot_plan.hot_rows].min() >= np.sort(folded)[::-1][t.hot_plan.num_hot - 1]


def test_duplication_tt_pins_outer_cores_first():
    bags = [BagConfig(emb=_tt_cfg(), pooling=8)]
    spec = bags[0].emb.tt_spec
    smalls = (spec.v1 * spec.g1_width + spec.v3 * spec.g3_width) * 4
    plan = duplication.plan_duplication(
        bags, [_counts()], num_shards=2, budget_bytes=smalls + 10
    )
    t = plan.tables[0]
    by_name = {d.name: d for d in t.decisions}
    assert by_name["g1"].replicated and by_name["g3"].replicated
    assert t.hot_plan.num_hot == 0              # nothing left for G2 rows
    assert t.local_share == pytest.approx(2 / 3)


def test_duplication_partial_profile_not_comm_free():
    """An all-hot *profile* must not flip comm_free: unseen indices can still
    arrive at serving time, so full-row coverage is required."""
    counts = np.zeros(4096, dtype=np.int64)
    counts[:800] = 50                           # only 100 of 512 q-rows touched
    bags = [BagConfig(emb=_qr_cfg(), pooling=8)]
    rb = 32 * 4
    budget = bags[0].emb.qr_spec.lut_bytes() + 150 * rb   # R + 150 hot rows
    plan = duplication.plan_duplication(
        bags, [counts], num_shards=4, budget_bytes=budget
    )
    t = plan.tables[0]
    assert t.hot_plan.expected_hot_hit == 1.0   # profile fully covered...
    assert not t.comm_free                      # ...but the table is not
    assert not plan.comm_free
    # generous budget replicates every row, including untouched ones
    plan_full = duplication.plan_duplication(
        bags, [counts], num_shards=4, budget_bytes=32 * 2**20
    )
    assert plan_full.tables[0].hot_plan.num_hot == 512
    assert plan_full.comm_free


def test_duplication_hashed_folds_counts():
    """Hashed tables fold logical counts through the k-ary hash, not truncate."""
    emb = EmbeddingConfig(vocab=4096, dim=32, kind="hashed", collision=8)
    bags = [BagConfig(emb=emb, pooling=8)]
    counts = np.zeros(4096, dtype=np.int64)
    counts[4000] = 100                          # hot logical id past row count
    plan = duplication.plan_duplication(
        bags, [counts], num_shards=2, budget_bytes=4 * 32 * 4
    )
    hot = plan.tables[0].hot_plan
    from repro.core import hashing

    expect_rows = set(np.asarray(
        hashing.k_ary_hash(np.array([4000]), emb.physical_hashed_rows, emb.hashed_k)
    ).reshape(-1).tolist())
    assert expect_rows <= set(hot.hot_rows.tolist())


def test_tt_pallas_flag_is_differentiable():
    """tt_exec='pallas' must stay legal under value_and_grad (training configs
    carry the flag); the kernel path has a reference-recompute vjp."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    dims = (2, 4, 2, 2)
    g1 = jax.random.normal(jax.random.PRNGKey(0), (4, 4))
    g2 = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    g3 = jax.random.normal(jax.random.PRNGKey(2), (4, 4))
    i = jax.random.randint(jax.random.PRNGKey(3), (3, 4), 0, 4)
    i2 = jax.random.randint(jax.random.PRNGKey(4), (3, 4), 0, 8)

    def loss(a, b, c, use_kernel):
        out = ops.tt_pooled_auto(
            a, b, c, i, i2, i, dims=dims, exec_mode="pallas",
            interpret=True if use_kernel else None,
        )
        return (out.astype(jnp.float32) ** 2).sum()

    gk = jax.grad(lambda a, b, c: loss(a, b, c, True), argnums=(0, 1, 2))(g1, g2, g3)
    gr = jax.grad(lambda a, b, c: loss(a, b, c, False), argnums=(0, 1, 2))(g1, g2, g3)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_duplication_zero_budget():
    bags = [BagConfig(emb=_qr_cfg(), pooling=8)]
    plan = duplication.plan_duplication(
        bags, [_counts()], num_shards=4, budget_bytes=0
    )
    assert plan.replicated_bytes == 0
    assert not plan.comm_free
    assert plan.tables[0].local_share == 0.0


# ---------------------------------------------------------------------------
# plan-aware sharded GnR (mesh subprocess)
# ---------------------------------------------------------------------------

def test_dup_gnr_matches_oracle(mesh_runner):
    mesh_runner(
        """
import numpy as np, jax, jax.numpy as jnp
from repro import engine as E
from repro.cache import duplication
from repro.core import embedding_bag, placement, sharded_embedding as SE
from repro.core.embedding_bag import BagConfig
from repro.core.qr_embedding import EmbeddingConfig
from repro.data.synthetic import zipf_trace
from repro.engine import EngineSpec
from repro.launch.mesh import make_mesh

emb = EmbeddingConfig(vocab=4096, dim=32, kind="qr", collision=8,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
bags = [BagConfig(emb=emb, pooling=8) for _ in range(2)]
tables = embedding_bag.init_tables(jax.random.PRNGKey(0), bags)
idx = jax.random.randint(jax.random.PRNGKey(1), (8, 2, 8), 0, 4096)
oracle = embedding_bag.multi_bag_lookup(tables, idx, bags)

counts = placement.profile_counts(zipf_trace(4096, 20000, seed=1), 4096)
mesh = make_mesh((2, 4), ("data", "model"))
for budget in (32 * 2**20, 8192):   # comm-free and mixed regimes
    plan = duplication.plan_duplication(
        bags, [counts] * 2, num_shards=4, budget_bytes=budget)
    spec = EngineSpec.from_bags(bags, duplication=True)
    fn = E.compile(E.plan(spec, mesh=mesh, dup=plan)).gnr(mesh)
    tiers = SE.make_dup_hot_tiers(tables, bags, plan)
    out = fn(tables, idx, tiers)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-5, atol=1e-5)
    assert plan.comm_free == (budget > 8192)
print("OK")
""",
        n_devices=8,
    )


# ---------------------------------------------------------------------------
# serving driver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["dlrm-qr", "dlrm-tt"])
def test_serve_rec_smoke(arch, capsys):
    from repro.launch import serve_rec

    rc = serve_rec.main([
        "--arch", arch, "--smoke", "--batch", "4", "--batches", "3",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "QPS" in out and "cache hit rate" in out
    assert "comm_free=True" in out
