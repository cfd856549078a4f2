"""Software-managed SRAM cache model with next-batch prefetch scheduling.

The paper's bg-PIM SRAM cache is *proactively* filled: the host knows batch
``t+1``'s embedding indices while batch ``t`` executes (inference requests are
queued), so the cache controller stages exactly the rows the next GnR will
touch — no reactive misses, no tag checks on the critical path.  Double
buffering hides the staging DMA behind the executing batch.

TPU realization: the "SRAM" is a VMEM-resident cache block (a ``(slots, width)``
array) plus a host-side slot map.  Per batch:

1. ``prefetch(next_idx)`` (called while batch ``t`` runs) runs two phases:
   ``rank`` orders the next batch's rows by in-batch access count × analyzer
   prefetch value and keeps the winners; ``update`` keeps already-resident
   winners (their staging cost is zero — the paper's inter-batch locality),
   evicts the rest of the residents, and stages the new winners into the
   freed slots;
2. ``slots_for(idx)`` translates batch ``t``'s accesses through the slot map
   — hits route to the cache block, misses stream from HBM — and records
   hit-rate / staged-row statistics (the modeled traffic).

The model is exact (slot map is ground truth, no approximation), host-side
numpy, and deliberately simple: one slot per row, full associativity,
value-ranked eviction.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class CacheStats:
    """Running counters over a serving session."""

    accesses: int = 0
    hits: int = 0
    staged_rows: int = 0        # rows DMA'd into the cache (prefetch traffic)
    kept_rows: int = 0          # next-batch rows already resident (free)
    evicted_rows: int = 0       # resident rows dropped from the cache
    batches: int = 0

    @property
    def hit_rate(self) -> float:
        return self.hits / max(1, self.accesses)

    @property
    def staged_per_batch(self) -> float:
        return self.staged_rows / max(1, self.batches)

    def traffic_bytes(self, row_bytes: int) -> dict:
        """Modeled DRAM bytes: uncached baseline vs cached (misses + staging)."""
        baseline = self.accesses * row_bytes
        cached = (self.accesses - self.hits + self.staged_rows) * row_bytes
        return {"baseline": baseline, "cached": cached}


class PrefetchScheduler:
    """Double-buffered next-batch prefetcher over one subtable.

    ``num_rows`` — subtable rows; ``num_slots`` — cache capacity in rows;
    ``value`` — optional (num_rows,) static prefetch value from the intra-GnR
    analyzer, used to break ties between rows with equal in-batch counts
    (rows that historically show more intra-GnR reuse win a slot).

    ``shards`` > 1 splits the rows into contiguous ranges of ``shard_rows``
    (default: an even split), one a chip of a row-sharded table: each shard
    has a cache block of its own ``num_slots`` slots holding only rows it
    owns.  Slots are numbered flat, shard ``k``'s block at
    ``[k * num_slots, (k + 1) * num_slots)``; ``slot_map`` gives each row's
    flat slot and ``cache_rows`` the rows of every block in turn.
    """

    def __init__(self, num_rows: int, num_slots: int, value: np.ndarray | None = None,
                 *, shards: int = 1, shard_rows: int | None = None):
        if num_slots <= 0:
            raise ValueError("num_slots must be positive")
        self.num_rows = num_rows
        self.shards = shards
        self.shard_rows = shard_rows or -(-num_rows // shards)
        if self.shard_rows * shards < num_rows:
            raise ValueError(f"{shards} shards of {self.shard_rows} rows do "
                             f"not cover {num_rows} rows")
        self.num_slots = min(num_slots, self.shard_rows)
        self.slot_rows = np.full(shards * self.num_slots, -1, dtype=np.int32)
        self.slot_map = np.full(num_rows, -1, dtype=np.int32)
        # an empty slot gathers its shard's first row (see cache_rows)
        self._empty_rows = np.repeat(
            np.arange(shards, dtype=np.int32) * self.shard_rows, self.num_slots)
        if value is not None and value.shape != (num_rows,):
            raise ValueError(f"value must be ({num_rows},), got {value.shape}")
        # normalized to [0, 1): strictly a tiebreak under integer counts
        if value is None:
            self.value = np.zeros(num_rows)
        else:
            v = np.asarray(value, dtype=np.float64)
            self.value = v / (v.max() + 1.0) if v.size else v
        self.stats = CacheStats()

    def prefetch(self, next_idx: np.ndarray) -> int:
        """Stage batch ``t+1``'s most valuable rows; returns rows DMA'd.

        Runs (in hardware: overlapped) during batch ``t``: ``update(rank(
        next_idx))``.
        """
        return self.update(self.rank(next_idx))

    def rank(self, next_idx: np.ndarray) -> np.ndarray:
        """The rows batch ``t+1`` should find resident, best first (shard by
        shard when there are several).

        Rows are ranked by in-batch access count + analyzer tiebreak; the top
        ``num_slots`` of each shard that the batch touches win residency.
        Only the touched rows are ranked: an untouched row never wins, since
        its key (``value < 1``) is below every touched row's, and ``rows``
        comes sorted, so the stable sort keeps row order among ties.
        """
        rows, counts = self._touched(np.asarray(next_idx).reshape(-1))
        key = -(counts + self.value[rows])
        cuts = np.searchsorted(
            rows, np.arange(1, self.shards) * self.shard_rows)
        return np.concatenate([
            r[np.argsort(k, kind="stable")[: self.num_slots]]
            for r, k in zip(np.split(rows, cuts), np.split(key, cuts))])

    def _touched(self, flat: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """The rows ``flat`` touches, sorted, and how often each: by counting
        where the rows are no more than the accesses, else by sorting."""
        if self.num_rows <= flat.size:
            counts = np.bincount(flat, minlength=self.num_rows)
            rows = np.flatnonzero(counts)
            return rows, counts[rows]
        return np.unique(flat, return_counts=True)

    def update(self, want: np.ndarray) -> int:
        """Make ``want`` (from ``rank``) the resident set; returns rows DMA'd.

        Winners already resident keep their slot — only the difference is
        staged, which is what makes steady-state Zipf traffic small (the hot
        head barely changes between batches).
        """
        want = np.asarray(want)
        owner = want // self.shard_rows
        staged = 0
        for k in range(self.shards):
            staged += self._update_block(want[owner == k], k * self.num_slots)
        return staged

    def _update_block(self, want: np.ndarray, lo: int) -> int:
        """``update`` for the cache block whose slots start at ``lo``."""
        block = self.slot_rows[lo:lo + self.num_slots]          # a view
        resident = set(int(r) for r in block if r >= 0)
        keep = np.array([r for r in want if int(r) in resident], dtype=np.int32)
        stage = np.array([r for r in want if int(r) not in resident], dtype=np.int32)

        # evict non-winners, then fill free slots with the staged rows
        keep_set = set(int(r) for r in keep)
        evicted = 0
        for s, r in enumerate(block):
            if r >= 0 and int(r) not in keep_set:
                self.slot_map[r] = -1
                block[s] = -1
                evicted += 1
        free = np.flatnonzero(block < 0)
        for s, r in zip(free, stage):
            block[s] = r
            self.slot_map[r] = lo + s

        self.stats.staged_rows += int(stage.size)
        self.stats.kept_rows += int(keep.size)
        self.stats.evicted_rows += evicted
        return int(stage.size)

    def slots_for(self, idx: np.ndarray, *, record: bool = True) -> np.ndarray:
        """Slot per access (-1 = miss) for the executing batch; records stats."""
        idx = np.asarray(idx)
        slots = self.slot_map[idx]
        if record:
            self.stats.accesses += int(idx.size)
            self.stats.hits += int((slots >= 0).sum())
            self.stats.batches += 1
        return slots

    def cache_rows(self) -> np.ndarray:
        """(shards * num_slots,) row id per slot; an empty slot gathers its
        shard's first row (row 0 with one shard).

        Feeds the device-side cache-block gather ``table[cache_rows()]`` (the
        staging DMA made visible to jax); the slot map never routes an access
        to an empty slot, so the clamp is unobservable.
        """
        return np.where(self.slot_rows >= 0, self.slot_rows, self._empty_rows)


def simulate(
    batches: list[np.ndarray], num_rows: int, num_slots: int,
    value: np.ndarray | None = None,
) -> CacheStats:
    """Run the full double-buffered schedule over a batch sequence.

    Batch 0's staging is a cold start (nothing to overlap behind); every
    later prefetch overlaps the preceding batch — exactly the serve_rec loop.
    """
    sched = PrefetchScheduler(num_rows, num_slots, value)
    sched.prefetch(batches[0])
    for t, batch in enumerate(batches):
        sched.slots_for(batch)
        if t + 1 < len(batches):
            sched.prefetch(batches[t + 1])
    return sched.stats
