"""Tiered snapshots: the LSM-style tier stack behind DarTable.

The single-snapshot DarTable paid O(table) per fold: every overlay
flush repacked ALL records and re-uploaded the whole postings table to
HBM (fold_ms_mean ~10 s at 1M intents — ~100 s extrapolated at 10M,
during which the overlay and every query's host-scan cost grow without
bound).  The reference gets compaction for free from CockroachDB's LSM
(implementation_details.md:3-8); this module is the equivalent, built
as a first-class subsystem:

  L0 (base)   — one large, rarely-rewritten snapshot.  Holds every
                record as of the last MAJOR compaction.
  L1 (delta)  — one small snapshot absorbing minor folds: all records
                written/updated since L0 was built.  Rebuilt from the
                writer-tracked delta set on every fold — O(overlay+L1),
                never O(table).
  overlay     — unchanged: records since the last fold, spliced O(Δ)
                per write (dar/snapshot.py).

Shadowing (newest tier wins) is enforced at WRITE time, not query
time: updating or removing an entity marks its slot dead in every tier
that still holds it live, so each visible entity is live in exactly
one tier (or the overlay) and the query path simply merges per-tier
hits after per-tier dead filtering.  Tombstones accumulate in the
per-tier dead sets and are garbage-collected by the next major
compaction, which rebuilds L0 from the authoritative record dict.

Major compactions (L1 + tombstones merged into a fresh L0) trigger on
the churn ratio: when |delta records| + |shadowed rows| exceeds
DSS_TIER_RATIO x |L0| the amortized O(table) rebuild is paid once,
exactly like an LSM size-ratio trigger.  Why not full LSM levels: a
DAR serves point/area lookups over a covering index where every extra
tier costs one more host range-lookup + (possibly) one more device
window pass per query — two tiers bound that cost while already making
folds O(delta); more levels would buy lower write amplification this
workload (bounded by the WAL, not the fold) does not need.

Knobs (env, read at DarTable construction; docs/OPERATIONS.md):

  DSS_TIER_RATIO   — churn ratio triggering a major compaction
                     (default 0.25; 0 disables tiering: every fold is
                     a full rebuild, the pre-tier behavior).
  DSS_TIER_MIN_L0  — below this many L0 records every fold is major
                     (default 0; small tables repack in microseconds,
                     so tier bookkeeping can be skipped).
"""

from __future__ import annotations

import itertools
import os
import threading
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from dss_tpu.dar.oracle import Record
from dss_tpu.dar.pack import pack_records
from dss_tpu.ops.fastpath import FastTable

# CellClock incarnations are process-unique: a rebuilt/replaced index
# (region resync, restore_state) gets a fresh clock whose stamps start
# over, and the read cache must never compare stamps across clocks —
# the incarnation in the fence makes cross-clock comparison impossible
# (id() reuse after GC would not).
_INCARNATIONS = itertools.count(1)


class CellClock:
    """Per-cell monotonic write clock — the exact-invalidation currency
    of the version-fenced read cache (dar/readcache.py).

    One global counter per clock; every write bumps it once (the
    `generation`) and stamps that value onto each affected DAR key's
    slot.  Because the counter is shared, `max over a covering's
    cells` is a sufficient fence: any later write touching ANY of
    those cells stamps a value strictly greater than every earlier
    max — so a cached entry needs only the scalar max, not the
    per-cell vector.

    Stamps live in a FIXED hashed-slot int64 array (default 2^20
    slots, 8 MB), not a dict: a 10M-entity table touching millions of
    distinct cells must not grow clock bookkeeping without bound, and
    the bump under the write lock becomes one vectorized scatter
    instead of a Python per-key loop.  Two cells sharing a slot can
    only OVER-invalidate (a fence sees a too-new stamp and the cache
    re-runs the query) — collisions are a hit-rate tax, never a
    staleness bug.

    Stamps survive minor folds and major compactions by construction:
    the clock lives on the writer (DarTable / MemorySpatialIndex), not
    in the published snapshot state, so fold/compaction swaps never
    touch it.  Wholesale replacements (bulk_load) bump the `floor`
    instead of walking every record — every fence computed afterwards
    is at least the floor, which invalidates all earlier entries in
    O(1).

    Writers bump under their own write lock; `fence` is lock-free (a
    racing scatter shows each slot either the old or the new stamp —
    a newer value fails the fence, which is the safe direction)."""

    __slots__ = ("_clock", "_mask", "_gen", "_high", "_floor",
                 "incarnation", "_lock", "_mirror")

    SLOTS = 1 << 20  # per-class stamp array (8 MB); power of two

    def __init__(self, slots: Optional[int] = None):
        n = self.SLOTS if slots is None else int(slots)
        assert n & (n - 1) == 0, "slot count must be a power of two"
        # LAZY: the 8 MB stamp array materializes on the first bump.
        # Construction must stay ~free — index factories run inside
        # the region-resync swap, where every extra millisecond widens
        # the window lock-free readers can observe mid-rebuild (and a
        # store that never writes a class shouldn't pay the pages).
        self._clock: Optional[np.ndarray] = None
        self._mask = np.int64(n - 1)
        self._gen = 0
        self._high = 0  # highest stamp handed out to a cell slot
        self._floor = 0  # generation of the last wholesale bump_all
        self._lock = threading.Lock()
        self.incarnation = next(_INCARNATIONS)
        # optional broadcast hook (parallel/shmring.FenceMirror): the
        # shared-memory serving front mirrors every bump into the shm
        # fence segment so worker-local read caches fence on it.  One
        # None check per bump when no front is attached.
        self._mirror = None

    def _slots_of(self, keys) -> np.ndarray:
        return np.asarray(keys, np.int64).ravel() & self._mask

    def bump(self, *key_arrays) -> None:
        """One write: stamp every DAR key in the given arrays with a
        fresh generation.  An UPDATE must pass both the old and the new
        covering — a record that moved out of cell X changes X's
        answers just as much as moving in."""
        with self._lock:
            self._gen += 1
            g = self._gen
            self._high = g
            if self._clock is None:
                self._clock = np.zeros(int(self._mask) + 1, np.int64)
            for keys in key_arrays:
                if keys is None:
                    continue
                self._clock[self._slots_of(keys)] = g
            if self._mirror is not None:
                self._mirror.on_bump(key_arrays, g)

    def bump_all(self) -> None:
        """Wholesale invalidation (bulk_load / replayed snapshot):
        raise the floor so every fence computed afterwards exceeds any
        stamp handed out before — O(1), no per-record walk."""
        with self._lock:
            self._gen += 1
            self._floor = self._gen
            if self._mirror is not None:
                self._mirror.on_bump_all(self._gen)

    def attach_mirror(self, mirror) -> None:
        """Install the shared-memory fence broadcast hook and publish
        the clock's current fence metadata.  Under the bump lock so
        the initial sync and the first mirrored bump cannot race."""
        with self._lock:
            self._mirror = mirror
            if mirror is not None:
                mirror.sync(self)

    @property
    def floor(self) -> int:
        """Generation of the last wholesale invalidation."""
        return self._floor

    def fence(self, keys) -> "tuple[int, int, int, int]":
        """-> (incarnation, max stamp over keys, generation, floor).
        One vectorized gather+max per lookup; lock-free.  The floor is
        the generation of the last WHOLESALE invalidation: the cache's
        bounded-stale tolerance must refuse entries stamped before it
        (a bump_all advances the generation by one but represents
        unbounded change — counting it as one write of lag would let
        a stale hit serve the entire pre-replacement dataset)."""
        arr = self._clock  # one read: bump may swap it in concurrently
        m = self._floor
        if arr is not None:
            slots = self._slots_of(keys)
            if len(slots):
                m = max(m, int(arr[slots].max()))
        return (self.incarnation, m, self._gen, self._floor)

    @property
    def generation(self) -> int:
        """Total write operations (cell-stamping AND wholesale)."""
        return self._gen

    @property
    def high_water(self) -> int:
        """Highest stamp handed out to a cell slot — the generation of
        the last cell-stamping write.  Diverges from `generation` when
        wholesale invalidations (bump_all) have run since."""
        return self._high


class RangeLoad:
    """Per-key-range query-load EWMA — the measurement half of
    skew-aware shard placement (parallel/sharded.py weighted split).

    DAR keys bucket by prefix (`key >> shift`, default 12: ~4096
    adjacent level-13 cells per bucket, roughly a metro-scale S2
    region).  Every coalescer-served query stamps its covering's
    buckets with its measured candidate work (result count; PR 7 cache
    hits never reach a shard and therefore never stamp).  The
    accumulated load decays exponentially (`decay_factor`) at the
    rebalance-planning cadence — once per DSS_SHARD_MOVE_INTERVAL_S,
    applied by `plan_rebalance` — so the map tracks RECENT traffic: a
    hot spot that moved cities stops pinning shards to the old metro
    within a few planning intervals.

    Bucket count is bounded (`max_buckets`): when the dict overflows,
    the coldest half is dropped — losing cold-bucket precision only
    degrades the split toward equal-count, never correctness (placement
    is a performance mapping; answers never depend on it).

    Thread-safe: writers stamp under the lock from serving threads;
    `weights_for` / `bucket_loads` take a consistent snapshot."""

    __slots__ = ("shift", "decay_factor", "max_buckets", "_load",
                 "_queries", "_lock")

    def __init__(
        self,
        shift: Optional[int] = None,
        decay_factor: Optional[float] = None,
        max_buckets: int = 1 << 16,
    ):
        if shift is None:
            shift = int(os.environ.get("DSS_SHARD_LOAD_SHIFT", 12))
        if decay_factor is None:
            decay_factor = float(
                os.environ.get("DSS_SHARD_LOAD_DECAY", 0.5)
            )
        self.shift = int(shift)
        self.decay_factor = float(decay_factor)
        self.max_buckets = int(max_buckets)
        self._load: Dict[int, float] = {}
        self._queries = 0
        self._lock = threading.Lock()

    def record(self, keys, work: float = 1.0) -> None:
        """One served query: spread its measured work over the buckets
        its covering touches.  `work` is the candidate/result count
        (floored at 1 so pure-miss traffic still registers — an empty
        hot area still costs per-shard gather work)."""
        b = np.unique(np.asarray(keys, np.int64).ravel() >> self.shift)
        if not len(b):
            return
        w = max(float(work), 1.0) / len(b)
        with self._lock:
            self._queries += 1
            load = self._load
            for k in b.tolist():
                load[k] = load.get(k, 0.0) + w
            if len(load) > self.max_buckets:
                # drop the coldest half: bounded bookkeeping, and the
                # split degrades toward equal-count for cold ranges
                keep = sorted(
                    load.items(), key=lambda kv: kv[1], reverse=True
                )[: self.max_buckets // 2]
                self._load = dict(keep)

    def decay(self) -> None:
        """One fold boundary: age the EWMA.  Buckets decayed below
        noise are dropped so a vacated hot spot releases its shards."""
        with self._lock:
            f = self.decay_factor
            self._load = {
                k: v * f for k, v in self._load.items() if v * f > 1e-3
            }

    def total(self) -> float:
        with self._lock:
            return sum(self._load.values())

    @property
    def queries(self) -> int:
        return self._queries

    def bucket_loads(self) -> "Tuple[np.ndarray, np.ndarray]":
        """-> (sorted bucket ids i64, loads f64) — a consistent
        snapshot for split planning."""
        with self._lock:
            if not self._load:
                return _EMPTY_I64, np.zeros(0, np.float64)
            ks = np.asarray(sorted(self._load), np.int64)
            vs = np.asarray([self._load[int(k)] for k in ks], np.float64)
        return ks, vs

    def weights_for(self, post_key: np.ndarray) -> np.ndarray:
        """Per-posting load weight: w[i] = EWMA load of posting i's
        bucket, 0 for never-stamped buckets.  The splitter adds its
        own count baseline, so zero-load (cold start) degrades to the
        equal-count split exactly."""
        ks, vs = self.bucket_loads()
        pk = np.asarray(post_key, np.int64) >> self.shift
        if not len(ks):
            return np.zeros(len(pk), np.float64)
        pos = np.searchsorted(ks, pk)
        pos[pos == len(ks)] = 0
        w = vs[pos].copy()
        w[ks[pos] != pk] = 0.0
        return w

    def stats(self) -> dict:
        with self._lock:
            return {
                "shard_load_buckets": len(self._load),
                "shard_load_total": round(sum(self._load.values()), 2),
                "shard_load_queries": self._queries,
            }


class TierSnapshot(NamedTuple):
    """One immutable device snapshot (the former dar.snapshot._Snapshot,
    generalized: L0 and L1 are both instances of this)."""

    fast: Optional[FastTable]
    owner: Optional[np.ndarray]  # i32 per slot
    ids: List[str]  # slot -> entity_id
    slot_of: Dict[str, int]  # entity_id -> slot
    recs: Dict[str, Record]  # id -> Record at build time (immutable)


EMPTY_SNAPSHOT = TierSnapshot(None, None, [], {}, {})


# shadowed-slot bookkeeping: dead slots live in TWO sorted int64
# arrays per tier — a small `dead_recent` (grown by O(recent) insert
# per write) and a large, stable `dead_base`.  When recent crosses
# this threshold it folds into base (one O(base) union).  This bounds
# the per-write copy AND the per-query filter cost to O(threshold) no
# matter how much churn accumulates between major compactions — a
# single frozenset would degrade both to O(accumulated churn) at 10M
# scale (dead sets persist until a major compaction now, unlike the
# pre-tier design where every fold reset them).
DEAD_FOLD_THRESHOLD = 4096

_EMPTY_I64 = np.zeros(0, np.int64)


class Tier(NamedTuple):
    """One published tier: an immutable snapshot plus the slots
    superseded/removed since it was built (never mutated — writers
    publish a replacement Tier with grown dead arrays)."""

    snap: TierSnapshot
    dead_recent: np.ndarray  # i64 sorted, small (<= threshold-ish)
    dead_base: np.ndarray  # i64 sorted, stable between threshold folds

    @property
    def dead(self) -> frozenset:
        """All shadowed slots (diagnostic/test view — the hot paths
        use the sorted arrays directly)."""
        return frozenset(
            int(s) for s in np.concatenate([self.dead_recent, self.dead_base])
        )

    @property
    def dead_count(self) -> int:
        return len(self.dead_recent) + len(self.dead_base)


def make_tier(snap: TierSnapshot, dead_slots=()) -> Tier:
    """A fresh Tier whose dead set starts as `dead_slots` (mid-fold
    reconciliation output)."""
    arr = np.asarray(sorted(dead_slots), np.int64)
    return Tier(snap, arr, _EMPTY_I64)


def _sorted_contains(arr: np.ndarray, v: int) -> bool:
    i = int(np.searchsorted(arr, v))
    return i < len(arr) and int(arr[i]) == v


def slot_dead(tier: Tier, slot: int) -> bool:
    return _sorted_contains(tier.dead_recent, slot) or _sorted_contains(
        tier.dead_base, slot
    )


def filter_dead(tier: Tier, qidx: np.ndarray, slots: np.ndarray):
    """Drop (qidx, slot) hits whose slot is shadowed in this tier.
    Both dead arrays are pre-sorted, so membership is a searchsorted
    pass per array — O(H log D), no per-query set conversion."""
    keep = None
    for arr in (tier.dead_recent, tier.dead_base):
        if not len(arr):
            continue
        pos = np.searchsorted(arr, slots)
        pos[pos == len(arr)] = 0  # any in-range index; compare below
        hit = arr[pos] == slots
        keep = ~hit if keep is None else keep & ~hit
    if keep is None:
        return qidx, slots
    return qidx[keep], slots[keep]


class TierPolicy(NamedTuple):
    ratio: float  # major compaction when churn > ratio * |L0|
    min_l0: int  # L0 sizes below this always compact major


def env_policy() -> TierPolicy:
    """Tier policy from DSS_TIER_* env vars (deployment-level knobs,
    docs/OPERATIONS.md); unset variables keep the defaults."""
    try:
        ratio = float(os.environ.get("DSS_TIER_RATIO", 0.25))
    except ValueError:
        raise ValueError(
            f"DSS_TIER_RATIO={os.environ['DSS_TIER_RATIO']!r} is not a float"
        )
    try:
        min_l0 = int(os.environ.get("DSS_TIER_MIN_L0", 0))
    except ValueError:
        raise ValueError(
            f"DSS_TIER_MIN_L0={os.environ['DSS_TIER_MIN_L0']!r} is not an int"
        )
    return TierPolicy(ratio=ratio, min_l0=min_l0)


def build_snapshot(live: List[Record]) -> TierSnapshot:
    """Pack records into one device-resident snapshot (postings +
    exact attribute columns + host decode state)."""
    if not live:
        return EMPTY_SNAPSHOT
    packed = pack_records(live, pad_postings=False)
    pe = packed.post_ent
    ft = FastTable(
        packed.post_key,
        pe,
        packed.alt_lo[pe],
        packed.alt_hi[pe],
        packed.t_start[pe],
        packed.t_end[pe],
        packed.active[pe],
        slot_exact={
            "alt_lo": packed.alt_lo,
            "alt_hi": packed.alt_hi,
            "t0": packed.t_start,
            "t1": packed.t_end,
            "live": packed.active.copy(),
        },
    )
    ids = [r.entity_id for r in live]
    return TierSnapshot(
        fast=ft,
        owner=packed.owner,
        ids=ids,
        slot_of={eid: i for i, eid in enumerate(ids)},
        recs={r.entity_id: r for r in live},
    )


def mark_dead(tiers: Tuple[Tier, ...], entity_id: str) -> Tuple[Tier, ...]:
    """Shadow an entity everywhere: mark its slot dead in every tier
    that still holds it live.  Returns the input tuple unchanged when
    nothing needed marking (no allocation on the brand-new-entity fast
    path).  Per-write cost is O(len(dead_recent)) <= O(threshold) — a
    small sorted insert — never O(accumulated churn); a recent array
    crossing the threshold folds into the base once (O(base))."""
    out = None
    for i, t in enumerate(tiers):
        s = t.snap.slot_of.get(entity_id)
        if s is None or slot_dead(t, s):
            continue
        recent = np.insert(
            t.dead_recent, int(np.searchsorted(t.dead_recent, s)), s
        )
        base = t.dead_base
        if len(recent) > DEAD_FOLD_THRESHOLD:
            # amortized: one O(base) merge per threshold shadowings
            base = np.union1d(base, recent)
            recent = _EMPTY_I64
        if out is None:
            out = list(tiers)
        out[i] = Tier(t.snap, recent, base)
    return tiers if out is None else tuple(out)


def resolve_record(
    tiers: Tuple[Tier, ...], entity_id: str
) -> Optional[Record]:
    """The entity's visible record across the tier stack, newest tier
    first (an id live in two tiers would be a shadowing bug; dead
    filtering makes the newest copy the only live one)."""
    for t in reversed(tiers):
        s = t.snap.slot_of.get(entity_id)
        if s is not None and not slot_dead(t, s):
            return t.snap.recs.get(entity_id)
    return None


def stats(tiers: Tuple[Tier, ...]) -> dict:
    """Gauge-ready tier metrics (flow into /metrics as
    dss_dar_<class>_tier_* via the index stats)."""
    l0 = len(tiers[0].snap.ids) if tiers else 0
    l1 = sum(len(t.snap.ids) for t in tiers[1:])
    shadowed = sum(t.dead_count for t in tiers)
    fasts = [t.snap.fast for t in tiers if t.snap.fast is not None]
    return {
        "tier_count": len(tiers),
        "tier_l0_records": l0,
        "tier_l1_records": l1,
        "tier_l0_dead": tiers[0].dead_count if tiers else 0,
        "tier_shadowed_rows": shadowed,
        # what the device holds for this class: postings across tiers
        # and the bytes of their block columns
        "tier_postings": sum(ft.n_postings for ft in fasts),
        "tier_device_bytes": sum(ft.device_bytes() for ft in fasts),
    }
