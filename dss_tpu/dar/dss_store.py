"""The store implementation: host-authoritative state + spatial index + WAL.

One implementation serves both backends — the spatial index strategy is
injected (`--storage=memory` -> MemorySpatialIndex linear scans,
`--storage=tpu` -> TpuSpatialIndex HBM DarTable), mirroring how the
reference selects its store behind the repository seam.

Semantics mirrored from the reference:
  - RID fenced writes on the commit-timestamp version
    (pkg/rid/cockroach/identification_service_area.go:97-162)
  - RID notification fanout = bump live subs intersecting cells
    (pkg/rid/cockroach/subscriptions.go:204-219)
  - SCD upsert fencing + OVN key check for Accepted/Activated
    (pkg/scd/store/cockroach/operations.go:304-372)
  - SCD delete with implicit-subscription GC
    (operations.go:239-301)
  - SCD subscription quota / dependent-op delete block
    (subscriptions.go:369-495)

Every mutation appends to the WAL after applying; replay rebuilds the
dicts and the spatial indexes (the HBM snapshot is a cache of the WAL,
the checkpoint/resume story per SURVEY.md §5).
"""

from __future__ import annotations

import contextlib
import copy
import dataclasses
import logging
import threading
import time
from datetime import datetime, timedelta, timezone
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from dss_tpu import chaos, errors
from dss_tpu.clock import Clock, to_nanos
from dss_tpu.dar import boot, codec
from dss_tpu.dar import readcache as rcache
from dss_tpu.obs import stages, trace
from dss_tpu.dar.index import MemorySpatialIndex, TpuSpatialIndex
from dss_tpu.dar.store import RIDStore, SCDStore
from dss_tpu.dar.wal import WriteAheadLog
from dss_tpu.geo.covering import canonical_cells
from dss_tpu.models import rid as ridm
from dss_tpu.models import scd as scdm
from dss_tpu.models.core import Version, new_ovn_from_time

MAX_RID_SUBSCRIPTIONS_PER_AREA = 10  # DSS0030
MAX_SCD_SUBSCRIPTIONS_PER_AREA = 10


# a record with no end time never expires: int64 max keeps every
# t_end >= now refilter (the read cache's, a worker cache's) a no-op
_NEVER_NS = int(np.iinfo(np.int64).max)


def _t_ends(ids, recs: dict) -> Tuple[List[str], List[int]]:
    """-> (the ids that `recs` still holds, each one's end time in ns):
    one lookup and one conversion per id.  An id whose record vanished
    between the index query and this pass is left out — a concurrent
    remove's clock bump fences a cache entry without it out, and the
    fresh path would leave it out right now."""
    out_ids: List[str] = []
    t1s: List[int] = []
    for i in ids:
        rec = recs.get(i)
        if rec is None:
            continue
        end = rec.end_time
        out_ids.append(i)
        t1s.append(_NEVER_NS if end is None else to_nanos(end))
    return out_ids, t1s


def _copy_rec(rec):
    """Shallow defensive copy for search-result assembly: callers may
    mutate the returned object (e.g. the SCD service blanks `ovn` for
    non-owners) without touching the shared stored record.  Equivalent
    to `dataclasses.replace(rec)` for these pure-data records but
    ~1.5x cheaper — per-record assembly is the read path's largest
    single cost at poll-heavy hit rates.

    Only the COPYING record-level depth of a search does this
    (`_assemble`: the callers that change or keep what they get — the
    OVN precheck, the conflict listing, federation).  The stored depth
    (`_stored`: the services' encoder, which only reads) and the
    id-level depth (`_id_answer`: what DSSStore.shm_serve puts in a
    ring slot) never do — a slot carries ids and end times, and the
    worker that asked joins the answer from its own replica's
    records."""
    return copy.copy(rec)


def _lock_txn(lock):
    """Default transaction factory: just the store lock."""

    @contextlib.contextmanager
    def txn():
        with lock:
            yield

    return txn


class _PushMixin:
    """Reverse-query push wiring shared by both sub-stores
    (dss_tpu/push/): DSSStore.attach_push hands the pipeline to the
    unwrapped impls; the notify paths then (a) run subscriber matching
    through the pipeline's rqmatch route instead of the read-side
    coalescer — bit-identical by the MatchStage contract, but priced
    and counted as write-side work — and (b) fan the bumped subscriber
    set into the durable delivery queue after the journal record
    lands.  Without a pipeline everything behaves exactly as before
    push existed."""

    _push = None

    def set_push(self, pipeline) -> None:
        self._push = pipeline

    def _push_match_ids(self, cls, cells, *, alt_lo=None, alt_hi=None,
                        t_start_ns=None, t_end_ns=None):
        """The subscriber-id match for a write volume: the push
        pipeline's MatchStage when attached (planner rqmatch route,
        host-oracle fallback), else the index's own query path.
        Returns ids in arbitrary order — callers sort."""
        push = self._push
        if push is not None and push.bound:
            return push.match_ids(
                cls, cells, alt_lo=alt_lo, alt_hi=alt_hi,
                t_start_ns=t_start_ns, t_end_ns=t_end_ns,
                now_ns=self._now_ns(),
            )
        # no pipeline: the match is a query of the subscription index
        # (stage sub_match_ms; the pipeline's own match marks
        # push_match_ms)
        with stages.stage("sub_match_ms", "write.sub_match"):
            return self._sub_index.query_ids(
                cells, alt_lo=alt_lo, alt_hi=alt_hi,
                t_start=t_start_ns, t_end=t_end_ns, now=self._now_ns(),
            )

    def _offer_push(self, trigger, entity, subs, *, removed=False,
                    emergency=False, alt_lo=None, alt_hi=None,
                    t_start=None, t_end=None) -> None:
        """Hand the bumped subscriber set to the delivery pipeline once
        the transaction's journal records are durable (`_after_commit`)
        — O(1) per subscriber (durable append + worker wake); webhook
        I/O never runs on the write path."""
        push = self._push
        if push is None or not push.bound:
            return

        def offer():
            with stages.stage("push_offer_ms", "push.offer"):
                push.offer(
                    trigger, entity, subs, removed=removed,
                    emergency=emergency, alt_lo=alt_lo, alt_hi=alt_hi,
                    t_start_ns=None if t_start is None else to_nanos(t_start),
                    t_end_ns=None if t_end is None else to_nanos(t_end),
                )

        self._after_commit(offer)


class _TxnTimeMixin:
    """Per-transaction pinned 'now' (the stand-in for CRDB's txn
    timestamp): every visibility/expiry check inside one transaction
    reads the same instant, so a precheck and the mutation that follows
    it can never disagree about which records are visible (a record
    expiring mid-txn would otherwise abort the txn after journaling).
    Thread-local so lock-free readers keep their own wall-clock now."""

    def _init_txn_time(self):
        self._txn_time = threading.local()

    @contextlib.contextmanager
    def _txn_scope(self):
        tl = self._txn_time
        if getattr(tl, "now", None) is not None:
            # a re-entry (the thread holds the lock): nothing to pin,
            # nothing waited for
            with self._txn():
                yield
            return
        with contextlib.ExitStack() as held:
            # the outermost entry: from asking for the store's lock
            # (region mode: whatever the region's txn waits for) to
            # holding it is stage txn_wait_ms, so a queue behind the
            # lock and a slow write read differently
            with stages.stage("txn_wait_ms", "write.lock_wait"):
                held.enter_context(self._txn())
            tl.now = to_nanos(self._clock.now())
            try:
                # the transaction's journal records go to the log as
                # one append as it ends, the lock still held; what
                # waits for them to be durable runs after that append
                with self._journal_group() as after:
                    tl.after = after
                    yield
            finally:
                tl.now = tl.after = None

    def _after_commit(self, fn) -> None:
        """Run `fn` once this thread's transaction is durable: after
        the outermost scope's journal append, and only if the scope
        and the append both succeed; at once outside a transaction."""
        after = getattr(self._txn_time, "after", None)
        if after is None:
            fn()
        else:
            after.append(fn)

    def _now_ns(self) -> int:
        pinned = getattr(self._txn_time, "now", None)
        return pinned if pinned is not None else to_nanos(self._clock.now())

    @contextlib.contextmanager
    def transaction(self):
        with self._txn_scope():
            yield self


class _CachedSearchMixin:
    """The version-fenced read-cache seam shared by both sub-stores.

    `_cached_ids` fronts an index query_ids call: the covering is
    canonicalized (sorted, deduped — the same form the pack path
    assumes), the per-cell clock fence is read BEFORE the fresh query
    runs, and a fenced hit returns in microseconds without ever
    reaching the coalescer — no admission, no deadline stamp, no
    Retry-After backlog contribution, no device.  Misses populate on
    the way out (the coalescer's collect path has already resolved by
    then) unless the answer came from the bounded-stale mesh replica,
    which must never be stamped as fresh.

    One search, three depths.  Each class's `_<cls>_ids` defines the
    search once and returns what `_cached_ids` found; the record level
    copies the records out for a caller that changes or keeps them
    (`_assemble`: a precheck, the conflict listing, federation) or
    hands them out as they are stored to one that only reads
    (`_stored`: the services' encoder); the id level (`_id_answer`)
    pairs each id with its end time for a shared-memory ring slot.  No
    depth knows who calls it."""

    _cache: Optional[rcache.ReadCache] = None
    _epoch_fn = staticmethod(lambda: "")
    # records of this store's search answers, by whether their wire
    # bytes were remembered or encoded (serialization.*_body)
    _wire_memo_hits = 0
    _wire_memo_misses = 0

    def note_wire_memo(self, hits: int, misses: int) -> None:
        # unlocked read-modify-write from the service's threads: a
        # lost update is a count off by one answer, never a wrong one
        self._wire_memo_hits += hits
        self._wire_memo_misses += misses

    def _init_cache(self, cache, epoch_fn):
        self._cache = cache
        if epoch_fn is not None:
            self._epoch_fn = epoch_fn

    def _fenced_index_swap(self, *old_indexes):
        """Fresh indexes for a state reset, carrying the old cell
        clocks with a bump_all() floor — THE mid-resync staleness
        invariant, shared by both store classes so the ordering cannot
        drift apart: flush the cache first (reclaims entries the floor
        is about to orphan), build the replacement indexes BEFORE the
        caller clears its dicts (factory cost stays outside the window
        lock-free readers can observe), adopt each predecessor's clock
        (O(1) — no stamp-array churn in the window), then floor it so
        every fence stamped before the reset fails."""
        if self._cache is not None:
            self._cache.invalidate_all()
        fresh = []
        for ix in old_indexes:
            clock = ix.cell_clock
            new_ix = self._index_factory()
            new_ix.adopt_cell_clock(clock)
            clock.bump_all()
            fresh.append(new_ix)
        return fresh

    def _cached_ids(
        self,
        cls: str,
        index,
        cells,  # canonical uint64 covering
        qkey: tuple,  # class-specific window/alt key components
        now_ns: int,  # the query's `now` (its only time-variant input)
        allow_stale: bool,
        run,  # () -> List[str], the fresh path (index.query_ids)
        recs: dict,  # the class's records by id (end times come from it)
        owner_id: Optional[int] = None,
    ) -> Tuple[List[str], Optional[List[int]]]:
        """-> (ids, t_end ns per id).  The end times are those the
        miss path read to populate the cache (ids whose record had
        already vanished are then left out of both lists), or None
        where no such pass ran — a fenced hit, a mesh answer, a cache
        that is off — so a record-level hit never pays for one."""
        cache = self._cache
        clock_fence = getattr(index, "clock_fence", None)
        if (
            cache is None
            or not cache.enabled
            or clock_fence is None
            # near-the-area-cap coverings: the O(|cells|) fence walk
            # stops being "microseconds" — serve fresh rather than
            # cache a key nobody repeats cheaply
            or len(cells) > 16384
        ):
            rcache.take_mesh_served()
            ids = run()
            rcache.note_last_search_meshed(rcache.take_mesh_served())
            return ids, None
        th = trace.current()
        t_cl_w = t_cl0 = 0
        if th is not None:
            t_cl_w, t_cl0 = time.time_ns(), time.perf_counter()
        epoch = self._epoch_fn()
        fence = clock_fence(cells)
        key = (cls, owner_id, qkey, cells.tobytes())
        ids = cache.lookup(
            cls, key, fence, epoch, int(now_ns), allow_stale
        )
        if th is not None:
            trace.add_span(
                th, "cache.lookup", t_cl_w,
                (time.perf_counter() - t_cl0) * 1000,
                attrs={"cls": cls, "hit": ids is not None},
            )
        if ids is not None:
            rcache.note_search(cls, epoch, fence[2], True)
            rcache.note_last_search_meshed(False)
            return ids, None
        rcache.take_mesh_served()  # clear any stale flag before running
        ids = run()
        meshed = rcache.take_mesh_served()
        rcache.note_last_search_meshed(meshed)
        t1s: Optional[List[int]] = None
        if not meshed:
            ids, t1s = _t_ends(ids, recs)
            try:
                # chaos seam: population is best-effort by contract —
                # an injected failure here leaves the next poll a
                # miss, never a wrong answer
                chaos.fault_point("cache.populate", detail=cls)
                cache.insert(
                    cls, key, fence, epoch, int(now_ns), ids, t1s
                )
            except chaos.FaultError:
                pass
        rcache.note_search(cls, epoch, fence[2], False)
        return ids, t1s

    @staticmethod
    def _id_answer(
        found, recs: dict, *, by_id: bool
    ) -> Tuple[List[str], List[int]]:
        """The id-level depth: what `_cached_ids` found as a ring slot
        carries it, (ids, t_end ns per id).  Each id's end time is read
        once: by the miss path's cache-populating pass where it ran,
        else here.  `by_id` re-sorts the answer by id (the SCD classes
        answer in id order, the RID classes in the index's)."""
        ids, t1s = found
        if t1s is None:
            return _t_ends(sorted(ids) if by_id else ids, recs)
        if by_id and ids:
            ids, t1s = map(list, zip(*sorted(zip(ids, t1s))))
        return ids, t1s

    @staticmethod
    def _stored(ids, recs: dict) -> list:
        """The record-level depth for a caller that only reads: each
        found id's record as it is stored, in the order given.  .get():
        a concurrent delete between the index query and this pass must
        skip, not KeyError (reads are lock-free)."""
        return [rec for rec in map(recs.get, ids) if rec is not None]

    @classmethod
    def _assemble(cls, ids, recs: dict) -> list:
        """The record-level depth for a caller that may change what it
        gets: a defensive copy of each record `_stored` finds."""
        return [_copy_rec(rec) for rec in cls._stored(ids, recs)]


class TimestampOracle:
    """Strictly-increasing commit timestamps (microsecond granularity),
    the stand-in for CRDB's transaction_timestamp()."""

    def __init__(self, clock: Clock):
        self._clock = clock
        self._last: Optional[datetime] = None
        self._lock = threading.Lock()

    def commit_ts(self) -> datetime:
        with self._lock:
            now = self._clock.now()
            if self._last is not None and now <= self._last:
                now = self._last + timedelta(microseconds=1)
            self._last = now
            return now


class OwnerInterner:
    """Thread-safe string->id interner.  Lock-free callers (owner-scoped
    searches) may intern concurrently, so the check-then-set must be
    atomic or two owners could share one id (tenant mixing)."""

    def __init__(self):
        self._ids: Dict[str, int] = {}
        self._lock = threading.Lock()

    def intern(self, owner: str) -> int:
        existing = self._ids.get(owner)  # fast path, no lock
        if existing is not None:
            return existing
        with self._lock:
            return self._ids.setdefault(owner, len(self._ids))


class RIDStoreImpl(_PushMixin, _TxnTimeMixin, _CachedSearchMixin, RIDStore):
    def __init__(
        self, *, clock, ts_oracle, owners, lock, journal, journal_group,
        index_factory, txn=None, capture_undo=False, cache=None,
        epoch_fn=None,
    ):
        self._clock = clock
        self._ts = ts_oracle
        self._owners = owners
        self._lock = lock
        self._txn = txn if txn is not None else _lock_txn(lock)
        self._journal = journal
        self._journal_group = journal_group
        self._index_factory = index_factory
        # region mode: each journal record carries an "undo" list (wal
        # records that revert the mutation) so the coordinator can roll
        # back an aborted txn precisely instead of resyncing from the log
        self._capture_undo = capture_undo
        self._init_txn_time()
        self._init_cache(cache, epoch_fn)
        self._isas: Dict[str, ridm.IdentificationServiceArea] = {}
        self._subs: Dict[str, ridm.Subscription] = {}
        self._isa_index = index_factory()
        self._sub_index = index_factory()

    def reset_state(self):
        """Drop all local state (region resync rebuilds from the log);
        _fenced_index_swap keeps the cache coherent and the readers'
        mid-resync window as narrow as before the cache existed."""
        new_isa, new_sub = self._fenced_index_swap(
            self._isa_index, self._sub_index
        )
        self._isas = {}
        self._subs = {}
        self._isa_index = new_isa
        self._sub_index = new_sub

    def serialize_state(self) -> dict:
        """Full-state snapshot as plain JSON docs (region snapshot
        upload; the CRDB-range-snapshot analog)."""
        return self.serialize_refs(self.snapshot_refs())

    def snapshot_refs(self) -> tuple:
        """Grab record references for a consistent snapshot cut (cheap;
        call under the store lock).  Records are immutable — replaced,
        never mutated — so serialize_refs may run outside the lock."""
        return (list(self._isas.values()), list(self._subs.values()))

    @staticmethod
    def serialize_refs(refs: tuple) -> dict:
        isas, subs = refs
        return {
            "isas": [codec.isa_to_doc(x) for x in isas],
            "subs": [codec.rid_sub_to_doc(x) for x in subs],
        }

    def restore_state(self, state: dict) -> None:
        self.reset_state()
        for d in state.get("isas", []):
            isa = codec.doc_to_isa(d)
            self._isas[isa.id] = isa
            self._index_isa(isa)
        for d in state.get("subs", []):
            sub = codec.doc_to_rid_sub(d)
            self._subs[sub.id] = sub
            self._index_sub(sub)


    # -- ISAs ----------------------------------------------------------------

    def index_stats(self) -> dict:
        return self._isa_index.stats()

    def sub_index_stats(self) -> dict:
        return self._sub_index.stats()

    def get_isa(self, id):
        # lock-free read: dict get is atomic; records are replaced, not
        # mutated, on write
        isa = self._isas.get(id)
        return dataclasses.replace(isa) if isa else None

    def _index_isa(self, isa):
        self._isa_index.put(
            isa.id,
            isa.cells,
            isa.altitude_lo,
            isa.altitude_hi,
            to_nanos(isa.start_time),
            to_nanos(isa.end_time),
            self._owners.intern(isa.owner),
        )

    def insert_isa(self, isa):
        with self._txn_scope():
            old = self._isas.get(isa.id)
            if isa.version is None or isa.version.empty:
                if old is not None:
                    raise errors.internal(
                        "insert of existing ISA (application precheck bypassed)"
                    )
            else:
                if old is None or not isa.version.matches(old.version):
                    return None  # fenced write matched no row
            stored = dataclasses.replace(
                isa, version=Version.from_time(self._ts.commit_ts())
            )
            self._isas[stored.id] = stored
            self._index_isa(stored)
            rec = {"t": "isa_put", "doc": codec.isa_to_doc(stored)}
            if self._capture_undo:
                rec["undo"] = [
                    {"t": "isa_put", "doc": codec.isa_to_doc(old)}
                    if old is not None
                    else {"t": "isa_del", "id": stored.id}
                ]
            self._journal(rec)
            return dataclasses.replace(stored)

    def delete_isa(self, isa):
        with self._txn_scope():
            old = self._isas.get(isa.id)
            if (
                old is None
                or old.owner != isa.owner
                or isa.version is None
                or not isa.version.matches(old.version)
            ):
                return None
            del self._isas[isa.id]
            self._isa_index.remove(isa.id)
            rec = {"t": "isa_del", "id": isa.id}
            if self._capture_undo:
                rec["undo"] = [{"t": "isa_put", "doc": codec.isa_to_doc(old)}]
            self._journal(rec)
            return dataclasses.replace(old)

    def _isa_ids(self, cells, earliest, latest, *, allow_stale=False):
        # lock-free read against the index's published snapshot;
        # allow_stale additionally permits a fresh mesh-replica answer
        # for oversized coalesced batches (service SEARCH paths only —
        # transactional reads never set it).  The version-fenced cache
        # fronts the whole thing: a fenced hit never reaches the index.
        if len(np.asarray(cells).ravel()) == 0:
            raise errors.bad_request("missing cell IDs for query")
        if earliest is None:
            raise errors.internal("must call with an earliest start time.")
        cells = canonical_cells(cells)
        e_ns = to_nanos(earliest)
        l_ns = None if latest is None else to_nanos(latest)
        # `earliest` is the query's `now` (the service clamps past
        # starts to the wall clock), and its ONLY effect on the result
        # is the t_end >= earliest expiry filter — which the cache
        # re-applies at now_ns on every hit.  Keying it would stamp
        # the wall clock into the key and make every repeat poll a
        # unique, never-hit line; only `latest` shapes the entry.
        return self._cached_ids(
            "isa", self._isa_index, cells,
            qkey=(l_ns,), now_ns=e_ns, allow_stale=allow_stale,
            run=lambda: self._isa_index.query_ids(
                cells, t_start=e_ns, t_end=l_ns, now=e_ns,
                allow_stale=allow_stale,
            ),
            recs=self._isas,
        )

    def search_isa_ids(self, cells, earliest, latest, *, allow_stale=False):
        return self._id_answer(
            self._isa_ids(cells, earliest, latest, allow_stale=allow_stale),
            self._isas, by_id=False,
        )

    def search_isas(self, cells, earliest, latest, *, allow_stale=False):
        ids, _ = self._isa_ids(
            cells, earliest, latest, allow_stale=allow_stale
        )
        return self._assemble(ids, self._isas)

    def stored_isas(self, cells, earliest, latest, *, allow_stale=False):
        ids, _ = self._isa_ids(
            cells, earliest, latest, allow_stale=allow_stale
        )
        return self._stored(ids, self._isas)

    # -- Subscriptions -------------------------------------------------------

    def get_subscription(self, id):
        sub = self._subs.get(id)
        return dataclasses.replace(sub) if sub else None

    def _index_sub(self, sub):
        self._sub_index.put(
            sub.id,
            sub.cells,
            sub.altitude_lo,
            sub.altitude_hi,
            to_nanos(sub.start_time),
            to_nanos(sub.end_time),
            self._owners.intern(sub.owner),
        )

    def insert_subscription(self, sub):
        with self._txn_scope():
            old = self._subs.get(sub.id)
            if sub.version is None or sub.version.empty:
                if old is not None:
                    raise errors.internal(
                        "insert of existing subscription (precheck bypassed)"
                    )
            else:
                if old is None or not sub.version.matches(old.version):
                    return None
            stored = dataclasses.replace(
                sub, version=Version.from_time(self._ts.commit_ts())
            )
            self._subs[stored.id] = stored
            self._index_sub(stored)
            rec = {"t": "rid_sub_put", "doc": codec.rid_sub_to_doc(stored)}
            if self._capture_undo:
                rec["undo"] = [
                    {"t": "rid_sub_put", "doc": codec.rid_sub_to_doc(old)}
                    if old is not None
                    else {"t": "rid_sub_del", "id": stored.id}
                ]
            self._journal(rec)
            return dataclasses.replace(stored)

    def delete_subscription(self, sub):
        with self._txn_scope():
            old = self._subs.get(sub.id)
            if (
                old is None
                or old.owner != sub.owner
                or sub.version is None
                or not sub.version.matches(old.version)
            ):
                return None
            del self._subs[sub.id]
            self._sub_index.remove(sub.id)
            rec = {"t": "rid_sub_del", "id": sub.id}
            if self._capture_undo:
                rec["undo"] = [
                    {"t": "rid_sub_put", "doc": codec.rid_sub_to_doc(old)}
                ]
            self._journal(rec)
            return dataclasses.replace(old)

    def _rid_sub_ids(self, cells, owner=None):
        """Live subscriptions intersecting cells; of `owner` alone
        where one is given (the owner scope is part of the cache key)."""
        if len(np.asarray(cells).ravel()) == 0:
            raise errors.bad_request("no location provided")
        cells = canonical_cells(cells)
        now = self._now_ns()
        oid = None if owner is None else self._owners.intern(owner)
        return self._cached_ids(
            "rid_sub", self._sub_index, cells,
            qkey=(), now_ns=now, allow_stale=False,
            run=lambda: self._sub_index.query_ids(
                cells, now=now, owner_id=oid
            ),
            recs=self._subs,
            owner_id=oid,
        )

    def search_subscription_ids(self, cells, owner=None):
        return self._id_answer(
            self._rid_sub_ids(cells, owner), self._subs, by_id=False
        )

    def search_subscriptions(self, cells):
        ids, _ = self._rid_sub_ids(cells)
        return self._assemble(ids, self._subs)

    def search_subscriptions_by_owner(self, cells, owner):
        ids, _ = self._rid_sub_ids(cells, owner)
        return self._assemble(ids, self._subs)

    def max_subscription_count_in_cells_by_owner(self, cells, owner):
        return self._sub_index.max_owner_count(
            cells, self._owners.intern(owner), now=self._now_ns()
        )

    def update_notification_idxs_in_cells(self, cells, *, entity=None,
                                          removed=False):
        """Bump + return RID subscriptions intersecting cells: the
        stored records, which callers only read (boot.bump_sub).  The
        service passes the triggering ISA as `entity` so an attached
        push pipeline can fan the bump out as deliveries; without a
        pipeline the extra args are inert."""
        with self._txn_scope():
            ids = self._push_match_ids("rid_sub", cells)
            out = []
            undo = []
            for i in sorted(ids):
                if self._capture_undo:
                    prev = self._subs.get(i)
                    if prev is not None:
                        undo.append(
                            {"t": "rid_sub_put", "doc": codec.rid_sub_to_doc(prev)}
                        )
                bumped = boot.bump_sub(self._subs, i)
                if bumped is not None:
                    out.append(bumped)
            if out:
                rec = {"t": "rid_sub_bump", "ids": [s.id for s in out]}
                if self._capture_undo:
                    rec["undo"] = undo
                self._journal(rec)
                self._offer_push("rid", entity, out, removed=removed)
            return out

    # -- WAL replay ----------------------------------------------------------

    def apply_wal(self, rec: dict):
        t = rec["t"]
        if t == "isa_put":
            isa = codec.doc_to_isa(rec["doc"])
            self._isas[isa.id] = isa
            self._index_isa(isa)
        elif t == "isa_del":
            self._isas.pop(rec["id"], None)
            self._isa_index.remove(rec["id"])
        elif t == "rid_sub_put":
            sub = codec.doc_to_rid_sub(rec["doc"])
            self._subs[sub.id] = sub
            self._index_sub(sub)
        elif t == "rid_sub_del":
            self._subs.pop(rec["id"], None)
            self._sub_index.remove(rec["id"])
        elif t == "rid_sub_bump":
            for i in rec["ids"]:
                boot.bump_sub(self._subs, i)


class SCDStoreImpl(_PushMixin, _TxnTimeMixin, _CachedSearchMixin, SCDStore):
    # writes that had subscribers to tell, and the subscribers they
    # bumped and returned (dss_scd_notifying_writes_total,
    # dss_scd_subscribers_notified_total)
    _notifying_writes = 0
    _subs_notified = 0

    def index_stats(self) -> dict:
        return self._op_index.stats()

    def sub_index_stats(self) -> dict:
        return self._sub_index.stats()

    def cst_index_stats(self) -> dict:
        return self._cst_index.stats()

    def __init__(
        self, *, clock, ts_oracle, owners, lock, journal, journal_group,
        index_factory, txn=None, capture_undo=False, cache=None,
        epoch_fn=None,
    ):
        self._clock = clock
        self._ts = ts_oracle
        self._owners = owners
        self._lock = lock
        self._txn = txn if txn is not None else _lock_txn(lock)
        self._journal = journal
        self._journal_group = journal_group
        self._index_factory = index_factory
        self._capture_undo = capture_undo
        self._init_txn_time()
        self._init_cache(cache, epoch_fn)
        self._ops: Dict[str, scdm.Operation] = {}
        self._subs: Dict[str, scdm.Subscription] = {}
        self._csts: Dict[str, scdm.Constraint] = {}
        self._op_index = index_factory()
        self._sub_index = index_factory()
        self._cst_index = index_factory()

    def reset_state(self):
        """Drop all local state (region resync rebuilds from the log);
        _fenced_index_swap keeps the cache coherent — see RIDStoreImpl."""
        new_op, new_sub, new_cst = self._fenced_index_swap(
            self._op_index, self._sub_index, self._cst_index
        )
        self._ops = {}
        self._subs = {}
        self._csts = {}
        self._op_index = new_op
        self._sub_index = new_sub
        self._cst_index = new_cst

    def serialize_state(self) -> dict:
        """Full-state snapshot as plain JSON docs (region snapshot
        upload; the CRDB-range-snapshot analog)."""
        return self.serialize_refs(self.snapshot_refs())

    def snapshot_refs(self) -> tuple:
        """Record references for a consistent cut (cheap; call under
        the store lock); serialize_refs may then run outside it."""
        return (
            list(self._ops.values()),
            list(self._subs.values()),
            list(self._csts.values()),
        )

    @staticmethod
    def serialize_refs(refs: tuple) -> dict:
        ops, subs, csts = refs
        return {
            "ops": [codec.op_to_doc(x) for x in ops],
            "subs": [codec.scd_sub_to_doc(x) for x in subs],
            "constraints": [codec.constraint_to_doc(x) for x in csts],
        }

    def restore_state(self, state: dict) -> None:
        self.reset_state()
        for d in state.get("ops", []):
            op = codec.doc_to_op(d)
            self._ops[op.id] = op
            self._index_op(op)
        for d in state.get("subs", []):
            sub = codec.doc_to_scd_sub(d)
            self._subs[sub.id] = sub
            self._index_scd_sub(sub)
        # absent on pre-constraint snapshots (rolling upgrade): .get
        for d in state.get("constraints", []):
            cst = codec.doc_to_constraint(d)
            self._csts[cst.id] = cst
            self._index_cst(cst)


    def _visible_op(self, id) -> Optional[scdm.Operation]:
        """Expired operations are invisible (operations.go:103-112)."""
        op = self._ops.get(id)
        if op is None or to_nanos(op.end_time) < self._now_ns():
            return None
        return op

    def _visible_sub(self, id) -> Optional[scdm.Subscription]:
        sub = self._subs.get(id)
        if sub is None or to_nanos(sub.end_time) < self._now_ns():
            return None
        return sub

    def _visible_cst(self, id) -> Optional[scdm.Constraint]:
        """Expired constraints are invisible, same rule as operations."""
        cst = self._csts.get(id)
        if cst is None or to_nanos(cst.end_time) < self._now_ns():
            return None
        return cst

    # -- Operations ----------------------------------------------------------

    def get_operation(self, id):
        op = self._visible_op(id)
        if op is None:
            raise errors.not_found(id)
        return dataclasses.replace(op)

    def _index_op(self, op):
        self._op_index.put(
            op.id,
            op.cells,
            op.altitude_lower,
            op.altitude_upper,
            to_nanos(op.start_time),
            to_nanos(op.end_time),
            self._owners.intern(op.owner),
        )

    def _index_scd_sub(self, sub):
        self._sub_index.put(
            sub.id,
            sub.cells,
            sub.altitude_lo,
            sub.altitude_hi,
            to_nanos(sub.start_time),
            to_nanos(sub.end_time),
            self._owners.intern(sub.owner),
        )

    def _index_cst(self, cst):
        self._cst_index.put(
            cst.id,
            cst.cells,
            cst.altitude_lower,
            cst.altitude_upper,
            to_nanos(cst.start_time),
            to_nanos(cst.end_time),
            self._owners.intern(cst.owner),
        )

    def _volume_ids(
        self, cls, index, recs, cells, alt_lo, alt_hi, earliest, latest,
        allow_stale,
    ):
        """The 4D-volume search the `op` and `constraint` classes
        share, defined once for both depths."""
        cells = canonical_cells(cells)
        t0_ns = None if earliest is None else to_nanos(earliest)
        t1_ns = None if latest is None else to_nanos(latest)
        now = self._now_ns()
        return self._cached_ids(
            cls, index, cells,
            qkey=(
                None if alt_lo is None else float(alt_lo),
                None if alt_hi is None else float(alt_hi),
                t0_ns, t1_ns,
            ),
            now_ns=now, allow_stale=allow_stale,
            run=lambda: index.query_ids(
                cells,
                alt_lo=alt_lo,
                alt_hi=alt_hi,
                t_start=t0_ns,
                t_end=t1_ns,
                now=now,
                allow_stale=allow_stale,
            ),
            recs=recs,
        )

    def _op_ids(
        self, cells, alt_lo, alt_hi, earliest, latest, *, allow_stale=False
    ):
        # ONE cached integration point for every operation search:
        # public SEARCH, OVN-conflict prechecks, dependent-operation
        # resolution, the shm ring.  A fenced hit is bit-identical to
        # the fresh path (the precheck runs under the pinned txn
        # timestamp, which is exactly the `now` the cache re-filters
        # at), so serving write-safety checks from it is sound.
        return self._volume_ids(
            "op", self._op_index, self._ops,
            cells, alt_lo, alt_hi, earliest, latest, allow_stale,
        )

    def _search_ops(
        self, cells, alt_lo, alt_hi, earliest, latest, *, allow_stale=False
    ):
        ids, _ = self._op_ids(
            cells, alt_lo, alt_hi, earliest, latest, allow_stale=allow_stale
        )
        return self._assemble(sorted(ids), self._ops)

    def search_operation_ids(
        self, cells, alt_lo, alt_hi, earliest, latest, *, allow_stale=False
    ):
        if len(np.asarray(cells).ravel()) == 0:
            raise errors.bad_request("missing cell IDs for query")
        return self._id_answer(
            self._op_ids(
                cells, alt_lo, alt_hi, earliest, latest,
                allow_stale=allow_stale,
            ),
            self._ops, by_id=True,
        )

    def search_operations(
        self, cells, alt_lo, alt_hi, earliest, latest, *, allow_stale=False
    ):
        if len(np.asarray(cells).ravel()) == 0:
            raise errors.bad_request("missing cell IDs for query")
        return self._search_ops(
            cells, alt_lo, alt_hi, earliest, latest, allow_stale=allow_stale
        )

    def stored_operations(
        self, cells, alt_lo, alt_hi, earliest, latest, *, allow_stale=False
    ):
        if len(np.asarray(cells).ravel()) == 0:
            raise errors.bad_request("missing cell IDs for query")
        ids, _ = self._op_ids(
            cells, alt_lo, alt_hi, earliest, latest, allow_stale=allow_stale
        )
        return self._stored(sorted(ids), self._ops)

    def _cst_ids(
        self, cells, alt_lo, alt_hi, earliest, latest, *, allow_stale=False
    ):
        """ONE cached integration point for every constraint search
        (public QUERY + the constraint-aware OVN precheck + the shm
        ring), the mirror of _op_ids: fenced hits are bit-identical to
        the fresh path, so serving write-safety checks from the cache
        is sound for the fifth class exactly as for the other four."""
        return self._volume_ids(
            "constraint", self._cst_index, self._csts,
            cells, alt_lo, alt_hi, earliest, latest, allow_stale,
        )

    def _search_csts(
        self, cells, alt_lo, alt_hi, earliest, latest, *, allow_stale=False
    ):
        ids, _ = self._cst_ids(
            cells, alt_lo, alt_hi, earliest, latest, allow_stale=allow_stale
        )
        return self._assemble(sorted(ids), self._csts)

    def search_constraint_ids(
        self, cells, alt_lo, alt_hi, earliest, latest, *, allow_stale=False
    ):
        if len(np.asarray(cells).ravel()) == 0:
            raise errors.bad_request("missing cell IDs for query")
        return self._id_answer(
            self._cst_ids(
                cells, alt_lo, alt_hi, earliest, latest,
                allow_stale=allow_stale,
            ),
            self._csts, by_id=True,
        )

    def search_constraints(
        self, cells, alt_lo, alt_hi, earliest, latest, *, allow_stale=False
    ):
        if len(np.asarray(cells).ravel()) == 0:
            raise errors.bad_request("missing cell IDs for query")
        return self._search_csts(
            cells, alt_lo, alt_hi, earliest, latest, allow_stale=allow_stale
        )

    def _notify_subs_locked(
        self, cells, *, trigger: str = "operations",
        alt_lo=None, alt_hi=None, t_start=None, t_end=None, ids=None,
    ) -> List[scdm.Subscription]:
        """Bump + return live subscriptions intersecting cells whose
        notification trigger matches the writing entity class
        (subscriptions.go:128-173): operation writes bump
        notify_for_operations subscriptions, constraint writes bump
        notify_for_constraints ones.  Constraint callers additionally
        pass the write's altitude/time window so only subscriptions
        whose 4D volumes intersect the constraint fan out (an airport
        closure must not wake a subscriber watching a different
        altitude band).

        With a push pipeline attached the id lookup rides the
        planner's rqmatch route (dss_tpu/push/match.py) — the write IS
        a reverse query — instead of the read-side coalescer; the
        MatchStage contract keeps the id set bit-identical, so the
        returned subscriber list (and the response built from it)
        cannot change.  A caller that already holds the match's answer
        (upsert_operation_with_subscription) hands it in as `ids`.

        The records returned are the stored ones, one new object each
        (boot.bump_sub): the response and the push offer read them and
        never assign a field."""
        if ids is None:
            ids = self._push_match_ids(
                "scd_sub", cells, alt_lo=alt_lo, alt_hi=alt_hi,
                t_start_ns=None if t_start is None else to_nanos(t_start),
                t_end_ns=None if t_end is None else to_nanos(t_end),
            )
        want_constraints = trigger == "constraints"
        out = []
        undo = []
        # the bump and its journal record: O(matched) under the write
        # lock (stage sub_bump_ms; dss.sub.bump on a capture; the
        # record is appended with the transaction's others as it ends)
        with stages.stage("sub_bump_ms", "sub.bump"):
            for i in sorted(ids):
                prev = self._subs.get(i)
                if prev is None:
                    continue
                if want_constraints:
                    if not prev.notify_for_constraints:
                        continue
                elif not prev.notify_for_operations:
                    continue
                if self._capture_undo:
                    undo.append({
                        "t": "scd_sub_put",
                        "doc": codec.scd_sub_to_doc(prev),
                    })
                out.append(boot.bump_sub(self._subs, i))
            if out:
                rec = {"t": "scd_sub_bump", "ids": [s.id for s in out]}
                if self._capture_undo:
                    rec["undo"] = undo
                self._journal(rec)
        if out:
            self._notifying_writes += 1
            self._subs_notified += len(out)
        return out

    def _precheck_op_upsert(self, op, key, *, check_key: bool = True):
        """All upsert preconditions (version fencing, ownership, time
        range, OVN key check — operations.go:305-364), no mutation.
        Returns the old record (or None).  check_key=False skips the
        (expensive) OVN conflict search — only valid when the caller
        already ran it inside the same transaction scope (the pinned
        txn timestamp guarantees the same visibility answers)."""
        old = self._visible_op(op.id)
        if old is None and op.version != 0:
            raise errors.not_found(op.id)
        if old is not None and op.version == 0:
            raise errors.already_exists(op.id)
        if old is not None and op.version != old.version:
            raise errors.version_mismatch("old version")
        if old is not None and old.owner != op.owner:
            raise errors.permission_denied(
                f"Operation is owned by {old.owner}"
            )
        op.validate_time_range()

        if check_key and op.state in scdm.OperationState.REQUIRES_KEY:
            # the conflict search and the key comparison: stage
            # precheck_ms, on both PUTs of a planned flight
            with stages.stage("precheck_ms", "write.precheck"):
                self._check_key(op, key)
        return old

    def _check_key(self, op, key) -> None:
        """The OVN key check: every operation (and, for a
        constraint-aware op, every constraint) that intersects the
        op's volume must have its OVN in `key`, else MISSING_OVNS."""
        conflicting = self._search_ops(
            op.cells,
            op.altitude_lower,
            op.altitude_upper,
            op.start_time,
            op.end_time,
        )
        key_set = set(key)
        missing = [c for c in conflicting if c.ovn not in key_set]
        if op.constraint_aware:
            # constraint-aware deconfliction: the op's USS consumes
            # constraint updates, so its key must also cover every
            # intersecting constraint's OVN — a stale view of an
            # airspace closure is exactly the conflict the key
            # check exists to catch
            missing.extend(
                c
                for c in self._search_csts(
                    op.cells,
                    op.altitude_lower,
                    op.altitude_upper,
                    op.start_time,
                    op.end_time,
                )
                if c.ovn not in key_set
            )
        if missing:
            raise errors.missing_ovns(missing)

    def validate_operation_upsert(self, op, key):
        """Read-only precheck, run by the service BEFORE any journaled
        mutation (e.g. the implicit subscription) so a rejected conflict
        — a routine outcome — aborts the transaction with an empty
        journal buffer: nothing to roll back, no region resync.  The
        upsert that follows (with key_checked=True) re-runs only the
        cheap fencing checks; the pinned per-txn timestamp keeps both
        passes' visibility answers identical."""
        with self._txn_scope():
            self._precheck_op_upsert(op, key)

    def upsert_operation(self, op, key, *, key_checked: bool = False):
        with self._txn_scope():
            old = self._precheck_op_upsert(
                op, key, check_key=not key_checked
            )
            return self._notify_op_locked(self._put_op_locked(op, old))

    def upsert_operation_with_subscription(
        self, op, key, sub, *, key_checked: bool = False
    ):
        """A planned flight's 200 as one transaction: `sub`, the new
        implicit subscription that `op` rides (op.subscription_id, the
        op's cells, notify_for_operations), then the op.  The same as
        upsert_subscription followed by upsert_operation, with the
        subscription table read once: the quota's count and the
        subscribers' match read one volume (the flight's cells, live
        at the pinned now, no altitude or time filter) but for the
        owner filter, and between them the table changes only by
        `sub`.  So the match's answer, taken before `sub` is written,
        gives the owner's count per cell (DSS0030, refused before
        anything is written), and with `sub`, where it is live, the
        subscribers.  The subscription's `affected` search is not
        run: no caller reads it here."""
        with self._txn_scope():
            old = self._precheck_op_upsert(
                op, key, check_key=not key_checked
            )
            old_sub = self._precheck_sub_upsert(sub)
            ids = set(self._push_match_ids("scd_sub", op.cells))
            # the quota counted from the answer (the owner's own by
            # their records here, each one's cells by the table's),
            # the new version's record and the scd_sub splice: stage
            # sub_index_ms
            with stages.stage("sub_index_ms", "write.sub_index"):
                subs = self._subs
                mine = [i for i in ids if i in subs
                        and subs[i].owner == sub.owner]
                stored_sub = self._put_sub_locked(
                    sub, old_sub,
                    self._sub_index.max_count_of(mine, sub.cells),
                )
            if self._visible_sub(stored_sub.id) is not None:
                ids.add(stored_sub.id)
            return self._notify_op_locked(
                self._put_op_locked(op, old), ids=ids
            )

    def _put_op_locked(self, op, old):
        """The op's new version: record, overlay splice, journal."""
        ts = self._ts.commit_ts()
        stored = dataclasses.replace(
            op,
            version=(old.version if old else 0) + 1,
            ovn=new_ovn_from_time(ts, op.id),
        )
        if self._capture_undo:
            # exact inverse: restore whatever the id maps to NOW,
            # including an expired (invisible) record `old` misses
            prev_raw = self._ops.get(op.id)
            undo = [
                {"t": "scd_op_put", "doc": codec.op_to_doc(prev_raw)}
                if prev_raw is not None
                else {"t": "scd_op_del", "id": stored.id}
            ]
        self._ops[stored.id] = stored
        # the scd_op table's overlay splice, its wait for the
        # table's write lock (a fold's swap holds it) included
        with stages.stage("op_index_ms", "write.op_index"):
            self._index_op(stored)
        rec = {"t": "scd_op_put", "doc": codec.op_to_doc(stored)}
        if self._capture_undo:
            rec["undo"] = undo
        self._journal(rec)
        return stored

    def _notify_op_locked(self, stored, ids=None):
        """The op's subscribers bumped and offered -> (a copy of the
        op, the subscribers)."""
        subs = self._notify_subs_locked(stored.cells, ids=ids)
        self._offer_push(
            "operations", stored, subs,
            emergency=stored.state in (
                scdm.OperationState.NON_CONFORMING,
                scdm.OperationState.CONTINGENT,
            ),
            alt_lo=stored.altitude_lower,
            alt_hi=stored.altitude_upper,
            t_start=stored.start_time, t_end=stored.end_time,
        )
        return dataclasses.replace(stored), subs

    def delete_operation(self, id, owner):
        with self._txn_scope():
            old = self._visible_op(id)
            if old is None:
                raise errors.not_found(id)
            if old.owner != owner:
                raise errors.permission_denied(f"Operation is owned by {old.owner}")
            subs = self._notify_subs_locked(old.cells)
            del self._ops[id]
            self._op_index.remove(id)
            rec = {"t": "scd_op_del", "id": id}
            if self._capture_undo:
                rec["undo"] = [{"t": "scd_op_put", "doc": codec.op_to_doc(old)}]
            self._journal(rec)
            # implicit-subscription GC (operations.go:249-267,296-298)
            sub = self._subs.get(old.subscription_id)
            if (
                sub is not None
                and sub.implicit_subscription
                and sub.owner == owner
                and not any(
                    o.subscription_id == sub.id for o in self._ops.values()
                )
            ):
                del self._subs[sub.id]
                self._sub_index.remove(sub.id)
                gc_rec = {"t": "scd_sub_del", "id": sub.id}
                if self._capture_undo:
                    gc_rec["undo"] = [
                        {"t": "scd_sub_put", "doc": codec.scd_sub_to_doc(sub)}
                    ]
                self._journal(gc_rec)
            self._offer_push(
                "operations", old, subs, removed=True,
                alt_lo=old.altitude_lower, alt_hi=old.altitude_upper,
                t_start=old.start_time, t_end=old.end_time,
            )
            return dataclasses.replace(old), subs

    # -- Constraints ---------------------------------------------------------
    #
    # The fifth entity class, beyond the reference (which stubs it):
    # same fencing/ownership discipline as operations, fan-out to
    # notify_for_constraints subscriptions whose 4D volumes intersect
    # the write, no OVN key check on the constraint itself.

    def get_constraint(self, id):
        cst = self._visible_cst(id)
        if cst is None:
            raise errors.not_found(id)
        return dataclasses.replace(cst)

    def upsert_constraint(self, cst):
        with self._txn_scope():
            old = self._visible_cst(cst.id)
            if old is None and cst.version != 0:
                raise errors.not_found(cst.id)
            if old is not None and cst.version == 0:
                raise errors.already_exists(cst.id)
            if old is not None and cst.version != old.version:
                raise errors.version_mismatch("old version")
            if old is not None and old.owner != cst.owner:
                raise errors.permission_denied(
                    f"Constraint is owned by {old.owner}"
                )
            cst.validate_time_range()
            ts = self._ts.commit_ts()
            stored = dataclasses.replace(
                cst,
                version=(old.version if old else 0) + 1,
                ovn=new_ovn_from_time(ts, cst.id),
            )
            if self._capture_undo:
                # exact inverse: raw get includes an expired
                # (invisible) record that `old` misses
                prev_raw = self._csts.get(cst.id)
                undo = [
                    {"t": "scd_cst_put",
                     "doc": codec.constraint_to_doc(prev_raw)}
                    if prev_raw is not None
                    else {"t": "scd_cst_del", "id": stored.id}
                ]
            self._csts[stored.id] = stored
            self._index_cst(stored)
            rec = {"t": "scd_cst_put", "doc": codec.constraint_to_doc(stored)}
            if self._capture_undo:
                rec["undo"] = undo
            self._journal(rec)
            subs = self._notify_subs_locked(
                stored.cells, trigger="constraints",
                alt_lo=stored.altitude_lower, alt_hi=stored.altitude_upper,
                t_start=stored.start_time, t_end=stored.end_time,
            )
            self._offer_push(
                "constraints", stored, subs,
                alt_lo=stored.altitude_lower,
                alt_hi=stored.altitude_upper,
                t_start=stored.start_time, t_end=stored.end_time,
            )
            return dataclasses.replace(stored), subs

    def delete_constraint(self, id, owner):
        with self._txn_scope():
            old = self._visible_cst(id)
            if old is None:
                raise errors.not_found(id)
            if old.owner != owner:
                raise errors.permission_denied(
                    f"Constraint is owned by {old.owner}"
                )
            subs = self._notify_subs_locked(
                old.cells, trigger="constraints",
                alt_lo=old.altitude_lower, alt_hi=old.altitude_upper,
                t_start=old.start_time, t_end=old.end_time,
            )
            del self._csts[id]
            self._cst_index.remove(id)
            rec = {"t": "scd_cst_del", "id": id}
            if self._capture_undo:
                rec["undo"] = [
                    {"t": "scd_cst_put", "doc": codec.constraint_to_doc(old)}
                ]
            self._journal(rec)
            self._offer_push(
                "constraints", old, subs, removed=True,
                alt_lo=old.altitude_lower, alt_hi=old.altitude_upper,
                t_start=old.start_time, t_end=old.end_time,
            )
            return dataclasses.replace(old), subs

    # -- Subscriptions -------------------------------------------------------

    def _dependent_ops(self, sub) -> List[str]:
        """The reference populates DependentOperations with the ids of
        operations intersecting the subscription's own 4D volume
        (subscriptions.go:212-249)."""
        if len(np.asarray(sub.cells).ravel()) == 0:
            return []
        ops = self._search_ops(
            sub.cells, sub.altitude_lo, sub.altitude_hi, sub.start_time, sub.end_time
        )
        return [o.id for o in ops]

    def get_subscription(self, id, owner):
        sub = self._visible_sub(id)
        if sub is None or sub.owner != owner:
            raise errors.not_found(id)
        out = dataclasses.replace(sub)
        out.dependent_operations = self._dependent_ops(sub)
        return out

    def upsert_subscription(self, sub):
        with self._txn_scope():
            old = self._precheck_sub_upsert(sub)
            # the quota count, the new version's record and the scd_sub
            # table's overlay splice: stage sub_index_ms
            with stages.stage("sub_index_ms", "write.sub_index"):
                stored = self._put_sub_locked(
                    sub, old,
                    self._sub_index.max_owner_count(
                        sub.cells, self._owners.intern(sub.owner),
                        now=self._now_ns(),
                    ),
                )
            # the operations the subscription's volume meets
            with stages.stage("sub_affected_ms", "write.sub_affected"):
                affected = (
                    self._search_ops(
                        stored.cells,
                        stored.altitude_lo,
                        stored.altitude_hi,
                        stored.start_time,
                        stored.end_time,
                    )
                    if len(np.asarray(stored.cells).ravel())
                    else []
                )
            return dataclasses.replace(stored), affected

    def _precheck_sub_upsert(self, sub):
        """A subscription upsert's version fencing and ownership, no
        mutation -> the visible old version (or None)."""
        old = self._visible_sub(sub.id)
        if old is None and sub.version != 0:
            raise errors.not_found(sub.id)
        if old is not None and sub.version == 0:
            raise errors.already_exists(sub.id)
        if old is not None and sub.version != old.version:
            raise errors.version_mismatch("old version")
        if old is not None and old.owner != sub.owner:
            raise errors.permission_denied(
                f"Subscription is owned by {old.owner}"
            )
        return old

    def _put_sub_locked(self, sub, old, count: int):
        """The subscription's new version: refused at the DSS0030
        quota (`count`, the owner's most live subscriptions in one of
        its cells), else record, overlay splice, journal."""
        if count >= MAX_SCD_SUBSCRIPTIONS_PER_AREA:
            msg = "too many existing subscriptions in this area already"
            if old is not None:
                msg += ", rejecting update request"
            raise errors.exhausted(msg)
        stored = dataclasses.replace(
            sub, version=(old.version if old else 0) + 1
        )
        if self._capture_undo:
            # exact inverse: raw get includes an expired (invisible)
            # record that `old` (visibility-filtered) misses
            prev_raw = self._subs.get(sub.id)
            undo = [
                {"t": "scd_sub_put", "doc": codec.scd_sub_to_doc(prev_raw)}
                if prev_raw is not None
                else {"t": "scd_sub_del", "id": stored.id}
            ]
        self._subs[stored.id] = stored
        self._index_scd_sub(stored)
        rec = {"t": "scd_sub_put", "doc": codec.scd_sub_to_doc(stored)}
        if self._capture_undo:
            rec["undo"] = undo
        self._journal(rec)
        return stored

    def delete_subscription(self, id, owner, version):
        with self._txn_scope():
            old = self._visible_sub(id)
            if old is None:
                raise errors.not_found(id)
            if version != 0 and version != old.version:
                raise errors.version_mismatch("old version")
            if old.owner != owner:
                raise errors.permission_denied(f"ISA is owned by {old.owner}")
            if any(o.subscription_id == id for o in self._ops.values()):
                raise errors.bad_request(
                    "failed to delete implicit subscription with active operation"
                )
            del self._subs[id]
            self._sub_index.remove(id)
            rec = {"t": "scd_sub_del", "id": id}
            if self._capture_undo:
                rec["undo"] = [
                    {"t": "scd_sub_put", "doc": codec.scd_sub_to_doc(old)}
                ]
            self._journal(rec)
            return dataclasses.replace(old)

    def _scd_sub_ids(self, cells, oid):
        """Live subscriptions of the interned owner `oid` intersecting
        cells.

        The reference's SQL uses a LEFT JOIN (subscriptions.go:500-521)
        which in effect ignores the cell filter; we implement the
        intended inner-join semantics (cells do filter).
        """
        if len(np.asarray(cells).ravel()) == 0:
            raise errors.bad_request("no location provided")
        cells = canonical_cells(cells)
        now = self._now_ns()
        return self._cached_ids(
            "scd_sub", self._sub_index, cells,
            qkey=(), now_ns=now, allow_stale=False,
            run=lambda: self._sub_index.query_ids(
                cells, now=now, owner_id=oid
            ),
            recs=self._subs,
            owner_id=oid,
        )

    def search_subscription_ids(self, cells, owner):
        # id level: the worker that asked resolves each sub's
        # dependent operations itself (through its own cached op
        # path), so a ring slot never carries nested lists
        oid = None if owner is None else self._owners.intern(owner)
        return self._id_answer(
            self._scd_sub_ids(cells, oid), self._subs, by_id=True
        )

    def search_subscriptions(self, cells, owner):
        ids, _ = self._scd_sub_ids(cells, self._owners.intern(owner))
        out = self._assemble(sorted(ids), self._subs)
        for s in out:
            # dependent ops resolve fresh each time (and their inner
            # _search_ops calls ride the cache themselves)
            s.dependent_operations = self._dependent_ops(s)
        return out

    # -- WAL replay ----------------------------------------------------------

    def apply_wal(self, rec: dict):
        t = rec["t"]
        if t == "scd_op_put":
            op = codec.doc_to_op(rec["doc"])
            self._ops[op.id] = op
            self._index_op(op)
        elif t == "scd_op_del":
            self._ops.pop(rec["id"], None)
            self._op_index.remove(rec["id"])
        elif t == "scd_sub_put":
            sub = codec.doc_to_scd_sub(rec["doc"])
            self._subs[sub.id] = sub
            self._index_scd_sub(sub)
        elif t == "scd_sub_del":
            self._subs.pop(rec["id"], None)
            self._sub_index.remove(rec["id"])
        elif t == "scd_sub_bump":
            for i in rec["ids"]:
                boot.bump_sub(self._subs, i)
        elif t == "scd_cst_put":
            cst = codec.doc_to_constraint(rec["doc"])
            self._csts[cst.id] = cst
            self._index_cst(cst)
        elif t == "scd_cst_del":
            self._csts.pop(rec["id"], None)
            self._cst_index.remove(rec["id"])


class DSSStore:
    """One DSS instance's storage: RID + SCD stores sharing a lock, a
    commit-timestamp oracle, an owner interner, and a durable log.

    Two durability modes:
      - standalone (default): a local WriteAheadLog is the source of
        truth; boot replays it.
      - region (`region_url` set): the shared region log
        (dss_tpu.region) is the source of truth; every mutation runs
        as a lease-fenced write-through transaction and a tail poller
        applies remote instances' writes.  The local WAL is disabled
        (the region server owns durability), mirroring the reference
        where instances keep no local state beside the shared CRDB
        cluster (README.md:22-49).
    """

    def __init__(
        self,
        *,
        storage: str = "tpu",
        clock: Optional[Clock] = None,
        wal_path: Optional[str] = None,
        wal_fsync: bool = False,
        region_url: Optional[str] = None,
        region_token: Optional[str] = None,
        region_poll_interval_s: float = 0.05,
        region_snapshot_every: int = 512,
        region_optimistic: bool = True,  # False forces the lease path
        #                    (bench/diagnosis of lease-path round trips)
        instance_id: Optional[str] = None,
    ):
        if storage == "tpu":
            index_factory = TpuSpatialIndex
        elif storage == "memory":
            index_factory = MemorySpatialIndex
        else:
            raise ValueError(f"unknown storage backend {storage!r}")
        if region_url and wal_path:
            raise ValueError(
                "wal_path is unused in region mode: the region log server "
                "owns durability (give the WAL path to the region server)"
            )
        self.storage = storage
        self.clock = clock or Clock()
        # the graceful-degradation ladder (chaos/ladder.py): ONE
        # explicit health state machine for this store — the planner
        # reads device_ok from it, the region client drives
        # REGION_LOG_DOWN into it, and recovery re-warms (AOT grid)
        # before re-admitting routes.  Surfaced in /status,
        # X-DSS-Freshness, and the dss_degraded_mode gauge.
        self.health = chaos.DegradationLadder()
        self.health.on_recover("device_lost", self._rewarm_after_device_loss)
        # one interner for both sub-stores; made before the log is
        # opened because the boot resolves the log while the WAL's
        # recovery pass reads it (_replay commits what it resolved)
        owners = OwnerInterner()
        self._boot_resolver = None
        if wal_path and not region_url:
            self._boot_resolver = boot.Resolver(owners)
        self.wal = WriteAheadLog(
            None if region_url else wal_path, fsync=wal_fsync,
            sink=self._boot_resolver and self._boot_resolver.consume,
        )
        self._lock = threading.RLock()
        # the open journal group of this thread's transaction
        # (_journal_group)
        self._group = threading.local()
        self.region = None
        txn = None
        epoch_fn = None
        if region_url:
            from dss_tpu.region.client import RegionClient
            from dss_tpu.region.coordinator import RegionCoordinator

            self._region_client = RegionClient(
                region_url, instance_id, auth_token=region_token,
                health=self.health,
            )
            txn = self._region_txn
            # region epoch joins the cache fence: a promotion or a
            # restored-backup rotation invalidates every cached answer
            epoch_fn = self._region_client.current_epoch
        # version-fenced read cache (dar/readcache.py): one shared
        # instance fronting all five entity classes' search paths;
        # DSS_CACHE_* env knobs, configure_serving(cache=) at runtime
        self.cache = rcache.ReadCache(**rcache.env_knobs())
        # per-key-range query-load EWMA (dar/tiers.py RangeLoad): one
        # shared map across all five classes — they cover one S2 key
        # space and the sharded replica plans ONE boundary map from it.
        # Coalescer-served traffic stamps it below; attach_mesh_replica
        # hands the same instance to the replica so its own serving
        # entry accumulates into the same map.
        from dss_tpu.dar import tiers as _tiersmod

        self.range_load = _tiersmod.RangeLoad()
        ts = TimestampOracle(self.clock)
        self.rid = RIDStoreImpl(
            clock=self.clock,
            ts_oracle=ts,
            owners=owners,
            lock=self._lock,
            journal=self._journal,
            journal_group=self._journal_group,
            index_factory=index_factory,
            txn=txn,
            capture_undo=bool(region_url),
            cache=self.cache,
            epoch_fn=epoch_fn,
        )
        self.scd = SCDStoreImpl(
            clock=self.clock,
            ts_oracle=ts,
            owners=owners,
            lock=self._lock,
            journal=self._journal,
            journal_group=self._journal_group,
            index_factory=index_factory,
            txn=txn,
            capture_undo=bool(region_url),
            cache=self.cache,
            epoch_fn=epoch_fn,
        )
        # per-class cache hit/miss counters ride the coalescer stats
        # path (dss_dar_<class>_co_cache_* in /metrics), so hit rate
        # renders next to the route mix it removes load from
        for index, cls in (
            (self.rid._isa_index, "isa"),
            (self.rid._sub_index, "rid_sub"),
            (self.scd._op_index, "op"),
            (self.scd._sub_index, "scd_sub"),
            (self.scd._cst_index, "constraint"),
        ):
            co = getattr(index, "coalescer", None)
            if co is not None:
                co.set_cache_view(
                    lambda cls=cls: self.cache.class_stats(cls)
                )
                co.set_load_view(self.range_load)
                co.set_health(self.health)
        # multi-region federation (region/federation.py): None until
        # attach_federation wraps the sub-stores with the locality
        # router; stats() exports the stable dss_fed_* key set either
        # way so dashboards never miss a series
        self.federation = None
        # reverse-query push pipeline (push/pipeline.py): None until
        # attach_push wires the durable delivery queue onto the write
        # path; stats() exports the stable dss_push_* key set either way
        self.push = None
        # shared-memory serving front (parallel/shmring.py): None
        # until attach_shm_front makes this process the device owner
        self._shm_owner = None
        self._replaying = False
        # how the log was applied at boot (_replay, or a worker's first
        # catch-up): mode "bulk" or "loop", records, seconds by stage
        # (dss_boot_* on /metrics; cmds/server.py logs them)
        self.boot_stats: dict = {}
        if region_url:
            self.region = RegionCoordinator(
                self._region_client,
                self.rid,
                self.scd,
                self._lock,
                poll_interval_s=region_poll_interval_s,
                snapshot_every=region_snapshot_every,
                optimistic=region_optimistic,
            )
            self.region.bootstrap()
        else:
            self._replay()

    def _region_txn(self):
        return self.region.txn()

    def _rewarm_after_device_loss(self) -> None:
        """Recovery hook (ladder.on_recover): a returning device must
        be warm BEFORE the planner re-admits the device class, or the
        first post-recovery batches pay compile storms inside their
        deadlines.  Best-effort — a failed warm only means lazy
        warm-on-traffic, exactly the cold-boot behavior."""
        try:
            self.warm_resident()
        except Exception:  # noqa: BLE001 — recovery must not wedge
            import logging

            logging.getLogger("dss.chaos").exception(
                "post-device-loss re-warm failed; warming lazily"
            )

    def _journal(self, rec: dict):
        if self._replaying:
            return
        if self.region is not None:
            # the coordinator batches a region txn's records itself
            self.region.journal(rec)
            return
        group = getattr(self._group, "records", None)
        if group is not None:
            group.append(rec)
        else:
            self._append(rec)

    def _append(self, *records: dict) -> None:
        # stage wal_commit_ms: the one append of a transaction's
        # records, write, flush and fsync
        with stages.stage("wal_commit_ms", "wal.commit"):
            self.wal.append(*records)

    @contextlib.contextmanager
    def _journal_group(self):
        """One transaction's journal (both sub-stores' `_txn_scope`,
        outermost entry, the store's lock held): the records
        `_journal` is handed inside it go to the WAL as ONE append as
        it ends, an exception's end included, so the log never holds
        less than memory and a transaction's records are durable
        together.  Yields the list of what waits for them to be
        durable (`_after_commit`: push offers), run after the append
        and only if the transaction and the append both succeed.  An
        entry inside an open group joins it."""
        tl = self._group
        if getattr(tl, "records", None) is not None:
            yield tl.after
            return
        records, after = [], []
        tl.records, tl.after = records, after
        try:
            yield after
        finally:
            tl.records = tl.after = None
            if records:
                self._append(*records)
        for fn in after:
            fn()

    def apply_log_record(self, rec: dict) -> None:
        """Apply one WAL/region-log record to the right sub-store
        (caller holds the lock and has set _replaying)."""
        t = rec.get("t", "")
        if t.startswith("isa") or t.startswith("rid"):
            self.rid.apply_wal(rec)
        else:
            self.scd.apply_wal(rec)

    def boot_resolver(self) -> boot.Resolver:
        """A resolver of a whole log on this store's owner interner
        (dar/boot.py): feed it the log, then apply_log_bulk."""
        return boot.Resolver(self.scd._owners)

    def apply_log_bulk(self, resolved: boot.Resolver) -> bool:
        """Fill this EMPTY store with a log's resolved end state as one
        batch: the boot's path, and a worker's first catch-up.  Caller
        holds the lock.  -> False, with nothing changed and a warning
        logged, where the log cannot be taken so (a record type the
        bulk path does not know, a document the codec refuses): the
        caller then applies the log record by record."""
        try:
            done = resolved.commit(self)
        except Exception as e:  # noqa: BLE001 — the loop decides
            if not boot.is_empty(self):
                raise  # half a boot is no state to go on from
            logging.getLogger("dss.dar").warning(
                "bulk boot refused (%s): applying the log's %d records "
                "one by one", e, resolved.records,
            )
            self.boot_stats = {"mode": "loop", "records": resolved.records}
            return False
        self.boot_stats = {"mode": "bulk", **done}
        return True

    def _replay(self):
        """The boot: the log's end state as one batch, resolved by
        `_boot_resolver` while the WAL's recovery pass read the file
        (its one read); record by record where that was refused."""
        resolved, self._boot_resolver = self._boot_resolver, None
        if resolved is None or not resolved.records:
            return
        self._replaying = True
        try:
            if not self.apply_log_bulk(resolved):
                for rec in self.wal.replay():
                    self.apply_log_record(rec)
        finally:
            self._replaying = False

    def configure_serving(self, **knobs) -> None:
        """Fan serving-pipeline knobs (QueryCoalescer.configure:
        min_batch / max_batch / target_batch_ms / queue_depth /
        admission_wait_s / inline / slo_ms — the per-query serving SLO
        driving the deadline router — / resident, the persistent
        device-feeder loop) out to every entity class's coalescer.  Boot-time defaults come from DSS_CO_* env vars
        (coalesce.env_knobs); this is the runtime override for ops
        tuning and tests.  No-op on the memory backend — except
        `cache`, the version-fenced read cache toggle, which applies
        on both backends (disable flushes; see OPERATIONS.md runbook)."""
        cache = knobs.pop("cache", None)
        if cache is not None:
            self.cache.configure(enabled=bool(cache))
        if not knobs:
            return
        for index in (
            self.rid._isa_index, self.rid._sub_index,
            self.scd._op_index, self.scd._sub_index,
            self.scd._cst_index,
        ):
            co = getattr(index, "coalescer", None)
            if co is not None:
                co.configure(**knobs)

    def warm_resident(self) -> int:
        """AOT-compile the resident bucket grid for every entity
        class's current tiers (ops/resident.py).  Call AFTER
        configure_serving(resident=True) attached the loops; runs the
        multi-second XLA compiles off the serving path (the server's
        boot warm thread).  Returns executables built.  Its compiles
        count as dss_jax_compiles_boot_warm."""
        from dss_tpu.ops import compile_site

        n = 0
        for index in (
            self.rid._isa_index, self.rid._sub_index,
            self.scd._op_index, self.scd._sub_index,
            self.scd._cst_index,
        ):
            co = getattr(index, "coalescer", None)
            table = getattr(index, "table", None)
            if co is None or table is None:
                continue
            loop = co.resident_loop()
            if loop is None:
                continue
            warm = getattr(table, "warm_resident", None)
            if warm is not None:
                with compile_site("boot_warm"):
                    n += warm(loop.kernel)
        return n

    # -- shared-memory serving front (parallel/shmring.py) -------------------

    def _class_index(self, cls: str):
        return {
            "isa": self.rid._isa_index,
            "rid_sub": self.rid._sub_index,
            "op": self.scd._op_index,
            "scd_sub": self.scd._sub_index,
            "constraint": self.scd._cst_index,
        }[cls]

    def shm_serve(self, req) -> Tuple[List[str], List[int], int, int]:
        """Serve one shared-memory ring request (shmring.ShmRequest)
        through the SAME searches HTTP requests take — admission,
        deadline routing, the planner, and the owner's read cache all
        apply — at their id-level depth, returning (ids, t_end ns per
        id, class generation, response flags).  A slot carries ids and
        end times, never records, and the worker that asked assembles
        the records from its own replica: so the owner copies no
        record and reads each id's end time once.  The flags carry
        RESP_F_MESH_SERVED when the answer came from the bounded-stale
        mesh replica: the leader refuses to populate its own cache
        from such answers (_cached_ids), and the requesting worker
        must refuse too.

        Visibility is pinned to the WORKER's `now`: the request's
        clock instant rides the txn-time thread-local, so the answer
        is bit-identical to what the worker's own fresh path would
        have computed at that instant (expiry included).  The
        backwards-clock guards in the read cache already handle
        out-of-order nows across workers — this is the same contract
        as a txn-pinned precheck behind live pollers."""
        from dss_tpu.clock import from_nanos

        cls = req.cls
        cells = canonical_cells(req.cells)
        sub = self.rid if cls in ("isa", "rid_sub") else self.scd
        t0 = None if req.t0_ns is None else from_nanos(req.t0_ns)
        t1 = None if req.t1_ns is None else from_nanos(req.t1_ns)
        tl = sub._txn_time
        pinned = getattr(tl, "now", None) is None
        if pinned:
            tl.now = int(req.now_ns)
        rcache.take_device_served()  # a note no serve of this thread took
        try:
            if cls == "isa":
                ids, t1s = sub.search_isa_ids(
                    cells, t0, t1, allow_stale=req.allow_stale
                )
            elif cls in ("rid_sub", "scd_sub"):
                ids, t1s = sub.search_subscription_ids(
                    cells, req.owner or None
                )
            elif cls == "op":
                ids, t1s = sub.search_operation_ids(
                    cells, req.alt_lo, req.alt_hi, t0, t1,
                    allow_stale=req.allow_stale,
                )
            elif cls == "constraint":
                ids, t1s = sub.search_constraint_ids(
                    cells, req.alt_lo, req.alt_hi, t0, t1,
                    allow_stale=req.allow_stale,
                )
            else:
                raise errors.bad_request(f"unknown shm class {cls!r}")
        finally:
            if pinned:
                tl.now = None
        gen = self._class_index(cls).cell_clock.generation
        return ids, t1s, gen, self._shm_resp_flags()

    @staticmethod
    def _shm_resp_flags() -> int:
        from dss_tpu.parallel import shmring

        return (
            shmring.RESP_F_MESH_SERVED
            if rcache.take_last_search_meshed() else 0
        ) | (
            shmring.RESP_F_DEVICE_SERVED
            if rcache.take_device_served() else 0
        )

    def attach_shm_front(self, region, *, threads: int = None,
                         worker_ttl_s: float = 5.0, write_fn=None):
        """Make this store the device owner of a shared-memory serving
        front: every entity class's cell clock broadcasts its bumps
        into the region's fence segment, and a ShmOwner drain serves
        ring requests through shm_serve (and mutations through
        `write_fn`, api/app.py make_ring_write_fn).  Returns the started
        owner (the caller — cmds/server.py — reclaims dead workers'
        slots via owner.reclaim_worker)."""
        from dss_tpu.parallel import shmring

        if self._shm_owner is not None:
            raise RuntimeError("shm front already attached")
        for idx, cls in enumerate(shmring.SHM_CLASSES):
            self._class_index(cls).cell_clock.attach_mirror(
                shmring.FenceMirror(region, idx)
            )
        owner = shmring.ShmOwner(
            region, self.shm_serve, threads=threads,
            wal_seq_fn=lambda: self.wal.seq,
            worker_ttl_s=worker_ttl_s, write_fn=write_fn,
        )
        owner.start()
        self._shm_owner = owner
        return owner

    def attach_federation(self, router) -> None:
        """Put the multi-region FederationRouter in front of the
        store: binds the UNWRAPPED sub-stores for peer-facing serving
        (a remote's query must never recurse through the federation
        layer), wires the degradation ladder (remote-unreachable ->
        FEDERATION_DEGRADED, recovery re-syncs the follower tail
        before re-admission), swaps self.rid/self.scd for the
        federated wrappers (searches federate, cells-carrying writes
        are ownership-guarded), and starts the mirror sync loop.
        Call BEFORE building services — they must see the wrappers."""
        from dss_tpu.region import federation as fedmod

        if self.federation is not None:
            raise RuntimeError("federation already attached")
        epoch_fn = None
        if self.region is not None:
            epoch_fn = self._region_client.current_epoch
        router.bind_local(
            self.rid, self.scd, epoch_fn=epoch_fn,
            wall_clock=self.clock,
        )
        router.set_health(self.health)
        self.federation = router
        self.rid = fedmod.FederatedRIDStore(self.rid, router)
        self.scd = fedmod.FederatedSCDStore(self.scd, router)
        router.start()

    def attach_push(self, pipeline) -> None:
        """Wire the reverse-query push pipeline (push/pipeline.py)
        onto the write path: subscription-match lookups route through
        the pipeline's MatchStages (planner rqmatch candidate -> fused
        device kernel, host oracle fallback — bit-identical either
        way), matched writes fan out through the WAL-backed delivery
        queue, and the delivery workers start.  The sub-store hooks go
        on the UNWRAPPED impls so federated wrappers keep delegating;
        ladder edges (PUSH_DEGRADED) ride the pipeline's own health
        hook.  Safe under federation in either attach order."""
        if self.push is not None:
            raise RuntimeError("push pipeline already attached")
        pipeline.bind_store(self)
        getattr(self.rid, "_local", self.rid).set_push(pipeline)
        getattr(self.scd, "_local", self.scd).set_push(pipeline)
        self.push = pipeline

    def attach_mesh_replica(self, replica, min_batch: int = 64) -> None:
        """Route oversized bounded-staleness search batches from each
        entity class's coalescer to the multi-chip replica when it is
        fresh (VERDICT r4 #4).  Only queries flagged allow_stale (the
        service SEARCH paths) are eligible; conflict prechecks and
        transactional reads always serve locally."""
        pairs = [
            (self.rid._isa_index, "isas"),
            (self.rid._sub_index, "rid_subs"),
            (self.scd._op_index, "ops"),
            (self.scd._sub_index, "scd_subs"),
            (self.scd._cst_index, "constraints"),
        ]
        for index, cls in pairs:
            co = getattr(index, "coalescer", None)
            if co is None:
                continue  # memory backend: no coalescer tier

            def make(cls):
                def fn(keys_list, alo, ahi, ts, te, now_arr):
                    return replica.query_batch(
                        keys_list, alo, ahi, ts, te, now=now_arr, cls=cls
                    )

                return fn

            def bgen_fn(_r=replica):
                # plans record the shard placement generation they
                # were decided against (MultihostReplica wraps the
                # inner ShardedReplica that owns the boundary map)
                inner = getattr(_r, "_inner", _r)
                return getattr(inner, "boundary_gen", 0)

            co.set_mesh_delegate(
                make(cls), replica.fresh, min_batch=min_batch,
                bgen_fn=bgen_fn,
            )
        # one load map: coalescer-served AND replica-served traffic
        # accumulate into the store's RangeLoad, which the replica's
        # rebalancer plans from at fold boundaries
        use_load = getattr(replica, "use_load", None)
        if use_load is not None:
            use_load(self.range_load)

    def close(self):
        if self.push is not None:
            self.push.close()
        if self._shm_owner is not None:
            self._shm_owner.close()
        if self.federation is not None:
            self.federation.close()
        if self.region is not None:
            self.region.close()
        for index in (
            self.rid._isa_index, self.rid._sub_index,
            self.scd._op_index, self.scd._sub_index,
            self.scd._cst_index,
        ):
            closer = getattr(index, "close", None)
            if closer is not None:
                closer()
        self.wal.close()

    def stats(self) -> dict:
        """Per-index gauges for /metrics (dss_dar_* names)."""
        out = {}
        for name, stats in (
            ("isa", self.rid.index_stats),
            ("rid_sub", self.rid.sub_index_stats),
            ("op", self.scd.index_stats),
            ("scd_sub", self.scd.sub_index_stats),
            ("constraint", self.scd.cst_index_stats),
        ):
            for k, v in stats().items():
                out[f"dss_dar_{name}_{k}"] = v
        # store-wide read-cache gauges (stable key set whether the
        # cache is enabled or not — dashboards expect the series)
        for k, v in self.cache.stats().items():
            out[f"dss_cache_{k}"] = v
        # search answers' records by whether this process remembered
        # their wire bytes or encoded them (a --workers front counts
        # in the shared block instead: dss_shm_worker_wire_memo_*)
        out["dss_wire_memo_hits"] = (
            self.rid._wire_memo_hits + self.scd._wire_memo_hits
        )
        out["dss_wire_memo_misses"] = (
            self.rid._wire_memo_misses + self.scd._wire_memo_misses
        )
        # the SCD write path's subscription leg: writes that found
        # subscribers, and the subscribers they bumped and returned
        scd = getattr(self.scd, "_local", self.scd)
        out["dss_scd_notifying_writes_total"] = scd._notifying_writes
        out["dss_scd_subscribers_notified_total"] = scd._subs_notified
        # the journal since the process started (dar/wal.py): records,
        # bytes, fsyncs, seconds of the append and of the fsync alone;
        # all zero in region mode, where the region server journals
        out.update(self.wal.stats())
        # the boot: records of the log, and seconds by stage (a boot
        # that fell back to the loop reports the records alone)
        out["dss_boot_records"] = self.boot_stats.get("records", 0)
        out["dss_boot_seconds"] = {
            stage: self.boot_stats.get(f"{stage}_s", 0.0)
            for stage in ("parse", "build")
        }
        # per-key-range load accounting (the skew-aware rebalancer's
        # measurement input)
        for k, v in self.range_load.stats().items():
            out[f"dss_{k}"] = v
        # degradation ladder + fault-injection + breaker gauges: the
        # key set is stable on every deployment (dict-valued entries
        # render as labeled families — dss_breaker_state{remote},
        # dss_fault_injected_total{site})
        out.update(self.health.stats())
        out["dss_fault_injected_total"] = (
            chaos.registry().injected_by_site()
        )
        breakers = {}
        if self.region is not None:
            fn = getattr(self._region_client, "breaker_states", None)
            if fn is not None:
                breakers = fn()
        out["dss_breaker_state"] = breakers
        # federation gauges: the stable key set whether or not a
        # router is attached (dss_fed_peer_state/mirror_lag_s render
        # as labeled families keyed by region)
        from dss_tpu.region import federation as _fedmod

        if self.federation is not None:
            out.update(self.federation.stats())
        else:
            out.update(_fedmod.empty_stats())
        # shared-memory front gauges: same stable-key-set discipline
        # (per-worker counters render as dss_shm_worker_*{process})
        from dss_tpu.parallel import shmring as _shmmod

        if self._shm_owner is not None:
            out.update(self._shm_owner.stats())
        else:
            out.update(_shmmod.empty_stats())
        # push-pipeline gauges: stable key set whether or not the
        # pipeline is attached (dss_push_breaker_state renders as a
        # labeled family keyed by uss)
        from dss_tpu import push as _pushmod

        if self.push is not None:
            out.update(self.push.stats())
        else:
            out.update(_pushmod.empty_stats())
        # trace recorder gauges (obs/trace.py): sampling config, kept/
        # dropped counters, ring depth, and the allocation counter the
        # zero-cost-when-disabled contract is asserted against
        out.update(trace.stats())
        if self.storage == "tpu":
            # XLA compiles of this process (count, seconds, persistent
            # cache hits) — after boot warm each one is a compile on a
            # request or fold path
            from dss_tpu.ops import compile_stats, device_memory_stats

            out.update(compile_stats())
            # the allocator's bytes in use and peak, read at scrape
            # time; series only where the backend reports them (the
            # leader's chip — a worker's CPU backend reports none)
            out.update(device_memory_stats())
        if self.region is not None:
            out.update(self.region.stats())
        return out

    def freshness_status(self) -> dict:
        """Operator view of the version-fence state (GET /status):
        region epoch, per-class write generation + cell-clock
        high-water mark, and the cache counters — enough to verify
        fence behaviour without reading code."""
        classes = {}
        for name, index in (
            ("isa", self.rid._isa_index),
            ("rid_sub", self.rid._sub_index),
            ("op", self.scd._op_index),
            ("scd_sub", self.scd._sub_index),
            ("constraint", self.scd._cst_index),
        ):
            clock = getattr(index, "cell_clock", None)
            classes[name] = {
                "generation": 0 if clock is None else clock.generation,
                "cell_clock_high_water": (
                    0 if clock is None else clock.high_water
                ),
                "live_records": index.stats().get("live_records", 0),
            }
        epoch = ""
        if self.region is not None:
            epoch = self._region_client.current_epoch()
        return {
            "storage": self.storage,
            "epoch": epoch,
            "cache": self.cache.stats(),
            "classes": classes,
            # the degradation ladder's operator view: current mode +
            # every active condition with its age and reason
            "degraded_mode": self.health.mode_name(),
            "degraded": self.health.active(),
            # multi-region view: local region id, peer breaker states,
            # mirror lags — the partition drill's observability seam
            "federation": (
                None if self.federation is None
                else self.federation.status()
            ),
            # push-pipeline view: queue depth/lag, breaker states,
            # parked count — the delivery-backlog runbook's first stop
            "push": None if self.push is None else self.push.status(),
        }
