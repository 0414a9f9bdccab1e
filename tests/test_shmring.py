"""Shared-memory serving front (parallel/shmring.py + dar/shmfront.py
+ plan/shmroute.py): slot codecs, the seqlock state machine, the fence
broadcast's NO-TTL rules, the worker-vs-leader bit-identity contract,
and the never-block/never-5xx fallback ladder.

The correctness story under test:
  - a worker-served search is BIT-IDENTICAL to the leader-served
    search at the same state, across folds, major compactions,
    tombstones, and owner scoping (the differential harness);
  - a worker cache hit NEVER crosses a stale fence — the owner's
    broadcast applies the exact rules of dar/readcache.py (epoch /
    incarnation / covering-cell advance / wholesale floor), and a
    faulted broadcast POISONS the fence (over-invalidation) instead
    of dropping the bump;
  - the hot path performs ZERO per-request JSON/pickle between worker
    and owner (counted, not assumed);
  - every failure arm (ring full, owner dead, oversized payload,
    injected enqueue fault) degrades to ShmFallback — the loopback
    proxy — never a block, never an error;
  - read-your-writes: a search right after a leader write never
    serves a pre-write answer (the response's WAL seq bounds a
    replica-catchup wait).
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import subprocess
import sys
import threading
import time
import uuid
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

from dss_tpu import chaos, errors
from dss_tpu.clock import FakeClock, to_nanos
from dss_tpu.dar import readcache as rcache
from dss_tpu.dar.dss_store import DSSStore
from dss_tpu.dar.follower import WalFollower
from dss_tpu.dar.shmfront import (
    ShmFallback,
    ShmRIDStore,
    ShmSCDStore,
    ShmSearchFront,
)
from dss_tpu.dar.tiers import CellClock
from dss_tpu.geo.s2cell import dar_key_to_cell
from dss_tpu.models import rid as ridm
from dss_tpu.models import scd as scdm
from dss_tpu.parallel import shmring
from dss_tpu.plan import shmroute

T0 = datetime(2026, 1, 1, tzinfo=timezone.utc)


@pytest.fixture(autouse=True)
def _clean_faults():
    chaos.clear_plan()
    chaos.registry().reset_counters()
    yield
    chaos.clear_plan()
    chaos.registry().reset_counters()


def _uuid(i: int) -> str:
    return str(uuid.UUID(int=i, version=4))


def _cells(lo: int, hi: int) -> np.ndarray:
    return dar_key_to_cell(np.arange(lo, hi, dtype=np.int64))


def _isa(i: int, cells, *, start=None, end=None, owner="u1"):
    return ridm.IdentificationServiceArea(
        id=_uuid(i),
        owner=owner,
        url="https://uss.example/f",
        cells=np.asarray(cells, np.uint64),
        start_time=start or T0,
        end_time=end or (T0 + timedelta(hours=12)),
        altitude_lo=0.0,
        altitude_hi=3000.0,
    )


def _op(i: int, cells, *, alt=(0.0, 120.0), owner="u1", sub_id=""):
    return scdm.Operation(
        id=_uuid(i),
        owner=owner,
        start_time=T0,
        end_time=T0 + timedelta(hours=8),
        altitude_lower=alt[0],
        altitude_upper=alt[1],
        uss_base_url="https://uss.example",
        state="Accepted",
        cells=np.asarray(cells, np.uint64),
        subscription_id=sub_id or _uuid(9000 + i),
    )


def _cst(i: int, cells, *, owner="u1"):
    return scdm.Constraint(
        id=_uuid(i),
        owner=owner,
        start_time=T0,
        end_time=T0 + timedelta(hours=8),
        altitude_lower=0.0,
        altitude_upper=500.0,
        uss_base_url="https://uss.example",
        cells=np.asarray(cells, np.uint64),
    )


def _scd_sub(i: int, cells, *, owner="u1"):
    return scdm.Subscription(
        id=_uuid(i),
        owner=owner,
        start_time=T0,
        end_time=T0 + timedelta(hours=8),
        altitude_lo=0.0,
        altitude_hi=500.0,
        base_url="https://uss.example",
        notify_for_operations=True,
        cells=np.asarray(cells, np.uint64),
    )


def _sig(rec) -> tuple:
    """A record's identity-relevant fields (np cells excluded: dict
    replicas replay them through the codec, array dtype may differ)."""
    out = [rec.id, rec.owner, getattr(rec, "version", None)]
    for f in ("start_time", "end_time"):
        v = getattr(rec, f, None)
        out.append(None if v is None else to_nanos(v))
    return tuple(out)


def _sigs(recs) -> list:
    return sorted(_sig(r) for r in recs)


# ---------------------------------------------------------------------------
# region geometry + slot codecs
# ---------------------------------------------------------------------------


def test_region_create_open_and_header(tmp_path):
    p = str(tmp_path / "r.shm")
    r = shmring.ShmRegion.create(
        p, nworkers=3, depth=8, slot_bytes=8192, fence_slots=1 << 10
    )
    try:
        r2 = shmring.ShmRegion.open_existing(p)
        assert (r2.nworkers, r2.depth, r2.slot_bytes, r2.fence_slots) == (
            3, 8, 8192, 1 << 10,
        )
        assert r2.nclasses == len(shmring.SHM_CLASSES)
        assert r.epoch_token == r2.epoch_token == 0
        r.bump_epoch_token()
        assert r2.epoch_token == 1  # shared pages, not copies
        assert r2.owner_heartbeat_age_s() < 2.0
        r2.close()
    finally:
        r.close()


def test_open_rejects_bad_magic_and_version(tmp_path):
    p = str(tmp_path / "junk.shm")
    with open(p, "wb") as fh:
        fh.write(b"\0" * 65536)
    with pytest.raises(ValueError, match="not a DSS shm region"):
        shmring.ShmRegion.open_existing(p)
    r = shmring.ShmRegion.create(p, nworkers=1, depth=4)
    r.close()
    import struct as _struct

    with open(p, "r+b") as fh:
        fh.seek(8)
        fh.write(_struct.pack("<I", shmring.VERSION + 1))
    with pytest.raises(ValueError, match="region format"):
        shmring.ShmRegion.open_existing(p)


def test_create_validates_geometry(tmp_path):
    with pytest.raises(ValueError, match="power of two"):
        shmring.ShmRegion.create(
            str(tmp_path / "a.shm"), nworkers=1, fence_slots=1000
        )
    with pytest.raises(ValueError, match="slot_bytes"):
        shmring.ShmRegion.create(
            str(tmp_path / "b.shm"), nworkers=1, slot_bytes=100
        )


def test_request_codec_roundtrip_all_fields(tmp_path):
    r = shmring.ShmRegion.create(
        str(tmp_path / "r.shm"), nworkers=2, depth=4
    )
    try:
        cells = np.asarray([5, 7, 1 << 60], np.uint64)
        r.write_request(
            1, 2, 42, cls_idx=shmring.SHM_CLASSES.index("op"),
            cells=cells, alt_lo=10.5, alt_hi=99.25, t0_ns=123,
            t1_ns=456, now_ns=789, deadline_ns=1000,
            owner="owner-x", allow_stale=True,
        )
        assert r.slot_state(1, 2) == shmring.REQ
        req = r.read_request(1, 2)
        assert req.cls == "op" and req.req_id == 42
        assert np.array_equal(req.cells, cells)
        assert (req.alt_lo, req.alt_hi) == (10.5, 99.25)
        assert (req.t0_ns, req.t1_ns, req.now_ns) == (123, 456, 789)
        assert req.deadline_ns == 1000
        assert req.owner == "owner-x" and req.allow_stale
        assert (req.worker, req.slot) == (1, 2)
    finally:
        r.close()


def test_request_codec_none_fields(tmp_path):
    r = shmring.ShmRegion.create(
        str(tmp_path / "r.shm"), nworkers=1, depth=4
    )
    try:
        r.write_request(
            0, 0, 1, cls_idx=0, cells=np.zeros(0, np.uint64),
            alt_lo=None, alt_hi=None, t0_ns=None, t1_ns=None,
            now_ns=5, deadline_ns=0, owner="", allow_stale=False,
        )
        req = r.read_request(0, 0)
        assert req.cls == "isa" and len(req.cells) == 0
        assert req.alt_lo is None and req.alt_hi is None
        assert req.t0_ns is None and req.t1_ns is None
        assert req.owner is None and not req.allow_stale
    finally:
        r.close()


def test_response_codec_roundtrip_and_overflow(tmp_path):
    r = shmring.ShmRegion.create(
        str(tmp_path / "r.shm"), nworkers=1, depth=4, slot_bytes=4096
    )
    try:
        ids = [_uuid(i) for i in range(5)]
        t1s = [10, 20, 30, 40, 50]
        r.write_response(
            0, 0, status=shmring.ST_OK, ids=ids, t1s=t1s,
            wal_seq=77, gen=9, retry_after_s=1.5,
        )
        assert r.slot_state(0, 0) == shmring.RESP
        resp = r.read_response(0, 0)
        assert resp.status == shmring.ST_OK
        assert resp.ids == ids
        assert resp.t1s.tolist() == t1s
        assert (resp.wal_seq, resp.gen) == (77, 9)
        assert resp.retry_after_s == 1.5
        # an answer too large for the slot publishes ST_OVERFLOW
        # (the worker re-asks over the loopback proxy)
        big = [_uuid(i) for i in range(200)]
        r.write_response(
            0, 1, status=shmring.ST_OK, ids=big,
            t1s=list(range(200)),
        )
        resp = r.read_response(0, 1)
        assert resp.status == shmring.ST_OVERFLOW
        assert resp.ids == [] and len(resp.t1s) == 0
    finally:
        r.close()


def test_oversized_request_raises(tmp_path):
    r = shmring.ShmRegion.create(
        str(tmp_path / "r.shm"), nworkers=1, depth=4, slot_bytes=4096
    )
    try:
        too_many = np.arange(4096, dtype=np.uint64)
        with pytest.raises(shmring.RingOversize, match="cells"):
            r.write_request(
                0, 0, 1, cls_idx=0, cells=too_many, alt_lo=None,
                alt_hi=None, t0_ns=None, t1_ns=None, now_ns=0,
                deadline_ns=0, owner="", allow_stale=False,
            )
        with pytest.raises(shmring.RingOversize, match="owner"):
            r.write_request(
                0, 0, 1, cls_idx=0, cells=np.zeros(1, np.uint64),
                alt_lo=None, alt_hi=None, t0_ns=None, t1_ns=None,
                now_ns=0, deadline_ns=0, owner="x" * 200,
                allow_stale=False,
            )
        assert r.slot_state(0, 0) == shmring.FREE  # nothing published
    finally:
        r.close()


# ---------------------------------------------------------------------------
# fence segment: broadcast + worker-side read
# ---------------------------------------------------------------------------


def test_fence_stamp_read_and_floor(tmp_path):
    r = shmring.ShmRegion.create(
        str(tmp_path / "r.shm"), nworkers=1, fence_slots=1 << 10
    )
    try:
        c = shmring.SHM_CLASSES.index("isa")
        r.fence_write_meta(c, inc=4, gen=0, floor=0, high=0)
        inc, m, gen, floor = r.fence_read(c, np.asarray([3, 9], np.int64))
        assert (inc, m, gen, floor) == (4, 0, 0, 0)
        r.fence_stamp(c, np.asarray([3], np.int64), 7)
        inc, m, gen, _ = r.fence_read(c, np.asarray([3, 9], np.int64))
        assert (inc, m, gen) == (4, 7, 7)
        # disjoint keys: stamp does not move
        _, m2, _, _ = r.fence_read(c, np.asarray([9], np.int64))
        assert m2 == 0
        # poison: floor jumps past the generation — every entry fails
        r.fence_poison(c)
        _, m3, gen3, floor3 = r.fence_read(c, np.asarray([9], np.int64))
        assert floor3 == gen3 == 8 and m3 >= 8
    finally:
        r.close()


def test_fence_mirror_rides_cell_clock(tmp_path):
    r = shmring.ShmRegion.create(str(tmp_path / "r.shm"), nworkers=1)
    try:
        clock = CellClock()
        clock.bump(np.asarray([5], np.int64))  # pre-attach history
        c = shmring.SHM_CLASSES.index("op")
        clock.attach_mirror(shmring.FenceMirror(r, c))
        view = shmring.WorkerFenceView(r)
        inc, m, gen, floor = view.fence("op", np.asarray([5], np.int64))
        assert inc == clock.incarnation and gen == 1
        # attach-time sync publishes the high-water as a conservative
        # stamp via meta, not per-key stamps; a bump after attach
        # scatters exactly
        clock.bump(np.asarray([11], np.int64))
        _, m2, gen2, _ = view.fence("op", np.asarray([11], np.int64))
        assert gen2 == 2 and m2 == 2
        _, m3, _, _ = view.fence("op", np.asarray([12345], np.int64))
        assert m3 <= 1  # untouched key (modulo hash collisions: none here)
        # wholesale: floor jumps with the generation
        clock.bump_all()
        _, m4, gen4, floor4 = view.fence("op", np.asarray([12345], np.int64))
        assert floor4 == gen4 == 3 and m4 >= 3
    finally:
        r.close()


def test_faulted_broadcast_poisons_not_drops(tmp_path):
    r = shmring.ShmRegion.create(str(tmp_path / "r.shm"), nworkers=1)
    try:
        clock = CellClock()
        c = shmring.SHM_CLASSES.index("isa")
        clock.attach_mirror(shmring.FenceMirror(r, c))
        view = shmring.WorkerFenceView(r)
        chaos.install_plan(
            {"events": [{"site": "shm.fence.broadcast", "count": 1}]}
        )
        clock.bump(np.asarray([42], np.int64))  # broadcast faulted
        # the bump did NOT reach slot 42's stamp — but the poisoned
        # floor fails EVERY fence, so no worker can serve across it
        _, m, gen, floor = view.fence("isa", np.asarray([999], np.int64))
        assert floor >= gen >= 1 and m >= floor
        assert chaos.registry().injected_by_site() == {
            "shm.fence.broadcast": 1
        }
    finally:
        r.close()


# ---------------------------------------------------------------------------
# worker stats blocks + owner aggregation
# ---------------------------------------------------------------------------


def test_worker_stats_single_writer_and_owner_aggregate(tmp_path):
    r = shmring.ShmRegion.create(str(tmp_path / "r.shm"), nworkers=2)
    try:
        r.stat_add(0, shmring.WS_ENQUEUED, 3)
        r.stat_add(1, shmring.WS_RING_FULL, 2)
        r.stat_set(0, shmring.WS_HEARTBEAT_NS, time.time_ns())
        ws0 = r.worker_stats(0)
        assert ws0["enqueued"] == 3 and ws0["ring_full"] == 0
        assert 0 <= ws0["heartbeat_age_s"] < 5
        assert r.worker_stats(1)["ring_full"] == 2
        owner = shmring.ShmOwner(r, lambda req: ([], [], 0))
        st = owner.stats()
        assert st["dss_shm_workers"] == 2
        assert st["dss_shm_worker_enqueued"] == {
            "worker-0": 3, "worker-1": 0,
        }
        assert st["dss_shm_ring_full_total"] == 2
        assert st["dss_shm_saturation"] == 0.0
        # the empty-stats key set matches the live key set (dashboards
        # never miss a series when no front is attached)
        assert set(shmring.empty_stats()) == set(st)
    finally:
        r.close()


# ---------------------------------------------------------------------------
# owner <-> worker round trips (in-process, two mappings of one file)
# ---------------------------------------------------------------------------


def _owner_region_pair(tmp_path, serve_fn, *, depth=8, nworkers=1,
                       wal_seq_fn=None, threads=2):
    path = str(tmp_path / "ring.shm")
    r_owner = shmring.ShmRegion.create(
        path, nworkers=nworkers, depth=depth
    )
    owner = shmring.ShmOwner(
        r_owner, serve_fn, threads=threads, wal_seq_fn=wal_seq_fn
    )
    owner.start()
    r_worker = shmring.ShmRegion.open_existing(path)
    return r_owner, owner, r_worker


def test_roundtrip_ok_overloaded_deadline(tmp_path):
    calls = []

    def serve(req):
        calls.append(req.cls)
        if req.owner == "overload-me":
            raise errors.OverloadedError("queue full", retry_after_s=3.5)
        return ["id-a", "id-b"], [111, 222], 5

    r_o, owner, r_w = _owner_region_pair(
        tmp_path, serve, wal_seq_fn=lambda: 99
    )
    client = shmring.ShmWorkerClient(r_w, 0, wait_s=5.0)
    try:
        resp = client.call(
            cls="isa", cells=np.asarray([1, 2], np.uint64),
            now_ns=to_nanos(T0),
        )
        assert resp.status == shmring.ST_OK
        assert resp.ids == ["id-a", "id-b"]
        assert resp.t1s.tolist() == [111, 222]
        assert (resp.wal_seq, resp.gen) == (99, 5)
        # owner admission verdict rides the slot: 429 + Retry-After
        resp = client.call(
            cls="isa", cells=np.asarray([1], np.uint64),
            now_ns=0, owner="overload-me",
        )
        assert resp.status == shmring.ST_OVERLOADED
        assert resp.retry_after_s == 3.5
        # pre-expired deadline: dropped at the owner without serving
        r_w.write_request(
            0, 7, 123, cls_idx=0, cells=np.zeros(0, np.uint64),
            alt_lo=None, alt_hi=None, t0_ns=None, t1_ns=None,
            now_ns=0, deadline_ns=1,  # long past
            owner="", allow_stale=False,
        )
        deadline = time.monotonic() + 5
        while (
            r_w.slot_state(0, 7) != shmring.RESP
            and time.monotonic() < deadline
        ):
            time.sleep(0.001)
        assert r_w.read_response(0, 7).status == shmring.ST_DEADLINE
        st = owner.stats()
        # served counts SUCCESSFUL serves only — the overload and the
        # deadline drop have their own counters and must not inflate
        # the drain rate an operator reads during saturation
        assert st["dss_shm_served_total"] == 1
        assert st["dss_shm_overloaded_total"] == 1
        assert st["dss_shm_deadline_drops_total"] == 1
        assert calls == ["isa", "isa"]  # the dropped one never served
    finally:
        client.close()
        owner.close()
        r_w.close()
        r_o.close()


def test_serve_exception_publishes_error_not_wedge(tmp_path):
    def serve(req):
        raise RuntimeError("boom")

    r_o, owner, r_w = _owner_region_pair(tmp_path, serve)
    client = shmring.ShmWorkerClient(r_w, 0, wait_s=5.0)
    try:
        resp = client.call(
            cls="isa", cells=np.asarray([1], np.uint64), now_ns=0
        )
        assert resp.status == shmring.ST_ERROR
        assert owner.stats()["dss_shm_errors_total"] == 1
        # the pool survived: a good request still serves
        owner._serve_fn = lambda req: (["ok"], [1], 0)
        resp = client.call(
            cls="isa", cells=np.asarray([1], np.uint64), now_ns=0
        )
        assert resp.status == shmring.ST_OK and resp.ids == ["ok"]
    finally:
        client.close()
        owner.close()
        r_w.close()
        r_o.close()


def test_concurrent_callers_share_one_ring(tmp_path):
    def serve(req):
        return [f"{req.cls}-{int(req.cells[0])}"], [1], 0

    r_o, owner, r_w = _owner_region_pair(tmp_path, serve, depth=16)
    client = shmring.ShmWorkerClient(r_w, 0, wait_s=10.0)
    out = {}
    errs = []

    def one(i):
        try:
            resp = client.call(
                cls="op", cells=np.asarray([i], np.uint64), now_ns=0
            )
            out[i] = resp.ids
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    try:
        ths = [
            threading.Thread(target=one, args=(i,)) for i in range(32)
        ]
        for t in ths:
            t.start()
        for t in ths:
            t.join(timeout=30)
        assert not errs
        assert out == {i: [f"op-{i}"] for i in range(32)}
        assert client.in_flight() == 0  # every slot returned
        # the drain threads' counts lose no update (one id an answer)
        assert owner.stats()["dss_shm_answer_ids_total"] == 32
    finally:
        client.close()
        owner.close()
        r_w.close()
        r_o.close()


def test_ring_timeout_abandons_then_reclaims_slot(tmp_path):
    release = threading.Event()

    def serve(req):
        release.wait(10)
        return ["late"], [1], 0

    r_o, owner, r_w = _owner_region_pair(tmp_path, serve, depth=4,
                                         threads=1)
    client = shmring.ShmWorkerClient(r_w, 0, wait_s=0.05)
    try:
        with pytest.raises(shmring.RingTimeout):
            client.call(
                cls="isa", cells=np.asarray([1], np.uint64), now_ns=0
            )
        assert client.in_flight() == 1  # abandoned, owner owns it
        assert client.stats()["timeouts"] == 1
        release.set()
        # once the owner publishes RESP the allocator sweep frees it
        deadline = time.monotonic() + 5
        while client.in_flight() and time.monotonic() < deadline:
            client._alloc_lock.acquire()
            client._alloc_lock.release()
            try:
                s = client._alloc()
                client._release(s)
            except shmring.RingFull:
                pass
            time.sleep(0.01)
        assert client.in_flight() == 0
    finally:
        client.close()
        owner.close()
        r_w.close()
        r_o.close()


def test_ring_full_raises_immediately(tmp_path):
    # no owner running: every call times out and abandons its slot;
    # once all slots are abandoned the next call fails FAST with
    # RingFull (the proxy-fallback trigger), never blocking
    r = shmring.ShmRegion.create(
        str(tmp_path / "r.shm"), nworkers=1, depth=2
    )
    client = shmring.ShmWorkerClient(r, 0, wait_s=0.02)
    try:
        for _ in range(2):
            with pytest.raises(shmring.RingTimeout):
                client.call(
                    cls="isa", cells=np.asarray([1], np.uint64),
                    now_ns=0,
                )
        t0 = time.perf_counter()
        with pytest.raises(shmring.RingFull):
            client.call(
                cls="isa", cells=np.asarray([1], np.uint64), now_ns=0
            )
        assert time.perf_counter() - t0 < 0.5
        assert client.stats()["ring_full"] == 1
    finally:
        client.close()
        r.close()


def test_reclaim_dead_worker_slots(tmp_path):
    r = shmring.ShmRegion.create(
        str(tmp_path / "r.shm"), nworkers=2, depth=4
    )
    try:
        for w, s in [(0, 0), (1, 0), (1, 2)]:
            r.write_request(
                w, s, 1, cls_idx=0, cells=np.zeros(0, np.uint64),
                alt_lo=None, alt_hi=None, t0_ns=None, t1_ns=None,
                now_ns=0, deadline_ns=0, owner="", allow_stale=False,
            )
        owner = shmring.ShmOwner(r, lambda req: ([], [], 0))
        freed = owner.reclaim_worker(1)
        assert freed == 2
        assert r.slot_state(1, 0) == shmring.FREE
        assert r.slot_state(1, 2) == shmring.FREE
        assert r.slot_state(0, 0) == shmring.REQ  # survivor untouched
        assert owner.stats()["dss_shm_reclaimed_total"] == 2
        # a dead worker's NEW requests are swept, a survivor's served
        r.write_request(
            1, 3, 2, cls_idx=0, cells=np.zeros(0, np.uint64),
            alt_lo=None, alt_hi=None, t0_ns=None, t1_ns=None,
            now_ns=0, deadline_ns=0, owner="", allow_stale=False,
        )
        owner.start()
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if (
                r.slot_state(1, 3) == shmring.FREE
                and r.slot_state(0, 0) == shmring.RESP
            ):
                break
            time.sleep(0.005)
        assert r.slot_state(1, 3) == shmring.FREE
        assert r.slot_state(0, 0) == shmring.RESP
        owner.close()
    finally:
        r.close()


def test_ttl_reclaimed_live_worker_revives_and_recovers_slots(tmp_path):
    # The stall scenario: a worker declared dead by the heartbeat TTL
    # while its process is actually alive.  The owner frees its REQ
    # slots to FREE; the worker's allocator sweep must take those
    # back (not just RESP slots), and the owner must REVIVE the worker
    # on the first heartbeat stamped after death was declared — else
    # the ring is permanently lost to that worker.
    r = shmring.ShmRegion.create(
        str(tmp_path / "r.shm"), nworkers=1, depth=2
    )
    client = shmring.ShmWorkerClient(
        r, 0, wait_s=0.05, heartbeat_s=0.05
    )
    owner = shmring.ShmOwner(r, lambda req: (["a"], [1], 0))
    try:
        # no serving yet: the call times out and abandons its slot (REQ)
        with pytest.raises(shmring.RingTimeout):
            client.call(
                cls="isa", cells=np.asarray([1], np.uint64), now_ns=0
            )
        assert client.in_flight() == 1
        owner.reclaim_worker(0)  # the TTL scan's decision, forced
        assert owner.stats()["dss_shm_dead_workers"] == 1
        assert r.slot_state(0, r.depth - 1) == shmring.FREE
        # worker side: the sweep recovers the owner-freed slot
        deadline = time.monotonic() + 5
        while client.in_flight() and time.monotonic() < deadline:
            try:
                s = client._alloc()
                client._release(s)
            except shmring.RingFull:
                pass
            time.sleep(0.01)
        assert client.in_flight() == 0
        # owner side: the live client's heartbeat thread writes a
        # stamp newer than the death declaration -> scan revives
        owner.start()
        deadline = time.monotonic() + 5
        while (
            owner.stats()["dss_shm_dead_workers"]
            and time.monotonic() < deadline
        ):
            time.sleep(0.02)
        assert owner.stats()["dss_shm_dead_workers"] == 0
        # and the revived worker round-trips again
        resp = client.call(
            cls="isa", cells=np.asarray([1], np.uint64), now_ns=0
        )
        assert resp.ids == ["a"]
    finally:
        client.close()
        owner.close()
        r.close()


def test_respawned_client_never_reuses_inflight_slot(tmp_path):
    # The respawn race: a worker dies while one of its requests is
    # BUSY in the owner (a slow serve).  reclaim_worker leaves BUSY
    # slots alone, and a respawned incarnation starts with a full
    # local free list — if its allocator handed that slot out, the
    # old serve's response would answer the NEW query.  The allocator
    # must skip slots the shared state says are not FREE.
    release = threading.Event()

    def serve(req):
        if req.owner == "slow":
            release.wait(10)
            return ["old-answer"], [1], 0
        return ["new-answer"], [2], 0

    r_o, owner, r_w = _owner_region_pair(
        tmp_path, serve, depth=2, threads=2
    )
    old = shmring.ShmWorkerClient(r_w, 0, wait_s=0.05)
    new = None
    try:
        with pytest.raises(shmring.RingTimeout):
            old.call(
                cls="isa", cells=np.asarray([1], np.uint64),
                now_ns=0, owner="slow",
            )
        old.close()  # the SIGKILL analog: heartbeats stop
        owner.reclaim_worker(0)  # leader reaps; BUSY slot untouched
        # respawn: fresh incarnation, same ring row
        new = shmring.ShmWorkerClient(r_w, 0, wait_s=2.0)
        owner.revive_worker(0)
        resp = new.call(
            cls="isa", cells=np.asarray([2], np.uint64), now_ns=0
        )
        assert resp.ids == ["new-answer"]  # never the old serve's
        # the old incarnation's slot is still the owner's
        assert shmring.BUSY in {
            r_w.slot_state(0, s) for s in range(r_w.depth)
        }
        release.set()
        # once the old serve publishes, the new allocator's sweep
        # recovers the slot — the ring heals to full depth
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            try:
                s = new._alloc()
                new._release(s)
            except shmring.RingFull:
                pass
            if new.in_flight() == 0 and not any(
                r_w.slot_state(0, s) != shmring.FREE
                for s in range(r_w.depth)
            ):
                break
            time.sleep(0.01)
        assert new.in_flight() == 0
    finally:
        old.close()
        if new is not None:
            new.close()
        owner.close()
        r_w.close()
        r_o.close()


def test_owner_reclaimed_slot_falls_back_immediately(tmp_path):
    # When the owner force-frees a waiting slot (it declared this
    # worker dead during a stall), no response is ever coming: the
    # waiter must fall back NOW, not burn the full wait bound.
    r = shmring.ShmRegion.create(
        str(tmp_path / "r.shm"), nworkers=1, depth=2
    )
    client = shmring.ShmWorkerClient(r, 0, wait_s=5.0)
    try:
        res = {}

        def go():
            t0 = time.monotonic()
            try:
                client.call(
                    cls="isa", cells=np.asarray([1], np.uint64),
                    now_ns=0,
                )
            except shmring.RingTimeout:
                res["elapsed"] = time.monotonic() - t0

        th = threading.Thread(target=go)
        th.start()
        deadline = time.monotonic() + 2
        while (
            r.slot_state(0, 1) != shmring.REQ
            and time.monotonic() < deadline
        ):
            time.sleep(0.001)
        assert r.slot_state(0, 1) == shmring.REQ
        r.set_slot_state(0, 1, shmring.FREE)  # the owner's reclaim
        th.join(timeout=3)
        assert not th.is_alive()
        assert res["elapsed"] < 2.0  # nowhere near the 5s bound
        assert client.in_flight() == 0  # slot back in the local pool
    finally:
        client.close()
        r.close()


def test_mesh_served_answer_never_populates_worker_cache(tmp_path):
    # A bounded-stale mesh answer is refused by the LEADER's cache
    # (_cached_ids take_mesh_served guard); the RESP_F_MESH_SERVED
    # flag must carry that refusal across the ring so the worker's
    # cache refuses it too — a later strict poll fencing clean would
    # otherwise serve the lagging answer as fresh.
    def serve(req):
        return ["mesh-id"], [10 ** 18], 7, shmring.RESP_F_MESH_SERVED

    r_o, owner, r_w = _owner_region_pair(tmp_path, serve)
    client = shmring.ShmWorkerClient(r_w, 0, wait_s=2.0)

    class _Follower:
        def wait_for(self, seq, bound_s):
            return True

    front = ShmSearchFront(r_w, client, _Follower(), FakeClock(T0))
    try:
        cells = np.asarray([5], np.uint64)
        ids = front.serve(
            "isa", cells, qkey=(), now_ns=to_nanos(T0)
        )
        assert ids == ["mesh-id"]
        assert front.cache.stats()["entries"] == 0  # NOT populated
        # the repeat poll misses again — back to the ring, no hit
        ids2 = front.serve(
            "isa", cells, qkey=(), now_ns=to_nanos(T0)
        )
        assert ids2 == ["mesh-id"]
        assert client.stats()["cache_hits"] == 0
        assert client.stats()["enqueued"] == 2
        # the flag itself round-trips the codec
        resp = client.call(
            cls="isa", cells=cells, now_ns=to_nanos(T0)
        )
        assert resp.mesh_served
    finally:
        client.close()
        owner.close()
        r_w.close()
        r_o.close()


def test_proxy_fallback_feeds_cost_model():
    # api/app.py worker_proxy: a ShmFallback-proxied SEARCH must feed
    # its measured round trip to WorkerCostModel.observe_proxy, so the
    # shm-vs-proxy comparison learns the real loopback cost instead of
    # trusting the DSS_SHM_PROXY_MS seed forever.
    import requests
    from aiohttp import web

    from dss_tpu.api.app import make_worker_proxy_middleware
    from tests.live_server import LiveServer

    async def leader_search(request):
        return web.json_response({"service_areas": []})

    leader_app = web.Application()
    leader_app.router.add_get(
        "/v1/dss/identification_service_areas", leader_search
    )
    leader = LiveServer(leader_app)

    cm = shmroute.WorkerCostModel(rtt_ms=1.0, proxy_ms=50.0)
    mw = make_worker_proxy_middleware(leader.base, costs=cm)

    async def worker_search(request):
        raise ShmFallback("ring-full")

    worker_app = web.Application(middlewares=[mw])
    worker_app.router.add_get(
        "/v1/dss/identification_service_areas", worker_search
    )
    worker = LiveServer(worker_app)
    try:
        rsp = requests.get(
            f"{worker.base}/v1/dss/identification_service_areas",
            timeout=10,
        )
        assert rsp.status_code == 200
        assert rsp.json() == {"service_areas": []}
        assert cm.proxy_obs == 1
        assert cm.est_proxy_ms < 50.0  # moved toward the measured cost
    finally:
        worker.stop()
        leader.stop()


def test_owner_close_drains_claimed_slots(tmp_path):
    started = threading.Event()
    release = threading.Event()

    def serve(req):
        started.set()
        release.wait(10)
        return ["drained"], [1], 0

    r_o, owner, r_w = _owner_region_pair(tmp_path, serve, threads=1)
    client = shmring.ShmWorkerClient(r_w, 0, wait_s=10.0)
    got = {}

    def call():
        got["resp"] = client.call(
            cls="isa", cells=np.asarray([1], np.uint64), now_ns=0
        )

    t = threading.Thread(target=call)
    try:
        t.start()
        assert started.wait(5)
        closer = threading.Thread(target=owner.close)
        closer.start()
        release.set()  # shutdown with the slot still in flight
        closer.join(timeout=10)
        t.join(timeout=10)
        assert got["resp"].ids == ["drained"]
    finally:
        release.set()
        client.close()
        owner.close()
        r_w.close()
        r_o.close()


# ---------------------------------------------------------------------------
# the worker-side route decision (plan/shmroute.py)
# ---------------------------------------------------------------------------


def _wstate(**kw):
    base = dict(
        est_shm_rtt_ms=1.0, est_owner_serve_ms=1.0, est_proxy_ms=10.0,
        ring_in_flight=0, ring_depth=8, owner_threads=2,
        owner_alive=True, shm_attached=True,
    )
    base.update(kw)
    return shmroute.WorkerState(**base)


def test_decide_worker_policy_table():
    assert shmroute.decide_worker(_wstate()).route == "shm"
    p = shmroute.decide_worker(_wstate(shm_attached=False))
    assert (p.route, p.reason) == ("proxy", "no-ring")
    p = shmroute.decide_worker(_wstate(owner_alive=False))
    assert (p.route, p.reason) == ("proxy", "owner-dead")
    p = shmroute.decide_worker(_wstate(ring_in_flight=8))
    assert (p.route, p.reason) == ("proxy", "ring-full")
    # ring priced above the proxy AND the headroom -> proxy
    slow = _wstate(est_shm_rtt_ms=50.0, est_proxy_ms=10.0)
    p = shmroute.decide_worker(slow, headroom_ms=20.0)
    assert (p.route, p.reason) == ("proxy", "ring-slow")
    # ...but a ring inside the headroom keeps the zero-marshal path
    # even when the proxy estimate is lower (the estimate includes a
    # marshal tax the predictor can't see)
    p = shmroute.decide_worker(slow, headroom_ms=100.0)
    assert p.route == "shm"
    p = shmroute.decide_worker(slow, headroom_ms=None)
    assert p.route == "proxy"


def test_decide_worker_queue_pressure_prices_in():
    s = _wstate(
        est_shm_rtt_ms=1.0, est_owner_serve_ms=4.0, est_proxy_ms=5.0,
        ring_in_flight=4, owner_threads=2,
    )
    # 1 + 4 * 4/2 = 9ms > 5ms proxy, headroom 6ms -> proxy
    p = shmroute.decide_worker(s, headroom_ms=6.0)
    assert p.route == "proxy" and p.reason == "ring-slow"
    assert s.predict_shm_ms() == pytest.approx(9.0)


def test_worker_state_roundtrip():
    s = _wstate(ring_in_flight=3)
    assert shmroute.WorkerState.from_dict(s.to_dict()) == s


def test_cost_model_ewma_and_winsorize():
    m = shmroute.WorkerCostModel(rtt_ms=1.0, proxy_ms=10.0, alpha=0.5)
    m.observe_shm(2.0)
    assert m.est_shm_rtt_ms == pytest.approx(1.5)
    # a 1000ms stall is winsorized at 4x the estimate
    m.observe_shm(1000.0)
    assert m.est_shm_rtt_ms == pytest.approx(0.5 * 1.5 + 0.5 * 6.0)
    m.observe_proxy(20.0)
    assert m.est_proxy_ms == pytest.approx(15.0)
    st = m.stats()
    assert st["shm_rtt_obs"] == 2 and st["shm_proxy_obs"] == 1
    ws = m.state(
        ring_in_flight=1, ring_depth=8, owner_threads=2,
        owner_alive=True,
    )
    assert ws.est_proxy_ms == m.est_proxy_ms


# ---------------------------------------------------------------------------
# the full worker front: leader store + replica + ring + fenced cache
# ---------------------------------------------------------------------------


class _FrontHarness:
    """Leader DSSStore (device owner, shm front attached) + one
    worker: WAL-tail replica + ring client + fenced local cache —
    the cmds/server.py worker topology, in-process."""

    def __init__(self, tmp_path, storage="memory", depth=16,
                 cache_cap=256, slot_bytes=32768, writes=None):
        """`writes(leader store, clock)` -> the owner's write lane
        function (tests/test_ring_writes.py)."""
        self.clock = FakeClock(T0)
        self.wal_path = str(tmp_path / "wal.jsonl")
        self.leader = DSSStore(
            storage=storage, clock=self.clock, wal_path=self.wal_path
        )
        self.region_path = str(tmp_path / "ring.shm")
        region = shmring.ShmRegion.create(
            self.region_path, nworkers=1, depth=depth,
            slot_bytes=slot_bytes, fence_slots=1 << 12,
        )
        self.owner_region = region
        self.owner = self.leader.attach_shm_front(
            region,
            write_fn=None if writes is None
            else writes(self.leader, self.clock),
        )
        self.replica = DSSStore(storage="memory", clock=self.clock)
        self.follower = WalFollower(
            self.replica, self.wal_path, interval_s=0.005
        )
        self.follower.start()
        self.worker_region = shmring.ShmRegion.open_existing(
            self.region_path
        )
        self.client = shmring.ShmWorkerClient(
            self.worker_region, 0, wait_s=10.0
        )
        self.front = ShmSearchFront(
            self.worker_region, self.client, self.follower, self.clock,
            cache=rcache.ReadCache(capacity=cache_cap, shards=4),
            catchup_s=5.0,
        )
        self.rid = ShmRIDStore(self.replica.rid, self.front)
        self.scd = ShmSCDStore(self.replica.scd, self.front)

    def sync(self):
        """Barrier: the replica has applied everything the leader
        logged (test determinism only — serving never needs it)."""
        target = self.leader.wal.seq
        assert self.follower.wait_for(target, timeout_s=10.0)

    def close(self):
        self.client.close()
        self.follower.close()
        self.leader.close()  # closes the owner too
        self.replica.close()
        self.worker_region.close()
        self.owner_region.close()


@pytest.fixture
def front(tmp_path):
    h = _FrontHarness(tmp_path)
    yield h
    h.close()


def _search_pairs(h, cells, *, e=None, l=None):
    """(leader, worker) ISA search signatures at the same instant."""
    e = e or (T0 + timedelta(minutes=5))
    leader = h.leader.rid.search_isas(cells, e, l)
    worker = h.rid.search_isas(cells, e, l)
    return _sigs(leader), _sigs(worker)


def test_worker_search_matches_leader(front):
    cells = _cells(100, 132)
    front.leader.rid.insert_isa(_isa(1, cells))
    front.leader.rid.insert_isa(_isa(2, _cells(116, 140)))
    front.leader.rid.insert_isa(_isa(3, _cells(500, 510)))  # disjoint
    front.sync()
    leader, worker = _search_pairs(front, cells)
    assert worker == leader and len(worker) == 2


def test_worker_cache_hit_skips_ring_and_survives_expiry(front):
    cells = _cells(200, 216)
    front.leader.rid.insert_isa(
        _isa(4, cells, end=T0 + timedelta(minutes=30))
    )
    front.leader.rid.insert_isa(
        _isa(5, cells, end=T0 + timedelta(hours=6))
    )
    front.sync()
    _, w1 = _search_pairs(front, cells)
    assert len(w1) == 2
    enq0 = front.client.stats()["enqueued"]
    _, w2 = _search_pairs(front, cells)
    assert w2 == w1
    st = front.client.stats()
    assert st["enqueued"] == enq0  # pure local hit: zero ring trips
    assert st["cache_hits"] >= 1


def test_cached_answer_expires_records_never_resurrects(front):
    """The one time-variant predicate (t_end >= now) is re-applied on
    every worker-local HIT: as the wall clock advances, a cached
    answer can only expire records out — bit-identical to fresh."""
    cells = _cells(250, 274)
    op_short = _op(70, cells)
    op_short.end_time = T0 + timedelta(minutes=30)
    front.leader.scd.upsert_operation(op_short, key=[], key_checked=True)
    op_long = _op(71, cells)
    front.leader.scd.upsert_operation(op_long, key=[], key_checked=True)
    front.sync()
    e, l = T0 + timedelta(minutes=1), T0 + timedelta(hours=2)
    w1 = front.scd.search_operations(cells, None, None, e, l)
    assert len(w1) == 2  # populate
    enq0 = front.client.stats()["enqueued"]
    front.clock.advance(hours=1)  # past op_short's end, same query key
    leader = front.leader.scd.search_operations(cells, None, None, e, l)
    worker = front.scd.search_operations(cells, None, None, e, l)
    assert _sigs(worker) == _sigs(leader)
    assert {r.id for r in worker} == {op_long.id}
    assert front.client.stats()["enqueued"] == enq0  # still a HIT


def test_write_invalidates_worker_cache_exactly(front):
    a, b = _cells(300, 316), _cells(400, 416)
    front.leader.rid.insert_isa(_isa(6, a))
    front.leader.rid.insert_isa(_isa(7, b))
    front.sync()
    _search_pairs(front, a)
    _search_pairs(front, b)
    enq0 = front.client.stats()["enqueued"]
    # a write in B's covering fences B's entry out — A's stays live
    front.leader.rid.insert_isa(_isa(8, b))
    front.sync()
    la, wa = _search_pairs(front, a)
    assert wa == la
    assert front.client.stats()["enqueued"] == enq0  # A: still a hit
    lb, wb = _search_pairs(front, b)
    assert wb == lb and len(wb) == 2
    assert front.client.stats()["enqueued"] == enq0 + 1  # B: refetched


def test_tombstone_never_resurrected_from_worker_cache(front):
    cells = _cells(600, 616)
    isa = _isa(9, cells)
    front.leader.rid.insert_isa(isa)
    front.sync()
    _, w1 = _search_pairs(front, cells)
    assert len(w1) == 1
    got = front.leader.rid.get_isa(isa.id)
    front.leader.rid.delete_isa(got)
    front.sync()
    leader, worker = _search_pairs(front, cells)
    assert worker == leader == []


def test_epoch_token_bump_fences_all_entries(front):
    cells = _cells(700, 716)
    front.leader.rid.insert_isa(_isa(10, cells))
    front.sync()
    _search_pairs(front, cells)
    enq0 = front.client.stats()["enqueued"]
    front.worker_region.bump_epoch_token()
    leader, worker = _search_pairs(front, cells)
    assert worker == leader
    assert front.client.stats()["enqueued"] == enq0 + 1  # re-fetched


def test_owner_scoped_sub_search_matches_leader(front):
    cells = _cells(800, 816)
    front.leader.scd.upsert_subscription(_scd_sub(20, cells, owner="ua"))
    front.leader.scd.upsert_subscription(_scd_sub(21, cells, owner="ub"))
    front.leader.scd.upsert_operation(
        _op(22, cells, owner="ua", sub_id=_uuid(20)), key=[],
        key_checked=True,
    )
    front.sync()
    for owner in ("ua", "ub"):
        leader = front.leader.scd.search_subscriptions(cells, owner)
        worker = front.scd.search_subscriptions(cells, owner)
        assert _sigs(worker) == _sigs(leader)
        assert [
            sorted(s.dependent_operations) for s in sorted(
                worker, key=lambda s: s.id
            )
        ] == [
            sorted(s.dependent_operations) for s in sorted(
                leader, key=lambda s: s.id
            )
        ]


def test_ops_and_constraints_match_leader_with_windows(front):
    cells = _cells(900, 932)
    front.leader.scd.upsert_operation(
        _op(30, cells, alt=(0.0, 50.0)), key=[], key_checked=True
    )
    front.leader.scd.upsert_operation(
        _op(31, cells, alt=(200.0, 260.0)), key=[], key_checked=True
    )
    front.leader.scd.upsert_constraint(_cst(32, cells))
    front.sync()
    e, l = T0 + timedelta(minutes=1), T0 + timedelta(hours=2)
    for alt in (None, (0.0, 100.0), (220.0, 230.0)):
        alo, ahi = alt if alt else (None, None)
        leader = front.leader.scd.search_operations(
            cells, alo, ahi, e, l
        )
        worker = front.scd.search_operations(cells, alo, ahi, e, l)
        assert _sigs(worker) == _sigs(leader), alt
    leader = front.leader.scd.search_constraints(cells, None, None, e, l)
    worker = front.scd.search_constraints(cells, None, None, e, l)
    assert _sigs(worker) == _sigs(leader) and len(worker) == 1


def test_read_your_writes_across_the_ring(front):
    """A write acknowledged by the leader, then a search on the worker:
    the ring response's WAL seq bounds a replica-catchup wait, so the
    worker NEVER serves a pre-write answer — no sync() here."""
    cells = _cells(1000, 1016)
    for i in range(8):
        front.leader.rid.insert_isa(_isa(40 + i, cells))
        # deliberately NO front.sync(): serve immediately after ack
        worker = front.rid.search_isas(
            cells, T0 + timedelta(minutes=5), None
        )
        assert _uuid(40 + i) in {r.id for r in worker}, i


def test_hot_path_performs_zero_serialization(front, monkeypatch):
    """The acceptance contract: the worker->owner search round trip
    performs ZERO JSON / pickle work — counted, not assumed."""
    import json as _json
    import pickle as _pickle

    cells = _cells(1100, 1132)
    front.leader.rid.insert_isa(_isa(50, cells))
    front.sync()  # replica caught up: catchup wait won't poll-decode
    calls = {"n": 0}

    def counting(orig):
        def wrapper(*a, **kw):
            calls["n"] += 1
            return orig(*a, **kw)

        return wrapper

    for mod, names in ((_json, ("dumps", "loads")),
                       (_pickle, ("dumps", "loads"))):
        for name in names:
            monkeypatch.setattr(mod, name, counting(getattr(mod, name)))
    # miss -> ring -> populate, then a local hit: both serializer-free
    worker = front.rid.search_isas(cells, T0 + timedelta(minutes=5), None)
    assert len(worker) == 1
    worker = front.rid.search_isas(cells, T0 + timedelta(minutes=5), None)
    assert len(worker) == 1
    assert calls["n"] == 0, (
        f"hot path performed {calls['n']} serializer calls"
    )


def test_injected_enqueue_fault_falls_back_not_errors(front):
    cells = _cells(1200, 1216)
    front.leader.rid.insert_isa(_isa(60, cells))
    front.sync()
    chaos.install_plan(
        {"events": [{"site": "shm.ring.enqueue", "count": 1}]}
    )
    with pytest.raises(ShmFallback):
        front.rid.search_isas(cells, T0 + timedelta(minutes=5), None)
    assert front.client.stats()["proxy_fallbacks"] == 1
    assert chaos.registry().injected_by_site() == {
        "shm.ring.enqueue": 1
    }
    # the plan is exhausted: the next search rides the ring again
    worker = front.rid.search_isas(cells, T0 + timedelta(minutes=5), None)
    assert len(worker) == 1


def test_dead_owner_routes_to_proxy(front):
    cells = _cells(1300, 1316)
    front.leader.rid.insert_isa(_isa(61, cells))
    front.sync()
    front.front.owner_ttl_s = -1.0  # every heartbeat age is "stale"
    with pytest.raises(ShmFallback):
        front.rid.search_isas(cells, T0 + timedelta(minutes=5), None)
    st = front.client.stats()
    assert st["plan_proxy"] >= 1 and st["proxy_fallbacks"] >= 1


def test_overload_verdict_crosses_the_ring(front, monkeypatch):
    cells = _cells(1400, 1416)
    front.leader.rid.insert_isa(_isa(62, cells))
    front.sync()

    def overloaded(req):
        raise errors.OverloadedError("busy", retry_after_s=2.25)

    monkeypatch.setattr(front.owner, "_serve_fn", overloaded)
    with pytest.raises(errors.OverloadedError) as ei:
        front.rid.search_isas(cells, T0 + timedelta(minutes=5), None)
    assert ei.value.retry_after_s == 2.25


def test_front_stats_key_set(front):
    st = front.front.stats()
    for k in ("shm_cache_hits", "shm_cache_misses", "shm_est_rtt_ms",
              "shm_enqueued", "shm_served", "shm_ring_full"):
        assert k in st, k


# ---------------------------------------------------------------------------
# the owner serves ids: DSSStore.shm_serve answers at the id-level depth
# of the SAME search whose record-level depth HTTP handlers and
# prechecks call — equal id for id and end time for end time, no record
# copied, each id's end time read once
# ---------------------------------------------------------------------------

_DEPTH_CELLS = _cells(4000, 4024)
_DEPTH_OWNER = "ua"
_NEVER = int(np.iinfo(np.int64).max)


def _rid_sub(i: int, cells, *, owner="u1", end=None):
    return ridm.Subscription(
        id=_uuid(i),
        owner=owner,
        url="https://uss.example/s",
        cells=np.asarray(cells, np.uint64),
        start_time=T0,
        end_time=end or (T0 + timedelta(hours=8)),
        altitude_lo=0.0,
        altitude_hi=3000.0,
    )


def _depth_store(storage: str):
    """A leader store holding seven entities of every ring class over
    one area, inserted in an order that is not the ids' order, with
    end times that all differ (the seventh ends first)."""
    store = DSSStore(storage=storage, clock=FakeClock(T0))
    for n, i in enumerate((5, 2, 7, 1, 6, 3, 4)):
        end = T0 + timedelta(hours=1 + n)
        cells = _DEPTH_CELLS[n % 3:]
        store.rid.insert_isa(_isa(100 + i, cells, end=end))
        store.rid.insert_subscription(
            _rid_sub(200 + i, cells, owner=_DEPTH_OWNER, end=end)
        )
        store.scd.upsert_subscription(
            dataclasses.replace(
                _scd_sub(400 + i, cells, owner=_DEPTH_OWNER), end_time=end
            )
        )
        store.scd.upsert_operation(
            dataclasses.replace(
                _op(300 + i, cells, owner=_DEPTH_OWNER,
                    sub_id=_uuid(400 + i)),
                end_time=end,
            ),
            key=[], key_checked=True,
        )
        store.scd.upsert_constraint(
            dataclasses.replace(_cst(500 + i, cells), end_time=end)
        )
    return store


def _ring_req(cls: str, now: datetime, *, allow_stale=False):
    """The request a worker would put in a slot for `cls` over the
    depth area at its instant `now`."""
    windowed = cls in ("isa", "op", "constraint")
    return shmring.ShmRequest(
        cls=cls, cells=_DEPTH_CELLS,
        alt_lo=0.0 if cls in ("op", "constraint") else None,
        alt_hi=400.0 if cls in ("op", "constraint") else None,
        t0_ns=to_nanos(now) if windowed else None,
        t1_ns=to_nanos(now + timedelta(hours=12)) if windowed else None,
        now_ns=to_nanos(now), deadline_ns=0,
        owner=_DEPTH_OWNER if cls in ("rid_sub", "scd_sub") else "",
        allow_stale=allow_stale, worker=0, slot=0, req_id=1,
    )


def _record_level(store, req) -> tuple:
    """(ids, end times) of the record-level search for the same
    request, at the same pinned `now`."""
    from dss_tpu.clock import from_nanos

    cls = req.cls
    sub = store.rid if cls in ("isa", "rid_sub") else store.scd
    t0 = None if req.t0_ns is None else from_nanos(req.t0_ns)
    t1 = None if req.t1_ns is None else from_nanos(req.t1_ns)
    sub._txn_time.now = int(req.now_ns)
    try:
        if cls == "isa":
            recs = sub.search_isas(req.cells, t0, t1)
        elif cls == "rid_sub":
            recs = sub.search_subscriptions_by_owner(req.cells, req.owner)
        elif cls == "scd_sub":
            recs = sub.search_subscriptions(req.cells, req.owner)
        elif cls == "op":
            recs = sub.search_operations(
                req.cells, req.alt_lo, req.alt_hi, t0, t1
            )
        else:
            recs = sub.search_constraints(
                req.cells, req.alt_lo, req.alt_hi, t0, t1
            )
    finally:
        sub._txn_time.now = None
    return (
        [r.id for r in recs],
        [_NEVER if r.end_time is None else to_nanos(r.end_time)
         for r in recs],
    )


def _served(store, req) -> tuple:
    ids, t1s, gen, flags = store.shm_serve(req)
    assert gen == store._class_index(req.cls).cell_clock.generation
    return (list(ids), [int(t) for t in t1s]), flags


@pytest.mark.parametrize("storage", ["memory", "tpu"])
@pytest.mark.parametrize("cls", shmring.SHM_CLASSES)
def test_shm_serve_equals_record_level_search(cls, storage):
    store = _depth_store(storage)
    try:
        req = _ring_req(cls, T0 + timedelta(minutes=5))
        cache = store.cache
        entries0 = cache.stats()["entries"]  # the writes' prechecks
        miss, flags = _served(store, req)  # fills the owner's cache
        assert flags == 0
        assert cache.stats()["entries"] == entries0 + 1
        hits0 = cache.class_stats(cls)["co_cache_hits"]
        hit, _ = _served(store, req)  # a fenced hit: the same pairs
        assert cache.class_stats(cls)["co_cache_hits"] == hits0 + 1
        want = _record_level(store, req)
        assert len(want[0]) == 7 and len(set(want[1])) == 7
        assert miss == want and hit == want
        if cls in ("op", "scd_sub", "constraint"):
            # these classes answer in id order whatever the index's
            assert want[0] == sorted(want[0])
        store.configure_serving(cache=False)
        fresh, _ = _served(store, req)
        assert fresh == want == _record_level(store, req)
    finally:
        store.close()


@pytest.mark.parametrize("cached", [True, False], ids=["cache", "nocache"])
@pytest.mark.parametrize(
    "case", ["vanished", "no_end", "expired", "mesh"]
)
def test_shm_serve_id_level_edges(case, cached, monkeypatch):
    store = _depth_store("memory")
    try:
        store.configure_serving(cache=cached)
        ops = store.scd._ops
        now = T0 + timedelta(minutes=5)
        if case == "vanished":
            # the record went between the index query and the end-time
            # pass: skipped, as the fresh path would skip it right now
            del ops[_uuid(303)]
            (ids, t1s), _ = _served(store, _ring_req("op", now))
            assert _uuid(303) not in ids and len(ids) == len(t1s) == 6
        elif case == "no_end":
            ops[_uuid(303)] = dataclasses.replace(ops[_uuid(303)], end_time=None)
            (ids, t1s), _ = _served(store, _ring_req("op", now))
            assert t1s[ids.index(_uuid(303))] == _NEVER
            assert sum(t == _NEVER for t in t1s) == 1 and len(ids) == 7
        elif case == "expired":
            # id 305 ends at T0 + 1 h: in the answer before, out after,
            # whether the later answer is a refiltered hit or fresh
            (ids, _), _ = _served(store, _ring_req("op", now))
            assert _uuid(305) in ids
            now = T0 + timedelta(minutes=90)
            (ids, t1s), _ = _served(store, _ring_req("op", now))
            assert _uuid(305) not in ids and len(ids) == 6
            assert min(t1s) >= to_nanos(now)
        else:
            # a bounded-stale mesh answer: the flag crosses the ring
            # and the owner's cache takes nothing from it
            index = store.scd._op_index
            real = index.query_ids

            def meshed(*a, **kw):
                rcache.note_mesh_served()
                return real(*a, **kw)

            monkeypatch.setattr(index, "query_ids", meshed)
            entries0 = store.cache.stats()["entries"]
            (ids, t1s), flags = _served(
                store, _ring_req("op", now, allow_stale=True)
            )
            assert flags == shmring.RESP_F_MESH_SERVED
            assert store.cache.stats()["entries"] == entries0
            assert len(ids) == len(t1s) == 7
            monkeypatch.undo()
        req = _ring_req("op", now)
        assert _served(store, req)[0] == _record_level(store, req)
    finally:
        store.close()


def test_shm_serve_copies_no_record(monkeypatch):
    from dss_tpu.dar import dss_store as ds

    copies = []
    real = ds._copy_rec
    monkeypatch.setattr(
        ds, "_copy_rec", lambda rec: copies.append(rec.id) or real(rec)
    )
    store = _depth_store("memory")
    try:
        copies.clear()  # the writes' own prechecks copied records
        now = T0 + timedelta(minutes=5)
        for cls in shmring.SHM_CLASSES:
            req = _ring_req(cls, now)
            for _ in range(2):  # a miss, then a hit
                (ids, _), _ = _served(store, req)
                assert len(ids) == 7
        assert copies == []
        # the record level still hands out copies, never the store's own
        req = _ring_req("op", now)
        recs = store.scd.search_operations(
            req.cells, req.alt_lo, req.alt_hi, None, None
        )
        assert sorted(copies) == [r.id for r in recs] and len(recs) == 7
        assert all(r is not store.scd._ops[r.id] for r in recs)
    finally:
        store.close()


def test_answer_ids_counted_on_successful_serves_only(tmp_path):
    def serve(req):
        if req.owner == "shed":
            raise errors.OverloadedError("queue full", retry_after_s=1.0)
        if req.owner == "boom":
            raise RuntimeError("boom")
        return [f"id-{k}" for k in range(len(req.cells))], \
            [10 ** 18] * len(req.cells), 3

    r_o, owner, r_w = _owner_region_pair(tmp_path, serve)
    client = shmring.ShmWorkerClient(r_w, 0, wait_s=5.0)
    try:
        want = 0
        for n, who, status in (
            (5, "", shmring.ST_OK),
            (3, "shed", shmring.ST_OVERLOADED),
            (4, "boom", shmring.ST_ERROR),
            (0, "", shmring.ST_OK),
            (2, "", shmring.ST_OK),
        ):
            resp = client.call(
                cls="op", cells=np.arange(1, n + 1, dtype=np.uint64),
                now_ns=to_nanos(T0), owner=who,
            )
            assert resp.status == status
            if status == shmring.ST_OK:
                assert len(resp.ids) == n
                want += n
            assert owner.stats()["dss_shm_answer_ids_total"] == want
        # any process mapping the region renders the same family
        assert shmring.front_stats(r_w)["dss_shm_answer_ids_total"] == 7
        assert "dss_shm_answer_ids_total" in shmring.empty_stats()
    finally:
        client.close()
        owner.close()
        r_w.close()
        r_o.close()


def _until(cond, timeout_s=5.0):
    t_end = time.monotonic() + timeout_s
    while not cond():
        assert time.monotonic() < t_end, "never happened"
        time.sleep(0.002)


def test_serves_are_counted_by_the_path_that_answered(tmp_path):
    """Successful serves and their time land on the host's or the
    device's pair of owner header words, by the flag the serve fn
    hands back; the two counts sum to the served count, and any
    process mapping the region renders the same families."""
    def serve(req):
        if req.owner == "boom":
            raise RuntimeError("boom")
        time.sleep(0.002)
        flags = shmring.RESP_F_DEVICE_SERVED if req.owner == "dev" else 0
        return ["id-1"], [10 ** 18], 3, flags

    r_o, owner, r_w = _owner_region_pair(tmp_path, serve)
    client = shmring.ShmWorkerClient(r_w, 0, wait_s=5.0)
    try:
        for who in ("", "dev", "", "boom", "dev", ""):
            resp = client.call(
                cls="op", cells=np.asarray([1], np.uint64),
                now_ns=to_nanos(T0), owner=who,
            )
            assert resp.status == (
                shmring.ST_ERROR if who == "boom" else shmring.ST_OK)
            # the flag rides the response; a worker decides nothing on it
            assert resp.flags == (
                shmring.RESP_F_DEVICE_SERVED if who == "dev" else 0)
            assert not resp.mesh_served
        # the per-path words are written after the response is out
        _until(lambda: owner.stats()["dss_shm_served_total"] == 5
               and owner.stats()["dss_shm_host_served_total"]
               + owner.stats()["dss_shm_device_served_total"] == 5)
        for st in (owner.stats(), shmring.front_stats(r_w)):
            assert st["dss_shm_host_served_total"] == 3
            assert st["dss_shm_device_served_total"] == 2
            assert st["dss_shm_host_serve_ms_total"] >= 3 * 2.0
            assert st["dss_shm_device_serve_ms_total"] >= 2 * 2.0
            assert (st["dss_shm_host_serve_ms_total"]
                    + st["dss_shm_device_serve_ms_total"]
                    <= st["dss_shm_serve_ms_total"])
        for name in ("host_served_total", "host_serve_ms_total",
                     "device_served_total", "device_serve_ms_total"):
            assert f"dss_shm_{name}" in shmring.empty_stats()
    finally:
        client.close()
        owner.close()
        r_w.close()
        r_o.close()


def test_the_store_tells_the_owner_which_path_answered(monkeypatch):
    """shm_serve flags an answer of the fused kernel and no other: a
    host scan, a hit in the owner's cache and the next request on the
    thread all read 0.  The coalescer's accounts by path and the
    owner's per-path families come out on /metrics."""
    from dss_tpu.obs.metrics import MetricsRegistry
    from dss_tpu.ops.fastpath import FastTable

    store = _depth_store("tpu")
    try:
        store.scd._op_index.table.fold()  # postings, not only overlay
        now = T0 + timedelta(minutes=5)
        st0 = store.stats()  # the writes' own prechecks scanned too

        def moved(name):
            return store.stats()[name] - st0[name]

        req = _ring_req("op", now)
        (ids, _), flags = _served(store, req)  # 7 ops: a host scan
        assert len(ids) == 7 and flags == 0
        assert moved("dss_dar_op_co_host_scans") == 1
        assert moved("dss_dar_op_co_host_members") == 1
        assert moved("dss_dar_op_co_host_scan_candidates_total") >= 7
        assert moved("dss_dar_op_co_device_members") == 0

        monkeypatch.setattr(FastTable, "HOST_MAX_CANDIDATES", 3)
        later = _ring_req("op", now + timedelta(seconds=1))  # a miss
        (ids, _), flags = _served(store, later)
        assert len(ids) == 7
        assert flags == shmring.RESP_F_DEVICE_SERVED
        (ids, _), flags = _served(store, later)  # the owner's cache
        assert len(ids) == 7 and flags == 0
        monkeypatch.setattr(FastTable, "HOST_MAX_CANDIDATES", 1 << 16)
        _, flags = _served(store, _ring_req("isa", now))
        assert flags == 0
        assert moved("dss_dar_op_co_device_members") == 1
        assert moved("dss_dar_op_co_device_members_under_cap") == 0
        assert moved("dss_dar_op_co_drains_mixed") == 0
        assert moved("dss_dar_op_co_host_scans") == 1
        st = store.stats()

        reg = MetricsRegistry()
        for name, val in {**st, **shmring.empty_stats()}.items():
            if not isinstance(val, dict):
                reg.set_gauge(name, val)
        text = reg.render()
        for name in (
            "dss_dar_op_co_host_scans", "dss_dar_op_co_host_scan_ms_total",
            "dss_dar_op_co_host_scan_candidates_total",
            "dss_dar_op_co_host_members", "dss_dar_op_co_device_members",
            "dss_dar_op_co_device_members_under_cap",
            "dss_dar_op_co_drains_mixed", "dss_shm_host_served_total",
            "dss_shm_host_serve_ms_total", "dss_shm_device_served_total",
            "dss_shm_device_serve_ms_total",
        ):
            assert f"\n{name} " in text, name
    finally:
        store.close()


# ---------------------------------------------------------------------------
# the waits: a round trip's two cross-process waits block on words of
# the shared mapping until the other side wakes them (futex), with the
# old sleeps' caps as the backstop of a lost wake; where the platform
# has no futex the same loops sleep
# ---------------------------------------------------------------------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_needs_futex = pytest.mark.skipif(
    shmring._futex is None, reason="this platform answered: no futex"
)


@pytest.fixture(params=["futex", "poll"])
def wait_body(request, monkeypatch):
    """Both bodies of the wait, the polling one forced where the
    platform chose the other at import."""
    if request.param == "futex":
        if shmring._futex is None:
            pytest.skip("this platform answered: no futex")
    else:
        monkeypatch.setattr(shmring, "_wait_word", shmring._poll_wait)
        monkeypatch.setattr(shmring, "_wake_word", shmring._poll_wake)
    return request.param


def _echo(req):
    return [str(int(c)) for c in req.cells], [7] * len(req.cells), 1


def _reclaimed_under_a_waiter(r, client, reclaim) -> float:
    """A request thread blocks on slot (0, 1) of an owner that never
    scans; `reclaim()` takes the slot back.  -> seconds from the
    reclaim to the waiter's RingTimeout."""
    res = {}

    def go():
        try:
            client.call(
                cls="isa", cells=np.asarray([1], np.uint64), now_ns=0
            )
        except shmring.RingTimeout:
            res["at"] = time.monotonic()

    th = threading.Thread(target=go)
    th.start()
    _until(lambda: r.slot_state(0, 1) == shmring.REQ)
    time.sleep(0.02)  # the waiter is inside its wait
    t_free = time.monotonic()
    reclaim()
    th.join(timeout=4)
    assert not th.is_alive()
    assert client.in_flight() == 0  # the slot is back in the local pool
    return res["at"] - t_free


def _wake_counts(owner, client):
    st, ws = owner.stats(), client.stats()
    return (st["dss_shm_owner_wakes_total"],
            st["dss_shm_owner_wake_backstops_total"],
            ws["wakes"], ws["wake_backstops"])


def test_round_trips_under_either_wait_body(tmp_path, wait_body):
    """The same round-trip cases whichever body waits: answers, the
    owner's verdicts, a timed-out waiter's abandoned slot coming back,
    and the count of how each wait ended."""
    gate = threading.Event()
    gate.set()

    def serve(req):
        if req.owner == "shed":
            raise errors.OverloadedError("queue full", retry_after_s=1.5)
        if req.owner == "boom":
            raise RuntimeError("boom")
        if req.owner == "slow":
            gate.wait(10)
        return _echo(req)

    r_o, owner, r_w = _owner_region_pair(tmp_path, serve, depth=4)
    client = shmring.ShmWorkerClient(r_w, 0, wait_s=5.0)
    try:
        for k in range(20):
            time.sleep(0.003 * (k % 3))  # an idle scanner, and a busy one
            resp = client.call(
                cls="op", cells=np.asarray([k, k + 1], np.uint64), now_ns=0
            )
            assert resp.status == shmring.ST_OK
            assert resp.ids == [str(k), str(k + 1)]
            assert resp.stamps[0] <= resp.stamps[1] <= resp.stamps[2]
        for who, status in (("shed", shmring.ST_OVERLOADED),
                            ("boom", shmring.ST_ERROR)):
            resp = client.call(
                cls="op", cells=np.asarray([1], np.uint64), now_ns=0,
                owner=who,
            )
            assert resp.status == status
        ow, ob, ww, wb = _wake_counts(owner, client)
        assert ww + wb == 22  # one count for every answer met
        assert 1 <= ow + ob <= 22  # one for every scan that found work
        if wait_body == "futex":
            # woken, or there before any wait; a loaded machine may
            # deschedule a waker between its store and its wake
            assert ww >= 18 and ow >= 18
        # a waiter gives up at its deadline; its slot comes back once
        # the owner has answered
        gate.clear()
        with pytest.raises(shmring.RingTimeout):
            client.call(
                cls="op", cells=np.asarray([1], np.uint64), now_ns=0,
                owner="slow", deadline_s=0.05,
            )
        assert client.in_flight() == 1 and client.stats()["timeouts"] == 1
        gate.set()

        def swept():
            try:
                client._release(client._alloc())
            except shmring.RingFull:
                pass
            return client.in_flight() == 0

        _until(swept)
    finally:
        gate.set()
        client.close()
        owner.close()
        r_w.close()
        r_o.close()


def test_reclaimed_slot_frees_its_waiter_under_either_body(
        tmp_path, wait_body):
    """FREE means reclaimed, under either body: the waiter takes its
    slot back and raises RingTimeout long before its deadline."""
    r = shmring.ShmRegion.create(
        str(tmp_path / "r.shm"), nworkers=1, depth=2
    )
    owner = shmring.ShmOwner(r, _echo)  # never started: nothing scans
    client = shmring.ShmWorkerClient(r, 0, wait_s=5.0)
    try:
        took = _reclaimed_under_a_waiter(
            r, client, lambda: owner.reclaim_worker(0)
        )
        assert took < 1.0  # nowhere near the 5 s bound
        assert client.stats()["timeouts"] == 1
        assert owner.stats()["dss_shm_reclaimed_total"] == 1
    finally:
        client.close()
        r.close()


@_needs_futex
def test_idle_owner_round_trip_is_well_under_the_old_scan_cap(tmp_path):
    """An idle scanner used to sleep at its 2 ms cap and a waiter at up
    to 1 ms, so pickup + return read ~1.4-1.8 ms a round trip; woken,
    both are wake-up latencies (~0.3 ms together).  A wake-up is as
    late as the machine is loaded, so each attempt also reads how late
    a plain 0.5 ms timer fires, and allows the median that much more;
    three attempts, and a machine too loaded to time anything skips."""
    r_o, owner, r_w = _owner_region_pair(tmp_path, _echo)
    client = shmring.ShmWorkerClient(r_w, 0, wait_s=5.0)
    try:
        seen = []
        for attempt in range(3):
            waits, lates = [], []
            for k in range(40):
                t = time.perf_counter_ns()
                time.sleep(0.0005)
                lates.append((time.perf_counter_ns() - t) / 1e6 - 0.5)
                time.sleep(0.01)  # the scanner is idle, blocked
                t0 = time.perf_counter_ns()
                resp = client.call(
                    cls="op", cells=np.asarray([k], np.uint64), now_ns=0
                )
                t1 = time.perf_counter_ns()
                assert resp.ids == [str(k)]
                waits.append(
                    (resp.stamps[0] - t0 + t1 - resp.stamps[2]) / 1e6
                )
            wait, late = sorted(waits)[20], sorted(lates)[20]
            seen.append((round(wait, 3), round(late, 3)))
            if wait < 1.0 + 2 * late:
                break
        else:
            if min(late for _, late in seen) > 0.3:
                pytest.skip(f"too loaded to time a wake-up: {seen}")
            pytest.fail(f"(median wait, timer lateness) ms: {seen}")
        n = 40 * len(seen)
        ow, ob, ww, wb = _wake_counts(owner, client)
        assert (ow + ob, ww + wb) == (n, n)
        # woken, not found by a backstop
        assert ow >= n * 0.85 and ww >= n * 0.85
    finally:
        client.close()
        owner.close()
        r_w.close()
        r_o.close()


@pytest.mark.parametrize("lost", ["doorbell", "response", "both"])
def test_a_lost_wake_is_survived_by_the_backstop(tmp_path, monkeypatch, lost):
    """Suppress one side's wake-ups (or both: the polling body, where
    nobody wakes anybody): every request is still served, within that
    wait's backstop, and the backstop's counter moves, not the woken
    one.  The backstops are stretched to 50 ms here and each store
    waits until its waiter has begun a wait, so whatever the machine's
    load the store lands inside a wait that only its limit can end."""
    if lost != "both" and shmring._futex is None:
        pytest.skip("this platform answered: no futex")
    r_o, r_w = None, None
    in_bell_wait, in_slot_wait = threading.Event(), threading.Event()
    real_wait, real_wake = (
        (shmring._poll_wait, shmring._poll_wake) if lost == "both"
        else (shmring._futex_wait, shmring._futex_wake)
    )

    def is_bell(addr):
        return addr in (r_o.bell_addr, r_w.bell_addr)

    def wait(addr, expected, limit_s, turn):
        (in_bell_wait if is_bell(addr) else in_slot_wait).set()
        return real_wait(addr, expected, limit_s, turn)

    def wake(addr):
        if is_bell(addr) != (lost == "doorbell"):
            real_wake(addr)

    def inside(entered):
        entered.clear()
        assert entered.wait(2)
        time.sleep(0.0003)

    def serve(req):
        inside(in_slot_wait)
        return _echo(req)

    monkeypatch.setattr(shmring, "_OWNER_BACKSTOP_S", 0.05)
    monkeypatch.setattr(shmring, "_WORKER_BACKSTOP_S", 0.05)
    r_o, owner, r_w = _owner_region_pair(tmp_path, serve)
    client = shmring.ShmWorkerClient(r_w, 0, wait_s=5.0)
    monkeypatch.setattr(shmring, "_wait_word", wait)
    monkeypatch.setattr(shmring, "_wake_word", wake)
    try:
        n = 8
        for k in range(n):
            inside(in_bell_wait)
            t0 = time.perf_counter_ns()
            resp = client.call(
                cls="op", cells=np.asarray([k], np.uint64), now_ns=0
            )
            t1 = time.perf_counter_ns()
            assert resp.status == shmring.ST_OK and resp.ids == [str(k)]
            # late by a backstop at most, with room for a loaded machine
            assert (resp.stamps[0] - t0) / 1e6 < 500
            assert (t1 - resp.stamps[2]) / 1e6 < 500
        ow, ob, ww, wb = _wake_counts(owner, client)
        assert (ow + ob, ww + wb) == (n, n)
        if lost in ("doorbell", "both"):
            assert ob >= n - 1
        else:
            assert ow >= n - 1
        if lost in ("response", "both"):
            assert wb >= n - 1
        else:
            assert ww >= n - 1
        assert client.stats()["timeouts"] == 0
    finally:
        client.close()
        owner.close()
        r_w.close()
        r_o.close()


@_needs_futex
def test_a_reclaimed_waiter_is_woken_not_found(tmp_path, monkeypatch):
    """Every FREE the owner stores into a slot it reclaims wakes the
    slot's waiter: with the backstop out of the way (5 s) only the
    wake-up can end the wait early."""
    monkeypatch.setattr(shmring, "_WORKER_BACKSTOP_S", 5.0)
    r = shmring.ShmRegion.create(
        str(tmp_path / "r.shm"), nworkers=1, depth=2
    )
    owner = shmring.ShmOwner(r, _echo)  # never started: nothing scans
    client = shmring.ShmWorkerClient(r, 0, wait_s=5.0)
    try:
        for reclaim in (lambda: r.free_slot(0, 1),
                        lambda: owner.reclaim_worker(0)):
            assert _reclaimed_under_a_waiter(r, client, reclaim) < 1.0
    finally:
        client.close()
        r.close()


_HAMMER = """
import json, sys, threading
import numpy as np
from dss_tpu.parallel import shmring

path, worker, threads, calls = sys.argv[1], *map(int, sys.argv[2:5])
region = shmring.ShmRegion.open_existing(path)
client = shmring.ShmWorkerClient(region, worker, wait_s=20.0)
bad = []

def run(t):
    for k in range(calls):
        cells = np.asarray([worker, t, k], np.uint64)
        try:
            resp = client.call(cls="op", cells=cells, now_ns=0)
        except Exception as e:
            bad.append(repr(e))
            continue
        if (resp.status != shmring.ST_OK
                or resp.ids != [str(worker), str(t), str(k)]):
            bad.append((resp.status, resp.ids, worker, t, k))

ts = [threading.Thread(target=run, args=(t,)) for t in range(threads)]
[t.start() for t in ts]
[t.join() for t in ts]
print(json.dumps({"bad": bad[:5], "n_bad": len(bad), **client.stats()}))
"""


def test_two_worker_processes_hammer_one_owner(tmp_path):
    """Two worker PROCESSES, three request threads each, 3,000 requests
    at one owner as fast as they go: none is lost, none times out, none
    gets another's answer, and every answer's wait is counted once."""
    threads, calls = 3, 500
    path = str(tmp_path / "ring.shm")
    r_o = shmring.ShmRegion.create(path, nworkers=2, depth=8)
    owner = shmring.ShmOwner(r_o, _echo, threads=4, worker_ttl_s=0)
    owner.start()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=_ROOT)
    try:
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _HAMMER, path, str(w),
                 str(threads), str(calls)],
                cwd=_ROOT, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )
            for w in range(2)
        ]
        outs = [p.communicate(timeout=150) for p in procs]
        for p, (out, err) in zip(procs, outs):
            assert p.returncode == 0, err[-2000:]
        per = threads * calls
        for out, _ in outs:
            got = json.loads(out.strip().splitlines()[-1])
            assert got["n_bad"] == 0, got["bad"]
            assert got["enqueued"] == per
            assert got["timeouts"] == 0 and got["ring_full"] == 0
            assert got["wakes"] + got["wake_backstops"] == per
        st = owner.stats()
        assert st["dss_shm_served_total"] == 2 * per
        assert st["dss_shm_slots_in_flight"] == 0
        assert st["dss_shm_reclaimed_total"] == 0
        assert 1 <= (st["dss_shm_owner_wakes_total"]
                     + st["dss_shm_owner_wake_backstops_total"]) <= 2 * per
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        owner.close()
        r_o.close()


def test_ring_wake_pct_reads_counters_a_front_renders(front):
    """Every gauge that dssbench/metrics/ring_wake_pct.json cites is on
    /metrics of a front that has served a request, bare or under the
    `process` label alone (what the benchmark's scrape keys by name),
    and the ratio it forms reads 100 when every wait was woken."""
    from dss_tpu.api.app import _GAUGE_VEC_LABELS
    from dss_tpu.obs.metrics import MetricsRegistry

    with open(os.path.join(
            _ROOT, "dssbench", "metrics", "ring_wake_pct.json")) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "scrape_ratio" and spec["args"]["scale"] == 100
    num, den = spec["args"]["num"], spec["args"]["den"]
    assert set(num) < set(den)

    h = front
    cells = _cells(1000, 1008)
    h.leader.rid.insert_isa(_isa(1, cells))
    h.sync()
    got = h.rid.search_isas(cells, T0 + timedelta(minutes=5), None)
    assert len(got) == 1  # through the ring: nothing was cached yet
    reg = MetricsRegistry(proc="worker-0:1")
    for name, val in h.front.stats().items():  # api/app.py metrics_handler
        if isinstance(val, dict):
            reg.set_gauge_vec(
                name, _GAUGE_VEC_LABELS.get(name, "shard"), val
            )
        else:
            reg.set_gauge(name, val)
    text = reg.render()
    read = {}
    for name in den:
        m = re.findall(
            rf'^{name}(?:\{{process="[^"]*"\}})? ([0-9.e+-]+)$', text, re.M
        )
        assert m, name
        read[name] = float(m[-1])
        assert name in shmring.empty_stats() or name.startswith(
            "dss_shm_worker_")
    assert sum(read.values()) >= 2  # the owner's scan, the worker's wait
    if shmring._futex is not None:
        assert sum(read[k] for k in num) >= sum(read.values()) - 1


# ---------------------------------------------------------------------------
# differential: worker == leader across folds / compactions / tombstones
# (tpu backend: the tier machinery is what the folds exercise)
# ---------------------------------------------------------------------------


def test_differential_worker_vs_leader_across_folds(tmp_path):
    h = _FrontHarness(tmp_path, storage="tpu", cache_cap=32)
    rng = np.random.default_rng(7)
    try:
        areas = [_cells(2000 + 40 * k, 2024 + 40 * k) for k in range(6)]
        live = []
        for step in range(60):
            k = int(rng.integers(0, len(areas)))
            roll = rng.uniform()
            if roll < 0.5 or not live:
                i = 3000 + step
                h.leader.rid.insert_isa(
                    _isa(i, areas[k], owner=f"u{step % 3}")
                )
                live.append(i)
            elif roll < 0.65:
                i = live.pop(int(rng.integers(0, len(live))))
                got = h.leader.rid.get_isa(_uuid(i))
                if got is not None:
                    h.leader.rid.delete_isa(got)
            if step % 11 == 10:
                # force the tier machinery mid-sequence: minor folds,
                # then every other round a full L0 major compaction
                for index in (h.leader.rid._isa_index,):
                    t = getattr(index, "table", None)
                    if t is not None:
                        if (step // 11) % 2:
                            t.compact()
                        else:
                            t.fold()
            h.sync()
            q = areas[int(rng.integers(0, len(areas)))]
            leader, worker = _search_pairs(h, q)
            assert worker == leader, step
        st = h.front.cache.stats()
        assert st["hits"] > 0, "cache path never exercised"
        assert h.client.stats()["served"] > 0, "ring never exercised"
    finally:
        h.close()
