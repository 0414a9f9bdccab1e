"""The bulk boot (dar/boot.py) against the per-record loop.

A store booted from a log in one batch has to be the store that
DSSStore.apply_log_record builds from the same records one by one (the
plain reference here): the same record maps in the same order, the same
notification indices, the same owner interning, the same WAL sequence
and the same answers on the host route and on the device route.  What
differs, and is asserted to differ, is what no reader can tell apart
after a boot: the bulk store's overlay is empty and each of its classes'
cell clocks was raised once (a fence reads the FLOOR, generation 1),
where the loop stamped every covering (a fence reads the newest STAMP
of its cells, floor 0).
"""

from __future__ import annotations

import json
import logging
import os

import numpy as np
import pytest

import dss_tpu.ops  # noqa: F401  (x64 before any int64 instant)
from dss_tpu.dar import boot, codec
from dss_tpu.dar import wal as walmod
from dss_tpu.dar.dss_store import DSSStore
from dss_tpu.dar.follower import WalFollower
from dss_tpu.dar.wal import WriteAheadLog
from dss_tpu.geo import s2cell
from dss_tpu.ops.conflict import NO_TIME_HI, NO_TIME_LO
from dss_tpu.ops.fastpath import FastTable
from dssbench.deploy import Metro

NS = 1_000_000_000
T0 = 1_790_000_000  # whole seconds: instants survive the datetime trip
GRID = 10
CELLS = Metro(GRID).cells  # 100 level-13 cell ids

# class -> (put type, del type, bump type, to_doc of the class's model)
KINDS = {
    "isa": ("isa_put", "isa_del", None),
    "rid_sub": ("rid_sub_put", "rid_sub_del", "rid_sub_bump"),
    "op": ("scd_op_put", "scd_op_del", None),
    "scd_sub": ("scd_sub_put", "scd_sub_del", "scd_sub_bump"),
    "constraint": ("scd_cst_put", "scd_cst_del", None),
}
TO_DOC = {
    "isa": codec.isa_to_doc, "rid_sub": codec.rid_sub_to_doc,
    "op": codec.op_to_doc, "scd_sub": codec.scd_sub_to_doc,
    "constraint": codec.constraint_to_doc,
}
MAPS = {
    "isa": ("rid", "_isas", "_isa_index"),
    "rid_sub": ("rid", "_subs", "_sub_index"),
    "op": ("scd", "_ops", "_op_index"),
    "scd_sub": ("scd", "_subs", "_sub_index"),
    "constraint": ("scd", "_csts", "_cst_index"),
}


def _doc(rng, cls: str, eid: str, version: int) -> dict:
    """A document of class `cls` as the codec writes it, with repeated
    cells now and then and, for RID, an unbounded altitude side."""
    n = int(rng.integers(1, 9))
    cells = [int(c) for c in rng.choice(CELLS, n)]  # with repeats
    t0 = T0 + int(rng.integers(-6 * 3600, 6 * 3600))
    t1 = t0 + int(rng.integers(600, 4 * 3600))
    lo = float(rng.integers(0, 40) * 25)
    hi = lo + float(rng.integers(10, 120))
    owner = f"uss{int(rng.integers(0, 7))}"
    base = {"id": eid, "owner": owner, "cells": cells,
            "start_time": t0 * NS, "end_time": t1 * NS}
    if cls == "isa":
        return {**base, "url": "https://u/f", "version": f"1v{version}",
                "altitude_hi": None if rng.random() < 0.3 else hi,
                "altitude_lo": None if rng.random() < 0.3 else lo}
    if cls == "rid_sub":
        return {**base, "url": "https://u/i", "version": f"1v{version}",
                "notification_index": int(rng.integers(0, 3)),
                "altitude_hi": hi, "altitude_lo": None}
    if cls == "op":
        return {**base, "version": version, "ovn": f"ovn{version}",
                "altitude_lower": lo, "altitude_upper": hi,
                "uss_base_url": "https://u/s", "state": "Accepted",
                "subscription_id": "", "constraint_aware": False}
    if cls == "scd_sub":
        return {**base, "version": version,
                "notification_index": int(rng.integers(0, 3)),
                "altitude_hi": hi, "altitude_lo": lo,
                "base_url": "https://u/s", "notify_for_operations": True,
                "notify_for_constraints": False,
                "implicit_subscription": False,
                "dependent_operations": []}
    return {**base, "version": version, "ovn": f"c{version}",
            "altitude_lower": lo, "altitude_upper": hi,
            "uss_base_url": "https://u/c"}


def make_log(seed: int, n: int = 3000) -> list:
    """A seeded log of `n` records over all five classes: new puts,
    re-puts under new versions, deletes, deletes of absent ids, and
    subscription bumps that name live, deleted and unknown ids."""
    rng = np.random.default_rng(seed)
    live = {c: [] for c in KINDS}
    version = {}
    recs = []
    classes = list(KINDS)
    while len(recs) < n:
        cls = classes[int(rng.choice(5, p=[0.15, 0.15, 0.4, 0.15, 0.15]))]
        put_t, del_t, bump_t = KINDS[cls]
        roll = rng.random()
        if roll < 0.55 or not live[cls]:
            eid = f"{cls}-{seed}-{len(version)}"
            version[eid] = 1
            live[cls].append(eid)
            rec = {"t": put_t, "doc": _doc(rng, cls, eid, 1)}
        elif roll < 0.75:
            eid = live[cls][int(rng.integers(len(live[cls])))]
            version[eid] += 1
            rec = {"t": put_t, "doc": _doc(rng, cls, eid, version[eid])}
        elif roll < 0.85:
            eid = live[cls].pop(int(rng.integers(len(live[cls]))))
            rec = {"t": del_t, "id": eid}
        elif roll < 0.9:
            rec = {"t": del_t, "id": f"{cls}-never-{len(recs)}"}
        elif bump_t:
            ids = [live[cls][int(k)] for k in
                   rng.integers(0, len(live[cls]), int(rng.integers(1, 5)))]
            rec = {"t": bump_t, "ids": ids + [f"{cls}-gone-{len(recs)}"]}
        else:
            continue
        recs.append({**rec, "seq": len(recs) + 1})
    return recs


def write_log(path, recs, *, torn: bytes = b"", head: bool = True) -> None:
    with open(path, "wb") as fh:
        if head:
            fh.write(json.dumps(walmod.format_record()).encode() + b"\n")
        for rec in recs:
            fh.write(json.dumps(rec, separators=(",", ":")).encode() + b"\n")
        fh.write(torn)


def looped(recs, storage: str) -> DSSStore:
    """The plain reference: a store fed `recs` one by one."""
    ref = DSSStore(storage=storage)
    ref._replaying = True
    for rec in recs:
        ref.apply_log_record(rec)
    ref._replaying = False
    return ref


def docs_of(store, cls: str) -> list:
    sub, recmap, _ = MAPS[cls]
    return [TO_DOC[cls](m) for m in getattr(getattr(store, sub),
                                            recmap).values()]


def index_of(store, cls: str):
    sub, _, index = MAPS[cls]
    return getattr(getattr(store, sub), index)


def index_records(index) -> dict:
    return index.table.records if hasattr(index, "table") else index._recs


def assert_same_state(got: DSSStore, ref: DSSStore) -> None:
    """Record maps (with their order and notification indices), owner
    interning and every index Record."""
    assert got.scd._owners._ids == ref.scd._owners._ids
    assert got.rid._owners is got.scd._owners
    for cls in KINDS:
        assert docs_of(got, cls) == docs_of(ref, cls), cls
        a, b = index_records(index_of(got, cls)), index_records(
            index_of(ref, cls))
        assert list(a) == list(b), cls
        for eid, ra in a.items():
            rb = b[eid]
            assert ra.keys.dtype == rb.keys.dtype == np.int32
            assert np.array_equal(ra.keys, rb.keys), (cls, eid)
            assert (ra.alt_lo, ra.alt_hi, ra.t_start, ra.t_end,
                    ra.owner_id) == (rb.alt_lo, rb.alt_hi, rb.t_start,
                                     rb.t_end, rb.owner_id), (cls, eid)


def searches(seed: int, n: int):
    """`n` seeded query volumes: (keys per query, alt, time, owners)."""
    rng = np.random.default_rng([seed, 99])
    keys, alt_lo, alt_hi, t0, t1, owner = [], [], [], [], [], []
    for _ in range(n):
        i, j = rng.integers(0, GRID - 3, 2)
        w, h = rng.integers(1, 4, 2)
        flat = (np.arange(i, i + w)[:, None] * GRID
                + np.arange(j, j + h)[None, :]).ravel()
        keys.append(s2cell.cell_to_dar_key(CELLS[flat]))
        lo = float(rng.integers(0, 40) * 25)
        band = rng.random() < 0.7
        alt_lo.append(lo if band else -np.inf)
        alt_hi.append(lo + 100 if band else np.inf)
        timed = rng.random() < 0.7
        a = T0 + int(rng.integers(-6 * 3600, 6 * 3600))
        t0.append(a * NS if timed else NO_TIME_LO)
        t1.append((a + 3600) * NS if timed else NO_TIME_HI)
        owner.append(int(rng.integers(0, 7)) if rng.random() < 0.3 else -1)
    return (keys, np.asarray(alt_lo, np.float32),
            np.asarray(alt_hi, np.float32), np.asarray(t0, np.int64),
            np.asarray(t1, np.int64), np.asarray(owner, np.int32))


def answers(index, q, *, host_route: bool, tiered: bool = False) -> list:
    """Sorted ids per query.  `tiered`: the table is known to hold its
    records in a tier (a bulk boot's does; the loop's may still hold
    them in the overlay, which the host scans on either route), so the
    route asked for is the route taken."""
    keys, alt_lo, alt_hi, t0, t1, owner = q
    pending = index.table.query_many_submit(
        keys, alt_lo, alt_hi, t0, t1, now=T0 * NS, owner_ids=owner,
        host_route=host_route)
    if tiered:
        assert pending.used_device() == (not host_route)
    return [sorted(ids) for ids in index.table.query_many_collect(pending)]


# ---------------------------------------------------------------------------
# (i) the differential test of the boot
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("storage", ["tpu", "memory"])
@pytest.mark.parametrize("seed", [11, 12, 13, 14])
def test_bulk_boot_equals_the_loop(tmp_path, seed, storage):
    recs = make_log(seed)
    path = str(tmp_path / "dss.wal")
    write_log(path, recs, torn=b'{"t":"scd_op_put","doc":{"id":"torn')
    got = DSSStore(storage=storage, wal_path=path)
    try:
        assert got.boot_stats["mode"] == "bulk"
        assert got.boot_stats["records"] == len(recs)
        assert got.wal.recovered_truncation  # the torn tail ended the log
        ref = looped(recs, storage)
        try:
            assert_same_state(got, ref)
            # the same WAL sequence: what the loop's own boot recovers
            again = WriteAheadLog(path)
            assert got.wal.seq == again.seq == len(recs)
            again.close()
            for cls in KINDS:
                n_writes = sum(
                    1 for r in recs
                    if r["t"] == KINDS[cls][0]
                ) + sum(
                    1 for k, r in enumerate(recs)
                    if r["t"] == KINDS[cls][1]
                    and any(p["t"] == KINDS[cls][0]
                            and p["doc"]["id"] == r["id"]
                            for p in recs[:k])
                )
                a, b = index_of(got, cls), index_of(ref, cls)
                keys = s2cell.cell_to_dar_key(CELLS[:16])
                # bulk: one wholesale bump; a fence reads the floor
                assert a.cell_clock.generation == 1
                assert a.cell_clock.floor == 1
                assert a.cell_clock.fence(keys)[1] == 1
                # loop: a bump per put and per delete that found its
                # id; a fence reads its cells' newest stamp
                assert b.cell_clock.floor == 0
                assert b.cell_clock.generation == n_writes
                assert 0 < b.cell_clock.fence(keys)[1] <= n_writes
        finally:
            ref.close()
    finally:
        got.close()


@pytest.mark.parametrize("route", ["host", "device"])
@pytest.mark.parametrize("seed", [21, 22, 23])
def test_bulk_boot_answers_as_the_loop(tmp_path, monkeypatch, seed, route):
    if route == "device":
        # nothing is small enough for the host scan: every tier with
        # postings answers through the fused kernel
        monkeypatch.setattr(FastTable, "HOST_MAX_CANDIDATES", -1)
    recs = make_log(seed)
    path = str(tmp_path / "dss.wal")
    write_log(path, recs)
    got = DSSStore(storage="tpu", wal_path=path)
    ref = looped(recs, "tpu")
    try:
        q = searches(seed, 300)
        for cls in KINDS:
            a, b = index_of(got, cls), index_of(ref, cls)
            st = a.table._state
            assert st.overlay is None and not st.pending  # one tier
            assert len(st.tiers) == 1
            mine = answers(a, q, host_route=route == "host", tiered=True)
            assert mine == answers(b, q, host_route=route == "host"), cls
            assert sum(map(len, mine)) > 0, cls
    finally:
        got.close()
        ref.close()


def test_bulk_boot_of_an_empty_and_of_a_missing_log(tmp_path):
    missing = DSSStore(storage="tpu", wal_path=str(tmp_path / "a.wal"))
    assert missing.boot_stats == {} and boot.is_empty(missing)
    missing.close()
    # reopened: the log now holds its format record alone
    empty = DSSStore(storage="tpu", wal_path=str(tmp_path / "a.wal"))
    assert empty.boot_stats == {} and boot.is_empty(empty)
    assert empty.scd._op_index.cell_clock.generation == 0
    empty.close()


def test_a_delete_of_an_absent_id_leaves_its_class_untouched(tmp_path):
    path = str(tmp_path / "dss.wal")
    write_log(path, [{"t": "scd_op_del", "id": "nobody", "seq": 1},
                     {"t": "isa_put", "doc": _doc(
                         np.random.default_rng(0), "isa", "i1", 1),
                      "seq": 2}])
    got = DSSStore(storage="tpu", wal_path=path)
    assert got.boot_stats["mode"] == "bulk"
    assert got.scd._op_index.cell_clock.generation == 0  # as the loop
    assert got.rid._isa_index.cell_clock.generation == 1
    assert list(got.rid._isas) == ["i1"]
    got.close()


def test_a_store_that_holds_records_is_not_filled_in_bulk(caplog):
    recs = make_log(5, 50)
    store = looped(recs, "memory")
    resolved = store.boot_resolver()
    resolved.consume(recs)
    with pytest.raises(ValueError, match="empty store"):
        resolved.commit(store)
    with pytest.raises(ValueError):  # not empty: no state to fall back from
        store.apply_log_bulk(resolved)
    store.close()


def test_the_resolver_keeps_the_end_state_and_nothing_of_the_log():
    recs = make_log(7, 2000)
    store = DSSStore(storage="memory")
    resolved = store.boot_resolver()
    resolved.consume(iter(recs))  # any iterable, taken once
    assert resolved.records == len(recs) and not resolved.refused
    ref = looped(recs, "memory")
    for cls in KINDS:
        sub, recmap, _ = MAPS[cls]
        assert list(resolved.live[cls]) == list(
            getattr(getattr(ref, sub), recmap)), cls
    assert store.apply_log_bulk(resolved)
    assert resolved.live == {}  # handed over
    assert_same_state(store, ref)


def test_boot_gauges_are_exported(tmp_path):
    recs = make_log(6, 200)
    path = str(tmp_path / "dss.wal")
    write_log(path, recs)
    got = DSSStore(storage="tpu", wal_path=path)
    stats = got.stats()
    assert stats["dss_boot_records"] == 200
    assert set(stats["dss_boot_seconds"]) == {"parse", "build"}
    assert all(v > 0 for v in stats["dss_boot_seconds"].values())
    assert got.boot_stats["postings"] > 0
    assert got.boot_stats["device_bytes"] > 0
    got.close()


# ---------------------------------------------------------------------------
# (ii) the worker's replica
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [31, 32, 33])
def test_follower_catches_up_in_bulk_then_tails(tmp_path, seed):
    recs = make_log(seed, 1500)
    head, during, after = recs[:1000], recs[1000:1200], recs[1200:]
    path = str(tmp_path / "dss.wal")
    write_log(path, head)
    replica = DSSStore(storage="tpu")
    follower = WalFollower(replica, path)
    bulk = replica.apply_log_bulk

    def bulk_while_the_leader_writes(resolved):
        # records that land while the catch-up runs: after the read,
        # before the batch is applied
        with open(path, "ab") as fh:
            for rec in during:
                fh.write(json.dumps(rec).encode() + b"\n")
        return bulk(resolved)

    replica.apply_log_bulk = bulk_while_the_leader_writes
    assert follower.poll_once() == len(head)
    assert replica.boot_stats["mode"] == "bulk"
    assert replica.boot_stats["records"] == len(head)
    assert follower.applied_seq == len(head)
    # the tail goes record by record, each record once
    assert follower.poll_once() == len(during)
    assert follower.applied_seq == 1200
    with open(path, "ab") as fh:
        for rec in after:
            fh.write(json.dumps(rec).encode() + b"\n")
    assert follower.poll_once() == len(after)
    assert follower.poll_once() == 0
    assert follower.applied_seq == len(recs)
    assert follower.stats()["follower_apply_errors"] == 0
    assert replica.boot_stats["records"] == len(head)  # the boot's alone
    leader = DSSStore(storage="tpu", wal_path=path)  # boots in bulk
    ref = looped(recs, "tpu")
    try:
        assert_same_state(replica, ref)
        assert_same_state(replica, leader)
        # a bump applied twice would show here
        bumped = {m.id: m.notification_index
                  for m in replica.scd._subs.values()}
        assert bumped == {m.id: m.notification_index
                          for m in ref.scd._subs.values()}
        q = searches(seed, 100)
        for cls in KINDS:
            assert answers(index_of(replica, cls), q, host_route=True) \
                == answers(index_of(leader, cls), q, host_route=True), cls
    finally:
        replica.close()
        leader.close()
        ref.close()


def test_follower_start_on_a_log_that_is_not_there_yet(tmp_path):
    path = str(tmp_path / "late.wal")
    replica = DSSStore(storage="memory")
    follower = WalFollower(replica, path)
    assert follower.poll_once() == 0
    write_log(path, make_log(41, 120))
    assert follower.poll_once() == 120
    assert replica.boot_stats["mode"] == "bulk"
    replica.close()


def test_follower_that_holds_records_tails_by_the_loop(tmp_path):
    recs = make_log(42, 100)
    path = str(tmp_path / "dss.wal")
    write_log(path, recs)
    replica = looped(recs[:10], "memory")
    follower = WalFollower(replica, path)
    assert follower.poll_once() == len(recs)  # re-applied: puts replace
    assert replica.boot_stats == {}
    ref = looped(recs[:10] + recs, "memory")
    assert_same_state(replica, ref)


def test_follower_refuses_a_newer_log_format(tmp_path):
    path = str(tmp_path / "dss.wal")
    with open(path, "wb") as fh:
        fh.write(b'{"t":"__format__","version":99}\n')
    follower = WalFollower(DSSStore(storage="memory"), path)
    with pytest.raises(walmod.LogFormatError):
        follower.poll_once()


# ---------------------------------------------------------------------------
# (iii) the fallback
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("where", ["leader", "follower"])
def test_unknown_record_type_boots_by_the_loop_and_says_so(
        tmp_path, caplog, where):
    recs = make_log(51, 400)
    recs.insert(200, {"t": "scd_airspace_future", "id": "x", "seq": 0})
    path = str(tmp_path / "dss.wal")
    write_log(path, recs)
    with caplog.at_level(logging.WARNING, logger="dss.dar"):
        if where == "leader":
            got = DSSStore(storage="tpu", wal_path=path)
        else:
            got = DSSStore(storage="tpu")
            assert WalFollower(got, path).poll_once() == len(recs)
    said = [r.getMessage() for r in caplog.records
            if r.name == "dss.dar"]
    assert any("bulk boot refused" in m and "scd_airspace_future" in m
               and "one by one" in m for m in said), said
    assert got.boot_stats == {"mode": "loop", "records": len(recs)}
    ref = looped(recs, "tpu")
    try:
        assert_same_state(got, ref)
        # the loop's marks: stamps, not a floor
        assert got.scd._op_index.cell_clock.floor == 0
        assert got.scd._op_index.cell_clock.generation == \
            ref.scd._op_index.cell_clock.generation
    finally:
        got.close()
        ref.close()


def test_a_document_the_codec_refuses_fails_the_boot_as_before(tmp_path):
    path = str(tmp_path / "dss.wal")
    write_log(path, [{"t": "scd_op_put", "doc": {"owner": "uss1"},
                      "seq": 1}])
    with pytest.raises(KeyError):  # no id: the loop raises it too
        DSSStore(storage="memory", wal_path=path)


# ---------------------------------------------------------------------------
# the log's reader: one pass for recovery and replay
# ---------------------------------------------------------------------------


def _lines(*objs) -> bytes:
    return b"".join(json.dumps(o).encode() + b"\n" for o in objs)


def scan_log(path, offset: int = 0):
    """(records, end of the valid prefix, max seq) of one LogScan."""
    scan = walmod.LogScan(path, offset)
    recs = list(scan)
    return recs, scan.valid, scan.seq


@pytest.mark.parametrize("tail,kept", [
    (b"", 3),  # clean
    (b'{"t":"x","seq":4', 3),  # torn, no newline
    (b'{"t":"x","se\n', 3),  # torn, with a newline
    (b"7\n", 3),  # rot that decodes as a scalar
    (b"\x00\x00\x00\x00\n", 3),  # NUL run
    (b"\n  \n", 3),  # blank lines pass
])
def test_scan_log_ends_at_the_first_bad_line(tmp_path, tail, kept):
    path = str(tmp_path / "x.wal")
    body = _lines(walmod.format_record(), {"t": "a", "seq": 1},
                  {"t": "b", "seq": 2}, {"t": "c", "seq": 3})
    with open(path, "wb") as fh:
        fh.write(body + tail)
    recs, valid, seq = scan_log(path)
    assert [r["t"] for r in recs] == ["a", "b", "c"][:kept]
    assert seq == 3
    clean = not tail.strip() or tail.isspace()
    assert valid == len(body) + (len(tail) if clean else 0)


def test_a_scan_from_an_offset_and_a_second_iteration_go_on(tmp_path):
    path = str(tmp_path / "x.wal")
    objs = [{"t": "r", "seq": k, "pad": "p" * (k % 7)} for k in range(1, 60)]
    with open(path, "wb") as fh:
        fh.write(_lines(*objs))
    recs, valid, seq = scan_log(path)
    assert recs == objs and valid == os.path.getsize(path) and seq == 59
    cut = len(_lines(*objs[:20]))
    rest, end, _ = scan_log(path, cut)
    assert rest == objs[20:] and end == valid
    # one pass, taken in two parts: nothing twice, nothing lost
    scan = walmod.LogScan(path)
    first = [rec for rec, _ in zip(scan, range(20))]
    scan.drain()
    assert first == objs[:20] and scan.valid == valid and scan.seq == 59
    assert list(scan) == []


def test_scan_log_gates_the_format_at_the_head_only(tmp_path):
    path = str(tmp_path / "x.wal")
    with open(path, "wb") as fh:
        fh.write(_lines({"t": "__format__", "version": 99}))
    with pytest.raises(walmod.LogFormatError):
        scan_log(path)
    assert scan_log(path, 1)[0] == []  # not the head: no gate


def test_a_boot_reads_its_log_once(tmp_path, monkeypatch):
    """The WAL's recovery pass is the boot's read: the store resolves
    the log through it (WriteAheadLog's `sink`) and opens the file for
    reading exactly once."""
    recs = make_log(61, 300)
    path = str(tmp_path / "dss.wal")
    write_log(path, recs)
    opened = []
    monkeypatch.setattr(
        walmod, "open",
        lambda p, mode="r", **kw: opened.append(mode) or open(p, mode, **kw),
        raising=False)
    got = DSSStore(storage="memory", wal_path=path)
    assert opened.count("rb") == 1  # one read; the rest truncate, append
    assert got.boot_stats["mode"] == "bulk" and got.wal.seq == len(recs)
    assert [r["seq"] for r in got.wal.replay()] == list(
        range(1, len(recs) + 1))  # a later replay reads the file
    assert opened.count("rb") == 2
    got.close()


def test_a_sink_that_stops_early_is_drained(tmp_path):
    path = str(tmp_path / "x.wal")
    w = WriteAheadLog(path)
    for k in range(5):
        w.append({"t": "r", "k": k})
    w.close()
    seen = []
    w = WriteAheadLog(path, sink=lambda recs: seen.append(next(iter(recs))))
    assert [r["k"] for r in seen] == [0] and w.seq == 5
    assert w.append({"t": "r", "k": 5}) == 6
    assert [r["k"] for r in w.replay()] == [0, 1, 2, 3, 4, 5]
    w.close()


def test_wal_tail_read_ahead_then_poll(tmp_path):
    from dss_tpu.parallel.replica import _WalTail

    path = str(tmp_path / "x.wal")
    tail = _WalTail(path)
    got = []
    assert tail.read_ahead(got.extend) == (0, 0)
    objs = [{"t": "r", "seq": k} for k in range(1, 9)]
    with open(path, "wb") as fh:
        fh.write(_lines(walmod.format_record(), *objs[:5]) + b'{"t":"r"')
    seq, end = tail.read_ahead(got.extend)
    assert got == objs[:5] and seq == 5
    assert tail.position == 0  # not moved until the records are taken
    tail.advance(end)
    assert tail.position == end and not tail.at_end()  # the torn tail
    with open(path, "ab") as fh:
        fh.write(b',"seq":6}\n' + _lines(*objs[6:]))
    assert tail.poll() == objs[5:]
    assert tail.at_end()
    assert tail.read_ahead(got.extend) == (0, os.path.getsize(path))
