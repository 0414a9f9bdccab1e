"""A subscription's notification-index bump (dar/boot.py bump_sub).

A write that a subscription's volume meets gives the subscription its
next notification index.  The bump is copy-on-write: a new record,
equal to the stored one but for the index and with none of its
remembered wire bytes, is stored under the id, and the old object is
left as it was for any lock-free reader that holds it.  The one helper
makes the bump on the leader's notify paths (SCD and RID), in a
replica's `apply_wal` and in a bulk boot, and the records a write
returns are the stored ones: nothing copies them again.  Under test,
on both storage backends:

  - a reference taken before the bump keeps its index and its memo;
  - the stored record after it is a new object, field for field the
    old one's but the index, with no `_wire` entry;
  - the subscribers a write returns are the stored objects;
  - a replica that applies the journal record by record, and one that
    boots from it in bulk, equal the leader field for field;
  - a subscription search after the bump answers the new index.
"""

from __future__ import annotations

import dataclasses
from datetime import timedelta

import numpy as np
import pytest

from dss_tpu.clock import FakeClock
from dss_tpu.dar.boot import bump_sub
from dss_tpu.dar.dss_store import DSSStore
from dss_tpu.dar.wal import LogScan
from dss_tpu.geo import s2cell
from dss_tpu.models import rid as ridm
from dss_tpu.models import scd as scdm
from dss_tpu.services import serialization as ser
from dss_tpu.services.rid import RIDService
from dss_tpu.services.scd import SCDService
from tests.test_fanout_storm import CELLS, T0, model_sub

STORAGES = ("memory", "tpu")
N_SUBS = 4
FLAT = np.arange(6)  # the block's first six cells: every volume meets them
SUB = "55555555-5555-4555-8555-{:012d}"
OP = "66666666-6666-4666-8666-{:012d}"


def _memo(rec) -> None:
    """Encode `rec` into a search body, so it remembers its bytes as a
    served record does (serialization._joined)."""
    ser._joined(b"subscriptions", [rec], ser.scd_sub_to_json,
                ser.scd_sub_to_json, None)


def _op(n: int) -> scdm.Operation:
    """An op intent over FLAT that every subscription's volume meets."""
    return scdm.Operation(
        id=OP.format(n), owner="uss9",
        start_time=T0 + timedelta(minutes=10),
        end_time=T0 + timedelta(hours=1),
        altitude_lower=50.0, altitude_upper=200.0,
        state=scdm.OperationState.ACCEPTED, cells=CELLS[FLAT])


def _fields(rec) -> dict:
    return {f.name: getattr(rec, f.name) for f in dataclasses.fields(rec)}


def _same_fields(a, b) -> bool:
    fa, fb = _fields(a), _fields(b)
    return fa.keys() == fb.keys() and all(
        np.array_equal(fa[k], fb[k]) if isinstance(fa[k], np.ndarray)
        else fa[k] == fb[k] for k in fa)


class Bumped:
    """A store with N_SUBS subscriptions over FLAT, each of them served
    once (so it carries a wire memo), then one op intent written into
    their cells: every one is bumped once."""

    def __init__(self, storage, tmp_path):
        self.clock = FakeClock(T0)
        self.wal = str(tmp_path / "dss.wal")
        self.store = DSSStore(storage=storage, clock=self.clock,
                              wal_path=self.wal)
        scd = self.store.scd
        self.ids = [SUB.format(k) for k in range(N_SUBS)]
        for k, sid in enumerate(self.ids):
            scd.upsert_subscription(model_sub(
                sid, f"uss{k}", FLAT, T0, T0 + timedelta(hours=2), True))
        self.before = {sid: scd._subs[sid] for sid in self.ids}
        for rec in self.before.values():
            _memo(rec)
        self.memos = {sid: rec.__dict__[ser._WIRE]
                      for sid, rec in self.before.items()}
        _, self.returned = scd.upsert_operation(_op(1), [], key_checked=True)
        self.after = {sid: scd._subs[sid] for sid in self.ids}

    def close(self):
        self.store.close()


@pytest.fixture(params=STORAGES)
def bumped(request, tmp_path):
    b = Bumped(request.param, tmp_path)
    yield b
    b.close()


def test_a_reference_taken_before_a_bump_keeps_its_index_and_memo(bumped):
    for sid, old in bumped.before.items():
        assert old.notification_index == 0
        assert bumped.after[sid].notification_index == 1
        memo = old.__dict__[ser._WIRE]
        assert memo is bumped.memos[sid] and memo[0]() is old
        assert b'"notification_index": 0' in memo[1]


def test_the_stored_record_after_a_bump_is_a_new_object(bumped):
    for sid, old in bumped.before.items():
        new = bumped.after[sid]
        assert new is not old and type(new) is type(old)
        assert ser._WIRE not in new.__dict__
        want = _fields(old)
        want["notification_index"] += 1
        got = _fields(new)
        assert got.keys() == want.keys()
        # the copy is shallow: every other field is the same object
        for name in want:
            if name != "notification_index":
                assert got[name] is want[name], name
        assert got["notification_index"] == want["notification_index"]


def test_the_subscribers_a_write_returns_are_the_stored_records(bumped):
    assert sorted(s.id for s in bumped.returned) == bumped.ids
    for s in bumped.returned:
        assert s is bumped.store.scd._subs[s.id]
        assert s.notification_index == 1
    # a second write bumps again, and leaves what the first returned
    first = list(bumped.returned)
    _, again = bumped.store.scd.upsert_operation(_op(2), [], key_checked=True)
    assert [s.notification_index for s in first] == [1] * N_SUBS
    assert [s.notification_index for s in again] == [2] * N_SUBS


def test_the_helper_leaves_an_absent_id_absent():
    subs = {}
    assert bump_sub(subs, "nope") is None
    assert subs == {}


def _ring(half=0.0005) -> list:
    """(lat, lng) corners of a small square at the centre of the
    block's first cell, counter-clockwise."""
    lat, lng = s2cell.xyz_to_latlng(s2cell.cell_center(CELLS[0]))
    return [(lat - half, lng - half), (lat - half, lng + half),
            (lat + half, lng + half), (lat + half, lng - half)]


def _aoi() -> dict:
    return {
        "volume": {
            "outline_polygon": {"vertices": [
                {"lat": a, "lng": b} for a, b in _ring()]},
            "altitude_lower": {"value": 50.0, "reference": "W84",
                               "units": "M"},
            "altitude_upper": {"value": 200.0, "reference": "W84",
                               "units": "M"},
        },
        "time_start": {"value": ser.format_time(T0 + timedelta(minutes=5)),
                       "format": "RFC3339"},
        "time_end": {"value": ser.format_time(T0 + timedelta(minutes=50)),
                     "format": "RFC3339"},
    }


def _rid_sub(k: int) -> ridm.Subscription:
    return ridm.Subscription(
        id=SUB.format(k), owner=f"uss{k}", url="https://uss.example/isa",
        cells=CELLS[FLAT], start_time=T0, end_time=T0 + timedelta(hours=2),
        altitude_lo=0.0, altitude_hi=3000.0)


def _apply(replica, wal_path) -> int:
    """The follower's loop (dar/follower.py poll_once): every record of
    the leader's journal, one by one -> the bump records applied."""
    bumps = 0
    with replica._lock:
        replica._replaying = True
        try:
            for rec in LogScan(wal_path):
                bumps += rec.get("t", "").endswith("_sub_bump")
                replica.apply_log_record(rec)
        finally:
            replica._replaying = False
    return bumps


@pytest.mark.parametrize("storage", STORAGES)
@pytest.mark.parametrize("boot", ["loop", "bulk"])
def test_a_replica_of_the_journal_equals_the_leader(storage, boot, tmp_path):
    clock = FakeClock(T0)
    wal = str(tmp_path / "dss.wal")
    leader = DSSStore(storage=storage, clock=clock, wal_path=wal)
    try:
        for k in range(N_SUBS):
            leader.scd.upsert_subscription(model_sub(
                SUB.format(k), f"uss{k}", FLAT, T0,
                T0 + timedelta(hours=2), True))
            leader.rid.insert_subscription(_rid_sub(k))
        for n in range(3):  # three notifying writes of each kind
            leader.scd.upsert_operation(_op(n), [], key_checked=True)
            assert len(leader.rid.update_notification_idxs_in_cells(
                CELLS[FLAT])) == N_SUBS
        if boot == "loop":
            replica = DSSStore(storage=storage, clock=FakeClock(T0))
            assert _apply(replica, wal) == 6
        else:
            replica = DSSStore(storage=storage, clock=FakeClock(T0),
                               wal_path=wal)
            assert replica.boot_stats["mode"] == "bulk"
        try:
            for sub_store in ("scd", "rid"):
                lead = getattr(leader, sub_store)._subs
                copy = getattr(replica, sub_store)._subs
                assert copy.keys() == lead.keys()
                for sid, rec in lead.items():
                    assert rec.notification_index == 3
                    assert _same_fields(copy[sid], rec), (sub_store, sid)
        finally:
            replica.close()
    finally:
        leader.close()


@pytest.mark.parametrize("storage", STORAGES)
def test_a_subscription_search_after_a_bump_answers_the_new_index(
        storage, tmp_path):
    clock = FakeClock(T0)
    store = DSSStore(storage=storage, clock=clock)
    try:
        scd = SCDService(store.scd, clock)
        rid = RIDService(store.rid, clock)
        store.scd.upsert_subscription(model_sub(
            SUB.format(0), "uss1", FLAT, T0, T0 + timedelta(hours=2), True))
        store.rid.insert_subscription(_rid_sub(1))
        area = {"area_of_interest": _aoi()}

        def scd_index():
            return [s["notification_index"] for s in
                    scd.query_subscriptions(area, "uss1")["subscriptions"]]

        rid_area = ",".join(f"{a},{b}" for a, b in _ring())

        def rid_index():
            return [s["notification_index"] for s in
                    rid.search_subscriptions(rid_area, "uss1")
                    ["subscriptions"]]

        assert scd_index() == [0] and rid_index() == [0]
        store.scd.upsert_operation(_op(1), [], key_checked=True)
        store.rid.update_notification_idxs_in_cells(CELLS[FLAT])
        assert scd_index() == [1] and rid_index() == [1]
    finally:
        store.close()
