"""A planned flight's 200 as one transaction, against the two calls it
replaced.

services/scd.py put_operation files a flight that asks for an implicit
subscription with ONE store call,
`upsert_operation_with_subscription`: the subscription table is read
once, for the owner's DSS0030 count and for the subscribers, and the
transaction's three journal records leave in one append.  The plain
reference here is what the service did before: `upsert_subscription`
and then `upsert_operation`, inside one transaction.  Over a seeded
stream of flights, on both spatial indexes and with and without a
bound push pipeline, the two must give the same 200s (subscribers and
their notification indices), refuse the same flights with the same
429, offer the pipeline the same notifications and leave the same
store and the same log, byte for byte.

The second half holds the journal to its side of the bargain: one
append a transaction, the log never behind memory, a torn group
replayed to a prefix, and nothing offered to the pipeline before the
records are durable.
"""

from __future__ import annotations

import dataclasses
import json
import os
from datetime import timedelta

import numpy as np
import pytest

from dss_tpu import chaos, errors
from dss_tpu.clock import FakeClock
from dss_tpu.dar import boot, codec
from dss_tpu.dar.dss_store import MAX_SCD_SUBSCRIPTIONS_PER_AREA, DSSStore
from dss_tpu.dar.follower import WalFollower
from dss_tpu.models import scd as scdm
from dss_tpu.push import PushPipeline
from dss_tpu.services.scd import SCDService
from tests.test_fanout_storm import CELLS, G, T0, model_sub, uuid_of

OWNERS = 6
N_SUBS = 240
N_FLIGHTS = 36
HOOKED = "uss1"  # the one USS with a webhook where a pipeline is bound
QUOTA = "quota"  # the owner held at the quota in HOT
# the random stream keeps to the block's first G - 2 rows; HOT is a
# cell of row G - 2 that only the quota owner's subscriptions hold,
# and row G - 1 stays empty
ROWS = G - 2
HOT = np.array([ROWS * G + 3])
EMPTY = np.array([(G - 1) * G + 4, (G - 1) * G + 5])


def seeded_subscriptions(rng) -> list:
    """model_sub arguments: L-shaped footprints of 2-8 cells, windows
    around T0 (some ended by the time the flights are filed), one in
    eight not interested in operations."""
    out = []
    for k in range(N_SUBS):
        a, b = int(rng.integers(1, 5)), int(rng.integers(1, 5))
        if a + b - 1 < 2:
            a = 2
        ci = int(rng.integers(0, ROWS - a + 1))
        cj = int(rng.integers(0, G - b + 1))
        flat = np.array([(ci + d) * G + cj for d in range(a)]
                        + [ci * G + cj + d for d in range(1, b)])
        t0 = T0 + timedelta(seconds=int(rng.integers(-4 * 3600, 3600)))
        t1 = t0 + timedelta(seconds=int(rng.integers(1800, 5 * 3600)))
        out.append((uuid_of(1, k), f"uss{int(rng.integers(0, OWNERS))}",
                    flat, t0, t1, bool(rng.random() >= 0.125)))
    return out


def quota_subscriptions() -> list:
    """The quota owner in HOT: eight live subscriptions that want
    operations, one live that does not (it counts, and is not told),
    and one that has ended (neither)."""
    live0, live1 = T0 - timedelta(hours=1), T0 + timedelta(hours=6)
    n = MAX_SCD_SUBSCRIPTIONS_PER_AREA - 2
    out = [(uuid_of(5, k), QUOTA, np.array([HOT[0], HOT[0] + 1 + k % 2]),
            live0, live1, True) for k in range(n)]
    out.append((uuid_of(5, n), QUOTA, HOT, live0, live1, False))
    out.append((uuid_of(5, n + 1), QUOTA, HOT, T0 - timedelta(hours=3),
                T0 - timedelta(minutes=5), True))
    return out


def seeded_flights(rng) -> list:
    """(op id, owner, flat cells, lo, hi, t_start, t_end, seconds the
    clock moves on before it): rectangles of 1-3 x 1-3 cells, a few of
    them already ended (their implicit subscription is not live), and
    at fixed places the two flights of the quota owner in HOT (the
    first takes its tenth subscription there, the second is refused)
    and one in airspace nobody watches."""
    out = []
    for k in range(N_FLIGHTS):
        w, h = int(rng.integers(1, 4)), int(rng.integers(1, 4))
        i = int(rng.integers(0, ROWS - w + 1))
        j = int(rng.integers(0, G - h + 1))
        flat = np.array([(i + a) * G + (j + b)
                         for a in range(w) for b in range(h)])
        owner = f"uss{int(rng.integers(0, OWNERS))}"
        t0 = T0 + timedelta(seconds=int(rng.integers(-7200, 7200)))
        t1 = t0 + timedelta(seconds=int(rng.integers(600, 3 * 3600)))
        if k in (5, 6):
            owner, flat = QUOTA, HOT
            t0, t1 = T0, T0 + timedelta(hours=5)
        elif k == 9:
            flat = EMPTY
        lo = float(rng.integers(0, 2800))
        out.append((uuid_of(2, k), owner, flat, lo, lo + 40.0, t0, t1,
                    int(rng.integers(0, 400))))
    return out


def flight_of(k, fid, owner, flat, lo, hi, t0, t1):
    sub = model_sub(uuid_of(3, k), owner, flat, t0, t1, True, implicit=True)
    op = scdm.Operation(
        id=fid, owner=owner, start_time=t0, end_time=t1,
        altitude_lower=lo, altitude_upper=hi,
        state=scdm.OperationState.ACCEPTED, cells=CELLS[flat],
        subscription_id=sub.id)
    return op, sub


def two_calls(store, op, sub):
    """The reference: what put_operation did before, one call for the
    subscription and one for the op, in one transaction."""
    with store.scd.transaction():
        store.scd.upsert_subscription(sub)
        return store.scd.upsert_operation(op, [], key_checked=True)


def one_call(store, op, sub):
    return store.scd.upsert_operation_with_subscription(
        op, [], sub, key_checked=True)


@dataclasses.dataclass
class Side:
    store: DSSStore
    wal: str
    pipe: object
    outcomes: list  # per flight: ("200", [(id, index)]) or ("429", None)
    wal_moved: list  # per flight: the journal's counters it moved
    reads: list  # per flight: the subscription table's reads it made


def fly(tmp, storage, push, call, name) -> Side:
    wal = str(tmp / f"{name}.wal")
    clock = FakeClock(T0 - timedelta(hours=5))
    store = DSSStore(storage=storage, clock=clock, wal_path=wal)
    pipe = None
    if push:
        pipe = PushPipeline(workers=1, transport=lambda url, body, hdrs: None)
        store.attach_push(pipe)
        pipe.log.register_hook(HOOKED, "http://hook.example/notify")
    rng = np.random.default_rng(20261017)
    for sid, owner, flat, t0, t1, notify in (
            seeded_subscriptions(rng) + quota_subscriptions()):
        store.scd.upsert_subscription(
            model_sub(sid, owner, flat, t0, t1, notify))
    clock.set(T0)
    # every read of the subscription table a flight makes, whichever
    # way it goes: the quota's count and the subscribers' match
    reads = []
    scd, index = store.scd, store.scd._sub_index
    match, count = scd._push_match_ids, index.max_owner_count

    def counted(fn, what):
        def run(*a, **kw):
            reads[-1].append(what)
            return fn(*a, **kw)
        return run

    scd._push_match_ids = counted(match, "match")
    index.max_owner_count = counted(count, "quota")
    outcomes, wal_moved = [], []
    for k, (fid, owner, flat, lo, hi, t0, t1, dt) in enumerate(
            seeded_flights(rng)):
        clock.advance(seconds=dt)
        op, sub = flight_of(k, fid, owner, flat, lo, hi, t0, t1)
        before = store.wal.stats()
        reads.append([])
        try:
            _, subs = call(store, op, sub)
            outcomes.append(
                ("200", [(s.id, s.notification_index) for s in subs]))
        except errors.StatusError as e:
            assert e.code == errors.Code.RESOURCE_EXHAUSTED, e
            outcomes.append(("429", None))
        after = store.wal.stats()
        wal_moved.append({n: after[n] - before[n] for n in (
            "dss_wal_appends_total", "dss_wal_records_total",
            "dss_wal_bytes_total")})
    return Side(store, wal, pipe, outcomes, wal_moved, reads)


@pytest.fixture(scope="module", params=[
    ("tpu", False), ("tpu", True), ("memory", False), ("memory", True)],
    ids=["tpu", "tpu-push", "memory", "memory-push"])
def flown(request, tmp_path_factory):
    storage, push = request.param
    tmp = tmp_path_factory.mktemp(f"{storage}{int(push)}")
    ref = fly(tmp, storage, push, two_calls, "two")
    got = fly(tmp, storage, push, one_call, "one")
    yield ref, got
    ref.store.close()
    got.store.close()


def test_every_flight_is_answered_as_the_two_calls_answer_it(flown):
    ref, got = flown
    assert got.outcomes == ref.outcomes
    answered = [subs for code, subs in ref.outcomes if code == "200"]
    # crowded airspace: a flight has others to tell
    assert sum(len(s) for s in answered) > 3 * len(answered)


def test_the_subscription_table_is_read_once_a_flight(flown):
    ref, got = flown
    assert all(r == ["quota", "match"] for r in ref.reads
               if r != ["quota"]), ref.reads
    assert all(r == ["match"] for r in got.reads), got.reads


def test_an_owner_at_the_quota_is_refused_alike_and_nothing_is_journalled(
        flown):
    ref, got = flown
    # flight 5 takes the quota owner's tenth live subscription in HOT
    # (the one that does not want operations counts, the ended one does
    # not); flight 6 would be the eleventh
    assert ref.outcomes[5][0] == "200" and ref.outcomes[6][0] == "429"
    assert got.wal_moved[6] == dict.fromkeys(got.wal_moved[6], 0)
    assert ref.wal_moved[6] == got.wal_moved[6]
    assert got.reads[6] == ["match"]  # refused on the one read's count
    assert uuid_of(3, 6) not in got.store.scd._subs
    assert uuid_of(2, 6) not in got.store.scd._ops


def test_an_ended_or_uninterested_subscription_is_not_told(flown):
    ref, got = flown
    n = MAX_SCD_SUBSCRIPTIONS_PER_AREA - 2
    told = {i for i, _ in got.outcomes[5][1]}
    assert {uuid_of(5, k) for k in range(n)} | {uuid_of(3, 5)} == told
    assert uuid_of(5, n) not in told  # notify_for_operations=False
    assert uuid_of(5, n + 1) not in told  # ended


def test_airspace_nobody_watches_names_only_the_flights_own(flown):
    ref, got = flown
    assert got.outcomes[9] == ref.outcomes[9] == (
        "200", [(uuid_of(3, 9), 1)])


def test_a_flight_that_has_ended_is_not_its_own_subscriber(flown):
    """Its implicit subscription is written and is not live, so it is
    not among the subscribers, as a match after the write would say."""
    ref, got = flown
    gone = 0
    for k, (code, subs) in enumerate(got.outcomes):
        sub = got.store.scd._subs.get(uuid_of(3, k))
        if code != "200" or sub is None:
            continue
        own = uuid_of(3, k) in {i for i, _ in subs}
        live = got.store.scd._visible_sub(sub.id) is not None
        gone += not own
        assert own or not live, k
    assert gone >= 1


def test_the_store_and_its_log_end_alike(flown):
    ref, got = flown
    a, b = ref.store.scd, got.store.scd
    assert [codec.scd_sub_to_doc(s) for s in a._subs.values()] == [
        codec.scd_sub_to_doc(s) for s in b._subs.values()]
    assert [codec.op_to_doc(o) for o in a._ops.values()] == [
        codec.op_to_doc(o) for o in b._ops.values()]
    with open(ref.wal, "rb") as fa, open(got.wal, "rb") as fb:
        assert fa.read() == fb.read()
    # the subscription index answers alike
    now = a._now_ns()
    for flat in (np.arange(G * G), HOT, EMPTY, np.arange(0, G * G, 7)):
        assert sorted(a._sub_index.query_ids(CELLS[flat], now=now)) == \
            sorted(b._sub_index.query_ids(CELLS[flat], now=now))


def test_each_flight_journals_once_what_the_two_calls_journalled(flown):
    ref, got = flown
    for k, (m_ref, m_got) in enumerate(zip(ref.wal_moved, got.wal_moved)):
        assert m_got["dss_wal_records_total"] == m_ref[
            "dss_wal_records_total"], k
        assert m_got["dss_wal_bytes_total"] == m_ref[
            "dss_wal_bytes_total"], k
        if got.outcomes[k][0] == "200":
            # scd_sub_put, scd_op_put, scd_sub_bump in one append
            assert m_got["dss_wal_records_total"] == 3, k
            assert m_got["dss_wal_appends_total"] == 1, k


def test_the_pipeline_is_offered_what_the_two_calls_offered(flown):
    ref, got = flown
    if ref.pipe is None:
        assert got.pipe is None
        return
    a, b = ref.pipe.stats(), got.pipe.stats()
    for name in ("dss_push_offers_total", "dss_push_enqueued_total",
                 "dss_push_skipped_total"):
        assert a[name] == b[name], name
    assert b["dss_push_offers_total"] == sum(
        code == "200" for code, _ in got.outcomes)
    assert b["dss_push_enqueued_total"] > 0


# -- the journal ---------------------------------------------------------


def _filed(tmp, storage="tpu", fsync=False, push=False, n=None):
    """A store with the seeded subscriptions and the first `n` seeded
    flights filed by the one call."""
    wal = str(tmp / "dss.wal")
    clock = FakeClock(T0 - timedelta(hours=5))
    store = DSSStore(storage=storage, clock=clock, wal_path=wal,
                     wal_fsync=fsync)
    pipe = None
    if push:
        pipe = PushPipeline(workers=1, transport=lambda url, body, hdrs: None)
        store.attach_push(pipe)
        pipe.log.register_hook(HOOKED, "http://hook.example/notify")
    rng = np.random.default_rng(20261017)
    for sid, owner, flat, t0, t1, notify in seeded_subscriptions(rng):
        store.scd.upsert_subscription(
            model_sub(sid, owner, flat, t0, t1, notify))
    clock.set(T0)
    flights = seeded_flights(rng)
    for k, (fid, owner, flat, lo, hi, t0, t1, _) in enumerate(
            flights[:n]):
        if owner != QUOTA:
            one_call(store, *flight_of(k, fid, owner, flat, lo, hi, t0, t1))
    return store, wal, pipe


def _lines(path) -> list:
    with open(path, "rb") as fh:
        return fh.read().splitlines(keepends=True)


def test_a_flights_200_is_one_append_of_three_lines(tmp_path):
    store, wal, _ = _filed(tmp_path, fsync=True, n=0)
    try:
        calls = []
        append = store.wal.append

        def counted(*records):
            calls.append(len(records))
            return append(*records)

        store.wal.append = counted
        before = store.wal.stats()
        op, sub = flight_of(0, uuid_of(2, 0), "uss2", np.array([0, 1]),
                            10.0, 50.0, T0, T0 + timedelta(hours=1))
        one_call(store, op, sub)
        after = store.wal.stats()
        assert calls == [3]
        recs = [json.loads(ln) for ln in _lines(wal)[-3:]]
        assert [r["t"] for r in recs] == [
            "scd_sub_put", "scd_op_put", "scd_sub_bump"]
        seqs = [r["seq"] for r in recs]
        assert seqs == list(range(seqs[0], seqs[0] + 3))
        assert store.wal.seq == seqs[-1]  # what read-your-writes waits for
        moved = {k: after[k] - before[k] for k in after}
        assert moved["dss_wal_fsyncs_total"] == 1
        assert moved["dss_wal_appends_total"] == 1
        assert (moved["dss_wal_records_total"]
                - moved["dss_wal_appends_total"]) == 2
    finally:
        store.close()


def test_a_one_record_transaction_is_one_append_as_before(tmp_path):
    store, wal, _ = _filed(tmp_path, n=0)
    try:
        before = store.wal.stats()
        store.scd.upsert_subscription(model_sub(
            uuid_of(6, 1), "uss2", np.array([0]), T0,
            T0 + timedelta(hours=1), True))
        after = store.wal.stats()
        assert after["dss_wal_appends_total"] - before[
            "dss_wal_appends_total"] == 1
        assert after["dss_wal_records_total"] - before[
            "dss_wal_records_total"] == 1
    finally:
        store.close()


def _same_scd_state(got, ref) -> None:
    assert [codec.scd_sub_to_doc(s) for s in got.scd._subs.values()] == [
        codec.scd_sub_to_doc(s) for s in ref.scd._subs.values()]
    assert [codec.op_to_doc(o) for o in got.scd._ops.values()] == [
        codec.op_to_doc(o) for o in ref.scd._ops.values()]


@pytest.mark.parametrize("path", ["bulk", "loop", "follower"])
def test_every_way_of_reading_the_log_reaches_the_writers_state(
        tmp_path, monkeypatch, path):
    store, wal, _ = _filed(tmp_path, storage="memory", n=N_FLIGHTS // 2)
    try:
        if path == "follower":
            replica = DSSStore(storage="memory", clock=FakeClock(T0))
            follower = WalFollower(replica, wal)
            follower.poll_once()  # the first catch-up, in bulk
            rng = np.random.default_rng(7)
            for k, (fid, owner, flat, lo, hi, t0, t1, _) in enumerate(
                    seeded_flights(rng)[:8]):
                if owner != QUOTA:
                    one_call(store, *flight_of(
                        100 + k, uuid_of(7, k), owner, flat, lo, hi, t0, t1))
            follower.poll_once()  # then the tail, group by group
            assert follower.applied_seq == store.wal.seq
        else:
            if path == "loop":
                def refuse(self, target):
                    raise ValueError("refused")
                monkeypatch.setattr(boot.Resolver, "commit", refuse)
            replica = DSSStore(storage="memory", clock=FakeClock(T0),
                               wal_path=wal)
            assert replica.boot_stats["mode"] == path
            assert replica.wal.seq == store.wal.seq
        try:
            _same_scd_state(replica, store)
        finally:
            replica.close()
    finally:
        store.close()


def test_a_tear_inside_a_group_replays_to_a_prefix(tmp_path):
    store, wal, _ = _filed(tmp_path, storage="memory", n=3)
    store.close()
    lines = _lines(wal)
    assert [json.loads(ln)["t"] for ln in lines[-3:]] == [
        "scd_sub_put", "scd_op_put", "scd_sub_bump"]
    # the crash came inside the last group's write: its first line
    # whole, its second cut short
    with open(wal, "r+b") as fh:
        fh.truncate(sum(map(len, lines[:-2])) + len(lines[-2]) // 2)
    ref = DSSStore(storage="memory", clock=FakeClock(T0))
    ref._replaying = True
    for ln in lines[1:-2]:
        ref.apply_log_record(json.loads(ln))
    ref._replaying = False
    fresh = DSSStore(storage="memory", clock=FakeClock(T0), wal_path=wal)
    try:
        assert fresh.wal.recovered_truncation
        assert fresh.wal.seq == json.loads(lines[-3])["seq"]
        _same_scd_state(fresh, ref)
        last_sub = json.loads(lines[-3])["doc"]["id"]
        assert last_sub in fresh.scd._subs
        assert json.loads(lines[-2])["doc"]["id"] not in fresh.scd._ops
    finally:
        fresh.close()
        ref.close()


def test_an_exception_inside_the_scope_still_journals_what_memory_holds(
        tmp_path):
    store, wal, pipe = _filed(tmp_path, storage="memory", push=True, n=0)
    try:
        offers = pipe.stats()["dss_push_offers_total"]
        sub = model_sub(uuid_of(6, 2), HOOKED, np.array([0, 1]), T0,
                        T0 + timedelta(hours=1), True)
        op = scdm.Operation(
            id=uuid_of(6, 3), owner=HOOKED, start_time=T0,
            end_time=T0 + timedelta(hours=1), altitude_lower=0.0,
            altitude_upper=50.0, state=scdm.OperationState.ACCEPTED,
            cells=CELLS[np.array([0, 1])])
        with pytest.raises(RuntimeError, match="after the writes"):
            with store.scd.transaction():
                store.scd.upsert_subscription(sub)
                store.scd.upsert_operation(op, [], key_checked=True)
                raise RuntimeError("after the writes")
        # in memory, so in the log: the three records, and a restart
        # holds what the store held
        assert [json.loads(ln)["t"] for ln in _lines(wal)[-3:]] == [
            "scd_sub_put", "scd_op_put", "scd_sub_bump"]
        fresh = DSSStore(storage="memory", clock=FakeClock(T0), wal_path=wal)
        try:
            _same_scd_state(fresh, store)
        finally:
            fresh.close()
        # the transaction failed: its subscribers were not offered
        assert pipe.stats()["dss_push_offers_total"] == offers
    finally:
        store.close()


def test_a_fault_at_the_fsync_offers_nothing_and_answers_no_200(tmp_path):
    store, wal, pipe = _filed(tmp_path, storage="memory", fsync=True,
                              push=True, n=0)
    svc = SCDService(store.scd, store.clock)
    body = {
        "extents": [{
            "volume": {
                "outline_polygon": {"vertices": [
                    {"lat": 34.0, "lng": -118.0},
                    {"lat": 34.0, "lng": -117.99},
                    {"lat": 34.01, "lng": -117.99}]},
                "altitude_lower": {"value": 50.0, "reference": "W84",
                                   "units": "M"},
                "altitude_upper": {"value": 90.0, "reference": "W84",
                                   "units": "M"},
            },
            "time_start": {"value": "2026-07-01T13:00:00Z",
                           "format": "RFC3339"},
            "time_end": {"value": "2026-07-01T14:00:00Z",
                         "format": "RFC3339"},
        }],
        "uss_base_url": "https://uss.example.com",
        "state": "Accepted",
        "new_subscription": {"uss_base_url": "https://uss.example.com"},
    }
    try:
        offers = pipe.stats()["dss_push_offers_total"]
        fsyncs = store.wal.stats()["dss_wal_fsyncs_total"]
        chaos.install_plan(chaos.FaultPlan.from_dict({"seed": 1, "events": [
            {"site": "wal.fsync", "action": "error", "count": 1}]}))
        try:
            with pytest.raises(chaos.FaultError):
                svc.put_operation(uuid_of(6, 4), body, HOOKED)
        finally:
            chaos.clear_plan()
        assert pipe.stats()["dss_push_offers_total"] == offers
        assert store.wal.stats()["dss_wal_fsyncs_total"] == fsyncs
        # written and flushed before the fsync failed: the file holds
        # what memory holds
        assert [json.loads(ln)["t"] for ln in _lines(wal)[-3:]] == [
            "scd_sub_put", "scd_op_put", "scd_sub_bump"]
        # the disk back: the next flight there is answered and offered
        body["key"] = [store.scd._ops[uuid_of(6, 4)].ovn]
        out = svc.put_operation(uuid_of(6, 5), body, HOOKED)
        assert out["operation_reference"]["id"] == uuid_of(6, 5)
        assert pipe.stats()["dss_push_offers_total"] == offers + 1
    finally:
        store.close()


def test_a_write_without_a_transaction_is_appended_at_once(tmp_path):
    """The journal outside any transaction scope (nothing in the store
    journals so; the hook stays whole for one that does)."""
    store = DSSStore(storage="memory", clock=FakeClock(T0),
                     wal_path=str(tmp_path / "solo.wal"))
    try:
        store._journal({"t": "scd_sub_del", "id": "x"})
        assert store.wal.stats()["dss_wal_appends_total"] == 1
        assert os.path.getsize(store.wal.path) > 0
    finally:
        store.close()
