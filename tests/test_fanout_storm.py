"""The subscription storm on the CPU, small: a store on `--storage tpu`'s
index holds ~2,000 seeded subscriptions of the deployment's shape
(scd-fanout-storm-125k: L-shaped, 2-12 cells, 64 owners, a third
already ended) on a 12 x 12 block of level-13 cells, and 60 seeded
flights are filed into it, each with its implicit subscription, as
services/scd.py put_operation files one.

What a flight's write must tell is written out below in plain numpy and
sets (`Reference`: live at now AND shares a cell AND
notify_for_operations) and shares nothing with the program's match
(dss_tpu.push, dar.oracle) or with the benchmark's reference.  It has to
equal, flight for flight and by every route the match can take:

  rqmatch     the push pipeline attached, the planner's device route.
              Under the host scan's cap (65,536 candidate postings a
              batch, far over any flight's) every tier of the table
              answers from its host postings copy and no kernel is
              launched, whatever the planner named;
  kernel      the same with the cap taken away, so that the match
              really runs the fused kernel (on the CPU backend here);
  hostchunk   the pipeline attached, the device class refused: the
              planner's host chunks;
  no_pipeline no pipeline: the index's own query path.

Four ways, one answer: the ids a write returns, every notification
index, the journal's `scd_sub_bump` records, and the indices after the
WAL is replayed into a fresh store.
"""

from __future__ import annotations

import dataclasses
import datetime
import json
import time
from datetime import timedelta, timezone

import numpy as np
import pytest

from dss_tpu import errors
from dss_tpu.clock import FakeClock
from dss_tpu.dar.dss_store import MAX_SCD_SUBSCRIPTIONS_PER_AREA, DSSStore
from dss_tpu.geo import s2cell
from dss_tpu.models import scd as scdm
from dss_tpu.obs import stages
from dss_tpu.ops.fastpath import FastTable
from dss_tpu.push import PushPipeline

T0 = datetime.datetime(2026, 7, 1, 12, 0, 0, tzinfo=timezone.utc)
G = 12  # the block's side
OWNERS = 64
N_SUBS = 2000
N_FLIGHTS = 60
ROUTES = ("rqmatch", "kernel", "hostchunk", "no_pipeline")
HOOKED = "uss3"  # the one USS with a webhook


def block() -> np.ndarray:
    """G x G level-13 cells around 34.05 N 118.25 W, flat index
    i * G + j."""
    shift = 30 - 13
    face, i, j, _ = s2cell.to_face_ij(
        s2cell.cell_id_from_latlng(34.05, -118.25, 30))
    i0, j0 = (int(i) >> shift) - G // 2, (int(j) >> shift) - G // 2
    return np.array([
        s2cell.cell_parent(s2cell.from_face_ij(
            face, (i0 + a) << shift, (j0 + b) << shift), 13)
        for a in range(G) for b in range(G)], dtype=np.uint64)


CELLS = block()


def uuid_of(kind: int, k: int) -> str:
    return f"{kind:08x}-0000-4000-8000-{k:012x}"


def seeded_subscriptions(rng) -> list:
    """(id, owner, flat cells, t_start, t_end, notify_for_operations):
    L-shaped footprints of 2-12 cells, windows as the harness's
    `_times` makes them (about a third already ended), one in ten not
    interested in operations."""
    out = []
    for k in range(N_SUBS):
        a, b = int(rng.integers(1, 8)), int(rng.integers(1, 7))
        if a + b - 1 < 2:
            a = 2
        ci, cj = int(rng.integers(0, G - a + 1)), int(rng.integers(0, G - b + 1))
        flat = [(ci + d) * G + cj for d in range(a)] + [
            ci * G + cj + d for d in range(1, b)]
        t0 = T0 + timedelta(seconds=int(rng.integers(-6 * 3600, 6 * 3600)))
        t1 = t0 + timedelta(seconds=int(rng.integers(1800, 4 * 3600)))
        if abs((t1 - T0).total_seconds()) < 3600:
            t1 += timedelta(hours=2)
        out.append((uuid_of(1, k), f"uss{int(rng.integers(0, OWNERS))}",
                    np.array(flat), t0, t1, bool(rng.random() >= 0.1)))
    return out


def seeded_flights(rng) -> list:
    """(id, owner, flat cells, lo, hi, t_start, t_end): rectangles of
    1-3 x 1-4 cells, a 40 m band, opening 2-4 h ahead."""
    out = []
    for k in range(N_FLIGHTS):
        w, h = int(rng.integers(1, 4)), int(rng.integers(1, 5))
        i, j = int(rng.integers(0, G - w + 1)), int(rng.integers(0, G - h + 1))
        flat = np.array([(i + a) * G + (j + b)
                         for a in range(w) for b in range(h)])
        lo = float(rng.integers(0, 2800))
        t0 = T0 + timedelta(seconds=int(rng.integers(7200, 14400)))
        out.append((uuid_of(2, k), f"uss{k % OWNERS}", flat, lo, lo + 40.0,
                    t0, t0 + timedelta(seconds=int(rng.integers(900, 3600)))))
    return out


class Reference:
    """What the subscriptions hold, in plain numpy and sets."""

    def __init__(self):
        self.ids, self.owner, self.t_end, self.notify = [], [], [], []
        self.member = np.zeros((0, G * G), bool)  # subscription x cell
        self.index = {}

    def add(self, sid, owner, flat, t_end, notify) -> None:
        row = np.zeros((1, G * G), bool)
        row[0, flat] = True
        self.member = np.concatenate([self.member, row])
        self.ids.append(sid)
        self.owner.append(owner)
        self.t_end.append(t_end)
        self.notify.append(notify)
        self.index[sid] = 0

    def notified_by(self, flat, now) -> set:
        """live at now AND shares a cell AND notify_for_operations."""
        shares = self.member[:, flat].any(axis=1)
        return {self.ids[k] for k in np.flatnonzero(shares)
                if self.t_end[k] >= now and self.notify[k]}

    def live_in_cell(self, owner, now) -> np.ndarray:
        """Per cell, the owner's live subscriptions (DSS0030 counts)."""
        mine = np.array([o == owner and t >= now
                         for o, t in zip(self.owner, self.t_end)], bool)
        return self.member[mine].sum(axis=0)


def model_sub(sid, owner, flat, t0, t1, notify, implicit=False):
    return scdm.Subscription(
        id=sid, owner=owner, start_time=t0, end_time=t1, altitude_lo=0.0,
        altitude_hi=3000.0, base_url=f"https://{owner}.example/scd",
        notify_for_operations=notify, implicit_subscription=implicit,
        cells=CELLS[flat])


@dataclasses.dataclass
class Storm:
    route: str
    store: DSSStore
    pipe: object
    ref: Reference
    wal: str
    delivered: list
    returned: list  # per flight: {subscription id: index in the answer}
    expected: list  # per flight: the reference's id set
    stats0: dict
    stats1: dict
    sink: dict
    after: dict  # the reference's indices after the last flight


@pytest.fixture(scope="module", params=ROUTES)
def storm(request, tmp_path_factory):
    route = request.param
    patch = pytest.MonkeyPatch()
    request.addfinalizer(patch.undo)
    if route == "kernel":
        # no batch is small enough for the host scan: every tier that
        # has postings under the flight launches the kernel
        patch.setattr(FastTable, "HOST_MAX_CANDIDATES", -1)
    wal = str(tmp_path_factory.mktemp(route) / "dss.wal")
    clock = FakeClock(T0 - timedelta(hours=7))
    store = DSSStore(storage="tpu", clock=clock, wal_path=wal)
    ref = Reference()
    rng = np.random.default_rng(20261003)
    for sid, owner, flat, t0, t1, notify in seeded_subscriptions(rng):
        store.scd.upsert_subscription(
            model_sub(sid, owner, flat, t0, t1, notify))
        ref.add(sid, owner, flat, t1, notify)
    clock.set(T0)
    pipe, delivered = None, []
    if route != "no_pipeline":
        pipe = PushPipeline(
            workers=1,
            transport=lambda url, body, hdrs: delivered.append((url, body)))
        store.attach_push(pipe)
        pipe.log.register_hook(HOOKED, "http://hook.example/notify")
        if route == "hostchunk":
            pipe.stage("scd_sub")._device_ok = lambda: False
    stats0 = store.stats()
    sink = {}
    stages.set_sink(sink)
    returned, expected = [], []
    try:
        for fid, owner, flat, lo, hi, t0, t1 in seeded_flights(rng):
            # as services/scd.py put_operation: the implicit
            # subscription and the op intent under it, one transaction
            sub = model_sub(uuid_of(3, len(returned)), owner, flat, t0, t1,
                            True, implicit=True)
            ref.add(sub.id, owner, flat, t1, True)
            want = ref.notified_by(flat, T0)
            for sid in want:
                ref.index[sid] += 1
            _, subs = store.scd.upsert_operation_with_subscription(
                scdm.Operation(
                    id=fid, owner=owner, start_time=t0, end_time=t1,
                    altitude_lower=lo, altitude_upper=hi,
                    state=scdm.OperationState.ACCEPTED, cells=CELLS[flat],
                    subscription_id=sub.id),
                [], sub, key_checked=True)
            returned.append({s.id: s.notification_index for s in subs})
            expected.append(want)
    finally:
        stages.set_sink(None)
    yield Storm(route, store, pipe, ref, wal, delivered, returned, expected,
                stats0, store.stats(), sink, dict(ref.index))
    store.close()


def test_the_reference_sees_a_storm(storm):
    sizes = [len(w) for w in storm.expected]
    assert np.mean(sizes) > 40 and min(sizes) >= 1
    ended = sum(t < T0 for t in storm.ref.t_end)
    assert 0.2 < ended / len(storm.ref.ids) < 0.45
    assert any(not n for n in storm.ref.notify)


def test_every_flight_is_answered_with_the_references_subscribers(storm):
    for k, (got, want) in enumerate(zip(storm.returned, storm.expected)):
        assert set(got) == want, (storm.route, k, len(set(got) ^ want))
        assert uuid_of(3, k) in got  # its own implicit subscription


def test_each_matched_subscription_is_bumped_once_and_no_other_moves(storm):
    running = dict.fromkeys(storm.ref.ids, 0)
    for got, want in zip(storm.returned, storm.expected):
        for sid in want:
            running[sid] += 1
        # the answer carries the index as this write left it
        assert got == {sid: running[sid] for sid in want}
    assert running == storm.after
    now = {sid: storm.store.scd._subs[sid].notification_index
           for sid in storm.ref.ids}
    assert now == storm.after
    assert sum(now.values()) == sum(len(w) for w in storm.expected)


def test_the_route_is_the_one_asked_for(storm):
    def moved(name):
        key = f"dss_dar_scd_sub_co_plan_{name}"
        return storm.stats1[key] - storm.stats0.get(key, 0)

    want = {"rqmatch": (N_FLIGHTS, 0), "kernel": (N_FLIGHTS, 0),
            "hostchunk": (0, N_FLIGHTS), "no_pipeline": (0, 0)}[storm.route]
    assert (moved("rqmatch"), moved("hostchunk")) == want
    # what the planner names and what runs are two things: only with
    # the host scan's cap away does a match launch the kernel
    launched = (storm.stats1["dss_push_match_device_total"]
                - storm.stats0["dss_push_match_device_total"])
    assert launched == (N_FLIGHTS if storm.route == "kernel" else 0)


def test_the_journal_holds_each_writes_bumped_ids(storm):
    # the WAL flushes every append
    with open(storm.wal, encoding="utf-8") as fh:
        bumps = [json.loads(ln)["ids"] for ln in fh if '"scd_sub_bump"' in ln]
    assert len(bumps) == N_FLIGHTS
    for k, (ids, want) in enumerate(zip(bumps, storm.expected)):
        assert len(ids) == len(set(ids)) and set(ids) == want, k


def test_a_replay_restores_every_notification_index(storm):
    fresh = DSSStore(storage="tpu", clock=FakeClock(T0), wal_path=storm.wal)
    try:
        assert {sid: fresh.scd._subs[sid].notification_index
                for sid in storm.ref.ids} == storm.after
    finally:
        fresh.close()


def test_the_program_counts_what_it_notified(storm):
    def moved(name):
        return storm.stats1[name] - storm.stats0.get(name, 0)

    told = sum(len(w) for w in storm.expected)
    assert moved("dss_scd_notifying_writes_total") == N_FLIGHTS
    assert moved("dss_scd_subscribers_notified_total") == told
    # the write's legs are stages of the request that writes
    assert storm.sink["sub_bump_ms"] > 0
    pushed = storm.route != "no_pipeline"
    assert ("push_offer_ms" in storm.sink) is pushed
    assert ("push_match_ms" in storm.sink) is pushed
    assert moved("dss_push_offers_total") == (N_FLIGHTS if pushed else 0)


def test_the_pipeline_enqueues_for_the_hook_and_skips_the_rest(storm):
    if storm.pipe is None:
        # no pipeline: the stable key set, all zero, nothing delivered
        assert not storm.delivered and not any(
            storm.stats1[k] for k in ("dss_push_enqueued_total",
                                      "dss_push_skipped_total",
                                      "dss_push_match_queries_total"))
        return
    owner_of = dict(zip(storm.ref.ids, storm.ref.owner))
    hooked = sum(owner_of[sid] == HOOKED
                 for want in storm.expected for sid in want)
    told = sum(len(w) for w in storm.expected)
    assert hooked > 0
    st = storm.pipe.stats()
    assert st["dss_push_enqueued_total"] == hooked
    assert st["dss_push_skipped_total"] == told - hooked
    deadline = time.monotonic() + 20
    while len(storm.delivered) < hooked and time.monotonic() < deadline:
        time.sleep(0.05)
    assert len(storm.delivered) == hooked
    seen = {}
    for _, body in storm.delivered:
        doc = body if isinstance(body, dict) else json.loads(body)
        sub = doc["subscription"]
        seen[sub["id"]] = max(seen.get(sub["id"], 0),
                              sub["notification_index"])
    assert seen == {sid: n for sid, n in storm.after.items()
                    if owner_of[sid] == HOOKED and n}


@pytest.mark.parametrize("held", [MAX_SCD_SUBSCRIPTIONS_PER_AREA - 1,
                                  MAX_SCD_SUBSCRIPTIONS_PER_AREA])
def test_an_owner_at_the_quota_in_a_cell_is_refused(held):
    """DSS0030: an owner with 10 live subscriptions in a cell gets 429
    for the next one there; with 9 it does not.  An ended one does not
    count."""
    store = DSSStore(storage="tpu", clock=FakeClock(T0 - timedelta(hours=7)))
    ref = Reference()
    try:
        hot = np.array([5 * G + 5])
        # one that will have ended by now: filed first, while it counts
        store.scd.upsert_subscription(model_sub(
            uuid_of(4, 99), "uss1", hot, T0 - timedelta(hours=6),
            T0 - timedelta(hours=2), True))
        ref.add(uuid_of(4, 99), "uss1", hot, T0 - timedelta(hours=2), True)
        for k in range(held - 1):
            store.scd.upsert_subscription(model_sub(
                uuid_of(4, k), "uss1", np.array([5 * G + 5, 5 * G + 6 + k % 3]),
                T0 - timedelta(hours=1), T0 + timedelta(hours=3), True))
            ref.add(uuid_of(4, k), "uss1", hot, T0 + timedelta(hours=3), True)
        store.clock.set(T0)
        # the last of the held, filed once the first has ended
        store.scd.upsert_subscription(model_sub(
            uuid_of(4, 98), "uss1", hot, T0, T0 + timedelta(hours=3), True))
        ref.add(uuid_of(4, 98), "uss1", hot, T0 + timedelta(hours=3), True)
        assert ref.live_in_cell("uss1", T0)[hot[0]] == held
        nxt = model_sub(uuid_of(4, 100), "uss1", hot, T0,
                        T0 + timedelta(hours=1), True, implicit=True)
        if held >= MAX_SCD_SUBSCRIPTIONS_PER_AREA:
            with pytest.raises(errors.StatusError) as e:
                store.scd.upsert_subscription(nxt)
            assert e.value.code == errors.Code.RESOURCE_EXHAUSTED
            assert e.value.http_status == 429
        else:
            store.scd.upsert_subscription(nxt)
        # another owner is never held to this one's count
        store.scd.upsert_subscription(dataclasses.replace(
            nxt, id=uuid_of(4, 101), owner="uss2"))
    finally:
        store.close()
