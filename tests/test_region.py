"""Multi-instance DSS Region interop tests.

The analog of the reference's interoperability suite
(test/interoperability/interop_test_suite.py:38-60): several live DSS
instances share one region log; every write on any primary must become
visible on all the others, for every choice of primary.  Plus the
failure-path tests the reference gets from CRDB: lease fencing, crash
resync, late-join recovery, and region-log durability.

Instances here are real DSSStore objects in region mode talking to a
real region log server over HTTP on localhost (the DCN stand-in).
"""

from __future__ import annotations

import asyncio
import os
import threading
import time
import uuid
from datetime import datetime, timedelta, timezone

import pytest
from aiohttp import web

from dss_tpu import errors
from dss_tpu.dar.dss_store import DSSStore
from dss_tpu.region.client import (
    RegionClient,
    RegionError,
    SnapshotRequired,
)
from dss_tpu.region.log_server import build_region_app
from dss_tpu.services.rid import RIDService
from dss_tpu.services.scd import SCDService
from dss_tpu.services.serialization import format_time
from tests.wire import body_json

POLL_S = 0.02  # tail-poll interval for all test instances
# generous vs the 20 ms poll: on a contended 1-core CI host the
# aiohttp log-server thread can be starved for seconds mid-suite
# (observed ~1-in-4 full-suite flakes at 3 s); the deadline only costs
# time on the FAILURE path
VISIBILITY_DEADLINE_S = 15.0


class RegionServerThread:
    """Run the region log app on a background event loop; real sockets."""

    def __init__(self, wal_path=None, auth_token=None, port=0, **kw):
        self._loop = asyncio.new_event_loop()
        self._app = build_region_app(wal_path, auth_token=auth_token, **kw)
        self._started = threading.Event()
        self.port = None
        self._want_port = port  # 0 = ephemeral; fixed for restarts
        self._runner = None
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        assert self._started.wait(10), "region server failed to start"
        node = self._app.get("region_node")
        if node is not None and node.advertise_url is None:
            # ephemeral port: only known now.  Without it a primary
            # later repointed into a mirror cannot register itself.
            node.advertise_url = self.url

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def _run(self):
        asyncio.set_event_loop(self._loop)
        self._runner = web.AppRunner(self._app)
        self._loop.run_until_complete(self._runner.setup())
        site = web.TCPSite(
            self._runner, "127.0.0.1", self._want_port,
            reuse_address=True,
        )
        self._loop.run_until_complete(site.start())
        self.port = site._server.sockets[0].getsockname()[1]
        self._started.set()
        self._loop.run_forever()
        self._loop.run_until_complete(self._runner.cleanup())

    def stop(self):
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=5)


def make_instance(url, name, token=None, storage="memory", snapshot_every=512):
    return DSSStore(
        storage=storage,
        region_url=url,
        region_token=token,
        region_poll_interval_s=POLL_S,
        region_snapshot_every=snapshot_every,
        instance_id=name,
    )


def wait_until(fn, deadline_s=VISIBILITY_DEADLINE_S):
    """Poll fn until it returns non-None; -> (value, elapsed_s)."""
    t0 = time.monotonic()
    while True:
        v = fn()
        if v is not None:
            return v, time.monotonic() - t0
        if time.monotonic() - t0 > deadline_s:
            raise AssertionError("not visible within deadline")
        time.sleep(0.005)


def rid_extents(lat=37.03, lng=-122.03, half=0.02):
    now = datetime.now(timezone.utc)
    return {
        "spatial_volume": {
            "footprint": {
                "vertices": [
                    {"lat": lat - half, "lng": lng - half},
                    {"lat": lat - half, "lng": lng + half},
                    {"lat": lat + half, "lng": lng + half},
                    {"lat": lat + half, "lng": lng - half},
                ]
            },
            "altitude_lo": 20.0,
            "altitude_hi": 400.0,
        },
        "time_start": format_time(now + timedelta(minutes=1)),
        "time_end": format_time(now + timedelta(hours=2)),
    }


def scd_extent(lat=40.0, lng=-100.0, half=0.02, alt=(50.0, 200.0)):
    now = datetime.now(timezone.utc)
    return {
        "volume": {
            "outline_polygon": {
                "vertices": [
                    {"lat": lat - half, "lng": lng - half},
                    {"lat": lat - half, "lng": lng + half},
                    {"lat": lat + half, "lng": lng + half},
                    {"lat": lat + half, "lng": lng - half},
                ]
            },
            "altitude_lower": {"value": alt[0], "reference": "W84", "units": "M"},
            "altitude_upper": {"value": alt[1], "reference": "W84", "units": "M"},
        },
        "time_start": {
            "value": format_time(now + timedelta(minutes=1)),
            "format": "RFC3339",
        },
        "time_end": {
            "value": format_time(now + timedelta(hours=1)),
            "format": "RFC3339",
        },
    }


def op_params(**kw):
    p = {
        "extents": [scd_extent()],
        "uss_base_url": "https://uss1.example.com",
        "new_subscription": {
            "uss_base_url": "https://uss1.example.com",
            "notify_for_constraints": False,
        },
        "state": "Accepted",
        "old_version": 0,
        "key": [],
    }
    p.update(kw)
    return p


@pytest.fixture
def region():
    server = RegionServerThread()
    stores = [make_instance(server.url, f"dss-{i}") for i in range(3)]
    yield server, stores
    for s in stores:
        s.close()
    server.stop()


# -- the interop suite ------------------------------------------------------


def test_rid_interop_all_primary_permutations(region):
    """interop_test_suite.py:38-60: create on each primary in turn,
    read on every other instance; versions must agree everywhere."""
    server, stores = region
    services = [RIDService(s.rid, s.clock) for s in stores]
    staleness = []
    for primary in range(3):
        isa_id = str(uuid.uuid4())
        out = services[primary].create_isa(
            isa_id,
            {"extents": rid_extents(), "flights_url": "https://u.example/f"},
            f"uss{primary}",
        )
        version = out["service_area"]["version"]
        # read-your-writes on the primary: immediate, no polling
        got = services[primary].get_isa(isa_id)
        assert got["service_area"]["version"] == version
        for other in range(3):
            if other == primary:
                continue

            def see():
                try:
                    return services[other].get_isa(isa_id)
                except errors.StatusError:
                    return None

            got, dt = wait_until(see)
            staleness.append(dt)
            assert got["service_area"]["version"] == version
            assert got["service_area"]["owner"] == f"uss{primary}"
    bound = max(staleness)
    print(f"\nmeasured cross-instance staleness: max {bound*1000:.1f} ms "
          f"over {len(staleness)} reads (poll interval {POLL_S*1000:.0f} ms)")
    assert bound < VISIBILITY_DEADLINE_S


def test_rid_update_and_search_across_instances(region):
    """Write on A, version-fenced update on B, search on C."""
    server, stores = region
    services = [RIDService(s.rid, s.clock) for s in stores]
    isa_id = str(uuid.uuid4())
    v1 = services[0].create_isa(
        isa_id, {"extents": rid_extents(), "flights_url": "https://u.example/f"},
        "uss1",
    )["service_area"]["version"]

    # B sees it, then updates it using A's version as the fencing token
    wait_until(lambda: stores[1].rid.get_isa(isa_id))
    out = services[1].update_isa(
        isa_id, v1,
        {"extents": rid_extents(), "flights_url": "https://u.example/f2"},
        "uss1",
    )
    v2 = out["service_area"]["version"]
    assert v2 != v1

    # a stale token is rejected on any instance (region-current check);
    # C must have tailed the create first or it 404s instead of 409ing
    wait_until(lambda: stores[2].rid.get_isa(isa_id))
    with pytest.raises(errors.StatusError) as ei:
        services[2].update_isa(
            isa_id, v1,
            {"extents": rid_extents(), "flights_url": "https://u.example/f3"},
            "uss1",
        )
    assert ei.value.http_status == 409

    # C's search converges to v2
    def see_v2():
        hits = body_json(services[2].search_isas(
            "37.0,-122.0,37.06,-122.0,37.06,-122.06,37.0,-122.06"
        ))["service_areas"]
        return next(
            (h for h in hits if h["id"] == isa_id and h["version"] == v2), None
        )

    wait_until(see_v2)


def test_scd_conflict_detected_across_instances(region):
    """The reference's core promise: USS2 (on another DSS instance)
    cannot claim airspace overlapping USS1's operation without
    presenting its OVN (prober two-USS flow, operations_handler.go
    :252-280)."""
    server, stores = region
    scd = [SCDService(s.scd, s.clock) for s in stores]
    op1 = str(uuid.uuid4())
    ref1 = scd[0].put_operation(op1, op_params(), "uss1")["operation_reference"]

    # instance 1: overlapping op, no key -> conflict listing op1.
    # A rejected conflict is a routine outcome: it must never trigger a
    # drop-state-and-replay resync (VERDICT r3 weak #3).
    resyncs = {"n": 0}
    real_resync = stores[1].region._resync_locked

    def counting_resync():
        resyncs["n"] += 1
        return real_resync()

    stores[1].region._resync_locked = counting_resync
    op2 = str(uuid.uuid4())

    def try_conflict():
        try:
            scd[1].put_operation(op2, op_params(), "uss2")
            return "no-conflict"
        except errors.StatusError as e:
            if e.code == errors.Code.MISSING_OVNS:
                return e
            return None

    err, _ = wait_until(try_conflict)
    assert err != "no-conflict", "conflict missed across instances"
    # the AirspaceConflictResponse wire body (pkg/scd/errors/errors.go:22-53)
    body = err.details
    assert body["message"]
    conflicting = body["entity_conflicts"]
    assert any(c["operation_reference"]["id"] == op1 for c in conflicting)
    # the rejected caller must be handed the conflicting op's OVN — that
    # is the point of the response
    ovns = [c["operation_reference"].get("ovn") for c in conflicting]
    assert ref1["ovn"] in ovns

    assert resyncs["n"] == 0, "a routine conflict rejection triggered a resync"
    # local state is intact: op1 still visible on the rejected instance
    wait_until(lambda: stores[1].scd._visible_op(op1))

    # with the OVN presented, the overlapping op is accepted
    out = scd[1].put_operation(
        op2, op_params(key=[ref1["ovn"]]), "uss2"
    )
    assert out["operation_reference"]["version"] == 1

    # instance 2 sees both
    def see_both():
        try:
            a = scd[2].get_operation(op1, "uss1")
            b = scd[2].get_operation(op2, "uss2")
            return (a, b)
        except errors.StatusError:
            return None

    wait_until(see_both)


def test_rid_notification_fanout_crosses_instances(region):
    """Subscription on B; ISA created on A must return B's subscriber
    and bump its notification index everywhere."""
    server, stores = region
    services = [RIDService(s.rid, s.clock) for s in stores]
    sub_id = str(uuid.uuid4())
    services[1].create_subscription(
        sub_id,
        {
            "extents": rid_extents(),
            "callbacks": {
                "identification_service_area_url": "https://u2.example/isa"
            },
        },
        "uss2",
    )

    isa_id = str(uuid.uuid4())

    def create_seeing_sub():
        out = services[0].create_isa(
            isa_id if isa_id else None,
            {"extents": rid_extents(), "flights_url": "https://u.example/f"},
            "uss1",
        )
        subs = out["subscribers"]
        return out if subs else None

    # the write-through catch-up means A sees B's subscription at
    # write validation time, with NO visibility wait needed
    out = create_seeing_sub()
    assert out is not None, "write-through catch-up missed B's subscription"
    assert out["subscribers"][0]["subscriptions"][0]["notification_index"] == 1

    def bumped_on_b():
        sub = stores[1].rid.get_subscription(sub_id)
        return sub if sub and sub.notification_index == 1 else None

    wait_until(bumped_on_b)


def test_late_joiner_recovers_full_state(region):
    server, stores = region
    services = [RIDService(s.rid, s.clock) for s in stores]
    ids = [str(uuid.uuid4()) for _ in range(5)]
    for i, isa_id in enumerate(ids):
        services[i % 3].create_isa(
            isa_id,
            {"extents": rid_extents(), "flights_url": "https://u.example/f"},
            "uss1",
        )
    late = make_instance(server.url, "dss-late")
    try:
        for isa_id in ids:
            assert late.rid.get_isa(isa_id) is not None, "late joiner missed a record"
    finally:
        late.close()


def test_lease_contention_write_waits_for_expiry(region):
    """A stuck writer's lease fences out others only until its TTL."""
    server, stores = region
    svc = RIDService(stores[0].rid, stores[0].clock)
    # simulate a crashed writer holding the lease (never releases)
    stuck = RegionClient(server.url, "stuck-writer", lease_ttl_s=0.8)
    stuck.acquire_lease()
    t0 = time.monotonic()
    svc.create_isa(
        str(uuid.uuid4()),
        {"extents": rid_extents(), "flights_url": "https://u.example/f"},
        "uss1",
    )
    dt = time.monotonic() - t0
    assert dt >= 0.5, f"write should have waited for lease expiry, took {dt:.2f}s"


def test_fenced_append_resyncs_and_recovers(region):
    """An append that loses the lease mid-write must not leave the
    fenced instance's local state diverged from the region."""
    server, stores = region
    svc = RIDService(stores[0].rid, stores[0].clock)
    coord = stores[0].region
    coord._optimistic = False  # exercising the lease flow explicitly
    real_append = coord._client.append
    calls = {"n": 0}

    def flaky_append(token, records, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RegionError("simulated fence: lease lost")
        return real_append(token, records, **kw)

    coord._client.append = flaky_append
    isa_id = str(uuid.uuid4())
    with pytest.raises(errors.StatusError) as ei:
        svc.create_isa(
            isa_id,
            {"extents": rid_extents(), "flights_url": "https://u.example/f"},
            "uss1",
        )
    assert ei.value.http_status == 503
    # rolled back: the ISA is NOT in local state (it never hit the log)
    assert stores[0].rid.get_isa(isa_id) is None
    # and the instance still works (resync left it clean)
    out = svc.create_isa(
        isa_id,
        {"extents": rid_extents(), "flights_url": "https://u.example/f"},
        "uss1",
    )
    assert out["service_area"]["id"] == isa_id
    assert calls["n"] == 2


def test_region_log_durability(tmp_path):
    """Region server restart: instances recover the full DAR from the
    log's WAL (checkpoint/resume, SURVEY.md §5)."""
    wal = str(tmp_path / "region.wal")
    server = RegionServerThread(wal_path=wal)
    store = make_instance(server.url, "dss-0")
    svc = RIDService(store.rid, store.clock)
    isa_id = str(uuid.uuid4())
    svc.create_isa(
        isa_id, {"extents": rid_extents(), "flights_url": "https://u.example/f"},
        "uss1",
    )
    store.close()
    server.stop()

    server2 = RegionServerThread(wal_path=wal)
    try:
        store2 = make_instance(server2.url, "dss-1")
        try:
            assert store2.rid.get_isa(isa_id) is not None
        finally:
            store2.close()
    finally:
        server2.stop()


def test_region_auth_enforced(tmp_path):
    server = RegionServerThread(auth_token="s3cret")
    try:
        with pytest.raises(RegionError):
            make_instance(server.url, "dss-bad", token="wrong")
        good = make_instance(server.url, "dss-good", token="s3cret")
        try:
            svc = RIDService(good.rid, good.clock)
            svc.create_isa(
                str(uuid.uuid4()),
                {"extents": rid_extents(), "flights_url": "https://u.example/f"},
                "uss1",
            )
        finally:
            good.close()
    finally:
        server.stop()


def test_region_mode_on_tpu_storage(region):
    """One smoke pass with the DarTable index backend in region mode."""
    server, stores = region
    tpu_store = make_instance(server.url, "dss-tpu", storage="tpu")
    try:
        svc = RIDService(tpu_store.rid, tpu_store.clock)
        isa_id = str(uuid.uuid4())
        svc.create_isa(
            isa_id,
            {"extents": rid_extents(), "flights_url": "https://u.example/f"},
            "uss1",
        )
        # visible via the fused path on the tpu instance itself
        hits = body_json(svc.search_isas(
            "37.0,-122.0,37.06,-122.0,37.06,-122.06,37.0,-122.06"
        ))["service_areas"]
        assert any(h["id"] == isa_id for h in hits)
        # and on a memory-backed peer
        wait_until(lambda: stores[0].rid.get_isa(isa_id))
    finally:
        tpu_store.close()


# -- region v2: rollback, snapshots/compaction, robustness -------------------


def test_txn_rollback_without_resync(region):
    """An aborted txn that already journaled records rolls back from
    captured undo state — no resync, nothing visible anywhere, and the
    instance keeps working (the reference's txn-rollback analog,
    pkg/scd/store/store.go:83-130)."""
    server, stores = region
    store = stores[0]
    scd_svc = SCDService(store.scd, store.clock)
    coord = store.region

    # seed one op so there is pre-existing state to preserve
    op1 = str(uuid.uuid4())
    scd_svc.put_operation(op1, op_params(), "uss1")
    base_resyncs = coord.stats()["region_resyncs"]

    marker = str(uuid.uuid4())

    class Boom(Exception):
        pass

    with pytest.raises(Boom):
        with store.scd.transaction():
            # journals a record into the txn buffer...
            store.scd.upsert_subscription(
                __import__("dss_tpu.models.scd", fromlist=["scd"]).Subscription(
                    id=marker,
                    owner="uss1",
                    start_time=datetime.now(timezone.utc),
                    end_time=datetime.now(timezone.utc) + timedelta(hours=1),
                    altitude_lo=0.0,
                    altitude_hi=100.0,
                    cells=store.scd._ops[op1].cells,
                    base_url="https://uss1.example.com",
                    notify_for_operations=True,
                )
            )
            # ...then the txn aborts
            raise Boom()

    st = coord.stats()
    assert st["region_resyncs"] == base_resyncs, "rollback resynced"
    assert st["region_rollbacks"] >= 1
    # nothing local, nothing region-visible
    assert store.scd._subs.get(marker) is None
    time.sleep(POLL_S * 5)
    assert stores[1].scd._subs.get(marker) is None
    # pre-existing state intact, instance still writable
    assert store.scd._visible_op(op1) is not None
    op2 = str(uuid.uuid4())
    scd_svc.put_operation(
        op2, op_params(extents=[scd_extent(lat=44.0)]), "uss1"
    )
    wait_until(lambda: stores[2].scd._visible_op(op2))


def test_snapshot_compaction_bounds_late_join(region):
    """VERDICT r3 #4: with snapshots + compaction, boot/late-join fetch
    snapshot + tail instead of replaying history — bounded fetches over
    a log with >=10k records (the CRDB range-snapshot analog,
    implementation_details.md:11-42)."""
    server, stores = region
    store = stores[0]
    rid_svc = RIDService(store.rid, store.clock)

    # one real write gives us a template doc in region format
    isa_id = str(uuid.uuid4())
    rid_svc.create_isa(
        isa_id, {"extents": rid_extents(), "flights_url": "https://u.example/f"},
        "uss1",
    )
    from dss_tpu.dar import codec

    template = codec.isa_to_doc(store.rid._isas[isa_id])

    # bulk-append 10k records (200 entries x 50) straight to the log —
    # the history a long-lived region accumulates
    client = RegionClient(server.url, "bulk-writer")
    n_entries, per = 200, 50
    made = []
    for e in range(n_entries):
        token, _head = client.acquire_lease()
        recs = []
        for i in range(per):
            doc = dict(template, id=str(uuid.uuid4()))
            made.append(doc["id"])
            recs.append({"t": "isa_put", "doc": doc})
        client.append(token, recs, release=True)

    # the live instance tails up to head, then uploads a snapshot and
    # the log compacts below it
    wait_until(
        lambda: store.region.applied >= n_entries + 1 or None,
        deadline_s=30,
    )
    store.region._snapshot_every = 1  # due for a snapshot immediately
    # the tail poller serializes + uploads the snapshot off the write path
    wait_until(
        lambda: store.region._last_snapshot >= store.region.applied or None,
        deadline_s=30,
    )
    with pytest.raises(SnapshotRequired):
        client.fetch(0)  # history below the snapshot is gone

    # late joiner: bounded fetches (snapshot + tail), full state
    fetches = {"n": 0}
    orig_fetch = RegionClient.fetch

    def counting_fetch(self, from_index):
        if self.instance_id == "dss-late":
            fetches["n"] += 1
        return orig_fetch(self, from_index)

    RegionClient.fetch = counting_fetch
    try:
        late = make_instance(server.url, "dss-late")
    finally:
        RegionClient.fetch = orig_fetch
    try:
        assert late.region.applied == store.region.applied
        assert late.rid.get_isa(isa_id) is not None
        for got_id in (made[0], made[len(made) // 2], made[-1]):
            assert late.rid.get_isa(got_id) is not None
        assert len(late.rid._isas) == len(store.rid._isas)
        # bootstrap fetch count is bounded by the post-snapshot tail,
        # not by the 10k-record history
        assert fetches["n"] <= 4, fetches
    finally:
        late.close()


def test_client_malformed_response_is_region_error():
    """ADVICE r3: a 200 with a non-JSON or wrong-shape body must surface
    as RegionError (-> 503 UNAVAILABLE), not a bare KeyError/TypeError
    (-> internal 500)."""

    app = web.Application()

    async def ok_text(request):
        return web.Response(text="ok")  # 200, not JSON

    async def wrong_shape(request):
        return web.json_response({"unexpected": True})

    app.router.add_post("/lease", ok_text)
    app.router.add_get("/records", wrong_shape)
    app.router.add_get("/snapshot", wrong_shape)

    loop = asyncio.new_event_loop()
    started = threading.Event()
    holder = {}

    def run():
        asyncio.set_event_loop(loop)
        runner = web.AppRunner(app)
        loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", 0)
        loop.run_until_complete(site.start())
        holder["port"] = site._server.sockets[0].getsockname()[1]
        started.set()
        loop.run_forever()
        loop.run_until_complete(runner.cleanup())

    th = threading.Thread(target=run, daemon=True)
    th.start()
    assert started.wait(10)
    try:
        client = RegionClient(
            f"http://127.0.0.1:{holder['port']}", "c", acquire_timeout_s=0.2
        )
        with pytest.raises(RegionError):
            client.acquire_lease()
        with pytest.raises(RegionError):
            client.fetch(0)
        with pytest.raises(RegionError):
            client.get_snapshot()
    finally:
        loop.call_soon_threadsafe(loop.stop)
        th.join(timeout=5)


def test_resync_failure_keeps_serving_old_state(region):
    """ADVICE r3: when the region is unreachable and local state is
    dirty, reads keep serving the previous (stale-but-consistent)
    state; writes refuse with UNAVAILABLE; the tail poller completes
    the resync once the region returns."""
    server, stores = region
    store = stores[0]
    rid_svc = RIDService(store.rid, store.clock)
    isa_id = str(uuid.uuid4())
    rid_svc.create_isa(
        isa_id, {"extents": rid_extents(), "flights_url": "https://u.example/f"},
        "uss1",
    )
    coord = store.region

    # region goes dark: every fetch fails
    orig_fetch = coord._client.fetch

    def dead_fetch(from_index):
        raise RegionError("simulated region outage")

    coord._client.fetch = dead_fetch
    with store._lock:
        coord._resync_or_mark_dirty()
    assert coord.stats()["region_dirty"] == 1

    # reads: previous state still served, not emptied
    assert store.rid.get_isa(isa_id) is not None
    # writes: refuse while dirty
    with pytest.raises(errors.StatusError) as ei:
        rid_svc.create_isa(
            str(uuid.uuid4()),
            {"extents": rid_extents(), "flights_url": "https://u.example/f"},
            "uss1",
        )
    assert ei.value.http_status == 503
    assert store.rid.get_isa(isa_id) is not None

    # region returns: poller resyncs, writes work again
    coord._client.fetch = orig_fetch
    wait_until(lambda: (not coord.stats()["region_dirty"]) or None)
    rid_svc.create_isa(
        str(uuid.uuid4()),
        {"extents": rid_extents(), "flights_url": "https://u.example/f"},
        "uss1",
    )
    assert store.rid.get_isa(isa_id) is not None


def test_concurrent_writers_across_instances_converge(region):
    """Parallel writers on all three instances: the lease serializes
    every write (including the piggybacked-release fast path), nothing
    deadlocks, and all instances converge to the identical entity set.
    The reference gets this from CRDB txns; here it pins the
    acquire(head)/append(release) protocol under real contention."""
    server, stores = region
    services = [RIDService(s.rid, s.clock) for s in stores]
    per_thread = 4
    threads_per_instance = 3
    created = []
    created_mu = threading.Lock()
    failures = []

    def writer(svc_i, t_i):
        for k in range(per_thread):
            isa_id = str(uuid.uuid4())
            try:
                services[svc_i].create_isa(
                    isa_id,
                    {
                        "extents": rid_extents(
                            lat=37.03 + 0.001 * (svc_i * 10 + t_i)
                        ),
                        "flights_url": "https://u.example/f",
                    },
                    f"uss{svc_i}",
                )
                with created_mu:
                    created.append(isa_id)
            except Exception as e:  # noqa: BLE001 — collect, don't die
                failures.append((svc_i, t_i, k, repr(e)))

    ths = [
        threading.Thread(target=writer, args=(si, ti), daemon=True)
        for si in range(3)
        for ti in range(threads_per_instance)
    ]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in ths), "a writer deadlocked"
    assert not failures, failures[:3]
    assert len(created) == 3 * threads_per_instance * per_thread

    # every instance converges to the full set
    def all_visible(store):
        return (
            all(store.rid.get_isa(i) is not None for i in created) or None
        )

    for s in stores:
        wait_until(lambda s=s: all_visible(s), deadline_s=10)
    # and every instance lands on the same applied log index
    wait_until(
        lambda: (len({st.region.applied for st in stores}) == 1) or None,
        deadline_s=10,
    )


def test_optimistic_disjoint_writers_skip_the_lease(region):
    """Disjoint-area writes from different instances commit via the
    optimistic cell-disjoint append — no lease round trips, full
    parallelism (the CRDB per-range write analog)."""
    server, stores = region
    services = [RIDService(s.rid, s.clock) for s in stores]
    # far-apart metros: footprints provably disjoint
    lats = [10.0, 30.0, 50.0]
    ids = []
    for i, svc in enumerate(services):
        isa_id = str(uuid.uuid4())
        svc.create_isa(
            isa_id,
            {
                "extents": rid_extents(lat=lats[i], lng=-100.0),
                "flights_url": "https://u.example/f",
            },
            f"uss{i}",
        )
        ids.append(isa_id)
    for i, s in enumerate(stores):
        st = s.region.stats()
        assert st["region_optimistic_commits"] >= 1, (i, st)
        assert st["region_optimistic_conflicts"] == 0, (i, st)
    # convergence: every instance sees every ISA
    deadline = time.monotonic() + 10
    for s in stores:
        svc = RIDService(s.rid, s.clock)
        for isa_id in ids:
            while True:
                try:
                    svc.get_isa(isa_id)
                    break
                except errors.StatusError:
                    assert time.monotonic() < deadline
                    time.sleep(0.05)


def test_optimistic_conflict_retries_transparently(region):
    """Same-area writes racing from two instances: the loser's
    optimistic append is refused, the service retry re-runs it on the
    lease path, and BOTH writes land (no client-visible failure) —
    the reference's internal txn-retrier contract."""
    server, stores = region
    services = [RIDService(s.rid, s.clock) for s in stores[:2]]
    n_per = 6
    failures = []
    done_ids = [[], []]

    def writer(i):
        for k in range(n_per):
            isa_id = str(uuid.uuid4())
            try:
                services[i].create_isa(
                    isa_id,
                    {
                        # same metro: overlapping coverings
                        "extents": rid_extents(lat=37.03, lng=-122.03),
                        "flights_url": "https://u.example/f",
                    },
                    f"uss{i}",
                )
                done_ids[i].append(isa_id)
            except errors.StatusError as e:
                failures.append((i, k, str(e)))

    ths = [threading.Thread(target=writer, args=(i,)) for i in range(2)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(timeout=120)
    assert not failures, failures[:3]
    assert len(done_ids[0]) == len(done_ids[1]) == n_per
    # all writes visible everywhere
    deadline = time.monotonic() + 15
    for s in stores:
        svc = RIDService(s.rid, s.clock)
        for isa_id in done_ids[0] + done_ids[1]:
            while True:
                try:
                    svc.get_isa(isa_id)
                    break
                except errors.StatusError:
                    assert time.monotonic() < deadline
                    time.sleep(0.05)


def test_optimistic_ambiguous_failure_converges(region):
    """A network-ambiguous optimistic append (it actually landed) rolls
    back locally and re-applies from the log — no divergence."""
    server, stores = region
    svc = RIDService(stores[0].rid, stores[0].clock)
    coord = stores[0].region
    real = coord._client.append_optimistic
    calls = {"n": 0}

    def flaky(expected_head, records, cells):
        idx = real(expected_head, records, cells)
        calls["n"] += 1
        if calls["n"] == 1:
            raise RegionError("simulated timeout after landing")
        return idx

    coord._client.append_optimistic = flaky
    isa_id = str(uuid.uuid4())
    with pytest.raises(errors.StatusError) as ei:
        svc.create_isa(
            isa_id,
            {"extents": rid_extents(), "flights_url": "https://u.example/f"},
            "uss0",
        )
    assert ei.value.code == errors.Code.UNAVAILABLE
    # the append landed: the tail poller re-applies it; reads converge
    deadline = time.monotonic() + 10
    while True:
        try:
            got = svc.get_isa(isa_id)
            break
        except errors.StatusError:
            assert time.monotonic() < deadline
            time.sleep(0.05)
    assert got["service_area"]["id"] == isa_id


def test_log_regression_triggers_resync(tmp_path):
    """The log server crashes having lost acked-but-unsynced entries
    (fsync off is the documented group-commit tradeoff) — or an
    operator restores an older WAL.  Instances whose applied index is
    now AHEAD of the log head must detect the regression and resync to
    the log's truth (dropping the lost writes) instead of silently
    skipping every new entry until the head re-crosses their stale
    cursor."""
    wal = str(tmp_path / "region.wal")
    server = RegionServerThread(wal_path=wal)
    port = server.port
    a = make_instance(server.url, "reg-a")
    b = make_instance(server.url, "reg-b")
    try:
        svc_a = RIDService(a.rid, a.clock)
        svc_b = RIDService(b.rid, b.clock)
        isa1, isa2 = str(uuid.uuid4()), str(uuid.uuid4())
        svc_a.create_isa(
            isa1,
            {"extents": rid_extents(), "flights_url": "https://u.e/1"},
            "uss1",
        )
        wait_until(lambda: b.rid.get_isa(isa1))
        keep_bytes = os.path.getsize(wal)
        svc_a.create_isa(
            isa2,
            {"extents": rid_extents(lat=37.2), "flights_url": "https://u.e/2"},
            "uss1",
        )
        wait_until(lambda: b.rid.get_isa(isa2))

        # crash the log server and lose isa2's entry (torn/unsynced)
        server.stop()
        with open(wal, "r+b") as f:
            f.truncate(keep_bytes)
        server = RegionServerThread(wal_path=wal, port=port)

        # both instances adopt the log's truth: isa2 vanishes
        for store in (a, b):
            wait_until(
                lambda s=store: True
                if s.rid.get_isa(isa2) is None else None
            )
            assert store.rid.get_isa(isa1) is not None
            # the mechanism is an epoch-triggered resync, not luck
            assert store.stats().get("region_resyncs", 0) >= 1
        # and the region keeps working end to end afterwards
        isa3 = str(uuid.uuid4())
        svc_b.create_isa(
            isa3,
            {"extents": rid_extents(lat=37.4), "flights_url": "https://u.e/3"},
            "uss2",
        )
        wait_until(lambda: a.rid.get_isa(isa3))
    finally:
        a.close()
        b.close()
        server.stop()


def _crash_wal(path):
    """Strip the clean-shutdown marker (and any trailing blank) from a
    stopped server's WAL — the on-disk shape a SIGKILL leaves, which
    boot must treat as 'acked entries may be lost' (epoch rotates)."""
    with open(path, "rb") as f:
        lines = f.readlines()
    while lines and (b'"__clean__"' in lines[-1] or not lines[-1].strip()):
        lines.pop()
    with open(path, "wb") as f:
        f.writelines(lines)


def test_epoch_wire_contract(tmp_path):
    """The epoch fence at the client/server seam: a client that tailed
    epoch A must (a) raise EpochChanged on the first fetch against a
    crash-reborn server, (b) keep raising until adopt_epoch, (c) have
    its stale-epoch optimistic appends and lease appends refused
    server-side BEFORE anything lands."""
    from dss_tpu.region.client import (
        EpochChanged,
        OptimisticRejected,
        RegionClient,
    )

    wal = str(tmp_path / "region.wal")
    server = RegionServerThread(wal_path=wal)
    port = server.port
    c = RegionClient(server.url, "epoch-test")
    token, _head = c.acquire_lease()
    assert c.append(token, [{"t": "x"}], release=True) == 0
    entries, head = c.fetch(0)
    assert head == 1 and len(entries) == 1

    # CRASH-reborn server (no clean-shutdown marker), same WAL, same
    # port -> boot cannot prove no acked entry was lost -> new epoch
    server.stop()
    _crash_wal(wal)
    server = RegionServerThread(wal_path=wal, port=port)
    try:
        with pytest.raises(EpochChanged):
            c.fetch(0)
        with pytest.raises(EpochChanged):  # keeps raising until adopted
            c.fetch(0)
        # stale-epoch optimistic append: refused server-side (409 ->
        # OptimisticRejected), nothing lands
        with pytest.raises(OptimisticRejected):
            c.append_optimistic(1, [{"t": "y"}], cells=[1, 2])
        # stale-epoch lease append: fenced even if an integer token
        # collides across the reboot
        t2, _ = RegionClient(server.url, "other").acquire_lease()
        with pytest.raises(RegionError):
            c.append(t2, [{"t": "z"}])
        _, head = RegionClient(server.url, "check").fetch(0)
        assert head == 1  # nothing landed from the stale client
        # adoption restores service
        c.adopt_epoch()
        entries, head = c.fetch(0)
        assert head == 1 and entries[0][1][0]["t"] == "x"
    finally:
        server.stop()


def test_clean_restart_keeps_epoch_no_resync(tmp_path):
    """ADVICE r5 (persisted epoch): a CLEAN log-server restart keeps
    the epoch — no fleet-wide writer fence, no snapshot+tail resync
    storm.  The epoch rotates only on recovery rotation (crash/torn
    tail) or promotion."""
    wal = str(tmp_path / "region.wal")
    server = RegionServerThread(wal_path=wal)
    port = server.port
    store = make_instance(server.url, "dss-clean")
    try:
        svc = RIDService(store.rid, store.clock)
        isa1 = str(uuid.uuid4())
        svc.create_isa(
            isa1,
            {"extents": rid_extents(), "flights_url": "https://u.e/1"},
            "uss1",
        )
        epoch_before = store.region._client._seen_epoch
        assert epoch_before is not None
        base_resyncs = store.region.stats()["region_resyncs"]

        server.stop()  # clean: appends the shutdown marker
        server = RegionServerThread(wal_path=wal, port=port)

        # a post-restart write commits against the SAME epoch with
        # zero resyncs (the client's bounded transport retry rides out
        # the restart gap)
        def write_ok():
            try:
                svc.create_isa(
                    str(uuid.uuid4()),
                    {
                        "extents": rid_extents(lat=37.2),
                        "flights_url": "https://u.e/2",
                    },
                    "uss1",
                )
                return True
            except errors.StatusError:
                return None  # restart gap: retry

        wait_until(write_ok)
        assert store.region._client._seen_epoch == epoch_before
        assert store.region.stats()["region_resyncs"] == base_resyncs
        assert store.rid.get_isa(isa1) is not None
    finally:
        store.close()
        server.stop()
