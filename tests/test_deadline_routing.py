"""Deadline-aware serving routing (the r6 tentpole): EWMA cost models,
fake-clock routing decisions (tight headroom -> chunked exact host
scans, slack/stale -> fused device path), expired-in-queue fast-sheds
(typed 504), deadline-capped drains, host-chunk vs device differential
bit-identity, clean shutdown with queued deadlines, and a live-socket
overload smoke (no 5xx under a 2x burst).

Everything except the live smoke is deterministic: the coalescer takes
an injectable clock, and routing decisions are driven through seeded
cost models instead of wall-clock timing."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from dss_tpu import errors
from dss_tpu.dar import deadline as deadline_mod
from dss_tpu.dar.coalesce import QueryCoalescer, _BatchController, _CostModel, _Item
from dss_tpu.dar.snapshot import DarTable
from dss_tpu.plan import plan_drain_cap
from dss_tpu.plan.planner import state_of

NOW = 1_700_000_000_000_000_000
HOUR = 3_600_000_000_000


def _fill(table, n, key_space, rng, prefix="e"):
    for i in range(n):
        nk = int(rng.integers(1, 6))
        keys = np.unique(rng.integers(0, key_space, nk).astype(np.int32))
        alo, ahi = sorted(rng.uniform(0, 3000, 2))
        table.upsert(
            f"{prefix}{i}", keys, float(alo), float(ahi),
            NOW - HOUR, NOW + HOUR, i % 5,
        )


def _item(deadline=None, allow_stale=False):
    return _Item(
        np.asarray([3], np.int32), None, None, None, None, NOW, None,
        allow_stale=allow_stale, deadline=deadline,
    )


# -- cost model --------------------------------------------------------------


def test_cost_model_ewma_converges_to_observed_device_cost():
    """From a badly-wrong seed, repeated observations converge the
    device prediction to the measured batch cost (the router's input),
    and mixed sizes keep the floor/per-item split sane."""
    m = _CostModel(floor_ms=2.0, item_ms=0.001, chunk_ms=0.1)
    for _ in range(40):
        m.observe_device(256, 110.0 + 0.01 * 256)
    assert m.predict_device_ms(256) == pytest.approx(112.56, rel=0.1)
    # a second size disambiguates the floor from the slope
    for _ in range(40):
        m.observe_device(2048, 110.0 + 0.01 * 2048)
        m.observe_device(256, 110.0 + 0.01 * 256)
    assert m.predict_device_ms(1024) == pytest.approx(120.2, rel=0.25)
    assert m.est_floor_ms > 50.0  # the floor dominates, as measured


def test_cost_model_host_chunk_ewma():
    m = _CostModel(chunk_ms=5.0, chunk=64)
    for _ in range(40):
        m.observe_host(256, 4 * 0.5)  # 4 chunks at 0.5 ms each
    assert m.est_chunk_ms == pytest.approx(0.5, rel=0.05)
    assert m.predict_host_ms(640) == pytest.approx(5.0, rel=0.05)
    assert m.host_qps() == pytest.approx(128_000, rel=0.05)


def test_drain_cap_respects_headroom():
    """The controller never drains more than the predicted route cost
    fits into the minimum queued headroom, and never below one warmed
    chunk (forward progress)."""
    ctl = _BatchController(min_batch=64, max_batch=4096, start=4096)
    cost = _CostModel(floor_ms=100.0, item_ms=0.01, chunk_ms=0.5, chunk=64)
    state = state_of(cost)  # nothing in flight, no resident loop
    # rich headroom: AIMD size stands
    assert plan_drain_cap(ctl.cur, None, state) == 4096
    assert plan_drain_cap(ctl.cur, 10_000.0, state) == 4096
    # tight headroom: only the host chunks that fit half of it
    cap = plan_drain_cap(ctl.cur, 10.0, state)
    assert cap == 64 * (int(5.0 / 0.5))  # 10 chunks
    # even 1 ms of headroom still drains one chunk
    assert plan_drain_cap(ctl.cur, 1.0, state) == 64


# -- routing decisions (fake clock, seeded estimates) ------------------------


def _route(co, batch, headroom_ms, allow_resident=True):
    """The planner's route for this drain, as the pack stage asks it
    (unrecorded; the mesh candidate is decided before this choice)."""
    return co._planner.plan(
        co._shape_of(batch), co._capture_state(), headroom_ms,
        allow_resident=allow_resident, allow_mesh=False, record=False,
    ).route


def _host_route(co, batch, headroom_ms):
    """Whether a consumer that cannot ride the resident loop (the
    inline lone caller, the mesh fallback) is sent to host chunks."""
    return _route(co, batch, headroom_ms, allow_resident=False) == "hostchunk"


def _routing_co(table, **kw):
    kw.setdefault("inline", False)
    kw.setdefault("min_batch", 1)
    kw.setdefault("queue_depth", 64)
    return QueryCoalescer(table, **kw)


def test_tight_headroom_routes_host_slack_routes_device():
    table = DarTable()
    co = _routing_co(
        table, est_floor_ms=100.0, est_item_ms=0.01, est_chunk_ms=0.2,
    )
    try:
        clock = [1000.0]
        co._clock = lambda: clock[0]
        batch = [_item() for _ in range(200)]
        # 8 ms of headroom: predicted device (100 ms floor) blows it,
        # predicted host (4 chunks * 0.2 ms) does not -> host route
        assert _host_route(co, batch, 8.0) is True
        # a second of headroom: the device fits -> device route
        assert _host_route(co, batch, 1000.0) is False
        # no fresh deadlines at all (bulk / all-stale): device route
        assert _host_route(co, batch, None) is False
        # headroom blown by BOTH routes: pick the lesser evil (device
        # when host chunks are predicted slower)
        co._cost.est_chunk_ms = 1000.0
        assert _host_route(co, batch, 8.0) is False
    finally:
        co.close()
        table.close()


def test_drain_splits_expired_and_computes_fresh_headroom():
    """_drain_locked (fake clock): expired items split out, headroom
    taken over fresh non-stale deadlines only, stale items ride along."""
    table = DarTable()
    clock = [1000.0]
    co = _routing_co(table, clock=lambda: clock[0])
    try:
        items = [
            _item(deadline=999.0),              # expired in queue
            _item(deadline=1000.050),           # 50 ms of headroom
            _item(deadline=1000.010),           # 10 ms -> the minimum
            _item(deadline=1000.001, allow_stale=True),  # stale: ignored
            _item(),                            # no deadline
        ]
        with co._cond:
            co._queue.extend(items)
            batch, expired, headroom_ms = co._drain_locked()
            assert not co._queue
        assert expired == [items[0]]
        assert batch == items[1:]
        assert headroom_ms == pytest.approx(10.0, abs=0.5)
        # all-stale drain: no headroom constraint (device eligible)
        with co._cond:
            co._queue.extend(
                [_item(deadline=1000.001, allow_stale=True)] * 3
            )
            batch, expired, headroom_ms = co._drain_locked()
        assert len(batch) == 3 and not expired and headroom_ms is None
    finally:
        co.close()
        table.close()


class _GatedTable:
    """DarTable wrapper whose submit blocks until the gate opens."""

    def __init__(self, table):
        self._table = table
        self.gate = threading.Event()

    def query_many_submit(self, *a, **kw):
        self.gate.wait(10.0)
        return self._table.query_many_submit(*a, **kw)

    def query_many_collect(self, pq):
        return self._table.query_many_collect(pq)

    def query_many(self, *a, **kw):
        self.gate.wait(10.0)
        return self._table.query_many(*a, **kw)


def test_expired_in_queue_items_fast_shed_with_504():
    """An item whose deadline passes while queued behind a stalled
    batch is shed with a typed DEADLINE_EXCEEDED (HTTP 504) instead of
    riding a kernel; fresh items in the same drain still complete."""
    inner = DarTable()
    inner.upsert("e0", np.asarray([3], np.int32), None, None,
                 NOW - HOUR, NOW + HOUR, 0)
    table = _GatedTable(inner)
    clock = [1000.0]
    # est_chunk_ms huge: the router predicts the host route slower than
    # the device, so the blocker's batch takes the device path and
    # parks the PACK stage inside the gated submit (forced host-chunk
    # batches would block the collect stage instead)
    co = _routing_co(
        table, slo_ms=20.0, clock=lambda: clock[0], est_chunk_ms=1e6,
    )
    results, shed_errors = [], []

    def blocker():
        # first in: occupies the pack stage inside the gated submit
        results.append(co.query(np.asarray([3], np.int32), now=NOW))

    def victim():
        try:
            co.query(np.asarray([3], np.int32), now=NOW)
        except errors.StatusError as e:
            shed_errors.append(e)

    def survivor():
        # stale-ok: no SLO deadline, survives the clock jump
        results.append(
            co.query(np.asarray([3], np.int32), now=NOW, allow_stale=True)
        )

    try:
        t1 = threading.Thread(target=blocker)
        t1.start()
        time.sleep(0.1)  # blocker is inside the gated submit
        t2 = threading.Thread(target=victim)
        t2.start()
        t3 = threading.Thread(target=survivor)
        t3.start()
        deadline = time.time() + 5.0
        while co.stats()["co_queue_depth"] < 2 and time.time() < deadline:
            time.sleep(0.005)
        clock[0] += 10.0  # fake clock: every SLO deadline long gone
        table.gate.set()
        for t in (t1, t2, t3):
            t.join(10.0)
        assert len(shed_errors) == 1
        e = shed_errors[0]
        assert e.code == errors.Code.DEADLINE_EXCEEDED
        assert e.http_status == 504
        assert results == [["e0"], ["e0"]]
        st = co.stats()
        assert st["co_deadline_shed"] == 1
        assert st["co_shed"] == 0  # not an admission shed
    finally:
        table.gate.set()
        co.close()
        inner.close()


def test_route_deadline_caps_slo_deadline():
    """The propagated route deadline (dar/deadline.py, installed by the
    HTTP timeout middleware) caps the SLO-derived item deadline."""
    table = DarTable()
    clock = [50.0]
    co = _routing_co(table, slo_ms=60_000.0, clock=lambda: clock[0])
    try:
        deadline_mod.set_route_deadline(50.0 + 0.25)
        gate = threading.Event()
        orig = table.query_many_submit

        def gated(*a, **kw):
            gate.wait(10.0)
            return orig(*a, **kw)

        table.query_many_submit = gated
        caught = []

        def client():
            deadline_mod.set_route_deadline(50.0 + 0.25)
            try:
                co.query(np.asarray([3], np.int32), now=NOW)
            except errors.StatusError as e:
                caught.append(e)
            finally:
                deadline_mod.set_route_deadline(None)

        # occupy the pack stage, then queue the capped item
        t1 = threading.Thread(target=client)
        t1.start()
        time.sleep(0.1)
        t2 = threading.Thread(target=client)
        t2.start()
        deadline = time.time() + 5.0
        while co.stats()["co_queue_depth"] < 1 and time.time() < deadline:
            time.sleep(0.005)
        clock[0] += 1.0  # past the 250 ms route deadline, far under SLO
        gate.set()
        for t in (t1, t2):
            t.join(10.0)
        assert len(caught) == 1
        assert caught[0].code == errors.Code.DEADLINE_EXCEEDED
    finally:
        deadline_mod.set_route_deadline(None)
        gate.set()
        co.close()
        table.close()


# -- differential: host chunks vs device, bit-identical ----------------------


def test_host_chunk_route_matches_device_route_exactly():
    """query_many(host_route=True) — the router's forced chunked host
    scans — returns results bit-identical to the fused device path,
    across tiers + overlay + tombstones + owner filters."""
    rng = np.random.default_rng(23)
    table = DarTable(delta_capacity=256)
    _fill(table, 400, 60, rng)
    table.fold()  # L0/L1 tier structure
    _fill(table, 80, 60, rng, prefix="late")  # overlay on top
    for i in range(0, 40, 7):
        table.remove(f"e{i}")  # tombstones
    try:
        b = 200  # well beyond the 64-query auto host cutoff
        keys_list = [
            np.unique(rng.integers(0, 60, 4).astype(np.int32))
            for _ in range(b)
        ]
        args = (
            keys_list,
            rng.uniform(0, 2000, b).astype(np.float32),
            rng.uniform(2000, 4000, b).astype(np.float32),
            np.full(b, NOW - HOUR, np.int64),
            np.full(b, NOW + HOUR, np.int64),
        )
        owners = np.where(
            np.arange(b) % 3 == 0, np.arange(b) % 5, -1
        ).astype(np.int32)
        device = table.query_many(*args, now=NOW, owner_ids=owners)
        host = table.query_many(
            *args, now=NOW, owner_ids=owners, host_route=True
        )
        assert device == host
        # the forced route really did stay off the device
        pq = table.query_many_submit(
            *args, now=NOW, owner_ids=owners, host_route=True
        )
        assert all(p is None for p in pq.tier_pending)
        table.query_many_collect(pq)
    finally:
        table.close()


def test_forced_host_route_counted_in_stats():
    """An end-to-end forced host-chunk batch shows up in the route-mix
    counters (co_route_hostchunk_batches) with zero device batches."""
    rng = np.random.default_rng(5)
    table = DarTable()
    _fill(table, 200, 50, rng)
    # seeded estimates make the device look catastrophically slow, so
    # any fresh-deadline batch routes host
    co = _routing_co(
        table, max_batch=512, slo_ms=50.0,
        est_floor_ms=10_000.0, est_item_ms=0.0, est_chunk_ms=0.01,
    )
    try:
        cases = [
            np.unique(rng.integers(0, 50, 3).astype(np.int32))
            for _ in range(128)
        ]
        with ThreadPoolExecutor(max_workers=32) as pool:
            got = list(
                pool.map(lambda k: co.query(k, now=NOW), cases)
            )
        serial = [table.query(k, now=NOW) for k in cases]
        assert [sorted(g) for g in got] == [sorted(s) for s in serial]
        st = co.stats()
        assert st["co_route_device_batches"] == 0
        assert st["co_route_host_batches"] >= 1
        assert st["co_deadline_shed"] == 0
        # batches above the 64 auto cutoff exercised the FORCED route
        if st["co_last_batch"] > 64:
            assert st["co_route_hostchunk_batches"] >= 1
    finally:
        co.close()
        table.close()


# -- shutdown ----------------------------------------------------------------


def test_clean_shutdown_with_queued_deadlines():
    """close(join=True) with deadline-carrying items queued: fresh
    items complete, expired ones get their typed 504, both stage
    threads exit — no hang, no dropped waiter."""
    inner = DarTable()
    inner.upsert("e0", np.asarray([3], np.int32), None, None,
                 NOW - HOUR, NOW + HOUR, 0)
    table = _GatedTable(inner)
    clock = [1000.0]
    co = _routing_co(
        table, slo_ms=20.0, max_batch=2, clock=lambda: clock[0]
    )
    outcomes = []

    def client():
        try:
            outcomes.append(co.query(np.asarray([3], np.int32), now=NOW))
        except errors.StatusError as e:
            outcomes.append(e.code)

    try:
        ths = [threading.Thread(target=client) for _ in range(6)]
        for t in ths:
            t.start()
            time.sleep(0.02)
        deadline = time.time() + 5.0
        while co.stats()["co_queue_depth"] < 4 and time.time() < deadline:
            time.sleep(0.005)
        clock[0] += 10.0  # queued items' SLO deadlines all expire
        table.gate.set()
        co.close(join=True)
        for t in ths:
            t.join(10.0)
        assert len(outcomes) == 6
        assert not co._pack_thread.is_alive()
        assert not co._collect_thread.is_alive()
        served = [o for o in outcomes if o == ["e0"]]
        shed = [o for o in outcomes if o == errors.Code.DEADLINE_EXCEEDED]
        assert len(served) + len(shed) == 6
        assert len(shed) >= 1  # the expired-in-queue ones
    finally:
        table.gate.set()
        co.close()
        inner.close()


# -- Retry-After from the live drain EWMA ------------------------------------


def test_retry_after_uses_live_drain_rate():
    table = DarTable()
    co = QueryCoalescer(table, est_chunk_ms=0.5)
    try:
        with co._cond:
            co._queue.extend(_item() for _ in range(100))
            co._inflight_items = 50
            co._ema_qps = 300.0
            assert co._retry_after_locked() == pytest.approx(0.5)
            # no drains measured yet: the cost model's host throughput
            # stands in (64 / 0.5 ms = 128k qps), clamped at the floor
            co._ema_qps = 0.0
            assert co._retry_after_locked() == pytest.approx(0.05)
            co._queue.clear()
            co._inflight_items = 0
    finally:
        co.close()
        table.close()


# -- live-socket overload smoke ----------------------------------------------


def test_no_5xx_under_2x_overload_burst():
    """A 2x overload burst on a live socket resolves as 200s plus 429
    admission sheds — never a 5xx (the deadline machinery must not
    convert ordinary overload into 504s/500s)."""
    import requests

    from dss_tpu.api.app import build_app
    from dss_tpu.clock import Clock
    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.services.rid import RIDService
    from tests.live_server import LiveServer

    clock = Clock()
    store = DSSStore(storage="tpu", clock=clock)
    app = build_app(
        RIDService(store.rid, clock), None, None, enable_scd=False,
        default_timeout_s=30.0,
    )
    srv = LiveServer(app)
    try:
        # tiny queue: the burst MUST overflow admission (2x the
        # capacity the pipeline can hold), while a 2 s SLO keeps
        # deadline sheds out of ordinary queue waits
        store.configure_serving(
            min_batch=1, max_batch=2, queue_depth=1,
            admission_wait_s=0.0, inline=False, slo_ms=2000.0,
        )
        area = "40.0,-100.0,40.02,-100.0,40.02,-99.98,40.0,-99.98"
        url = f"{srv.base}/v1/dss/identification_service_areas"
        codes = []

        def search(_):
            r = requests.get(url, params={"area": area}, timeout=30)
            codes.append(r.status_code)

        with ThreadPoolExecutor(max_workers=16) as pool:
            list(pool.map(search, range(64)))
        assert codes and all(c in (200, 429) for c in codes), codes
        assert 200 in codes
    finally:
        srv.stop()
        store.close()
