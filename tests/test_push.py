"""Reverse-query push pipeline tests (dss_tpu/push/).

Four tiers, mirroring the subsystem's layering:

  1. planner: the rqmatch route's candidate set, cost keys, and
     degradation behavior (bounded-stale routes never admissible).
  2. queue: WAL-backed durability — cursor/ack semantics, QoS bands,
     the depth bound, and byte-level crash replay.
  3. delivery: retry/backoff/breaker flow control and parking.
  4. pipeline: store integration — match-vs-host-oracle bit identity
     on both backends, fan-out QoS, federation ingest, health edges,
     and the zero-acked-loss crash drill the chaos leg scales up.
"""

import datetime
import threading
import time
from datetime import timedelta, timezone

import numpy as np
import pytest

from dss_tpu import chaos
from dss_tpu.clock import FakeClock
from dss_tpu.dar.dss_store import DSSStore
from dss_tpu.geo import covering
from dss_tpu.models import rid as ridm
from dss_tpu.models import scd as scdm
from dss_tpu.plan import costs as plancosts
from dss_tpu.plan.planner import (
    BatchShape,
    ModelState,
    Planner,
    decide,
    enumerate_candidates,
)
from dss_tpu.push import PushPipeline, empty_stats
from dss_tpu.push.deliver import DeliveryPool
from dss_tpu.push.match import MatchStage
from dss_tpu.push.queue import DeliveryLog

T0 = datetime.datetime(2026, 7, 1, 12, 0, 0, tzinfo=timezone.utc)


@pytest.fixture(autouse=True)
def _clean_faults():
    chaos.clear_plan()
    chaos.registry().reset_counters()
    yield
    chaos.clear_plan()
    chaos.registry().reset_counters()


def cells_at(lat, lng, half=0.03):
    return covering.covering_polygon(
        [
            (lat - half, lng - half),
            (lat - half, lng + half),
            (lat + half, lng + half),
            (lat + half, lng - half),
        ]
    )


CELLS_A = cells_at(34.0, -118.0)
CELLS_B = cells_at(34.06, -118.0)
CELLS_FAR = cells_at(-33.9, 151.2)


def st(**kw) -> ModelState:
    base = dict(
        est_floor_ms=100.0,
        est_item_ms=0.01,
        est_chunk_ms=0.2,
        est_res_floor_ms=25.0,
        est_res_lat_ms=100.0,
        est_rq_floor_ms=2.0,
        est_rq_item_ms=0.01,
        chunk=64,
    )
    base.update(kw)
    return ModelState(**base)


# ---------------------------------------------------------------------------
# 1. planner: the rqmatch route
# ---------------------------------------------------------------------------


def test_rqmatch_candidates_exclude_stale_routes():
    """A write-side match may only ride exact routes: the fused kernel
    or the bit-identical host oracle.  cache/mesh/resident/inline are
    bounded-stale (or lone-caller) read routes — a missed subscription
    is a correctness bug, so they are never admissible."""
    cand = enumerate_candidates(
        BatchShape(n=32, rqmatch=True),
        st(resident_ready=True, mesh_ready=True),
        None,
    )
    assert cand["rqmatch"] is not None
    assert cand["hostchunk"] is not None
    for route in ("cache", "inline", "mesh", "resident", "device"):
        assert cand[route] is None


def test_rqmatch_device_lost_routes_host():
    plan = decide(BatchShape(n=32, rqmatch=True), st(device_ok=False), None)
    assert plan.route == "hostchunk"


def test_rqmatch_headroom_escape():
    # rq predicted 2.0 + 32*0.01 = 2.32 ms; headroom 1 ms and the host
    # chunks finish sooner -> hostchunk (the deadline router's escape)
    s = st(est_chunk_ms=0.001)
    plan = decide(BatchShape(n=32, rqmatch=True), s, 1.0)
    assert plan.route == "hostchunk"
    # rich headroom keeps the kernel
    plan = decide(BatchShape(n=32, rqmatch=True), s, 100.0)
    assert plan.route == "rqmatch"


def test_rqmatch_cost_keys_isolated():
    """rqmatch observations train est_rq_* only — the device keys the
    read routes price against are untouched (and vice versa)."""
    cm = plancosts.CostModel(floor_ms=100.0, item_ms=0.01)
    floor0, item0 = cm.est_floor_ms, cm.est_item_ms
    for _ in range(50):
        cm.observe_rqmatch(64, 4.0)
    assert cm.est_floor_ms == floor0 and cm.est_item_ms == item0
    assert cm.est_rq_floor_ms < floor0  # converged toward ~3.4 ms
    pred = cm.predict_rqmatch_ms(64)
    assert 0.0 < pred < 20.0


def test_rqmatch_state_defaults_fall_back_to_device_keys():
    """ModelStates recorded before the route existed replay: zeroed
    est_rq_* fall back to the device keys instead of predicting 0."""
    s = st(est_rq_floor_ms=0.0, est_rq_item_ms=0.0)
    assert s.predict_rqmatch_ms(10) == pytest.approx(
        plancosts.predict_device_ms(s.est_floor_ms, s.est_item_ms, 10)
    )


def test_planner_observe_rqmatch_counter():
    pl = Planner()
    plan = pl.plan(BatchShape(n=8, rqmatch=True), st(), None)
    assert plan.route == "rqmatch"
    pl.observe_rqmatch(8, 3.0)
    assert pl.stats()["co_plan_rqmatch"] == 1


# ---------------------------------------------------------------------------
# 2. queue: durable cursor/ack + QoS
# ---------------------------------------------------------------------------


def test_queue_fifo_and_ack():
    log = DeliveryLog()
    n1 = log.enqueue("a", "http://a", {"k": 1})
    n2 = log.enqueue("a", "http://a", {"k": 2})
    assert (n1, n2) == (1, 2)
    t1 = log.take(timeout_s=0)
    t2 = log.take(timeout_s=0)
    assert [t1.body["k"], t2.body["k"]] == [1, 2]
    assert log.take(timeout_s=0) is None
    assert log.ack(t1.nid) and log.ack(t2.nid)
    assert not log.ack(t1.nid)  # double-ack is a no-op
    assert log.depth() == 0
    log.close()


def test_queue_emergency_preempts_bulk():
    log = DeliveryLog()
    for i in range(3):
        log.enqueue("bulk-uss", "http://b", {"i": i}, qos="bulk")
    log.enqueue("em-uss", "http://e", {"i": 99}, qos="emergency")
    first = log.take(timeout_s=0)
    assert first.uss == "em-uss" and first.qos == "emergency"
    log.close()


def test_queue_blocked_uss_rotated_past():
    log = DeliveryLog()
    log.enqueue("dead", "http://d", {})
    log.enqueue("live", "http://l", {})
    n = log.take(blocked={"dead"}, timeout_s=0)
    assert n.uss == "live"
    # the blocked one is still pending, not lost
    assert log.depth() == 2
    log.close()


def test_queue_depth_bound_sheds_bulk_not_emergency():
    log = DeliveryLog(max_depth=2)
    assert log.enqueue("u", "h", {}) is not None
    assert log.enqueue("u", "h", {}) is not None
    assert log.enqueue("u", "h", {}) is None  # bulk shed at the bound
    assert log.enqueue("u", "h", {}, qos="emergency") is not None
    assert log.stats()["dropped"] == 1
    log.close()


def test_queue_requeue_bumps_attempts():
    log = DeliveryLog()
    log.enqueue("u", "h", {})
    n = log.take(timeout_s=0)
    log.requeue(n)
    again = log.take(timeout_s=0)
    assert again.nid == n.nid and again.attempts == 1
    log.close()


def test_queue_crash_replay_redelivers_unacked_only(tmp_path):
    """The durability contract: enqueued − acked survives a crash and
    is redelivered; acked (and parked) notifications never are; hook
    registrations ride the same log."""
    path = str(tmp_path / "push.wal")
    log = DeliveryLog(path)
    log.register_hook("ussA", "http://a/notify", qos="emergency")
    n1 = log.enqueue("ussA", "http://a", {"k": 1})
    n2 = log.enqueue("ussA", "http://a", {"k": 2}, qos="emergency")
    n3 = log.enqueue("ussB", "http://b", {"k": 3})
    n4 = log.enqueue("ussB", "http://b", {"k": 4})
    log.ack(n1)
    log.park(n4, reason="max_attempts")
    log.sync()
    # crash: drop the object without close(), reopen from bytes
    log2 = DeliveryLog(path)
    assert log2.hook_of("ussA") == {"url": "http://a/notify", "qos": "emergency"}
    pending = {log2.take(timeout_s=0).nid for _ in range(2)}
    assert pending == {n2, n3}
    assert log2.take(timeout_s=0) is None
    assert log2.seq > 0
    log2.close()


def test_queue_taken_but_unacked_survives_crash(tmp_path):
    """A worker crash mid-POST redelivers: take() alone must not
    count as delivery."""
    path = str(tmp_path / "push.wal")
    log = DeliveryLog(path)
    nid = log.enqueue("u", "h", {"k": 1})
    assert log.take(timeout_s=0).nid == nid
    log.sync()
    log2 = DeliveryLog(path)
    assert log2.take(timeout_s=0).nid == nid
    log2.close()


def test_queue_bad_qos_rejected():
    log = DeliveryLog()
    with pytest.raises(ValueError):
        log.register_hook("u", "h", qos="ludicrous")
    log.close()


# ---------------------------------------------------------------------------
# 3. delivery: retry / breaker / parking
# ---------------------------------------------------------------------------


def _pool(log, transport, **kw):
    kw.setdefault("workers", 2)
    kw.setdefault("breaker_reset_s", 0.05)
    return DeliveryPool(log, transport=transport, **kw)


def _wait(pred, timeout_s=5.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return pred()


def test_pool_delivers_and_acks():
    log = DeliveryLog()
    got = []
    pool = _pool(log, lambda url, body, hdrs: got.append((url, body)))
    pool.start()
    log.enqueue("u", "http://u/hook", {"k": 1}, traceparent="00-aa-bb-01")
    assert _wait(lambda: pool.delivered == 1)
    assert got[0][0] == "http://u/hook"
    assert log.depth() == 0 and log.stats()["acked"] == 1
    pool.close()
    log.close()


def test_pool_traceparent_header_propagates():
    log = DeliveryLog()
    seen = {}
    pool = _pool(log, lambda url, body, hdrs: seen.update(hdrs))
    pool.start()
    tp = "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01"
    log.enqueue("u", "h", {}, traceparent=tp)
    assert _wait(lambda: pool.delivered == 1)
    assert seen["traceparent"] == tp
    assert seen["X-Request-Id"] == "0af7651916cd43dd8448eb211c80319c"
    pool.close()
    log.close()


def test_pool_breaker_opens_and_other_uss_drains():
    """Consecutive failures open the dead USS's breaker; once open it
    costs zero attempts while the healthy USS keeps draining."""
    log = DeliveryLog()
    calls = {"dead": 0, "live": 0}

    def transport(url, body, hdrs):
        uss = "dead" if "dead" in url else "live"
        calls[uss] += 1
        if uss == "dead":
            raise OSError("connection refused")

    pool = _pool(log, transport, breaker_threshold=3, breaker_reset_s=60.0)
    pool.start()
    for i in range(5):
        log.enqueue("dead", "http://dead/h", {"i": i})
    for i in range(5):
        log.enqueue("live", "http://live/h", {"i": i})
    assert _wait(lambda: pool.delivered == 5)
    assert _wait(
        lambda: pool.breakers.states().get("dead") == chaos.BREAKER_OPEN
    )
    settled = calls["dead"]
    assert settled >= 3  # reached the threshold
    time.sleep(0.1)
    assert calls["dead"] == settled  # open breaker: no further attempts
    assert calls["live"] == 5
    pool.close()
    log.close()


def test_pool_parks_at_max_attempts():
    log = DeliveryLog()

    def transport(url, body, hdrs):
        raise OSError("always down")

    pool = _pool(
        log, transport, max_attempts=3,
        retry=chaos.RetryPolicy(base_s=0.001, cap_s=0.002, seed=1),
        breaker_threshold=100,
    )
    pool.start()
    log.enqueue("u", "h", {"k": 1})
    assert _wait(lambda: pool.parked == 1)
    assert log.depth() == 0  # parked = durably acked, never redelivered
    assert pool.failures == 3
    pool.close()
    log.close()


def test_pool_fault_site_push_deliver():
    """chaos site push.deliver injects per-USS (detail=uss) failures
    through the standard registry."""
    chaos.install_plan(
        chaos.FaultPlan.from_dict({
            "seed": 7,
            "events": [
                {"site": "push.deliver", "match": "flaky", "count": 2},
            ],
        })
    )
    log = DeliveryLog()
    got = []
    pool = _pool(
        log, lambda url, body, hdrs: got.append(url),
        retry=chaos.RetryPolicy(base_s=0.001, cap_s=0.002, seed=1),
        breaker_threshold=100,
    )
    pool.start()
    log.enqueue("flaky", "http://f/h", {})
    assert _wait(lambda: pool.delivered == 1)  # delivered on retry 3
    assert pool.failures == 2
    assert chaos.registry().injected_by_site()["push.deliver"] == 2
    pool.close()
    log.close()


# ---------------------------------------------------------------------------
# 4. match: bit identity vs the host oracle
# ---------------------------------------------------------------------------


def mk_scd_sub(id, owner="uss1", cells=None, *, alt_lo=None, alt_hi=None,
               hours=6, ops=True, csts=False):
    return scdm.Subscription(
        id=id,
        owner=owner,
        start_time=T0,
        end_time=T0 + timedelta(hours=hours),
        altitude_lo=alt_lo,
        altitude_hi=alt_hi,
        base_url=f"https://{owner}.example.com",
        notify_for_operations=ops,
        notify_for_constraints=csts,
        cells=CELLS_A if cells is None else cells,
    )


def _seeded_store(storage):
    clock = FakeClock(T0)
    store = DSSStore(storage=storage, clock=clock)
    sid = "00000000-0000-4000-8000-0000000000%02x"
    store.scd.upsert_subscription(mk_scd_sub(sid % 1, owner="uss1"))
    store.scd.upsert_subscription(
        mk_scd_sub(sid % 2, owner="uss2", cells=CELLS_B)
    )
    store.scd.upsert_subscription(
        mk_scd_sub(sid % 3, owner="uss3", alt_lo=0.0, alt_hi=60.0)
    )
    store.scd.upsert_subscription(
        mk_scd_sub(sid % 4, owner="uss4", hours=1)  # expires early
    )
    store.scd.upsert_subscription(
        mk_scd_sub(sid % 5, owner="uss5", cells=CELLS_FAR)
    )
    # a deleted subscription must never match (tombstone filtering)
    doomed, _ = store.scd.upsert_subscription(
        mk_scd_sub(sid % 6, owner="uss6")
    )
    store.scd.delete_subscription(doomed.id, "uss6", doomed.version)
    return store, clock


@pytest.mark.parametrize("storage", ["memory", "tpu"])
def test_match_bit_identical_to_oracle(storage):
    """The tentpole invariant: MatchStage through the planner's route
    == the host oracle, id-for-id, across cells/altitude/time filters,
    expiry tiers, and tombstones — on both backends."""
    store, clock = _seeded_store(storage)
    stage = MatchStage(store.scd._sub_index, health=store.health)
    now_ns = int(T0.timestamp() * 1e9)
    queries = [
        (CELLS_A, None, None, None, None),
        (CELLS_B, None, None, None, None),
        (CELLS_FAR, None, None, None, None),
        (CELLS_A, 100.0, 200.0, None, None),  # above sub 3's band
        (CELLS_A, 0.0, 50.0, None, None),  # inside it
        (
            CELLS_A, None, None,
            int((T0 + timedelta(hours=2)).timestamp() * 1e9),
            int((T0 + timedelta(hours=3)).timestamp() * 1e9),
        ),  # after sub 4 expired
    ]
    got = stage.match_many(queries, now_ns=now_ns)
    want = stage.oracle_many(queries, now_ns=now_ns)
    assert got == want
    # sanity: the scenario exercises real filtering, not empty sets
    sid = "00000000-0000-4000-8000-0000000000%02x"
    assert got[0] and sid % 6 not in got[0]  # tombstone filtered
    assert got[2] == [sid % 5]  # spatial isolation
    assert got[4] != got[3]  # the altitude band discriminates
    store.close()


def test_match_fault_absorbed_onto_oracle():
    """An injected push.match fault (or in-flight device loss) is
    absorbed: the host oracle serves the same answer, nothing raises,
    nothing is missed."""
    store, clock = _seeded_store("tpu")
    stage = MatchStage(store.scd._sub_index, health=store.health)
    now_ns = int(T0.timestamp() * 1e9)
    want = stage.oracle_many([(CELLS_A, None, None, None, None)],
                             now_ns=now_ns)
    chaos.install_plan(
        chaos.FaultPlan.from_dict({
            "seed": 3,
            "events": [{"site": "push.match", "count": 1}],
        })
    )
    got = stage.match_many([(CELLS_A, None, None, None, None)],
                           now_ns=now_ns)
    assert got == want
    assert stage.stats()["match_absorbed"] == 1
    store.close()


def test_match_feeds_stage_duration_histogram():
    """push_match_ms rides the bounded dss_stage_duration_seconds
    histogram (route class "push") when the stage is given a registry
    handle — match runs on writer/pipeline threads with no
    thread-local stage sink, so the direct observe_stage call is the
    only way the stage attribution ever sees it."""
    from dss_tpu.obs.metrics import MetricsRegistry

    store, clock = _seeded_store("memory")
    reg = MetricsRegistry()
    stage = MatchStage(
        store.scd._sub_index, health=store.health, metrics=reg
    )
    now_ns = int(T0.timestamp() * 1e9)
    stage.match_many(
        [(CELLS_A, None, None, None, None)] * 3, now_ns=now_ns
    )
    snap = reg.stage_hist_snapshot()
    assert ("push", "push_match_ms") in snap
    counts, sum_s, cnt = snap[("push", "push_match_ms")]
    assert cnt == 1  # one batch, one sample
    assert sum_s > 0.0
    # without the handle: no histogram row, and nothing raises
    silent = MatchStage(store.scd._sub_index, health=store.health)
    silent.match_many(
        [(CELLS_A, None, None, None, None)], now_ns=now_ns
    )
    store.close()


@pytest.mark.parametrize("storage", ["memory", "tpu"])
def test_write_path_responses_unchanged_by_push(storage):
    """Satellite 3's contract: attaching the pipeline must not change
    a single byte of the returned-subscriber-list responses."""
    clock = FakeClock(T0)
    plain = DSSStore(storage=storage, clock=clock)
    pushed = DSSStore(storage=storage, clock=FakeClock(T0))
    pipe = PushPipeline(workers=1, transport=lambda *a: None)
    pushed.attach_push(pipe)
    sid = "00000000-0000-4000-8000-0000000000%02x"
    for store in (plain, pushed):
        store.scd.upsert_subscription(mk_scd_sub(sid % 1, owner="uss1"))
        store.scd.upsert_subscription(
            mk_scd_sub(sid % 2, owner="uss2", cells=CELLS_B)
        )
    op = scdm.Operation(
        id=sid % 9, owner="writer", start_time=T0,
        end_time=T0 + timedelta(hours=1), altitude_lower=50.0,
        altitude_upper=120.0, state=scdm.OperationState.ACCEPTED,
        cells=CELLS_A, subscription_id=sid % 1,
    )
    import dataclasses as dc

    _, subs_plain = plain.scd.upsert_operation(dc.replace(op), [])
    _, subs_push = pushed.scd.upsert_operation(dc.replace(op), [])
    key = lambda s: (s.id, s.notification_index)  # noqa: E731
    assert sorted(map(key, subs_plain)) == sorted(map(key, subs_push))
    plain.close()
    pushed.close()


# ---------------------------------------------------------------------------
# 5. pipeline: store integration, QoS, health, federation ingest
# ---------------------------------------------------------------------------


def _pushed_store(storage="tpu", **pipe_kw):
    clock = FakeClock(T0)
    store = DSSStore(storage=storage, clock=clock)
    pipe_kw.setdefault("workers", 2)
    pipe_kw.setdefault("transport", lambda *a: None)
    pipe = PushPipeline(**pipe_kw)
    store.attach_push(pipe)
    return store, pipe, clock


def test_offer_routes_only_registered_hooks():
    got = []
    store, pipe, clock = _pushed_store(
        transport=lambda url, body, hdrs: got.append((url, body))
    )
    pipe.register_hook("uss1", "http://uss1/notify")
    sid = "00000000-0000-4000-8000-0000000000%02x"
    store.scd.upsert_subscription(mk_scd_sub(sid % 1, owner="uss1"))
    store.scd.upsert_subscription(mk_scd_sub(sid % 2, owner="uss2"))
    op = scdm.Operation(
        id=sid % 9, owner="writer", start_time=T0,
        end_time=T0 + timedelta(hours=1), state="Accepted",
        cells=CELLS_A, subscription_id=sid % 1,
    )
    store.scd.upsert_operation(op, [])
    assert pipe.drain(5.0)
    assert _wait(lambda: pipe.pool.delivered == 1)
    url, body = got[0]
    assert url == "http://uss1/notify"
    assert body["trigger"] == "operations"
    assert body["entity"]["id"] == sid % 9
    assert body["subscription"]["notification_index"] == 1
    assert pipe.skipped == 1  # uss2 matched+bumped, no hook registered
    store.close()


def test_emergency_operation_rides_emergency_band():
    store, pipe, clock = _pushed_store()
    bands = []
    orig = pipe.log.enqueue

    def spy(uss, target, body, *, qos="bulk", traceparent=""):
        bands.append(qos)
        return orig(uss, target, body, qos=qos, traceparent=traceparent)

    pipe.log.enqueue = spy
    pipe.register_hook("uss1", "http://uss1/notify", qos="bulk")
    sid = "00000000-0000-4000-8000-0000000000%02x"
    store.scd.upsert_subscription(mk_scd_sub(sid % 1, owner="uss1"))
    op = scdm.Operation(
        id=sid % 9, owner="writer", start_time=T0,
        end_time=T0 + timedelta(hours=1),
        state=scdm.OperationState.CONTINGENT,
        cells=CELLS_A, subscription_id=sid % 1,
    )
    store.scd.upsert_operation(op, [])
    assert bands == ["emergency"]  # QoS forced by the operation state
    store.close()


def test_constraint_notify_flag_respected():
    got = []
    store, pipe, clock = _pushed_store(
        transport=lambda url, body, hdrs: got.append(body)
    )
    pipe.register_hook("uss1", "http://uss1/n")
    pipe.register_hook("uss2", "http://uss2/n")
    sid = "00000000-0000-4000-8000-0000000000%02x"
    store.scd.upsert_subscription(
        mk_scd_sub(sid % 1, owner="uss1", ops=True, csts=False)
    )
    store.scd.upsert_subscription(
        mk_scd_sub(sid % 2, owner="uss2", ops=False, csts=True)
    )
    cst = scdm.Constraint(
        id=sid % 8, owner="authority", start_time=T0,
        end_time=T0 + timedelta(hours=1), cells=CELLS_A,
    )
    store.scd.upsert_constraint(cst)
    assert pipe.drain(5.0) and _wait(lambda: pipe.pool.delivered == 1)
    assert [b["trigger"] for b in got] == ["constraints"]
    assert got[0]["subscription"]["id"] == sid % 2
    store.close()


def test_rid_isa_write_fans_out():
    got = []
    store, pipe, clock = _pushed_store(
        transport=lambda url, body, hdrs: got.append(body)
    )
    pipe.register_hook("uss2", "http://uss2/n")
    sub = ridm.Subscription(
        id="00000000-0000-4000-8000-00000000s001", owner="uss2",
        url="https://uss2.example.com/isas", cells=CELLS_A,
        start_time=T0, end_time=T0 + timedelta(hours=4),
    )
    store.rid.insert_subscription(sub)

    class ISA:
        id = "isa-1"
        owner = "uss1"
        ovn = ""
        cells = CELLS_A

    bumped = store.rid.update_notification_idxs_in_cells(
        CELLS_A, entity=ISA()
    )
    assert [s.notification_index for s in bumped] == [1]
    assert pipe.drain(5.0) and _wait(lambda: pipe.pool.delivered == 1)
    assert got[0]["trigger"] == "rid"
    assert got[0]["entity"]["id"] == "isa-1"
    store.close()


def test_pipeline_health_saturation_edge():
    """Queue saturation enters push_degraded (the mildest ladder rung)
    and drains back to HEALTHY — serving routes never degraded."""
    store, pipe, clock = _pushed_store(max_depth=10)
    pipe.pool.close()  # deterministic depth: no workers draining
    pipe.register_hook("uss1", "http://u/n")
    for i in range(9):
        pipe.log.enqueue("uss1", "http://u/n", {"i": i})
    pipe._update_health()
    assert store.health.mode() == chaos.PUSH_DEGRADED
    assert store.health.mode_name() == "push_degraded"
    while True:
        n = pipe.log.take(timeout_s=0)
        if n is None:
            break
        pipe.log.ack(n.nid)
    pipe._update_health()
    assert store.health.mode() == chaos.HEALTHY
    store.close()


def test_pipeline_stats_stable_key_set():
    store, pipe, clock = _pushed_store()
    assert set(pipe.stats()) == set(empty_stats())
    bare = DSSStore(storage="memory", clock=FakeClock(T0))
    assert set(k for k in bare.stats() if k.startswith("dss_push_")) == (
        set(empty_stats())
    )
    assert bare.freshness_status()["push"] is None
    assert store.freshness_status()["push"] is not None
    bare.close()
    store.close()


def test_ingest_remote_matches_without_bump():
    """Federation fan-in: a remote region's write matches OUR
    subscription DAR and enqueues local deliveries — but never bumps
    notification indexes (the bump belongs to the writing region's
    txn) and never re-forwards."""
    got = []
    store, pipe, clock = _pushed_store(
        transport=lambda url, body, hdrs: got.append(body)
    )
    pipe.register_hook("uss1", "http://uss1/n")
    sid = "00000000-0000-4000-8000-0000000000%02x"
    stored, _ = store.scd.upsert_subscription(
        mk_scd_sub(sid % 1, owner="uss1")
    )
    out = pipe.ingest_remote({
        "trigger": "operations",
        "entity": {"id": "remote-op", "owner": "remote-uss"},
        "cells": [int(c) for c in np.asarray(CELLS_A, np.uint64)],
        "origin": "eu-west",
    })
    assert out == {"matched": 1, "enqueued": 1}
    assert pipe.drain(5.0) and _wait(lambda: pipe.pool.delivered == 1)
    assert got[0]["entity"]["origin"] == "eu-west"
    # the local index did NOT advance
    after = store.scd.get_subscription(sid % 1, "uss1")
    assert after.notification_index == stored.notification_index
    assert pipe.fed_ingested == 1
    store.close()


def test_offer_forwards_to_federation_peers():
    """A local write with federation attached rides the same durable
    queue as an @region: pseudo-notification per peer."""
    store, pipe, clock = _pushed_store()
    pipe.pool.close()  # keep the pseudo-notification queued for inspection

    class FakePeer:
        pass

    class FakeFed:
        region_id = "us-west"
        peers = {"eu-west": FakePeer()}

    store.federation = FakeFed()
    sid = "00000000-0000-4000-8000-0000000000%02x"
    store.scd.upsert_subscription(mk_scd_sub(sid % 1, owner="uss1"))
    op = scdm.Operation(
        id=sid % 9, owner="writer", start_time=T0,
        end_time=T0 + timedelta(hours=1), state="Accepted",
        cells=CELLS_A, subscription_id=sid % 1,
    )
    store.scd.upsert_operation(op, [])
    assert pipe.fed_forwarded == 1
    n = pipe.log.take(timeout_s=0)
    assert n.uss == "@region:eu-west" and n.target == "eu-west"
    assert n.body["origin"] == "us-west"
    assert n.body["cells"]  # the 4D volume travels for the remote match
    store.federation = None
    store.close()


# ---------------------------------------------------------------------------
# 6. the crash drill in miniature (the chaos leg scales this up)
# ---------------------------------------------------------------------------


def test_worker_crash_zero_acked_loss(tmp_path):
    """Kill the delivery pool mid-drain; reopen the log from bytes.
    Every notification the receiver saw acked stays acked; everything
    else redelivers; nothing is lost."""
    path = str(tmp_path / "push.wal")
    log = DeliveryLog(path)
    received = []
    lock = threading.Lock()

    def transport(url, body, hdrs):
        with lock:
            received.append(body["i"])

    pool = _pool(log, transport)
    pool.start()
    for i in range(50):
        log.enqueue("u", "http://u/n", {"i": i})
    _wait(lambda: pool.delivered >= 20)
    pool.close()  # SIGKILL stand-in: workers gone mid-queue
    log.sync()
    acked_before = log.stats()["acked"]
    log2 = DeliveryLog(path)
    assert log2.depth() == 50 - acked_before
    pool2 = _pool(log2, transport)
    pool2.start()
    assert _wait(lambda: log2.depth() == 0)
    pool2.close()
    log2.close()
    # at-least-once: every payload seen >= 1 time, none missing
    assert set(received) == set(range(50))
