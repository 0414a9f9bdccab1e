"""Resident serving kernel (the r6 tentpole, ops/resident.py): AOT
shape-bucket cache, donated-I/O safety, the persistent feeder loop,
the resident cost-model key, and the router's three-way route choice —
all on CPU, no live device needed (JAX_PLATFORMS=cpu in CI).

The correctness spine is the differential: resident-loop answers must
be bit-identical to the fused device path AND the forced chunked host
path across tiers, tombstones, overlay, and owner filters — the
resident kernel is the SAME traced function AOT-compiled with
donation, so any divergence is a bug in the plumbing, not a modeling
choice."""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from dss_tpu import errors  # noqa: F401 — typed shed errors surface here
from dss_tpu.dar.coalesce import QueryCoalescer, _CostModel
from dss_tpu.dar.snapshot import DarTable
from dss_tpu.ops import fastpath
from dss_tpu.ops.resident import (
    AotCache,
    ResidentKernel,
    ResidentLoop,
    max_words_for,
)
from tests.test_deadline_routing import _host_route, _route

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


def _query_args(rng, b, key_space, width=4):
    keys_list = [
        np.unique(rng.integers(0, key_space, width).astype(np.int32))
        for _ in range(b)
    ]
    return (
        keys_list,
        rng.uniform(0, 2000, b).astype(np.float32),
        rng.uniform(2000, 4000, b).astype(np.float32),
        np.full(b, NOW - HOUR, np.int64),
        np.full(b, NOW + HOUR, np.int64),
    )


# -- AOT cache ---------------------------------------------------------------


def test_aot_cache_compile_hit_miss_counters():
    """warm() compiles the grid once (idempotent); lookup() hits for
    warmed buckets, counts misses for unwarmed ones, and the per-table
    key is the block count — two tables with equal blocks share
    executables."""
    table = DarTable()
    rng = np.random.default_rng(1)
    _fill(table, 300, 40, rng)
    table.fold()
    try:
        ft = table._state.tiers[0].snap.fast
        cache = AotCache()
        kern = ResidentKernel(cache, autocompile=False)
        n = kern.warm(ft, batch_buckets=(128,), window_buckets=(256,))
        assert n == 1 and cache.compiles == 1
        # idempotent: same grid, nothing new
        assert kern.warm(ft, (128,), (256,)) == 0
        assert cache.compiles == 1
        mw = max_words_for(256)
        assert kern.lookup(ft, 256, 128, mw) is not None
        assert kern.hits == 1 and kern.misses == 0
        # unwarmed bucket: miss, no executable
        assert kern.lookup(ft, 1024, 128, max_words_for(1024)) is None
        assert kern.misses == 1
    finally:
        table.close()


def test_aot_async_compile_fills_missed_bucket():
    """A lookup miss with autocompile schedules the bucket on the
    background compiler; the next lookup hits."""
    table = DarTable()
    rng = np.random.default_rng(2)
    _fill(table, 200, 40, rng)
    table.fold()
    try:
        ft = table._state.tiers[0].snap.fast
        kern = ResidentKernel(AotCache(), autocompile=True)
        mw = max_words_for(256)
        assert kern.lookup(ft, 256, 128, mw) is None  # miss + schedule
        deadline = time.time() + 30.0
        while kern.lookup(ft, 256, 128, mw) is None and time.time() < deadline:
            time.sleep(0.05)
        assert kern.lookup(ft, 256, 128, mw) is not None
    finally:
        table.close()


def test_aot_cache_eviction_bounds_entries():
    """Tier rebuilds change the block count; executables for dead
    block counts must not accumulate forever — the cache evicts by
    last use past its cap."""
    table = DarTable()
    rng = np.random.default_rng(9)
    _fill(table, 200, 40, rng)
    table.fold()
    try:
        ft = table._state.tiers[0].snap.fast
        cache = AotCache(max_entries=3)
        kern = ResidentKernel(cache, autocompile=False)
        kern.warm(ft, batch_buckets=(16, 32, 64, 128),
                  window_buckets=(256,))
        assert cache.size() == 3
        assert cache.evictions == 1
        # the most recent bucket survived
        assert kern.lookup(ft, 256, 128, max_words_for(256)) is not None
    finally:
        table.close()


# -- differential: resident vs fused vs host chunks --------------------------


def test_resident_matches_fused_and_host_chunked_exactly():
    """The acceptance differential: resident answers == query_fused ==
    query_host_chunked across tiers + overlay + tombstones + owner
    filters, with the device tiers REALLY served by the AOT donated
    executables (hits > 0)."""
    rng = np.random.default_rng(23)
    # idle_fold_s=0: a background idle fold between the AOT warm and
    # the query would rebuild L1 with a new block count and turn every
    # warmed bucket into a miss — the production path re-warms via the
    # fold hook; this test pins the warmed-path differential
    table = DarTable(delta_capacity=256, idle_fold_s=0)
    _fill(table, 400, 60, rng)
    # the overlay overflow already queued a background fold; poll until
    # the tier structure is actually published (fold() no-ops while
    # one is in flight), or the warm below would run against a state
    # the swap is about to replace
    deadline = time.time() + 10.0
    while (
        table._state.pending or not table._state.tiers
    ) and time.time() < deadline:
        table.fold()
        time.sleep(0.01)
    assert table._state.tiers, "fold never published a tier"
    _fill(table, 80, 60, rng, prefix="late")  # overlay on top
    for i in range(0, 40, 7):
        table.remove(f"e{i}")  # tombstones
    try:
        b = 200  # beyond the 64-query auto host cutoff -> device tiers
        args = _query_args(rng, b, 60)
        owners = np.where(
            np.arange(b) % 3 == 0, np.arange(b) % 5, -1
        ).astype(np.int32)
        kern = ResidentKernel(AotCache(), autocompile=False)
        for tier in table._state.tiers:
            if tier.snap.fast is not None:
                kern.warm(
                    tier.snap.fast, batch_buckets=(256,),
                    window_buckets=(256, 512, 1024, 2048, 4096),
                )
        device = table.query_many(*args, now=NOW, owner_ids=owners)
        host = table.query_many(
            *args, now=NOW, owner_ids=owners, host_route=True
        )
        res = table.query_many(
            *args, now=NOW, owner_ids=owners, kernel=kern
        )
        assert device == res
        assert host == res
        assert kern.hits >= 1  # the AOT executables actually ran
    finally:
        table.close()


def test_resident_overflow_retry_stays_resident_and_exact():
    """A max_words overflow on the resident path retries through the
    SAME kernel selector at the hard bound and stays exact."""
    rng = np.random.default_rng(5)
    table = DarTable()
    # many entities on few keys -> dense postings runs -> many hits
    for i in range(500):
        table.upsert(
            f"e{i}", np.asarray([i % 3], np.int32), 0.0, 100.0,
            NOW - HOUR, NOW + HOUR, 0,
        )
    table.fold()
    try:
        ft = table._state.tiers[0].snap.fast
        kern = ResidentKernel(AotCache(), autocompile=False)
        b = 96
        qkeys = np.tile(np.asarray([0, 1, 2], np.int32), (b, 1))
        args = (
            qkeys,
            np.zeros(b, np.float32), np.full(b, 200.0, np.float32),
            np.full(b, NOW - HOUR, np.int64),
            np.full(b, NOW + HOUR, np.int64),
        )
        # tiny max_words forces the overflow-retry path
        pend = ft.submit(*args, now=NOW, max_words=16, kernel=kern)
        assert pend is not None and pend.kernel is kern
        qidx, slots = ft.collect(pend)
        ref_q, ref_s = ft.query_fused(*args, now=NOW)
        np.testing.assert_array_equal(qidx, ref_q)
        np.testing.assert_array_equal(slots, ref_s)
    finally:
        table.close()


# -- donation safety ---------------------------------------------------------


def test_donation_never_aliases_collected_results():
    """The donated executables recycle INPUT buffers only: a result
    collected from batch A must stay bit-stable (and correct) after
    batches B, C... are enqueued through the same bucket — the exact
    aliasing hazard donate_argnums could introduce if outputs shared
    donated memory."""
    rng = np.random.default_rng(11)
    table = DarTable()
    _fill(table, 600, 50, rng)
    table.fold()
    try:
        ft = table._state.tiers[0].snap.fast
        kern = ResidentKernel(AotCache(), autocompile=False)
        b = 128
        args_a = _query_args(rng, b, 50)
        qk = np.full((b, 8), -1, np.int32)
        for i, k in enumerate(args_a[0]):
            qk[i, : len(k)] = k
        a_in = (qk, args_a[1], args_a[2], args_a[3], args_a[4])
        kern.warm(ft, batch_buckets=(128,), window_buckets=(256, 1024))
        qidx_a, slots_a = ft.collect(ft.submit(*a_in, now=NOW, kernel=kern))
        snap_q, snap_s = qidx_a.copy(), slots_a.copy()
        # hammer the same bucket: donated input buffers get recycled
        for seed in range(6):
            r2 = np.random.default_rng(100 + seed)
            args_b = _query_args(r2, b, 50)
            qk2 = np.full((b, 8), -1, np.int32)
            for i, k in enumerate(args_b[0]):
                qk2[i, : len(k)] = k
            ft.collect(
                ft.submit(
                    qk2, args_b[1], args_b[2], args_b[3], args_b[4],
                    now=NOW, kernel=kern,
                )
            )
        np.testing.assert_array_equal(qidx_a, snap_q)
        np.testing.assert_array_equal(slots_a, snap_s)
        # and A's answer is still the correct one
        ref_q, ref_s = ft.query_fused(*a_in, now=NOW)
        np.testing.assert_array_equal(qidx_a, ref_q)
        np.testing.assert_array_equal(slots_a, ref_s)
        assert kern.hits >= 7
    finally:
        table.close()


# -- cost model: the resident key is isolated --------------------------------


def test_resident_observations_never_feed_cold_floor():
    """The satellite fix: resident-route observations move ONLY
    est_res_floor_ms; the cold-device floor (and its fit moments) stay
    untouched — and vice versa."""
    m = _CostModel(floor_ms=100.0, item_ms=0.01, chunk_ms=0.3,
                   res_floor_ms=25.0)
    for _ in range(40):
        m.observe_resident(256, 5.0 + 0.01 * 256)
    assert m.est_floor_ms == 100.0  # cold floor untouched
    assert m.est_res_floor_ms == pytest.approx(5.0, rel=0.1)
    assert m.resident_obs == 40 and m.device_obs == 0
    # cold observations leave the resident floor alone
    before = m.est_res_floor_ms
    for _ in range(40):
        m.observe_device(256, 110.0)
    assert m.est_res_floor_ms == before
    assert m.est_floor_ms > 50.0


def test_resident_seed_knob_and_default():
    """DSS_CO_EST_RES_FLOOR_MS seeds the resident floor; unset, the
    default derives from the cold seed (floor / 4).  The latency key
    defaults to one full cold round trip — a high-RTT host must not
    bet fresh deadlines on the stream until it MEASURES low latency."""
    m = _CostModel(floor_ms=100.0)
    assert m.est_res_floor_ms == pytest.approx(25.0)
    assert m.est_res_lat_ms == pytest.approx(100.0)
    m2 = _CostModel(floor_ms=100.0, res_floor_ms=3.0, res_lat_ms=8.0)
    assert m2.est_res_floor_ms == pytest.approx(3.0)
    assert m2.predict_resident_ms(100) == pytest.approx(
        3.0 + 0.02 * 100
    )
    # queued resident batches each add a resident floor, not a cold one
    assert m2.predict_resident_ms(100, inflight=2) == pytest.approx(
        9.0 + 0.02 * 100
    )
    # the latency view keeps the full round trip and adds queue floors
    assert m2.predict_resident_latency_ms(100, inflight=2) == (
        pytest.approx(8.0 + 6.0 + 0.02 * 100)
    )


def test_resident_latency_key_separates_throughput_from_deadline():
    """A saturated stream on a high-RTT host: the gap (floor) learns
    small while the latency stays ~RTT — the floor cut is real AND
    deadline routing still sees the wire."""
    m = _CostModel(floor_ms=110.0, res_floor_ms=30.0, res_lat_ms=110.0)
    for _ in range(60):
        m.observe_resident(256, gap_ms=6.0, lat_ms=112.0)
    assert m.est_res_floor_ms < 2.0  # amortized floor learned
    assert m.est_res_lat_ms > 80.0  # the round trip never vanishes


def test_env_knobs_parse_resident_settings(monkeypatch):
    from dss_tpu.dar.coalesce import env_knobs

    monkeypatch.setenv("DSS_CO_RESIDENT", "1")
    monkeypatch.setenv("DSS_CO_EST_RES_FLOOR_MS", "2.5")
    monkeypatch.setenv("DSS_CO_EST_RES_LAT_MS", "12.0")
    monkeypatch.setenv("DSS_CO_RES_RING", "8")
    monkeypatch.setenv("DSS_CO_RES_INFLIGHT", "2")
    k = env_knobs()
    assert k["resident"] is True
    assert k["est_res_floor_ms"] == 2.5
    assert k["est_res_lat_ms"] == 12.0
    assert k["res_ring"] == 8
    assert k["res_inflight"] == 2


# -- router: resident as a third candidate, no live device -------------------


class _NullLoop:
    """has_space-only stand-in so route choice is testable without a
    real loop (acceptance: route choice unit-tested against the
    resident cost-model key without a live device)."""

    def __init__(self, space=True):
        self.space = space

    def has_space(self):
        return self.space

    def close(self, join=True, timeout=30.0):
        pass


def test_router_three_way_choice_without_live_device():
    table = DarTable()
    co = QueryCoalescer(
        table, inline=False, min_batch=1,
        est_floor_ms=100.0, est_item_ms=0.01, est_chunk_ms=0.2,
        est_res_floor_ms=1.0, est_res_lat_ms=1.0,
    )
    try:
        co._res_loop = _NullLoop()
        batch = [object()] * 200
        # bulk (no deadlines): resident beats cold dispatch
        assert _route(co, batch, None) == "resident"
        # rich headroom: resident latency fits the budget
        assert _route(co, batch, 20.0) == "resident"
        # headroom too tight even for resident (3 ms pred vs 1 ms
        # budget) and host cheaper -> hostchunk
        assert _route(co, batch, 2.0) == "hostchunk"
        # ring full: resident inadmissible, cold device blows the
        # budget, host wins
        co._res_loop = _NullLoop(space=False)
        assert _route(co, batch, 20.0) == "hostchunk"
        # no loop at all: identical to the two-route PR5 router
        co._res_loop = None
        assert _route(co, batch, 20.0) == "hostchunk"
        assert _route(co, batch, None) == "device"
        assert _host_route(co, batch, 20.0) is True
    finally:
        co.close()
        table.close()


def test_queued_resident_work_counts_in_prediction():
    """Queued resident batches push the prediction up by resident
    floors — enough of them and the router overflows to another
    route (no unbounded device-stream queueing)."""
    table = DarTable()
    co = QueryCoalescer(
        table, inline=False, est_floor_ms=1000.0, est_item_ms=0.0,
        est_chunk_ms=0.1, est_res_floor_ms=4.0, est_res_lat_ms=4.0,
    )
    try:
        co._res_loop = _NullLoop()
        batch = [object()] * 64
        assert _route(co, batch, 20.0) == "resident"
        co._inflight_resident = 8  # 9 floors = 36 ms > 10 ms budget
        assert _route(co, batch, 20.0) == "hostchunk"
    finally:
        co.close()
        table.close()


# -- the loop: ring, backpressure, shutdown ----------------------------------


class _GatedTable:
    def __init__(self, table):
        self._table = table
        self.gate = threading.Event()

    def query_many_submit(self, *a, **kw):
        self.gate.wait(10.0)
        return self._table.query_many_submit(*a, **kw)

    def query_many_collect(self, pq):
        return self._table.query_many_collect(pq)

    def set_resident_warm(self, fn):
        pass


def _payload(keys=(3,)):
    b = 1
    return (
        [np.asarray(keys, np.int32)],
        np.full(b, -np.inf, np.float32),
        np.full(b, np.inf, np.float32),
        np.full(b, NOW - HOUR, np.int64),
        np.full(b, NOW + HOUR, np.int64),
        np.full(b, NOW, np.int64),
        np.full(b, -1, np.int32),
    )


def test_loop_ring_backpressure_and_delivery():
    inner = DarTable()
    inner.upsert("e0", np.asarray([3], np.int32), None, None,
                 NOW - HOUR, NOW + HOUR, 0)
    gated = _GatedTable(inner)
    loop = ResidentLoop(gated, ring_capacity=2, max_inflight=1)
    done_results = []
    ev = threading.Event()

    def done(results, err, gap_ms, lat_ms, used_device):
        done_results.append((results, err))
        if len(done_results) == 3:
            ev.set()

    try:
        assert loop.enqueue(_payload(), done)
        # wait until the feeder has TAKEN the first batch (it then
        # stalls in the gated submit); the ring holds the rest.  Racing
        # ahead while the batch still sits in the ring would fill the
        # cap one enqueue early.
        deadline = time.time() + 5.0
        while loop.stats()["ring_depth"] != 0 and time.time() < deadline:
            time.sleep(0.005)
        assert loop.enqueue(_payload(), done)
        assert loop.enqueue(_payload(), done)
        # ring full (cap 2, one stalled in the feeder): reject
        assert not loop.enqueue(_payload(), done)
        assert loop.stats()["rejected"] == 1
        gated.gate.set()
        assert ev.wait(10.0)
        assert all(err is None for _, err in done_results)
        assert all(res == [["e0"]] for res, _ in done_results)
    finally:
        gated.gate.set()
        loop.close()
        inner.close()


def test_loop_close_drains_queued_ring():
    """close() with batches still queued in the ring: every one is
    submitted, collected, delivered — then both threads exit."""
    inner = DarTable()
    inner.upsert("e0", np.asarray([3], np.int32), None, None,
                 NOW - HOUR, NOW + HOUR, 0)
    gated = _GatedTable(inner)
    loop = ResidentLoop(gated, ring_capacity=8, max_inflight=1)
    got = []

    def done(results, err, gap_ms, lat_ms, used_device):
        got.append((results, err))

    try:
        for _ in range(4):
            assert loop.enqueue(_payload(), done)
        deadline = time.time() + 5.0
        while loop.stats()["ring_depth"] < 3 and time.time() < deadline:
            time.sleep(0.005)
        assert loop.stats()["ring_depth"] >= 3  # queued at close time
        closer = threading.Thread(target=loop.close)
        closer.start()
        time.sleep(0.05)
        gated.gate.set()
        closer.join(15.0)
        assert not closer.is_alive()
        assert len(got) == 4
        assert all(err is None for _, err in got)
        assert loop.stats()["ring_depth"] == 0
        assert not loop._feeder.is_alive()
        assert not loop._collector.is_alive()
        # closed loop rejects new work
        assert not loop.enqueue(_payload(), done)
    finally:
        gated.gate.set()
        loop.close()
        inner.close()


# -- end-to-end through the coalescer ----------------------------------------


def test_end_to_end_resident_route_counted_and_exact():
    """A burst through a resident-enabled coalescer rides the loop
    (co_route_resident_batches > 0, zero cold-device batches), answers
    match the serial reference, and the resident floor estimate moved
    off its seed while the cold floor kept it."""
    rng = np.random.default_rng(7)
    table = DarTable()
    _fill(table, 300, 50, rng)
    co = QueryCoalescer(
        table, min_batch=1, max_batch=256, inline=False, queue_depth=64,
        slo_ms=0.0, resident=True,
        est_floor_ms=10_000.0, est_res_floor_ms=0.05, est_chunk_ms=1e6,
    )
    try:
        assert co.resident_loop() is not None
        cases = [
            np.unique(rng.integers(0, 50, 3).astype(np.int32))
            for _ in range(128)
        ]
        with ThreadPoolExecutor(max_workers=32) as pool:
            got = list(pool.map(lambda k: co.query(k, now=NOW), cases))
        serial = [table.query(k, now=NOW) for k in cases]
        assert [sorted(g) for g in got] == [sorted(s) for s in serial]
        deadline = time.time() + 10.0
        while co.stats()["co_inflight"] > 0 and time.time() < deadline:
            time.sleep(0.01)
        st = co.stats()
        assert st["co_route_resident_batches"] >= 1
        assert st["co_route_device_batches"] == 0
        assert st["co_est_device_floor_ms"] == 10_000.0  # never fed
        assert st["co_res_enqueued"] >= 1
    finally:
        co.close()
        table.close()


def test_coalescer_close_resolves_resident_queued_callers():
    """Coalescer shutdown with the resident ring non-empty: every
    admitted caller resolves (the CI resident-smoke contract)."""
    inner = DarTable()
    inner.upsert("e0", np.asarray([3], np.int32), None, None,
                 NOW - HOUR, NOW + HOUR, 0)
    gated = _GatedTable(inner)
    co = QueryCoalescer(
        gated, min_batch=1, inline=False, queue_depth=64, slo_ms=0.0,
        resident=True, est_floor_ms=10_000.0, est_res_floor_ms=0.05,
        est_chunk_ms=1e6,
    )
    results = []

    def client():
        results.append(co.query(np.asarray([3], np.int32), now=NOW))

    try:
        ths = [threading.Thread(target=client) for _ in range(5)]
        for t in ths:
            t.start()
            time.sleep(0.02)
        loop = co.resident_loop()
        deadline = time.time() + 5.0
        while (
            loop.stats()["ring_depth"] + loop.stats()["submitted"] < 1
            and time.time() < deadline
        ):
            time.sleep(0.005)
        closer = threading.Thread(target=co.close)
        closer.start()
        time.sleep(0.05)
        gated.gate.set()
        closer.join(20.0)
        for t in ths:
            t.join(10.0)
        assert len(results) == 5
        assert all(r == ["e0"] for r in results)
    finally:
        gated.gate.set()
        co.close()
        inner.close()


def test_configure_toggles_resident_loop():
    table = DarTable()
    co = QueryCoalescer(table)
    try:
        assert co.resident_loop() is None
        st = co.stats()
        # stable gauge keys even with no loop attached
        assert st["co_res_ring_cap"] == 0
        assert st["co_route_resident_batches"] == 0
        co.configure(resident=True)
        assert co.resident_loop() is not None
        assert co.stats()["co_res_ring_cap"] > 0
        co.configure(resident=False)
        assert co.resident_loop() is None
    finally:
        co.close()
        table.close()


@pytest.mark.parametrize("postings, sent_to_device, warmed", [
    (20_000, 5, False),  # a write cell's op L1 tier: the host scan's
    (fastpath.FastTable.HOST_MAX_CANDIDATES, 5, False),
    (fastpath.FastTable.HOST_MAX_CANDIDATES + 1, 5, True),
    (800_000, 5, True),  # an L0 of a class the kernel serves
    (80_000, 0, False),  # a city's subscriptions: never off the host
])
def test_fold_time_warm_skips_what_the_host_scan_answers(
        postings, sent_to_device, warmed, monkeypatch):
    """The fold-time hook compiles the AOT grid only for a tier that a
    drain can send to the device (one holding more postings than the
    host scan's cap), in a class that has sent a query there.  A
    smaller tier's block count moves at every fold, and a class the
    host answers alone rebuilds its L0 at every major compaction:
    warming either compiled beside served requests for nothing."""
    hooks = []

    class Table(_GatedTable):
        def set_resident_warm(self, fn):
            hooks.append(fn)

    inner = DarTable()
    co = QueryCoalescer(Table(inner), resident=True)
    try:
        asked = []
        monkeypatch.setattr(type(co.resident_loop().kernel), "warm_async",
                            lambda self, ft: asked.append(ft))
        co._stat_device_members = sent_to_device

        class Tier:
            n_postings = postings

        hooks[-1](Tier)
        assert (asked == [Tier]) is warmed
    finally:
        co.close()
        inner.close()
