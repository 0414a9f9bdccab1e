"""The plan layer (dss_tpu/plan): pure-function routing decisions.

Three tiers of protection for the planner refactor:

  1. GOLDEN TABLE — a recorded table of (model state, batch shape,
     headroom) -> expected plan, replayed against `decide` with no
     live coalescer, no device, no threads (the ROADMAP item 5
     done-condition).

  2. EQUIVALENCE SUITE — a verbatim port of the PRE-planner router
     (QueryCoalescer._choose_route / _BatchController.drain_cap /
     _CostModel.min_route_qps exactly as they shipped in PR 5/6) is
     replayed against the planner over a seeded trace of thousands of
     recorded model states: the refactor must be decision-identical,
     bit for bit, on every route choice and every drain cap.

  3. LIVE WIRING — a real QueryCoalescer's plans land in the
     co_plan_* counters, every route is reachable by SOME plan, and
     the Retry-After fallback quotes the chosen route's throughput
     (the PR 10 fix), not the unconditional min(host, device).
"""

import math

import numpy as np
import pytest

from dss_tpu.plan import (
    HEADROOM_SAFETY,
    ROUTES,
    BatchShape,
    CostModel,
    ModelState,
    Plan,
    Planner,
    decide,
    plan_drain_cap,
)

NOW = 1_700_000_000_000_000_000
HOUR = 3_600_000_000_000


def st(**kw) -> ModelState:
    base = dict(
        est_floor_ms=100.0,
        est_item_ms=0.01,
        est_chunk_ms=0.2,
        est_res_floor_ms=25.0,
        est_res_lat_ms=100.0,
        chunk=64,
    )
    base.update(kw)
    return ModelState(**base)


# -- 1. golden table ----------------------------------------------------------

# (state overrides, shape, headroom_ms, expected route,
#  expected deadline class, expected freshness class)
GOLDEN = [
    # tight headroom, host wins: the deadline router's escape hatch
    (dict(), BatchShape(n=200), 8.0, "hostchunk", "fresh", "fresh"),
    # rich headroom: the cold fused kernel fits the budget
    (dict(), BatchShape(n=200), 1000.0, "device", "fresh", "fresh"),
    # bulk / all-stale (no headroom): throughput decision -> device
    (dict(), BatchShape(n=200, all_stale=True), None,
     "device", "bulk", "fresh"),
    # resident attached with a measured-lower floor: bulk rides it
    (dict(resident_ready=True, est_res_floor_ms=5.0),
     BatchShape(n=200, all_stale=True), None,
     "resident", "bulk", "fresh"),
    # resident latency equal to cold at the seed state: tie-break
    # toward the stream (equal latency, strictly cheaper dispatch)
    (dict(resident_ready=True), BatchShape(n=200), 1000.0,
     "resident", "fresh", "fresh"),
    # both device-class candidates blow an 8 ms budget and the host
    # chunks are slower still: lesser evil, stay on the device class
    (dict(est_chunk_ms=1000.0), BatchShape(n=200), 8.0,
     "device", "fresh", "fresh"),
    # mesh-admissible (stale, unowned, in the size window): the mesh
    # IS the plan, carrying the placement generation it was made under
    (dict(mesh_ready=True, boundary_gen=7),
     BatchShape(n=128, all_stale=True), None,
     "mesh", "bulk", "bounded_stale"),
    # owner-scoped batches are never mesh-admissible
    (dict(mesh_ready=True),
     BatchShape(n=128, all_stale=True, owner_scoped=True), None,
     "device", "bulk", "fresh"),
    # a lone inline caller below the host cutoff: the inline route
    (dict(), BatchShape(n=1, inline=True), 1000.0,
     "inline", "fresh", "fresh"),
    # inline under deadline pressure still escapes to forced chunks
    (dict(), BatchShape(n=200, inline=True), 8.0,
     "hostchunk", "fresh", "fresh"),
    # ...but never for a host-only (event-loop) caller
    (dict(host_only=True, est_chunk_ms=0.01),
     BatchShape(n=200, inline=True), 8.0,
     "device", "fresh", "fresh"),
]


@pytest.mark.parametrize(
    "overrides,shape,headroom,route,dl,fresh",
    GOLDEN,
    ids=[f"g{i}-{g[3]}" for i, g in enumerate(GOLDEN)],
)
def test_golden_plans(overrides, shape, headroom, route, dl, fresh):
    state = st(**overrides)
    p = decide(shape, state, headroom)
    assert p.route == route
    assert p.deadline_class == dl
    assert p.freshness_class == fresh
    assert p.n == shape.n
    assert p.boundary_gen == state.boundary_gen
    # the chosen route's predicted cost is the plan's headline number
    cand = dict(p.candidates)
    if route != "inline":
        assert p.predicted_ms == pytest.approx(
            cand[route] if cand[route] is not None else p.predicted_ms
        )
    # decisions are pure: same inputs, same plan, every time
    assert decide(shape, state, headroom) == p


def test_state_and_shape_round_trip_serializable():
    """Recorded model states replay: to_dict/from_dict is lossless,
    so a decision trace captured in production replays offline."""
    s = st(resident_ready=True, inflight_device=3, boundary_gen=9)
    assert ModelState.from_dict(s.to_dict()) == s
    sh = BatchShape(n=77, all_stale=True)
    assert BatchShape.from_dict(sh.to_dict()) == sh
    p = decide(sh, s, 50.0)
    d = p.to_dict()
    assert d["route"] == p.route
    assert d["candidates"]["device"] == pytest.approx(
        s.predict_device_ms(77)
    )


# -- 2. equivalence vs the pre-planner router ---------------------------------
#
# The reference implementations below are VERBATIM ports of the PR 5/6
# router (dar/coalesce.py before the plan layer): _choose_route,
# _BatchController.drain_cap, and _CostModel.min_route_qps, expressed
# over a ModelState's numbers.  Do not "fix" them — their job is to be
# exactly what shipped.


def ref_choose_route(s: ModelState, n: int, headroom_ms,
                     allow_resident: bool = True) -> str:
    pred_dev = (
        s.est_floor_ms * (1 + max(0, s.inflight_device))
        + s.est_item_ms * n
    )
    res_ok = allow_resident and s.resident_ready
    if headroom_ms is None:
        pred_res = (
            s.est_res_floor_ms * (1 + max(0, s.inflight_resident))
            + s.est_item_ms * n
        )
        if res_ok and pred_res < pred_dev:
            return "resident"
        return "device"
    dc_lat, kind = pred_dev, "device"
    if res_ok:
        res_lat = (
            s.est_res_lat_ms
            + s.est_res_floor_ms * max(0, s.inflight_resident)
            + s.est_item_ms * n
        )
        if res_lat <= pred_dev:
            dc_lat, kind = res_lat, "resident"
    if dc_lat <= 0.5 * headroom_ms:
        return kind
    chunks = max(1, -(-n // s.chunk))
    pred_host = (
        (chunks + max(0, s.inflight_host_chunks)) * s.est_chunk_ms
        + max(0, s.inflight_device) * s.est_floor_ms
    )
    if pred_host < dc_lat:
        return "hostchunk"
    return kind


def ref_drain_cap(s: ModelState, cur: int, headroom_ms) -> int:
    if headroom_ms is None:
        return cur
    budget_ms = 0.5 * max(0.0, headroom_ms)
    pred_dev = (
        s.est_floor_ms * (1 + max(0, s.inflight_device))
        + s.est_item_ms * cur
    )
    if s.resident_ready:
        pred_dev = min(
            pred_dev,
            s.est_res_lat_ms
            + s.est_res_floor_ms * max(0, s.inflight_resident)
            + s.est_item_ms * cur,
        )
    if pred_dev <= budget_ms:
        return cur
    chunks = max(1, -(-cur // s.chunk))
    pred_host = (
        (chunks + max(0, s.inflight_host_chunks)) * s.est_chunk_ms
        + max(0, s.inflight_device) * s.est_floor_ms
    )
    if pred_host >= pred_dev:
        return cur
    fit = (
        int(
            (budget_ms - s.inflight_device * s.est_floor_ms)
            / max(s.est_chunk_ms, 1e-3)
        )
        - max(0, s.inflight_host_chunks)
    )
    return max(s.chunk, min(cur, s.chunk * max(1, fit)))


def _random_states(seed: int, count: int):
    """A seeded trace of recorded model states + batch shapes — the
    decision inputs a live coalescer produces, swept over the full
    dynamic range of every estimate and pressure counter."""
    rng = np.random.default_rng(seed)
    for _ in range(count):
        floor = float(10 ** rng.uniform(-1.3, 2.7))  # 0.05..500 ms
        s = ModelState(
            est_floor_ms=floor,
            est_item_ms=float(10 ** rng.uniform(-4, -1.3)),
            est_chunk_ms=float(10 ** rng.uniform(-2, 1.7)),
            est_res_floor_ms=float(
                max(0.02, floor * rng.uniform(0.02, 1.5))
            ),
            est_res_lat_ms=float(
                max(0.02, floor * rng.uniform(0.1, 2.0))
            ),
            chunk=64,
            inflight_device=int(rng.integers(0, 5)),
            inflight_host_chunks=int(rng.integers(0, 40)),
            inflight_resident=int(rng.integers(0, 8)),
            resident_ready=bool(rng.integers(0, 2)),
        )
        n = int(rng.integers(1, 4097))
        headroom = (
            None
            if rng.random() < 0.3
            else float(10 ** rng.uniform(-1, 3.3))  # 0.1..2000 ms
        )
        yield s, n, headroom


def test_decision_identical_to_pre_planner_router_on_trace():
    """The refactor cannot drift behavior: 4000 recorded (state,
    shape, headroom) tuples, every route choice identical to the
    pre-planner router, with and without the resident candidate."""
    checked = 0
    routes_seen = set()
    for s, n, headroom in _random_states(1234, 4000):
        for allow_res in (True, False):
            want = ref_choose_route(s, n, headroom, allow_res)
            got = decide(
                BatchShape(n=n), s, headroom,
                allow_resident=allow_res, allow_mesh=False,
            ).route
            assert got == want, (s, n, headroom, allow_res, got, want)
            routes_seen.add(got)
            checked += 1
    assert checked == 8000
    # the trace actually exercised all three queued-batch routes
    assert routes_seen == {"device", "resident", "hostchunk"}


def test_drain_cap_identical_to_pre_planner_controller_on_trace():
    for s, n, headroom in _random_states(987, 3000):
        cur = max(64, n)
        want = ref_drain_cap(s, cur, headroom)
        got = plan_drain_cap(cur, headroom, s)
        assert got == want, (s, cur, headroom, got, want)


def test_drain_cap_and_route_choice_share_one_budget():
    """The invariant the plan layer exists to enforce: whenever the
    drain cap shrinks to host chunks, the route choice at that size
    is the host route (same HEADROOM_SAFETY budget — the two can
    never disagree)."""
    for s, n, headroom in _random_states(55, 2000):
        if headroom is None:
            continue
        cur = max(64, n)
        cap = plan_drain_cap(cur, headroom, s)
        if cap < cur:
            # the cap only shrank because, at the drained size, the
            # device class blew the budget AND the host route was the
            # cheaper escape — which is precisely when decide() picks
            # the host route for that drain
            assert (
                decide(BatchShape(n=cur), s, headroom,
                       allow_mesh=False).route
                == "hostchunk"
            )


# -- cost model ownership -----------------------------------------------------


def test_planner_owns_cost_model_and_capture_freezes_it():
    pl = Planner(floor_ms=50.0, item_ms=0.01, chunk_ms=0.3, chunk=64)
    s0 = pl.capture()
    assert s0.est_floor_ms == 50.0
    # observations move the live model, never an already-frozen state
    for _ in range(50):
        pl.observe_device(256, 200.0)
    s1 = pl.capture()
    assert s1.est_floor_ms != s0.est_floor_ms
    assert s0.est_floor_ms == 50.0
    # the coalescer's _CostModel alias is the same moved class
    from dss_tpu.dar.coalesce import _CostModel

    assert _CostModel is CostModel


def test_every_route_reachable_by_some_plan():
    """The plan-smoke's unreachable-route guard, at the unit level:
    for each of the six routes there is a (shape, state, headroom)
    that selects it — `cache` through the external note seam (a hit
    is served before the coalescer; the store notes it as a plan)."""
    pl = Planner()
    reached = {}
    reached["device"] = pl.plan(
        BatchShape(n=256, all_stale=True), st(), None
    ).route
    reached["resident"] = pl.plan(
        BatchShape(n=256, all_stale=True),
        st(resident_ready=True, est_res_floor_ms=1.0), None,
    ).route
    reached["hostchunk"] = pl.plan(BatchShape(n=256), st(), 8.0).route
    reached["mesh"] = pl.plan(
        BatchShape(n=128, all_stale=True), st(mesh_ready=True), None
    ).route
    reached["inline"] = pl.plan(
        BatchShape(n=1, inline=True), st(), 1000.0
    ).route
    reached["rqmatch"] = pl.plan(
        BatchShape(n=32, rqmatch=True), st(), None
    ).route
    pl.note("cache")
    assert all(reached[r] == r for r in reached), reached
    stats = pl.stats()
    for r in ROUTES:
        assert stats[f"co_plan_{r}"] == 1, (r, stats)
    assert stats["co_plan_total"] == len(ROUTES)


# -- Retry-After: best-plan throughput (the PR 10 fix) ------------------------


def test_backlog_qps_quotes_the_chosen_route():
    """Overloaded clients are told to wait for the route that will
    actually serve them.  Pre-fix, min_route_qps quoted min(host,
    device) unconditionally."""
    pl = Planner(floor_ms=100.0, item_ms=0.0, chunk_ms=0.2, chunk=64,
                 res_floor_ms=2.0, res_lat_ms=5.0)
    s = pl.capture(resident_ready=True)
    n = 512
    host_qps = 64 / 0.2 * 1000.0
    dev_qps = n / 100.0 * 1000.0
    res_qps = n / 2.0 * 1000.0
    # fresh tight-SLO backlog drains hostward: quote host throughput
    assert pl.backlog_qps(n, s, 8.0) == pytest.approx(host_qps)
    # all-stale bulk backlog rides the resident stream: quote the
    # stream, NOT the cold-dispatch floor the old estimate used
    assert pl.backlog_qps(n, s, None, all_stale=True) == pytest.approx(
        res_qps
    )
    old = pl.cost.min_route_qps(n)
    assert old == pytest.approx(min(host_qps, dev_qps))
    assert pl.backlog_qps(n, s, None, all_stale=True) > 10 * old


def test_coalescer_retry_after_uses_planner_fallback():
    """Live wiring: an overloaded coalescer with no drain history
    quotes a Retry-After derived from the planner's best plan for the
    queued shape (finite, bounded, positive)."""
    from dss_tpu.dar.coalesce import QueryCoalescer
    from dss_tpu.dar.snapshot import DarTable

    table = DarTable()
    co = QueryCoalescer(
        table, inline=False, min_batch=1, queue_depth=1, max_batch=4,
        est_floor_ms=100.0, est_chunk_ms=0.2,
    )
    try:
        with co._cond:
            ra = co._retry_after_locked()
        assert 0.05 <= ra <= 5.0
    finally:
        co.close()
        table.close()


# -- live coalescer: plans flow into co_plan_* --------------------------------


def test_live_coalescer_counts_plans():
    from dss_tpu.dar.coalesce import QueryCoalescer
    from dss_tpu.dar.snapshot import DarTable

    rng = np.random.default_rng(3)
    table = DarTable()
    for i in range(64):
        keys = np.unique(rng.integers(0, 40, 3).astype(np.int32))
        table.upsert(f"e{i}", keys, 0.0, 100.0,
                     NOW - HOUR, NOW + HOUR, i % 3)
    co = QueryCoalescer(table)
    try:
        for _ in range(5):
            co.query(np.asarray([3], np.int32), now=NOW)
        stats = co.stats()
        for r in ROUTES:
            assert f"co_plan_{r}" in stats
        # lone callers ride the inline plan
        assert stats["co_plan_inline"] >= 1
        assert stats["co_plan_total"] >= 5
    finally:
        co.close()
        table.close()


def test_plan_counters_in_stats_are_stable_keys():
    """Dashboards and the plan-smoke expect the co_plan_* series on
    every deployment, routes attached or not."""
    from dss_tpu.dar.coalesce import QueryCoalescer
    from dss_tpu.dar.snapshot import DarTable

    table = DarTable()
    co = QueryCoalescer(table, inline=False)
    try:
        stats = co.stats()
        assert {f"co_plan_{r}" for r in ROUTES} <= set(stats)
        assert "co_plan_total" in stats
        assert "co_plan_fallbacks" in stats
    finally:
        co.close()
        table.close()


# -- 4. the offline profile's provenance (plan/autotune.py) ---------------------


def test_profile_staleness_flags_age_and_host_class():
    from dss_tpu.plan.autotune import host_class, profile_staleness

    now = 1_700_000_000.0
    fresh = {"host_class": host_class(),
             "measured_at": now - 3600.0}
    st = profile_staleness(fresh, now=now)
    assert st["has_timestamp"]
    assert st["age_s"] == pytest.approx(3600.0)
    assert st["host_class_match"]
    stale = {"host_class": "somewhere-else/gpu", "measured_at": now}
    st = profile_staleness(stale, now=now)
    assert not st["host_class_match"]
    # pre-versioning profile without a timestamp: age reads 0 (fresh)
    # but the flag lets boot warn that nothing is actually known
    st = profile_staleness({"host_class": host_class()}, now=now)
    assert not st["has_timestamp"]
    assert st["age_s"] == 0.0


def test_autotune_profiles_carry_measured_at(monkeypatch, tmp_path):
    """autotune() stamps measured_at so profile_staleness can age it;
    the knob payload itself stays on the KNOB_KEYS allowlist."""
    from dss_tpu.plan import autotune as at

    def fake_measure(*a, **k):
        return {"floor_ms": 2.0, "item_ms": 0.002, "chunk_ms": 0.2}

    # keep the test off real kernel timing: patch the measurement core
    # if present, otherwise run the real (CPU-cheap) path
    for name in ("measure_device", "_measure"):
        if hasattr(at, name):
            monkeypatch.setattr(at, name, fake_measure)
            break
    prof = at.autotune()
    assert "measured_at" in prof
    assert prof["measured_at"] > 1_600_000_000.0
    assert set(prof["knobs"]) <= set(at.KNOB_KEYS)
