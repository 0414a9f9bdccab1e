"""The write path's stages, counters and compile sites (ISSUE 39).

A planned flight through the app, as the benchmark's write cells file
one: PUT without a key into occupied airspace (409 with the conflicts'
OVNs), then the same PUT with that key (200, its implicit subscription
made on the way).  The request's stage sink, read from the
`X-Dss-Stages` header, holds the leader's time as disjoint legs; the
journal and the compiler count their own work where no request owns it.
"""

from __future__ import annotations

import threading
import time

import pytest
import requests

from dss_tpu.api.app import build_app
from dss_tpu.clock import Clock
from dss_tpu.dar.dss_store import DSSStore
from dss_tpu.obs import stages
from dss_tpu.obs.metrics import STAGE_NAMES, MetricsRegistry
from dss_tpu.push.pipeline import PushPipeline
from dss_tpu.services.rid import RIDService
from dss_tpu.services.scd import SCDService
from tests.live_server import LiveServer

OP1 = "eeeeeeee-eeee-4eee-8eee-eeeeeeeeeee1"
OP2 = "eeeeeeee-eeee-4eee-8eee-eeeeeeeeeee2"

# the leaves `write_unattributed_pct` sums against service_ms
# (dssbench/metrics/write_unattributed_pct.json holds the same list)
LEAVES = (
    "covering_ms", "txn_wait_ms", "precheck_ms", "conflict_list_ms",
    "sub_index_ms", "sub_affected_ms", "op_index_ms", "wal_commit_ms",
    "serialize_ms", "push_match_ms", "sub_bump_ms", "push_offer_ms",
    # both hops of run_in_executor (api/app.py _call): inside
    # service_ms, outside run(), so disjoint from every leg
    "exec_wait_ms",
    # what the chip's first reading left dark and this PR then lit: the
    # extents' parse and union, the match where no pipeline runs it
    "parse_ms", "sub_match_ms",
)


def _iso(offset_s: float) -> str:
    return time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(time.time() + offset_s))


def _flight(key=None) -> dict:
    body = {
        "extents": [{
            "volume": {
                "outline_polygon": {"vertices": [
                    {"lat": 40.0, "lng": -100.0},
                    {"lat": 40.02, "lng": -100.0},
                    {"lat": 40.02, "lng": -99.98},
                    {"lat": 40.0, "lng": -99.98},
                ]},
                "altitude_lower": {"value": 50.0, "reference": "W84",
                                   "units": "M"},
                "altitude_upper": {"value": 200.0, "reference": "W84",
                                   "units": "M"},
            },
            "time_start": {"value": _iso(600), "format": "RFC3339"},
            "time_end": {"value": _iso(3600), "format": "RFC3339"},
        }],
        "uss_base_url": "https://uss.example.com",
        "state": "Accepted",
        "new_subscription": {"uss_base_url": "https://uss.example.com"},
    }
    if key is not None:
        body["key"] = key
    return body


def _stages_of(resp) -> dict:
    return {k: float(v) for k, v in (
        kv.split("=") for kv in resp.headers["X-Dss-Stages"].split(";"))}


class Served:
    """One store with a journal behind the app, no authorizer (every
    caller `anonymous`), and what one planned flight left behind."""

    def __init__(self, tmp, fsync: bool, push: bool = False):
        self.wal_path = str(tmp / "dss.wal")
        self.profile_dir = tmp / "prof"
        clock = Clock()
        self.store = DSSStore(storage="tpu", clock=clock,
                              wal_path=self.wal_path, wal_fsync=fsync)
        if push:  # as --push: the pipeline runs the match and the offer
            self.store.attach_push(PushPipeline(
                workers=1, transport=lambda url, body, hdrs: None))
        self.metrics = MetricsRegistry()
        self.srv = LiveServer(build_app(
            RIDService(self.store.rid, clock),
            SCDService(self.store.scd, clock), None,
            enable_scd=True, metrics=self.metrics, trace_requests=True,
            profile_dir=str(self.profile_dir)))

    def put(self, op_id, key=None, headers=None):
        return requests.put(
            f"{self.srv.base}/dss/v1/operation_references/{op_id}",
            json=_flight(key), headers=headers, timeout=30)

    def flight(self, op_id, headers=None):
        """409, then 200 with the key the 409 listed."""
        r409 = self.put(op_id, headers=headers)
        assert r409.status_code == 409, r409.text
        key = [c["operation_reference"]["ovn"]
               for c in r409.json()["entity_conflicts"]]
        r200 = self.put(op_id, key, headers=headers)
        assert r200.status_code == 200, r200.text
        return r409, r200

    def close(self):
        self.srv.stop()
        self.store.close()


@pytest.fixture(scope="module")
def flown(tmp_path_factory):
    s = Served(tmp_path_factory.mktemp("flown"), fsync=True)
    try:
        first = s.put(OP1)  # empty airspace: 200 at once
        assert first.status_code == 200, first.text
        s.wal0 = s.store.wal.stats()
        s.r409 = s.put(OP2)
        assert s.r409.status_code == 409, s.r409.text
        s.wal409 = s.store.wal.stats()
        key = [c["operation_reference"]["ovn"]
               for c in s.r409.json()["entity_conflicts"]]
        s.r200 = s.put(OP2, key)
        assert s.r200.status_code == 200, s.r200.text
        s.wal200 = s.store.wal.stats()
        yield s
    finally:
        s.close()


def test_the_409_holds_the_two_searches_and_no_commit(flown):
    got = _stages_of(flown.r409)
    assert {"parse_ms", "txn_wait_ms", "precheck_ms", "conflict_list_ms",
            "covering_ms", "service_ms"} <= set(got), got
    for absent in ("wal_commit_ms", "sub_index_ms", "sub_affected_ms",
                   "op_index_ms", "sub_match_ms", "sub_bump_ms",
                   "serialize_ms"):
        assert absent not in got, (absent, got)
    assert got["precheck_ms"] > 0 and got["conflict_list_ms"] > 0


def test_the_200_holds_every_leg_of_the_commit(flown):
    got = _stages_of(flown.r200)
    assert {"parse_ms", "txn_wait_ms", "precheck_ms", "sub_index_ms",
            "op_index_ms", "wal_commit_ms", "sub_match_ms", "sub_bump_ms",
            "serialize_ms", "covering_ms", "service_ms"} <= set(got), got
    assert "conflict_list_ms" not in got, got
    # one transaction: the subscription's `affected` search, which no
    # one read, is not run
    assert "sub_affected_ms" not in got, got
    for name in ("precheck_ms", "sub_index_ms", "op_index_ms",
                 "wal_commit_ms", "sub_match_ms", "sub_bump_ms"):
        assert got[name] > 0, (name, got)


@pytest.fixture(scope="module")
def pushed(tmp_path_factory):
    """The same flight where a push pipeline is bound (`--push`, the
    storm cell): the pipeline's match and the offer are the legs."""
    s = Served(tmp_path_factory.mktemp("pushed"), fsync=False, push=True)
    try:
        assert s.put(OP1).status_code == 200
        s.r409, s.r200 = s.flight(OP2)
        yield s
    finally:
        s.close()


def test_a_bound_pipeline_runs_the_match_and_the_offer(pushed):
    got = _stages_of(pushed.r200)
    assert {"push_match_ms", "sub_bump_ms", "push_offer_ms"} <= set(got), got
    assert "sub_match_ms" not in got, got


@pytest.mark.parametrize("which", ["r409", "r200"])
@pytest.mark.parametrize("front", ["flown", "pushed"])
def test_the_leaves_of_one_request_fit_inside_its_service(
        request, front, which):
    """Disjoint by construction: every leaf but exec_wait_ms is a
    `stages.stage`, and a stage inside a stage marks nothing, so no
    leaf overlaps another; exec_wait_ms is service_ms less run(),
    which holds them all.  So their sum cannot pass service_ms (each
    is rounded to a microsecond when marked)."""
    got = _stages_of(getattr(request.getfixturevalue(front), which))
    total = sum(got.get(name, 0.0) for name in LEAVES)
    assert 0 < total <= got["service_ms"] + 0.001 * len(LEAVES), got


def test_every_new_stage_has_a_name_and_a_row(flown):
    """The sink's keys are in STAGE_NAMES (else they collapse to
    `other`), and the registry has a row for each under the PUT's
    route."""
    for name in LEAVES + ("leader_handler_ms",):
        assert name in STAGE_NAMES, name
    rows = {stage for (_route, stage) in flown.metrics.stage_hist_snapshot()}
    seen = set(_stages_of(flown.r409)) | set(_stages_of(flown.r200))
    assert seen <= rows, seen - rows
    assert "other" not in rows
    # one process, no front: the handler is observed under its one name
    assert "handler_ms" in rows and "leader_handler_ms" not in rows


def test_the_owner_of_a_front_names_its_handler_twice():
    """Behind a --workers front the store's owner observes the handler
    under `leader_handler_ms` too (cmds/server.py sets the pair): the
    merged `handler_ms` row there also holds the workers' interval,
    proxy hop included."""
    m = MetricsRegistry()
    m.handler_stages = ("handler_ms", "leader_handler_ms")

    class RID:
        def get_isa(self, id, owner=None):
            return {"service_area": {"id": id}}

    srv = LiveServer(build_app(RID(), None, None, metrics=m))
    try:
        r = requests.get(
            f"{srv.base}/v1/dss/identification_service_areas/x", timeout=10)
        assert r.status_code == 200
    finally:
        srv.stop()
    snap = m.stage_hist_snapshot()
    route = next(r for (r, st) in snap if st == "leader_handler_ms")
    assert snap[(route, "leader_handler_ms")][1:] == snap[
        (route, "handler_ms")][1:]


def test_a_put_through_the_ring_keeps_its_lights(tmp_path, keypair):
    """Behind a front a PUT rides the ring to the owner's write lane
    (tests/test_ring_writes.py): the owner still marks every leg within
    the request's `service_ms` and observes its handler, pickup to
    answer, as `leader_handler_ms`; the worker's wait for the leader is
    still `proxy_ms`; all on route class `write`.  No executor runs a
    ring write, so `exec_wait_ms` is the one leaf it never marks."""
    from dss_tpu.obs.metrics import route_class
    from tests.test_ring_writes import OP, ROUTE, RingFront
    from tests.test_ring_writes import _flight as _ring_flight

    f = RingFront(tmp_path, keypair)
    try:
        assert f.put(OP.format(1), _ring_flight()).status_code == 200
        r409 = f.put(OP.format(2), _ring_flight())
        assert r409.status_code == 409, r409.text
        key = [c["operation_reference"]["ovn"]
               for c in r409.json()["entity_conflicts"]]
        assert f.put(OP.format(2), _ring_flight(key)).status_code == 200
        leader = {st: v for (r, st), v in
                  f.leader_metrics.stage_hist_snapshot().items() if r == ROUTE}
        worker = {st: v for (r, st), v in
                  f.worker_metrics.stage_hist_snapshot().items() if r == ROUTE}
        assert f.stats()["write_ring"] == 3
    finally:
        f.close()
    assert route_class(ROUTE) == "write"
    lit = set(LEAVES) - {"exec_wait_ms", "push_match_ms", "push_offer_ms",
                         "sub_affected_ms"}
    assert lit <= set(leader), lit - set(leader)
    assert "exec_wait_ms" not in leader
    _buckets, service_s, n = leader["service_ms"]
    assert n == 3 and leader["leader_handler_ms"][2] == 3
    assert sum(leader[name][1] for name in lit) <= service_s + 1e-6 * len(lit)
    assert service_s <= leader["leader_handler_ms"][1]
    # the worker's side: its wait for the leader, its own handler
    assert worker["proxy_ms"][2] == 3 and worker["handler_ms"][2] == 3
    assert "service_ms" not in worker


def test_a_served_write_moves_the_journals_counters_by_its_records(flown):
    def moved(a, b, name):
        return b[name] - a[name]

    # the 409 journals nothing
    for name in flown.wal0:
        assert moved(flown.wal0, flown.wal409, name) == 0, name
    # the 200: scd_sub_put, scd_op_put, scd_sub_bump in one append,
    # fsynced once
    with open(flown.wal_path, "rb") as fh:
        lines = fh.read().splitlines(keepends=True)
    assert moved(flown.wal409, flown.wal200, "dss_wal_records_total") == 3
    assert moved(flown.wal409, flown.wal200, "dss_wal_appends_total") == 1
    assert moved(flown.wal409, flown.wal200, "dss_wal_fsyncs_total") == 1
    assert moved(flown.wal409, flown.wal200, "dss_wal_bytes_total") == sum(
        len(ln) for ln in lines[-3:])
    assert (0 < moved(flown.wal409, flown.wal200,
                      "dss_wal_fsync_seconds_total")
            <= moved(flown.wal409, flown.wal200,
                     "dss_wal_append_seconds_total"))
    # all of it since the process started: header aside, the file's bytes
    assert flown.wal200["dss_wal_bytes_total"] == sum(
        len(ln) for ln in lines[1:])
    # and on /metrics through DSSStore.stats
    assert flown.store.stats()["dss_wal_appends_total"] == flown.wal200[
        "dss_wal_appends_total"]


def test_a_replayed_log_marks_no_stage_and_moves_no_counter(flown):
    sink = {}
    stages.set_sink(sink)
    try:
        fresh = DSSStore(storage="tpu", clock=Clock(),
                         wal_path=flown.wal_path)
    finally:
        stages.set_sink(None)
    try:
        assert set(fresh.scd._ops) == {OP1, OP2}
        assert sink == {}
        assert not any(fresh.wal.stats().values()), fresh.wal.stats()
    finally:
        fresh.close()


def test_without_fsync_an_append_counts_and_no_fsync_does(tmp_path):
    s = Served(tmp_path, fsync=False)
    try:
        assert s.put(OP1).status_code == 200
        st = s.store.wal.stats()
        assert st["dss_wal_records_total"] == 3
        assert st["dss_wal_appends_total"] == 1
        assert st["dss_wal_fsyncs_total"] == 0
        assert st["dss_wal_fsync_seconds_total"] == 0
        s.store.wal.sync()  # the must-survive records' explicit fsync
        assert s.store.wal.stats()["dss_wal_fsyncs_total"] == 1
    finally:
        s.close()


def test_a_slow_disk_injected_at_the_fsync_reads_as_one(tmp_path):
    """The wal.fsync chaos seam models the disk: its delay lands in
    dss_wal_fsync_seconds_total, as wal.append's lands in the append's."""
    from dss_tpu import chaos
    from dss_tpu.dar.wal import WriteAheadLog

    wal = WriteAheadLog(str(tmp_path / "slow.wal"), fsync=True)
    chaos.install_plan(chaos.FaultPlan.from_dict({"seed": 1, "events": [
        {"site": "wal.fsync", "action": "delay", "delay_s": 0.05,
         "count": 1}]}))
    try:
        wal.append({"t": "noop"})
    finally:
        chaos.clear_plan()
        wal.close()
    st = wal.stats()
    assert st["dss_wal_fsyncs_total"] == 1
    assert (0.05 <= st["dss_wal_fsync_seconds_total"]
            <= st["dss_wal_append_seconds_total"])


def test_a_stage_inside_a_stage_marks_no_stage():
    sink = {}
    stages.set_sink(sink)
    try:
        with stages.stage("sub_bump_ms", "outer.seam"):
            time.sleep(0.002)
            with stages.stage("wal_commit_ms", "inner.seam"):
                time.sleep(0.002)
        assert set(sink) == {"sub_bump_ms"} and sink["sub_bump_ms"] >= 4.0
        # side by side both mark, and a repeated leg accumulates
        with stages.stage("wal_commit_ms", "inner.seam"):
            time.sleep(0.001)
        with stages.stage("wal_commit_ms", "inner.seam"):
            time.sleep(0.001)
        assert sink["wal_commit_ms"] >= 2.0
        # a leg that raises still marks, and unwinds the nesting
        with pytest.raises(KeyError):
            with stages.stage("precheck_ms", "outer.seam"):
                raise KeyError("conflict")
        assert "precheck_ms" in sink
        with stages.stage("op_index_ms", "outer.seam"):
            pass
        assert "op_index_ms" in sink
    finally:
        stages.set_sink(None)


def test_a_stage_without_a_sink_marks_nothing():
    assert stages.get_sink() is None
    with stages.stage("precheck_ms", "outer.seam"):
        with stages.stage("wal_commit_ms", "inner.seam"):
            pass
    assert stages.get_sink() is None


def test_a_sampled_requests_tree_holds_the_nested_stage_too():
    """A stage is a span of a recording trace under its own name; a
    nested stage is a span there, under the outer, and still no
    stage."""
    from dss_tpu.obs import trace

    trace.configure(sample=1.0, slow_ms=0.0)
    sink = {}
    stages.set_sink(sink)
    try:
        ctx = trace.new_trace(None, None)
        with trace.use(trace.SpanHandle(ctx, ctx.root_span_id)):
            with stages.stage("sub_bump_ms", "sub.bump"):
                with stages.stage("wal_commit_ms", "wal.commit"):
                    pass
        trace.finish_root(ctx, "test", 1.0)
        tree = trace.recorder().find(ctx.trace_id)
        (outer,) = [
            c for c in tree["root"]["children"]
            if c["name"] == "sub_bump_ms"
        ]
        # real nesting: the inner span parents under the outer
        assert [c["name"] for c in outer["children"]] == ["wal_commit_ms"]
        assert set(sink) == {"sub_bump_ms"}
    finally:
        stages.set_sink(None)
        trace.configure(sample=0.0, slow_ms=0.0)
        trace.recorder().clear()


def test_the_lock_wait_is_timed_at_the_outermost_entry_alone():
    """A second writer waits for the store's lock: its txn_wait_ms
    holds the wait; the re-entries inside its own transaction (the
    RLock, already held) mark nothing more."""
    store = DSSStore(storage="tpu", clock=Clock())
    holding, release = threading.Event(), threading.Event()

    def holder():
        with store.scd.transaction():
            holding.set()
            release.wait(10)

    th = threading.Thread(target=holder)
    th.start()
    sink = {}
    try:
        assert holding.wait(10)
        threading.Timer(0.05, release.set).start()
        stages.set_sink(sink)
        with store.scd.transaction():
            waited = sink["txn_wait_ms"]
            assert waited >= 40.0, sink
            with store.scd.transaction():
                pass
            assert sink["txn_wait_ms"] == waited
    finally:
        stages.set_sink(None)
        release.set()
        th.join(10)
        store.close()


def test_a_compile_counts_under_the_site_of_the_thread_that_compiled():
    import jax
    import jax.numpy as jnp

    from dss_tpu.ops import compile_site, compile_stats

    def moved(a, b):
        return {k: b[k] - a[k] for k in a if b[k] != a[k]}

    s0 = compile_stats()
    with compile_site("fold_warm"):
        jax.jit(lambda x: x * 3 + 39)(jnp.ones(7)).block_until_ready()
    s1 = compile_stats()
    got = moved(s0, s1)
    assert got.get("dss_jax_compiles_fold_warm", 0) >= 1, got
    assert "dss_jax_compiles_request" not in got, got
    assert "dss_jax_compile_seconds_request" not in got, got
    assert got["dss_jax_compiles"] == got["dss_jax_compiles_fold_warm"]
    # an unmarked thread is serving a request
    jax.jit(lambda x: x * 5 + 39)(jnp.ones(7)).block_until_ready()
    got = moved(s1, compile_stats())
    assert got.get("dss_jax_compiles_request", 0) >= 1, got
    assert "dss_jax_compiles_fold_warm" not in got, got
    assert "dss_jax_compiles_boot_warm" not in got, got


def test_the_fold_threads_own_compiles_are_no_requests():
    """Whatever the fold thread compiles (a new tier's build at a
    shape the process has not seen) costs no request."""
    import jax
    import jax.numpy as jnp

    from dss_tpu.dar.snapshot import DarTable
    from dss_tpu.ops import compile_stats

    table = DarTable()
    done = threading.Event()

    def fold():  # stands in for a build that misses the jit cache
        jax.jit(lambda x: x * 7 + 39)(jnp.ones(11)).block_until_ready()
        done.set()

    table.fold = fold
    s0 = compile_stats()
    try:
        table._request_fold()
        assert done.wait(60)
    finally:
        table.close()
    s1 = compile_stats()
    assert s1["dss_jax_compiles_fold_warm"] > s0[
        "dss_jax_compiles_fold_warm"]
    assert s1["dss_jax_compiles_request"] == s0["dss_jax_compiles_request"]


def test_a_folds_warm_hands_its_site_to_the_compiler_thread():
    """The fold's hook only schedules: the bucket is compiled on the
    cache's own thread, and is counted where it was asked for."""
    import numpy as np

    from dss_tpu.dar.snapshot import DarTable
    from dss_tpu.ops import compile_site, compile_stats
    from dss_tpu.ops.resident import AotCache, max_words_for

    table = DarTable()
    rng = np.random.default_rng(39)
    for k in range(150):
        table.upsert(f"e{k}", rng.integers(0, 40, 3).astype(np.int32),
                     0.0, 100.0, 0, 10**18, 1)
    table.fold()
    try:
        ft = table._state.tiers[0].snap.fast
        cache = AotCache()
        s0 = compile_stats()
        with compile_site("fold_warm"):
            cache.compile_async(ft, 256, 16, max_words_for(256))
        key = cache.key_for(ft, 256, 16, max_words_for(256))
        deadline = time.monotonic() + 60
        while cache.get(key) is None and time.monotonic() < deadline:
            time.sleep(0.02)
        assert cache.get(key) is not None
        s1 = compile_stats()
        assert s1["dss_jax_compiles_fold_warm"] > s0[
            "dss_jax_compiles_fold_warm"]
        assert s1["dss_jax_compiles_request"] == s0[
            "dss_jax_compiles_request"]
    finally:
        table.close()


def test_the_benchmarks_cover_reader_sums_the_same_leaves():
    """`write_unattributed_pct`: 100 x (1 - the leaves' seconds over
    the whole's) between the window's two scrapes; its file lists the
    leaves this module holds a request to; a program from before the
    legs (no such rows) reads what it leaves dark, and one that never
    observed the whole reads nothing."""
    import json
    import os

    from dssbench import deploy
    from dssbench.readers import stage_cover

    with open(os.path.join(deploy.REPO, "dssbench", "metrics",
                           "write_unattributed_pct.json")) as fh:
        args = json.load(fh)["args"]
    assert tuple(args["leaves"]) == LEAVES and args["whole"] == "service_ms"

    def row(stage):
        return ('dss_stage_duration_seconds_sum{route="write",'
                f'stage="{stage}"}}')

    s0 = {row("service_ms"): 1.0, row("precheck_ms"): 0.5,
          row("wal_commit_ms"): 0.1}
    s1 = {row("service_ms"): 3.0, row("precheck_ms"): 1.5,
          row("wal_commit_ms"): 0.6,
          # first observed inside the window: no row at its start
          row("op_index_ms"): 0.2,
          # another route's time is none of this one's
          'dss_stage_duration_seconds_sum{route="search",'
          'stage="covering_ms"}': 9.0}
    ctx = {"scrape0": {"front": s0}, "scrape1": {"front": s1}}
    got = stage_cover.read(ctx, **args)
    assert got == pytest.approx(100.0 * (1 - (1.0 + 0.5 + 0.2) / 2.0))
    dark = {"scrape0": {"front": {row("service_ms"): 1.0}},
            "scrape1": {"front": {row("service_ms"): 2.0}}}
    assert stage_cover.read(dark, **args) == pytest.approx(100.0)
    none = {"scrape0": {"front": {}}, "scrape1": {"front": {}}}
    assert stage_cover.read(none, **args) is None


# a planned flight's seams (`dss.write.sub_affected` is a subscription
# PUT's alone: a flight's one transaction runs no `affected` search)
SEAMS = ("dss.write.lock_wait", "dss.write.precheck", "dss.write.conflicts",
         "dss.write.sub_index", "dss.write.op_index", "dss.wal.commit",
         "dss.write.body", "dss.write.parse", "dss.write.sub_match",
         "dss.sub.bump")


def test_a_capture_of_a_flight_holds_every_seam_on_the_host_timeline(flown):
    """POST /debug/profile around one planned flight and one fold: the
    legs are `dss.*` events of the capture (what `dssbench/readers/
    spans.py` reads), the fold thread's two stretches among them."""
    from jax.profiler import ProfileData

    from dss_tpu.obs import trace

    answer = {}

    def capture():
        answer["r"] = requests.post(
            f"{flown.srv.base}/debug/profile",
            params={"seconds": "1.0"}, timeout=120)

    th = threading.Thread(target=capture)
    th.start()
    deadline = time.monotonic() + 60
    while trace.annotate("x") is trace.annotate("y"):
        assert time.monotonic() < deadline  # the capture's flag is not up
        time.sleep(0.01)
    time.sleep(0.3)  # flag up -> profiler session started
    flown.flight("eeeeeeee-eeee-4eee-8eee-eeeeeeeeeee3")
    assert flown.store.scd._op_index.table.fold()
    th.join(timeout=120)
    assert not th.is_alive() and answer["r"].status_code == 200
    found = list(flown.profile_dir.rglob("*.xplane.pb"))
    assert found, "no capture written"
    names = set()
    for plane in ProfileData.from_file(str(found[-1])).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    for seam in SEAMS + ("dss.fold.build", "dss.fold.swap"):
        assert seam in names, (seam, sorted(
            n for n in names if n.startswith("dss.")))


def test_a_sampled_flights_span_tree_holds_the_legs_under_its_id(flown):
    from dss_tpu.obs import trace

    trace.configure(sample=1.0, slow_ms=0.0)
    op = "eeeeeeee-eeee-4eee-8eee-eeeeeeeeeee4"
    tids = ("39f7651916cd43dd8448eb211c803409",
            "39f7651916cd43dd8448eb211c803200")
    try:
        key = None
        for tid, want in zip(tids, (409, 200)):
            r = flown.put(op, key, headers={
                "traceparent": trace.format_traceparent(tid, "b" * 16, True)})
            assert r.status_code == want, r.text
            if want == 409:
                key = [c["operation_reference"]["ovn"]
                       for c in r.json()["entity_conflicts"]]
        got = [requests.get(
            f"{flown.srv.base}/aux/v1/debug/traces",
            params={"trace_id": tid}, timeout=10).json()["traces"][0]
            for tid in tids]
    finally:
        trace.configure(sample=0.0, slow_ms=0.0)
        trace.recorder().clear()

    def names(node, acc):
        acc.add(node["name"])
        for c in node["children"]:
            names(c, acc)
        return acc

    seen = set()
    for t in got:
        names(t["root"], seen)
    assert {"parse_ms", "txn_wait_ms", "precheck_ms", "conflict_list_ms",
            "sub_index_ms", "op_index_ms", "wal_commit_ms",
            "sub_match_ms", "sub_bump_ms", "serialize_ms"} <= seen, seen


def test_every_key_the_write_storm_script_reads_is_exported():
    """`benchmarks/bench_scd_write.py` (`make bench-all`) is a reader of
    `DarTable.stats()`: a key it reads stays."""
    import os
    import re

    from dss_tpu.dar.snapshot import DarTable
    from dssbench import deploy

    with open(os.path.join(deploy.REPO, "benchmarks",
                           "bench_scd_write.py")) as fh:
        read = set(re.findall(r'\bst(?:_after)?\["([a-z0-9_]+)"\]', fh.read()))
    assert {"fold_ms_total", "fold_swap_ms_total", "folds"} <= read
    table = DarTable()
    try:
        assert read <= set(table.stats()), read - set(table.stats())
    finally:
        table.close()
