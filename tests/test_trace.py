"""The distributed-tracing subsystem (dss_tpu/obs/trace.py): W3C
propagation codec fuzz, head/tail sampling determinism, recorder
bounds, the zero-allocation disabled path, cross-thread span handoff
through a real coalescer, the shm slot trace-word codec, and ONE
stitched trace spanning two real processes over the shm ring."""

from __future__ import annotations

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest
import requests

from dss_tpu.obs import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _trace_reset():
    """Every test starts from tracing-disabled with a fresh recorder
    and leaves the process the same way (other test files rely on the
    zero-cost default)."""
    trace.configure(sample=0.0, slow_ms=0.0, ring=256, max_spans=256,
                    max_pending=1024)
    yield
    trace.configure(sample=0.0, slow_ms=0.0, ring=256, max_spans=256,
                    max_pending=1024)


def _ctx(sample=1.0, **kw):
    trace.configure(sample=sample, **kw)
    ctx = trace.new_trace()
    assert ctx is not None
    return ctx


# -- traceparent codec --------------------------------------------------------


def test_traceparent_roundtrip():
    tid, sid = "0af7651916cd43dd8448eb211c80319c", "b7ad6b7169203331"
    for sampled in (True, False):
        parsed = trace.parse_traceparent(
            trace.format_traceparent(tid, sid, sampled)
        )
        assert parsed == (tid, sid, sampled)


def test_traceparent_rejects_malformed():
    bad = [
        None, "", "00", "garbage", "00-zz-xx-01",
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",   # all-zero trace
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",   # all-zero span
        "ff-" + "a" * 32 + "-" + "b" * 16 + "-01",   # version ff
        "00-" + "a" * 31 + "-" + "b" * 16 + "-01",   # short trace id
        "00-" + "a" * 32 + "-" + "b" * 15 + "-01",   # short span id
        "00-" + "a" * 32 + "-" + "b" * 16 + "-1",    # short flags
        "00-" + "a" * 32 + "-" + "b" * 16 + "-01-x",  # v00 extra part
    ]
    for v in bad:
        assert trace.parse_traceparent(v) is None, v


def test_traceparent_fuzz_never_raises_and_valid_roundtrip():
    import random as _random

    rng = _random.Random(7)
    hexc = "0123456789abcdef"
    for _ in range(500):
        # random garbage must never raise
        s = "".join(
            rng.choice(hexc + "-zG ") for _ in range(rng.randrange(0, 60))
        )
        trace.parse_traceparent(s)  # no exception is the assertion
    for _ in range(200):
        tid = "".join(rng.choice(hexc) for _ in range(32))
        sid = "".join(rng.choice(hexc) for _ in range(16))
        if tid == "0" * 32 or sid == "0" * 16:
            continue
        sampled = rng.random() < 0.5
        assert trace.parse_traceparent(
            trace.format_traceparent(tid, sid, sampled)
        ) == (tid, sid, sampled)


def test_request_id_coercion():
    # hex-ish legacy ids stay greppable (zero-padded), opaque ids hash
    assert trace.trace_id_from_request_id("abcd1234") == (
        "0" * 24 + "abcd1234"
    )
    t = trace.trace_id_from_request_id("corr-123!")
    assert len(t) == 32 and t == trace.trace_id_from_request_id("corr-123!")


def test_head_sampling_deterministic_in_trace_id():
    trace.configure(sample=0.5)
    tp = trace.format_traceparent("a" * 32, "b" * 16, False)
    decisions = {
        trace.new_trace(tp).sampled for _ in range(5)
    }
    assert len(decisions) == 1  # same id -> same decision, always
    # an EXTERNAL sampled flag cannot override the local rate: with
    # sampling off (tail capture armed), flag=01 stays unsampled —
    # an OTel-instrumented client must not churn the flight recorder
    trace.configure(sample=0.0, slow_ms=50.0)
    tp1 = trace.format_traceparent("a" * 32, "b" * 16, True)
    ctx = trace.new_trace(tp1)
    assert not ctx.sampled
    assert ctx.recording  # tail capture still armed
    trace.finish_root(ctx, "r", 1.0)


def test_unsampled_without_tail_capture_records_nothing():
    """sample < 1 with DSS_TRACE_SLOW_MS off: unsampled requests must
    not allocate a pending buffer or occupy the pending map — only
    the head-sampled fraction pays recording cost."""
    trace.configure(sample=0.5, slow_ms=0.0)
    ctxs = [trace.new_trace() for _ in range(64)]
    sampled = [c for c in ctxs if c.sampled]
    unsampled = [c for c in ctxs if not c.sampled]
    assert sampled and unsampled  # both populations exist at 0.5
    assert all(not c.recording for c in unsampled)
    assert all(c.recording for c in sampled)
    assert trace.recorder().allocs == len(sampled)
    for c in ctxs:
        trace.finish_root(c, "r", 1.0)
    assert trace.stats()["dss_trace_pending"] == 0


# -- recorder ----------------------------------------------------------------


def test_recorder_ring_bounds_and_eviction():
    trace.configure(sample=1.0, ring=4)
    for i in range(6):
        ctx = trace.new_trace()
        trace.add_span(
            trace.SpanHandle(ctx, ctx.root_span_id), "store_ms",
            time.time_ns(), 1.0,
        )
        assert trace.finish_root(ctx, f"req-{i}", 5.0, status=200)
    rec = trace.recorder()
    kept = rec.traces()
    assert len(kept) == 4  # bounded flight recorder
    assert rec.evicted == 2
    st = trace.stats()
    assert st["dss_trace_dropped_total"] >= 2
    assert st["dss_trace_kept_sampled_total"] == 6
    # newest survive
    assert kept[-1]["root"]["name"] == "req-5"


def test_recorder_span_cap_counts_drops():
    trace.configure(sample=1.0, max_spans=8)
    ctx = trace.new_trace()
    h = trace.SpanHandle(ctx, ctx.root_span_id)
    for i in range(20):
        trace.add_span(h, "store_ms", time.time_ns(), 0.1)
    trace.finish_root(ctx, "req", 1.0)
    assert trace.recorder().dropped_spans == 12


def test_pending_cap_disables_recording_not_propagation():
    trace.configure(sample=1.0, max_pending=4)
    ctxs = [trace.new_trace() for _ in range(6)]
    assert sum(1 for c in ctxs if c.recording) == 4
    assert all(c.trace_id for c in ctxs)  # ids still propagate
    assert trace.recorder().dropped_pending == 2
    for c in ctxs:
        trace.finish_root(c, "r", 1.0)


def test_tail_sampling_deterministic_fake_clock():
    """sample=0 + slow_ms: a root breaching the bound is RETROACTIVELY
    kept (its buffered spans included); anything under is dropped.
    Durations are injected, so the decision is clock-deterministic."""
    trace.configure(sample=0.0, slow_ms=50.0)
    fast = trace.new_trace()
    assert not fast.sampled and fast.recording  # armed for tail capture
    trace.add_span(
        trace.SpanHandle(fast, fast.root_span_id), "store_ms",
        time.time_ns(), 10.0,
    )
    assert not trace.finish_root(fast, "fast", 49.999, status=200)

    slow = trace.new_trace()
    trace.add_span(
        trace.SpanHandle(slow, slow.root_span_id), "device.dispatch",
        time.time_ns(), 55.0,
    )
    assert trace.finish_root(slow, "slow", 50.0, status=200)
    kept = trace.recorder().traces()
    assert len(kept) == 1
    assert kept[0]["kept"] == "slow"
    assert kept[0]["root"]["name"] == "slow"
    names = {c["name"] for c in kept[0]["root"]["children"]}
    assert "device.dispatch" in names
    st = trace.stats()
    assert st["dss_trace_kept_slow_total"] == 1
    # the fast trace's buffer was reclaimed
    assert st["dss_trace_pending"] == 0


def test_disabled_path_zero_recorder_allocations():
    """The acceptance contract: with DSS_TRACE_SAMPLE=0 and no slow
    bound, every seam is one branch and the recorder allocates
    NOTHING — counter-verified, not assumed."""
    trace.configure(sample=0.0, slow_ms=0.0, ring=8)
    assert not trace.enabled()
    assert trace.new_trace("00-" + "a" * 32 + "-" + "b" * 16 + "-01") is None
    assert trace.current() is None
    assert trace.propagation_headers() == {}
    sp = trace.span("anything")
    with sp:
        pass
    trace.add_span(None, "x", time.time_ns(), 1.0)
    st = trace.stats()
    assert st["dss_trace_allocs_total"] == 0
    assert st["dss_trace_started_total"] == 0


def test_annotate_is_the_shared_noop_without_a_capture():
    """The third sink's off-path contract, counter-verified as the
    recorder's is: with no /debug/profile capture running every
    annotated seam gets the ONE shared no-op object and no
    TraceAnnotation is constructed."""
    from dss_tpu.obs import stages

    before = trace.stats()["dss_trace_annotations_total"]
    a = trace.annotate("owner.serve")
    assert a is trace.annotate("collect")
    assert a is trace.span("service")  # tracing off: span is annotate
    with a:
        pass
    with stages.stage("covering_ms"):  # no sink on this thread
        pass
    st = trace.stats()
    assert st["dss_trace_annotations_total"] == before
    assert st["dss_trace_allocs_total"] == 0


def test_annotate_under_a_capture_needs_no_sampling():
    """The capture's gate is its own: with DSS_TRACE_SAMPLE 0 (every
    benchmark run) and a capture on, span and stage still annotate —
    and record nothing in the flight recorder."""
    from dss_tpu.obs import stages

    before = trace.stats()["dss_trace_annotations_total"]
    trace.set_capture(True)
    try:
        ann = trace.annotate("owner.serve")
        assert type(ann).__name__ == "TraceAnnotation"
        with ann:
            pass
        with trace.span("service"), stages.stage("covering_ms"):
            pass
    finally:
        trace.set_capture(False)
    st = trace.stats()
    assert st["dss_trace_annotations_total"] == before + 3
    assert st["dss_trace_allocs_total"] == 0
    assert st["dss_trace_started_total"] == 0
    assert trace.annotate("x") is trace.annotate("y")  # off again


# -- cross-thread handoff through a real coalescer ---------------------------


class _FakePQ:
    def __init__(self, results):
        self.results = results

    def wait_device(self):
        time.sleep(0.001)

    def used_device(self):
        return True


class _FakeTable:
    """Submit/collect table shaped like DarTable's split: enough for
    the coalescer's full pack -> device -> collect pipeline."""

    def query_many_submit(self, keys, lo, hi, t0s, t1s, now=None,
                          owner_ids=None, host_route=False):
        return _FakePQ([[f"r{i}"] for i in range(len(keys))])

    def query_many_collect(self, pq):
        return pq.results


def test_cross_thread_span_handoff_through_coalescer():
    from dss_tpu.dar.coalesce import QueryCoalescer

    trace.configure(sample=1.0)
    co = QueryCoalescer(_FakeTable(), inline=False)
    try:
        ctx = trace.new_trace()
        h = trace.SpanHandle(ctx, ctx.root_span_id)
        with trace.use(h):
            out = co.query(np.asarray([5], np.int32), now=123)
        assert out == ["r0"]
        trace.finish_root(ctx, "http GET /search", 9.0, status=200)
    finally:
        co.close()
    tree = trace.recorder().find(ctx.trace_id)
    assert tree is not None

    def names(node, acc):
        acc.add(node["name"])
        for c in node["children"]:
            names(c, acc)
        return acc

    got = names(tree["root"], set())
    # the pipeline's stages became parented spans, recorded by the
    # CALLER's thread from the stamped batch timings
    for needed in ("admission", "plan", "device.dispatch",
                   "coalesce.pack", "device.wait", "collect"):
        assert needed in got, (needed, got)
    # the batch spans parent under the request, not floating ids
    assert tree["root"]["children"], tree


def test_untraced_coalescer_query_stays_unrecorded():
    from dss_tpu.dar.coalesce import QueryCoalescer

    trace.configure(sample=0.0, slow_ms=0.0)
    co = QueryCoalescer(_FakeTable(), inline=False)
    try:
        out = co.query(np.asarray([5], np.int32), now=123)
        assert out == ["r0"]
    finally:
        co.close()
    assert trace.stats()["dss_trace_allocs_total"] == 0


def test_inline_execution_counts_its_own_split_and_device_use():
    """An inline execution (the lone caller's own thread) feeds
    co_inline, co_inline_device (the device stub says it launched) and
    both co_inline_*_ms_total — always, not only when traced — and
    leaves the pipeline's co_pack_ms_total / co_batches alone, because
    pack_collect_ms_per_batch reads those."""
    from dss_tpu.dar.coalesce import QueryCoalescer

    class _SlowTable(_FakeTable):
        def query_many_submit(self, *a, **kw):
            time.sleep(0.002)
            return super().query_many_submit(*a, **kw)

        def query_many_collect(self, pq):
            time.sleep(0.003)
            return pq.results

    co = QueryCoalescer(_SlowTable(), inline=True)
    try:
        for _ in range(3):
            assert co.query(np.asarray([5], np.int32), now=123) == ["r0"]
        st = co.stats()
    finally:
        co.close()
    assert st["co_inline"] == 3
    assert st["co_inline_device"] == 3
    assert st["co_inline_submit_ms_total"] >= 3 * 2.0
    assert st["co_inline_collect_ms_total"] >= 3 * 3.0
    assert st["co_batches"] == 0
    assert st["co_pack_ms_total"] == 0 and st["co_collect_ms_total"] == 0
    assert trace.stats()["dss_trace_allocs_total"] == 0


# -- shm slot trace words ----------------------------------------------------


def test_shm_slot_trace_word_roundtrip(tmp_path):
    from dss_tpu.parallel import shmring

    r = shmring.ShmRegion.create(
        str(tmp_path / "t.shm"), nworkers=1, depth=4
    )
    try:
        tid = "0af7651916cd43dd8448eb211c80319c"
        r.write_request(
            0, 0, 1, cls_idx=0, cells=np.asarray([7], np.uint64),
            alt_lo=None, alt_hi=None, t0_ns=None, t1_ns=None,
            now_ns=5, deadline_ns=0, owner="", allow_stale=False,
            trace_id=tid, trace_sampled=True,
        )
        req = r.read_request(0, 0)
        assert req.trace_id == tid
        assert req.trace_sampled
        # response words carry the owner's span-slot durations back
        vec = [0] * len(trace.OWNER_SLOTS)
        vec[trace.OWNER_SLOTS.index("device.dispatch")] = 3_000_000
        vec[trace.OWNER_SLOTS.index("owner.serve")] = 4_500_000
        r.write_response(
            0, 0, status=shmring.ST_OK, ids=["a"], t1s=[9],
            gen=2, trace_ns=vec,
        )
        resp = r.read_response(0, 0)
        assert list(resp.trace_ns) == vec
        # id-less request encodes absent, not zeros-as-id
        r.write_request(
            0, 1, 2, cls_idx=0, cells=np.asarray([7], np.uint64),
            alt_lo=None, alt_hi=None, t0_ns=None, t1_ns=None,
            now_ns=5, deadline_ns=0, owner="", allow_stale=False,
        )
        req2 = r.read_request(0, 1)
        assert req2.trace_id is None and not req2.trace_sampled
        # tid split/join round trip incl. high-bit ids
        for t in (tid, "f" * 32, "8" + "0" * 31):
            assert shmring.tid_join(*shmring.tid_split(t)) == t
    finally:
        r.close()


def test_shm_stage_hist_blocks_merge(tmp_path):
    from dss_tpu.parallel import shmring

    r = shmring.ShmRegion.create(
        str(tmp_path / "t.shm"), nworkers=2, depth=4
    )
    try:
        w0 = shmring.StageHistWriter(r, 0)
        owner = shmring.StageHistWriter(r, 2)  # leader block
        route = "/v1/dss/identification_service_areas"
        w0.observe(route, "store_ms", 0.004)
        w0.observe(route, "store_ms", 0.020)
        owner.observe(route, "store_ms", 0.004)
        owner.observe("/dss/v1/operation_references/{entityuuid}",
                      "service_ms", 0.3)
        merged = shmring.shm_stage_hist(r)
        counts, ssum, cnt = merged[("search", "store_ms")]
        assert cnt == 3
        assert abs(ssum - 0.028) < 1e-9
        # bucket counts are cumulative-per-bucket sums across blocks
        from dss_tpu.obs.metrics import STAGE_BUCKETS

        assert counts[STAGE_BUCKETS.index(0.005)] == 2
        assert ("write", "service_ms") in merged
        # zero rows omitted
        assert ("other", "auth_ms") not in merged
    finally:
        r.close()


# -- one stitched trace across two real processes ----------------------------

_OWNER_CHILD = r"""
import sys, time
from dss_tpu.obs import trace
from dss_tpu.parallel import shmring

trace.configure(sample=1.0)
region = shmring.ShmRegion.open_existing(sys.argv[1])

def serve(req):
    with trace.span("admission"):
        pass
    with trace.span("plan"):
        pass
    with trace.span("device.dispatch"):
        time.sleep(0.003)
    with trace.span("collect"):
        pass
    return ["stitched-id"], [1 << 60], 7

owner = shmring.ShmOwner(region, serve, wal_seq_fn=lambda: 0)
owner.start()
print("ready", flush=True)
sys.stdin.read()  # parent closes stdin to stop
owner.close()
"""


class _NoFollower:
    def wait_for(self, seq, timeout_s):
        return True


class _FakeClock:
    def now(self):
        from datetime import datetime, timezone

        return datetime.now(timezone.utc)


def test_stitched_trace_across_two_processes(tmp_path):
    """The tentpole acceptance shape, at unit scale: a worker-process
    search rides the shm ring to an owner in ANOTHER OS process, and
    the worker's recorder holds ONE trace whose ring span's children
    are the owner's span slots (queue wait, plan, dispatch, collect)
    — stitched from the response words, no JSON anywhere."""
    from dss_tpu.dar.shmfront import ShmSearchFront
    from dss_tpu.parallel import shmring

    path = str(tmp_path / "ring.shm")
    region = shmring.ShmRegion.create(path, nworkers=1, depth=8)
    child = subprocess.Popen(
        [sys.executable, "-c", _OWNER_CHILD, path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        cwd=ROOT,
    )
    client = None
    try:
        assert child.stdout.readline().strip() == b"ready"
        client = shmring.ShmWorkerClient(region, 0)
        front = ShmSearchFront(
            region, client, _NoFollower(), _FakeClock()
        )
        trace.configure(sample=1.0)
        ctx = trace.new_trace()
        h = trace.SpanHandle(ctx, ctx.root_span_id)
        with trace.use(h):
            ids = front.serve(
                "isa", np.asarray([123456789], np.uint64),
                qkey=(None,), now_ns=1, t0_ns=1, allow_stale=False,
            )
        assert ids == ["stitched-id"]
        trace.finish_root(ctx, "http GET /search", 25.0, status=200)
        tree = trace.recorder().find(ctx.trace_id)
        assert tree is not None, "worker recorder lost the trace"
        # find the ring span and its stitched owner children
        stack, ring = [tree["root"]], None
        while stack:
            n = stack.pop()
            if n["name"] == "shm.ring":
                ring = n
                break
            stack.extend(n["children"])
        assert ring is not None, tree
        # the queue wait and the serve envelope under the ring span,
        # the serve path's own slots under the envelope
        owner_spans = {c["name"]: c for c in ring["children"]}
        assert sorted(owner_spans) == ["owner.queue_wait", "owner.serve"]
        inner = owner_spans["owner.serve"]["children"]
        owner_spans.update({c["name"]: c for c in inner})
        for needed in ("admission", "plan", "device.dispatch", "collect"):
            assert needed in owner_spans, (needed, sorted(owner_spans))
        # on one axis, in order and non-overlapping: enqueue <= claim
        # (queue wait) <= pickup (serve), and inside the serve envelope
        # the slots end to end from the pickup stamp — none at the ring
        # span's own start any more
        def end(c):
            return c["start_ns"] + int(c["duration_ms"] * 1e6)

        qw, sv = owner_spans["owner.queue_wait"], owner_spans["owner.serve"]
        slack = 2_000  # duration_ms is rounded to the microsecond
        assert ring["start_ns"] <= qw["start_ns"]
        assert end(qw) <= sv["start_ns"] + slack
        assert end(sv) <= end(ring) + slack
        assert [c["name"] for c in inner] == [
            n for n in trace.OWNER_SLOTS if n in {c["name"] for c in inner}
        ]
        assert inner[0]["start_ns"] == sv["start_ns"]
        for a, b in zip(inner, inner[1:]):
            assert end(a) <= b["start_ns"] + slack, (a, b)
        assert end(inner[-1]) <= end(sv) + slack
        # the injected 3ms dispatch sleep dominates the owner slots
        assert owner_spans["device.dispatch"]["duration_ms"] >= 2.5
        assert (
            owner_spans["owner.serve"]["duration_ms"]
            >= owner_spans["device.dispatch"]["duration_ms"]
        )
        # the worker-side cache lookup is part of the same tree
        stack, names = [tree["root"]], set()
        while stack:
            n = stack.pop()
            names.add(n["name"])
            stack.extend(n["children"])
        assert "cache.lookup" in names
    finally:
        if client is not None:
            client.close()
        child.stdin.close()
        try:
            child.wait(timeout=10)
        except subprocess.TimeoutExpired:
            child.kill()
        region.close()


# -- the ring split at its seams ---------------------------------------------


def test_ring_stamps_share_one_clock_across_processes():
    """The ring's four stages subtract instants taken in two processes:
    sound only because perf_counter_ns and monotonic_ns are both the
    host's CLOCK_MONOTONIC (Linux), one clock for every process."""
    for name in ("perf_counter", "monotonic"):
        info = time.get_clock_info(name)
        assert info.implementation == "clock_gettime(CLOCK_MONOTONIC)"
        assert info.monotonic
    before = time.perf_counter_ns()
    out = subprocess.run(
        [sys.executable, "-c",
         "import time; print(time.perf_counter_ns(), time.monotonic_ns())"],
        capture_output=True, text=True, check=True, timeout=60,
    ).stdout.split()
    after = time.monotonic_ns()
    assert before <= int(out[0]) <= int(out[1]) <= after


def test_ring_stages_sum_to_the_round_trip(tmp_path):
    """Every response — sampled or not — carries the owner's claim,
    pickup and write stamps; the worker marks four stages from them
    that are >= 0 and sum to shm_ring_ms (consecutive differences of
    one clock; the sink rounds each to the microsecond)."""
    from dss_tpu.dar.shmfront import ShmSearchFront
    from dss_tpu.obs import stages
    from dss_tpu.parallel import shmring

    region = shmring.ShmRegion.create(
        str(tmp_path / "ring.shm"), nworkers=1, depth=8
    )

    def serve(req):
        time.sleep(0.002)
        return ["an-id"], [1 << 60], 7

    owner = shmring.ShmOwner(region, serve, wal_seq_fn=lambda: 0)
    owner.start()
    client = shmring.ShmWorkerClient(region, 0)
    try:
        front = ShmSearchFront(region, client, _NoFollower(), _FakeClock())
        names = ("ring_pickup_ms", "ring_queue_ms", "ring_serve_ms",
                 "ring_return_ms")
        for k in range(5):
            sink = {}
            stages.set_sink(sink)
            try:
                ids = front.serve(
                    "isa", np.asarray([1000 + k], np.uint64),
                    qkey=(None,), now_ns=1, t0_ns=1, allow_stale=False,
                )
            finally:
                stages.set_sink(None)
            assert ids == ["an-id"]
            assert set(names) < set(sink), sink
            assert all(sink[n] >= 0.0 for n in names), sink
            assert sink["ring_serve_ms"] >= 2.0
            assert sum(sink[n] for n in names) == pytest.approx(
                sink["shm_ring_ms"], abs=0.003
            )
        # unsampled: no trace was started, nothing was allocated
        assert trace.stats()["dss_trace_allocs_total"] == 0
    finally:
        client.close()
        owner.close()
        region.close()


# -- live-socket HTTP: propagation + the debug endpoint ----------------------


class _SearchRID:
    def search_isas(self, area, earliest=None, latest=None):
        from dss_tpu.obs import stages

        with stages.stage("store_ms"):
            time.sleep(0.001)
        return {"service_areas": []}

    def get_isa(self, id, owner=None):
        return {"service_area": {"id": id}}


def test_http_traceparent_propagation_and_debug_endpoint():
    from dss_tpu.api.app import build_app
    from tests.live_server import LiveServer

    trace.configure(sample=1.0, slow_ms=10_000.0)
    srv = LiveServer(build_app(_SearchRID(), None, None))
    try:
        tid = "0af7651916cd43dd8448eb211c80319c"
        tp = trace.format_traceparent(tid, "b" * 16, True)
        r = requests.get(
            f"{srv.base}/v1/dss/identification_service_areas",
            params={"area": ""},
            headers={"traceparent": tp},
            timeout=5,
        )
        assert r.status_code == 200
        # the trace id IS the request id, and both headers round-trip
        assert r.headers["X-Request-Id"] == tid
        got = trace.parse_traceparent(r.headers["traceparent"])
        assert got is not None and got[0] == tid and got[2]
        # the sampled trace is served from the worker-local endpoint
        d = requests.get(
            f"{srv.base}/aux/v1/debug/traces",
            params={"trace_id": tid},
            timeout=5,
        ).json()
        assert len(d["traces"]) == 1
        root = d["traces"][0]["root"]
        assert root["name"].startswith("http GET ")

        def names(node, acc):
            acc.add(node["name"])
            for c in node["children"]:
                names(c, acc)
            return acc

        got_names = names(root, set())
        assert "service" in got_names
        assert "store_ms" in got_names
        assert d["stats"]["dss_trace_kept_sampled_total"] >= 1
        # error responses carry the id too
        r404 = requests.get(
            f"{srv.base}/no/such/route",
            headers={"traceparent": tp}, timeout=5,
        )
        assert r404.headers.get("X-Request-Id") == tid
    finally:
        srv.stop()
