"""Request tracing + profiling opt-in (SURVEY §5 tracing/profiling;
reference --trace-requests pkg/logging/http.go:36-55 and the
Cloud-Profiler opt-in recast as an on-demand JAX device-trace
capture)."""

from __future__ import annotations

import json
import logging
import threading
import time

import pytest
import requests

from dss_tpu.api.app import build_app
from tests.live_server import LiveServer


class EchoRID:
    def get_isa(self, id, owner=None):
        return {"service_area": {"id": id}}


class _Capture(logging.Handler):
    def __init__(self):
        super().__init__()
        self.fields = []

    def emit(self, record):
        f = getattr(record, "fields", None)
        if f:
            self.fields.append(f)


def test_request_id_assigned_and_propagated():
    srv = LiveServer(
        build_app(EchoRID(), None, None, trace_requests=True)
    )
    cap = _Capture()
    access = logging.getLogger("dss.access")
    access.addHandler(cap)
    try:
        r = requests.get(
            f"{srv.base}/v1/dss/identification_service_areas/x",
            timeout=5,
        )
        assert r.status_code == 200
        assert r.headers.get("X-Request-Id")
        # a caller-supplied id is propagated, not replaced
        r2 = requests.get(
            f"{srv.base}/v1/dss/identification_service_areas/x",
            headers={"X-Request-Id": "corr-123"},
            timeout=5,
        )
        assert r2.headers["X-Request-Id"] == "corr-123"
        # stage timings + request id land in the access log fields
        recs = [
            f for f in cap.fields
            if f.get("path", "").startswith("/v1/dss")
        ]
        assert any(f.get("request_id") == "corr-123" for f in recs)
        assert any("service_ms" in f for f in recs)
        # error responses carry the id too (correlation matters most
        # there)
        r404 = requests.get(f"{srv.base}/no/such/route", timeout=5)
        assert r404.status_code == 404
        assert r404.headers.get("X-Request-Id")
    finally:
        access.removeHandler(cap)
        srv.stop()


def test_profile_capture_writes_trace(tmp_path):
    srv = LiveServer(
        build_app(
            EchoRID(), None, None,
            trace_requests=True,
            profile_dir=str(tmp_path / "prof"),
        )
    )
    try:
        r = requests.post(
            f"{srv.base}/debug/profile",
            params={"seconds": "0.2"},
            timeout=30,
        )
        assert r.status_code == 200, r.text
        out = r.json()
        assert out["seconds"] == 0.2
        # the capture directory exists and holds a trace artifact
        prof = tmp_path / "prof"
        assert prof.exists()
        assert any(prof.rglob("*")), "no profiler artifacts written"
        # malformed seconds -> 400, not 500
        r = requests.post(
            f"{srv.base}/debug/profile",
            params={"seconds": "abc"},
            timeout=10,
        )
        assert r.status_code == 400
    finally:
        srv.stop()


def test_profile_absent_without_flag():
    srv = LiveServer(build_app(EchoRID(), None, None))
    try:
        r = requests.post(f"{srv.base}/debug/profile", timeout=5)
        assert r.status_code == 404
    finally:
        srv.stop()


# -- the program's spans on the profiler's clock ------------------------------


class _PQ:
    def __init__(self, results):
        self.results = results

    def wait_device(self):
        pass

    def used_device(self):
        return True


class _Table:
    def query_many_submit(self, keys, lo, hi, t0s, t1s, now=None,
                          owner_ids=None, host_route=False):
        time.sleep(0.002)
        return _PQ([["hit"] for _ in keys])

    def query_many_collect(self, pq):
        time.sleep(0.002)
        return pq.results


def _host_event_names(profile_dir) -> set:
    from jax.profiler import ProfileData

    found = list(profile_dir.rglob("*.xplane.pb"))
    assert found, "no capture written"
    names = set()
    for plane in ProfileData.from_file(str(found[-1])).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


@pytest.mark.parametrize("python", ["0", "1"])
def test_capture_holds_program_spans_and_python_only_on_request(
        tmp_path, python):
    """A CPU capture through /debug/profile around one coalescer query
    served over the ring: the program's seams are on the capture's
    timeline as dss.* events, and the profiler's Python tracer (every
    call of every thread, the events named `$file:line function`) is
    off unless ?python=1 asks for it."""
    import numpy as np

    from dss_tpu.dar.coalesce import QueryCoalescer
    from dss_tpu.obs import trace
    from dss_tpu.parallel import shmring

    prof = tmp_path / "prof"
    srv = LiveServer(
        build_app(EchoRID(), None, None, profile_dir=str(prof))
    )
    co = QueryCoalescer(_Table(), inline=True)
    region = shmring.ShmRegion.create(
        str(tmp_path / "ring.shm"), nworkers=1, depth=4
    )
    owner = shmring.ShmOwner(
        region,
        lambda req: (co.query(np.asarray([5], np.int32), now=1), [9], 1),
    )
    owner.start()
    client = shmring.ShmWorkerClient(region, 0)
    answer = {}

    def capture():
        answer["r"] = requests.post(
            f"{srv.base}/debug/profile",
            params={"seconds": "1.0", "python": python}, timeout=120,
        )

    th = threading.Thread(target=capture)
    try:
        th.start()
        deadline = time.monotonic() + 60
        while trace.annotate("x") is trace.annotate("y"):
            # still the shared no-op: the capture's flag is not up yet
            assert time.monotonic() < deadline
            time.sleep(0.01)
        time.sleep(0.3)  # flag up -> profiler session started
        resp = client.call(
            cls="isa", cells=np.asarray([7], np.uint64), now_ns=1
        )
        assert resp.status == shmring.ST_OK and resp.ids == ["hit"]
        th.join(timeout=120)
        assert not th.is_alive()
        assert answer["r"].status_code == 200, answer["r"].text
        assert answer["r"].json()["python"] is (python == "1")
    finally:
        client.close()
        owner.close()
        co.close()
        region.close()
        srv.stop()
    # the flag came down with the capture: seams are no-ops again
    assert trace.annotate("x") is trace.annotate("y")
    names = _host_event_names(prof)
    for needed in ("dss.owner.serve", "dss.device.dispatch",
                   "dss.collect", "dss.owner.idle"):
        assert needed in names, (needed, sorted(
            n for n in names if n.startswith("dss.")))
    hooked = {n for n in names if n.startswith("$")}
    assert bool(hooked) is (python == "1"), sorted(hooked)[:5]


class _SleepyRID:
    def search_isas(self, *a, **kw):
        time.sleep(0.01)
        return {"service_areas": []}


def test_exec_wait_and_loop_lag_are_stages():
    """exec_wait_ms: what a handler awaited beyond what run() ran —
    both hops of run_in_executor — lands in the request's stage sink
    (the X-Dss-Stages header shows it) and in the stage family;
    loop_lag_ms: the loop's 10 Hz self-timer observes its lateness
    under the fixed route, with no request at all."""
    from dss_tpu.obs.metrics import LOOP_ROUTE, MetricsRegistry

    m = MetricsRegistry()
    srv = LiveServer(
        build_app(_SleepyRID(), None, None, metrics=m, trace_requests=True)
    )
    try:
        r = requests.get(
            f"{srv.base}/v1/dss/identification_service_areas",
            params={"area": "0,0,0,1,1,1,1,0"}, timeout=10,
        )
        assert r.status_code == 200, r.text
        got = dict(
            kv.split("=") for kv in r.headers["X-Dss-Stages"].split(";")
        )
        assert 0.0 <= float(got["exec_wait_ms"]) < float(got["service_ms"])
        # the 10 ms of run() are in service_ms and not in the wait
        assert float(got["service_ms"]) >= 10.0
        assert float(got["exec_wait_ms"]) < 10.0
        time.sleep(0.35)  # three ticks of the self-timer
        text = requests.get(f"{srv.base}/metrics", timeout=10).text
    finally:
        srv.stop()
    assert 'stage="exec_wait_ms"' in text
    lag = [
        ln for ln in text.splitlines()
        if ln.startswith("dss_stage_duration_seconds_count")
        and f'route="{LOOP_ROUTE}"' in ln and 'stage="loop_lag_ms"' in ln
    ]
    assert lag and float(lag[0].rsplit(" ", 1)[1]) >= 2, text[-2000:]
