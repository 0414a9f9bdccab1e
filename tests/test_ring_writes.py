"""A planned flight's PUTs over the shared-memory ring (ISSUE 40).

A worker of the front hands `PUT /dss/v1/operation_references/{id}` to
the store's owner in a ring slot instead of the loopback proxy: the
worker authenticates, the owner's write lane runs the leader's own
service, and the HTTP status and body come back as the leader would
have answered them.  Under test, on `tests/test_shmring.py`'s front
harness with the two apps in front of it (the worker's, and the
leader's loopback that the proxy reaches):

  - the answers are byte for byte the proxy's: a 200, a 409 with its
    listing, a 200 with subscribers, a 400, an auth refusal;
  - a search on the same worker reads the 200 right after it;
  - only a write the owner never saw takes the proxy (ring full, the
    owner's heartbeat stale), and the counters say which went where;
  - a claimed write is never run twice: not when the worker stops
    waiting, not when its answer is larger than the slot;
  - the search slot's codec is the bytes it was.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import os
import subprocess
import sys
import time
import uuid
from datetime import timedelta

import numpy as np
import pytest
import requests

from dss_tpu.api.app import (
    RID_SCOPES,
    SCD_SCOPES,
    build_app,
    make_ring_write_fn,
    make_worker_proxy_middleware,
)
from dss_tpu.auth.authorizer import Authorizer, StaticKeyResolver
from dss_tpu.cmds.dummy_oauth import mint_token
from dss_tpu.obs.metrics import MetricsRegistry
from dss_tpu.parallel import shmring
from dss_tpu.services import scd as scd_service
from dss_tpu.services.rid import RIDService
from dss_tpu.services.scd import SCDService
from tests.live_server import LiveServer
from tests.test_shmring import T0, _FrontHarness

AUD = "localhost"
SCD_SCOPE = "utm.strategic_coordination"
RID_SCOPE = "dss.read.identification_service_areas"
ROUTE = "/dss/v1/operation_references/{entityuuid}"
OP = "eeeeeeee-eeee-4eee-8eee-{:012d}"


def _iso(t) -> str:
    return t.strftime("%Y-%m-%dT%H:%M:%SZ")


def _extent(lat=40.0, lng=-100.0, size=0.02):
    return {
        "volume": {
            "outline_polygon": {"vertices": [
                {"lat": lat, "lng": lng},
                {"lat": lat + size, "lng": lng},
                {"lat": lat + size, "lng": lng + size},
                {"lat": lat, "lng": lng + size},
            ]},
            "altitude_lower": {"value": 50.0, "reference": "W84",
                               "units": "M"},
            "altitude_upper": {"value": 200.0, "reference": "W84",
                               "units": "M"},
        },
        "time_start": {"value": _iso(T0 + timedelta(minutes=10)),
                       "format": "RFC3339"},
        "time_end": {"value": _iso(T0 + timedelta(hours=1)),
                     "format": "RFC3339"},
    }


def _flight(key=None, extent=None) -> dict:
    body = {
        "extents": [extent or _extent()],
        "uss_base_url": "https://uss.example.com",
        "state": "Accepted",
        "new_subscription": {"uss_base_url": "https://uss.example.com"},
    }
    if key is not None:
        body["key"] = key
    return body


def _query() -> dict:
    return {"area_of_interest": _extent()}


class RingFront:
    """cmds/server.py's front, in-process: the leader's store with the
    shm front and its write lane, the leader's app on a loopback (what
    the proxy reaches), and one worker (replica, ring, fenced cache)
    behind its own app.  `ring=False` is the worker of the parent: every
    mutation over the proxy."""

    def __init__(self, tmp_path, keypair, *, ring=True, depth=16,
                 slot_bytes=32768, timeout_s=10.0):
        self.priv, pub = keypair
        scopes = dict(RID_SCOPES)
        scopes.update(SCD_SCOPES)
        authorizer = Authorizer(
            StaticKeyResolver([pub]), audiences=[AUD], scopes_table=scopes
        )
        self.leader_metrics = MetricsRegistry()
        # as main() sets it on the store's owner of a --workers front
        self.leader_metrics.handler_stages = ("handler_ms",
                                              "leader_handler_ms")
        self.worker_metrics = MetricsRegistry()
        services = {}

        def writes(leader, clock):
            services["scd"] = SCDService(leader.scd, clock)
            self.write_fn = make_ring_write_fn(services,
                                               self.leader_metrics)
            return lambda req: self.write_fn(req)

        self.h = h = _FrontHarness(tmp_path, depth=depth,
                                   slot_bytes=slot_bytes, writes=writes)
        self.leader_srv = LiveServer(build_app(
            RIDService(h.leader.rid, h.clock), services["scd"], authorizer,
            enable_scd=True, metrics=self.leader_metrics,
            trace_requests=True, wal_seq_fn=lambda: h.leader.wal.seq,
        ))
        self.worker_srv = LiveServer(build_app(
            RIDService(h.rid, h.clock), SCDService(h.scd, h.clock),
            authorizer, enable_scd=True, metrics=self.worker_metrics,
            trace_requests=True, default_timeout_s=timeout_s,
            stats_fn=h.front.stats,
            worker_proxy=make_worker_proxy_middleware(
                self.leader_srv.base, follower=h.follower,
                costs=h.front.costs, ring=h.front if ring else None,
                authorizer=authorizer,
            ),
        ))

    def headers(self, scope=SCD_SCOPE, sub="uss1") -> dict:
        tok = mint_token(self.priv, scope=scope, intended_audience=AUD,
                         issuer="dummy-oauth", sub=sub)
        return {"Authorization": f"Bearer {tok}"}

    def put(self, op_id, body, headers=None):
        return requests.put(
            f"{self.worker_srv.base}/dss/v1/operation_references/{op_id}",
            data=json.dumps(body), timeout=30,
            headers={"Content-Type": "application/json",
                     **(headers or self.headers())})

    def search(self):
        r = requests.post(
            f"{self.worker_srv.base}/dss/v1/operation_references/query",
            json=_query(), headers=self.headers(), timeout=30)
        assert r.status_code == 200, r.text
        return {o["id"] for o in r.json()["operation_references"]}

    def wal_op_puts(self, op_id) -> int:
        """The journal's records of this op's puts (the file itself)."""
        with open(self.h.wal_path) as fh:
            return sum(
                1 for line in fh if op_id in line and '"scd_op_put"' in line
            )

    def wal_records(self) -> int:
        with open(self.h.wal_path) as fh:
            return sum(1 for _ in fh)

    def stats(self) -> dict:
        return self.h.client.stats()

    def close(self):
        self.worker_srv.stop()
        self.leader_srv.stop()
        self.h.close()


def _count_uuids(monkeypatch):
    """The implicit subscriptions' ids from a fresh counter: two fronts
    that are asked the same questions answer the same bytes."""
    counter = itertools.count(1)
    monkeypatch.setattr(scd_service.uuidlib, "uuid4",
                        lambda: uuid.UUID(int=next(counter)))


def _exchange(front) -> list:
    """(status, body bytes) of: a 200 into empty airspace, a 409 with
    its listing, the 200 keyed by it with a subscriber, a 400 (no
    volume), and a token without the scope."""
    out = []
    r = front.put(OP.format(1), _flight())
    out.append(r)
    r409 = front.put(OP.format(2), _flight())
    out.append(r409)
    key = [c["operation_reference"]["ovn"]
           for c in r409.json()["entity_conflicts"]]
    out.append(front.put(OP.format(2), _flight(key)))
    bad = _flight()
    bad["extents"] = [{"time_start": bad["extents"][0]["time_start"]}]
    out.append(front.put(OP.format(3), bad))
    out.append(front.put(OP.format(4), _flight(),
                         headers=front.headers(scope=RID_SCOPE)))
    return [(r.status_code, r.content) for r in out]


def test_the_ring_answers_the_proxys_bytes(tmp_path, keypair, monkeypatch):
    _count_uuids(monkeypatch)
    ringed = RingFront(tmp_path / "ring", keypair)
    try:
        over_ring = _exchange(ringed)
        st = ringed.stats()
    finally:
        ringed.close()
    # the same questions to a fresh front whose worker proxies them
    _count_uuids(monkeypatch)
    proxied = RingFront(tmp_path / "proxy", keypair, ring=False)
    try:
        over_proxy = _exchange(proxied)
    finally:
        proxied.close()
    assert [s for s, _ in over_ring] == [200, 409, 200, 400, 403]
    assert over_ring == over_proxy
    # the 409 lists the conflict with its OVN, the keyed 200 a subscriber
    listing = json.loads(over_ring[1][1])["entity_conflicts"]
    assert [c["operation_reference"]["id"] for c in listing] == [OP.format(1)]
    assert json.loads(over_ring[2][1])["subscribers"]
    # four PUTs reached the owner (the refusal never left the worker)
    assert st["write_ring"] == 4 and st["write_proxied"] == 0


@pytest.fixture
def front(tmp_path, keypair):
    f = RingFront(tmp_path, keypair, depth=4)
    yield f
    f.close()


def test_a_search_on_the_worker_reads_the_write_right_after_it(front):
    r = front.put(OP.format(1), _flight())
    assert r.status_code == 200, r.text
    assert OP.format(1) in front.search()
    r409 = front.put(OP.format(2), _flight())
    key = [c["operation_reference"]["ovn"]
           for c in r409.json()["entity_conflicts"]]
    assert front.put(OP.format(2), _flight(key)).status_code == 200
    assert {OP.format(1), OP.format(2)} <= front.search()
    owner = front.h.owner_region._ohdr
    assert owner[shmring.OH_WRITE_SERVED] == 3
    # the search-only owner words count the searches alone
    assert owner[shmring.OH_SERVED] == owner[shmring.OH_HOST_SERVED] + owner[
        shmring.OH_DEVICE_SERVED]
    assert front.wal_op_puts(OP.format(1)) == 1
    assert front.wal_op_puts(OP.format(2)) == 1


def test_a_full_ring_sends_the_write_over_the_proxy(front):
    held = [front.h.client._alloc() for _ in range(4)]
    try:
        r = front.put(OP.format(5), _flight())
    finally:
        for s in held:
            front.h.client._release(s)
    assert r.status_code == 200, r.text
    st = front.stats()
    assert st["write_proxied"] == 1 and st["write_ring"] == 0
    assert st["ring_full"] >= 1
    assert front.wal_op_puts(OP.format(5)) == 1
    # the proxy's read-your-writes: the replica waited for the seq
    assert OP.format(5) in front.search()


def test_a_stale_owner_sends_the_write_over_the_proxy(front):
    front.h.front.owner_ttl_s = -1.0  # every heartbeat age is stale
    r = front.put(OP.format(6), _flight())
    assert r.status_code == 200, r.text
    st = front.stats()
    assert st["write_proxied"] == 1 and st["write_ring"] == 0
    assert front.h.owner_region._ohdr[shmring.OH_WRITE_SERVED] == 0
    assert front.wal_op_puts(OP.format(6)) == 1


def test_a_worker_that_stops_waiting_never_sends_the_write_again(
        tmp_path, keypair):
    f = RingFront(tmp_path, keypair, timeout_s=0.5)
    served = f.write_fn

    def slow(req):  # the commit first, then a late answer
        out = served(req)
        time.sleep(1.0)
        return out

    f.write_fn = slow
    try:
        before = f.wal_records()
        r = f.put(OP.format(7), _flight())
        assert r.status_code == 504, r.text
        deadline = time.monotonic() + 10
        while (f.h.owner_region._ohdr[shmring.OH_WRITE_SERVED] < 1
               and time.monotonic() < deadline):
            time.sleep(0.05)
        time.sleep(0.2)
        assert f.h.owner_region._ohdr[shmring.OH_WRITE_SERVED] == 1
        st = f.stats()
        assert st["write_ring"] == 1 and st["write_proxied"] == 0
        # committed once, by the write lane alone: the implicit
        # subscription, the op, the subscribers' bump
        assert f.wal_op_puts(OP.format(7)) == 1
        assert f.wal_records() - before == 3
        assert f.h.leader.scd.get_operation(OP.format(7)) is not None
        # the abandoned slot comes back to the worker's allocator
        f.write_fn = served
        assert f.put(OP.format(8), _flight(
            extent=_extent(lat=41.0))).status_code == 200
        assert f.wal_op_puts(OP.format(7)) == 1
    finally:
        f.close()


def test_an_answer_larger_than_the_slot_is_read_once_from_its_spill(
        tmp_path, keypair):
    f = RingFront(tmp_path, keypair, slot_bytes=4096)
    try:
        svc = SCDService(f.h.leader.scd, f.h.clock)
        for i in range(80):  # the quota is 10 an owner a cell
            svc.put_subscription(
                str(uuid.UUID(int=1000 + i)),
                {"extents": _extent(), "notify_for_operations": True,
                 "uss_base_url": f"https://uss{i}.example.com"},
                f"owner{i // 8}",
            )
        before = f.wal_records()
        r = f.put(OP.format(9), _flight())
        assert r.status_code == 200, r.text
        assert len(r.content) > 4096
        assert f.wal_records() - before == 3
        assert len(r.json()["subscribers"]) == 81
        owner = f.h.owner_region._ohdr
        assert owner[shmring.OH_WRITE_SERVED] == 1
        assert owner[shmring.OH_WRITE_SPILLED] == 1
        assert not os.path.exists(f.h.worker_region.spill_path(0, 0))
        assert not any(".spill-" in n for n in os.listdir(tmp_path))
        assert f.wal_op_puts(OP.format(9)) == 1
        assert f.stats()["write_proxied"] == 0
    finally:
        f.close()


def test_the_front_exports_the_write_counters(front):
    from dssbench import deploy
    from dssbench.readers import scrape_ratio

    s0 = deploy.scrape(front.worker_srv.base)
    assert front.put(OP.format(10), _flight()).status_code == 200
    s1 = deploy.scrape(front.worker_srv.base)
    for key in ("dss_shm_worker_write_ring", "dss_shm_worker_write_proxied",
                "dss_shm_write_served_total", "dss_shm_write_spilled_total"):
        assert key in s1, key
    with open(os.path.join(deploy.REPO, "dssbench", "metrics",
                           "write_ring_pct.json")) as fh:
        args = json.load(fh)["args"]
    got = scrape_ratio.read({"scrape0": {"front": s0},
                             "scrape1": {"front": s1}}, **args)
    assert got == pytest.approx(100.0)
    # a front that carried no PUT in the window reads nothing
    assert scrape_ratio.read({"scrape0": {"front": s1},
                              "scrape1": {"front": s1}}, **args) is None
    empty = shmring.empty_stats()
    for key in ("dss_shm_write_served_total", "dss_shm_write_spilled_total",
                "dss_shm_worker_write_ring", "dss_shm_worker_write_proxied"):
        assert key in empty, key


def test_a_mutation_slot_round_trips_and_is_told_from_a_search(tmp_path):
    r = shmring.ShmRegion.create(str(tmp_path / "r.shm"), nworkers=1,
                                 depth=2, slot_bytes=4096, fence_slots=16)
    try:
        body = json.dumps(_flight()).encode()
        r.write_mutation(0, 1, 5, route=0, entity=OP.format(1),
                         owner="uss1", body=body, deadline_ns=99,
                         trace_id="0af7651916cd43dd8448eb211c80319c",
                         trace_sampled=True)
        assert r.is_mutation(0, 1)
        m = r.read_mutation(0, 1)
        assert (m.route, m.entity, m.owner, m.body, m.deadline_ns,
                m.req_id) == (0, OP.format(1), "uss1", body, 99, 5)
        assert m.trace_id == "0af7651916cd43dd8448eb211c80319c"
        assert m.trace_sampled
        assert not r.write_mutation_response(0, 1, status=409, body=b"{}",
                                             wal_seq=7, stamps=(1, 2))
        got = r.read_mutation_response(0, 1)
        assert (got.status, got.body, got.wal_seq) == (409, b"{}", 7)
        r.write_request(0, 0, 6, cls_idx=2, cells=np.arange(3, dtype=np.uint64),
                        alt_lo=None, alt_hi=None, t0_ns=None, t1_ns=None,
                        now_ns=1, deadline_ns=2, owner="", allow_stale=False)
        assert not r.is_mutation(0, 0)
        with pytest.raises(shmring.RingOversize):
            r.write_mutation(0, 1, 8, route=0, entity="x", owner="o",
                             body=b"x" * 4096, deadline_ns=0)
    finally:
        r.close()


def test_the_search_slot_codec_is_the_bytes_it_was(tmp_path):
    """A search request and its answer, encoded as every field set: the
    slot's bytes (the two clock stamps aside) hash as the parent's
    codec made them."""
    r = shmring.ShmRegion.create(str(tmp_path / "g.shm"), nworkers=1,
                                 depth=2, slot_bytes=4096, fence_slots=16)
    try:
        cells = np.arange(1000, 1040, dtype=np.uint64) * np.uint64(7919)
        r.write_request(
            0, 1, 77, cls_idx=2, cells=cells, alt_lo=10.5, alt_hi=200.25,
            t0_ns=1_700_000_000_000_000_000,
            t1_ns=1_700_000_360_000_000_000,
            now_ns=1_699_999_000_000_000_000, deadline_ns=123456789,
            owner="uss-golden", allow_stale=True,
            trace_id="0af7651916cd43dd8448eb211c80319c", trace_sampled=True)
        off = r._slot_off(0, 1)

        def digest():
            b = bytearray(r._mm[off:off + r.slot_bytes])
            b[shmring._PUBLISHED_OFF:shmring._PUBLISHED_OFF + 8] = bytes(8)
            w = shmring._STAMPS_OFF + 16  # the response's write stamp
            b[w:w + 8] = bytes(8)
            return hashlib.sha256(bytes(b)).hexdigest()

        assert digest() == ("c350fb1529807fbed0ec98328e7f0aae"
                            "e678d1826f5803c2dbe57f372a84ec17")
        req = r.read_request(0, 1)
        assert (req.cls, req.owner, list(req.cells)) == (
            "op", "uss-golden", list(cells))
        r.write_response(0, 1, status=0, ids=["a-1", "bb-22", "ccc-333"],
                         t1s=[5, 6, 7], wal_seq=42, gen=9,
                         retry_after_s=1.5, flags=2,
                         trace_ns=[1, 2, 3, 4, 5, 6, 7, 8], stamps=(11, 22))
        assert digest() == ("ce98448c7bc629829407691e7beb94e7"
                            "60c644862f0a7a8e255e3ca5414e032d")
        got = r.read_response(0, 1)
        assert got.ids == ["a-1", "bb-22", "ccc-333"] and got.wal_seq == 42
    finally:
        r.close()


def test_the_owner_heartbeat_is_never_read_torn(tmp_path):
    """Another process stamps the owner's heartbeat as fast as it can,
    and every read here is a whole stamp.  `struct.pack_into` zero-fills
    its field before it writes the bytes: the parent's heartbeat read 0
    now and then, and a PUT went to the proxy for an owner "1.79e9 s
    old" (my chip run, PR 40)."""
    path = str(tmp_path / "hb.shm")
    r = shmring.ShmRegion.create(path, nworkers=1, depth=2,
                                 slot_bytes=4096, fence_slots=16)
    first = int(r._heartbeat[0])
    writer = subprocess.Popen(
        [sys.executable, "-c",
         "import sys, time\n"
         "from dss_tpu.parallel import shmring\n"
         "r = shmring.ShmRegion.open_existing(sys.argv[1])\n"
         "end = time.monotonic() + 4.0\n"
         "while time.monotonic() < end:\n"
         "    r.set_owner_heartbeat()\n", path],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        deadline = time.monotonic() + 30
        while int(r._heartbeat[0]) == first:  # the writer has begun
            assert writer.poll() is None and time.monotonic() < deadline
            time.sleep(0.01)
        worst, reads = 0.0, 0
        end = time.monotonic() + 1.0
        while time.monotonic() < end:
            worst = max(worst, r.owner_heartbeat_age_s())
            reads += 1
        assert reads > 10_000
        assert worst < 1.0, worst
    finally:
        writer.wait(timeout=30)
        r.close()
