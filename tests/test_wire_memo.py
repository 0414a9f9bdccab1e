"""A search answer is joined from bytes each record was encoded to once
(services/serialization.py isas_body / operations_body).

Differential: the body the two record searches return equals, byte for
byte, what the dict-building path they replaced gave (`_old_*` below is
that path, kept here as the reference), on a cold memo and on a warm
one, for every store a service can hold.  Guarantees: OVNs stay private
whatever the order of askers; a write or a delete is seen through a
warm memo; a record missing from a worker's replica is skipped and
counted; a caller of the copying depth cannot reach what a later search
answers with."""

import copy
import json
from datetime import timedelta

import numpy as np
import pytest
import requests

from dss_tpu.api.app import build_app
from dss_tpu.clock import FakeClock
from dss_tpu.dar.dss_store import DSSStore
from dss_tpu.geo.covering import canonical_cells
from dss_tpu.models import rid as ridm
from dss_tpu.models import scd as scdm
from dss_tpu.region import federation as fed
from dss_tpu.services import rid as rid_service
from dss_tpu.services import scd as scd_service
from dss_tpu.services import serialization as ser
from tests.live_server import LiveServer
from tests.test_shmring import T0, _FrontHarness
from tests.wire import body_json

AREA = "37.01,-122.05,37.01,-122.01,37.05,-122.01,37.05,-122.05"
# non-ASCII on purpose: json.dumps escapes it, and so must a joined body
URL = "https://uss.example/volés/飞行"
A, B = "uss-a", "uss-b"


def _rid_extents(t0, t1):
    return {
        "spatial_volume": {
            "footprint": {"vertices": [
                {"lat": 37.01, "lng": -122.05}, {"lat": 37.01, "lng": -122.01},
                {"lat": 37.05, "lng": -122.01}, {"lat": 37.05, "lng": -122.05},
            ]},
            "altitude_lo": 20.0, "altitude_hi": 400.0,
        },
        "time_start": ser.format_time(t0), "time_end": ser.format_time(t1),
    }


def _scd_extent(t0, t1):
    return {
        "volume": {
            "outline_polygon": {"vertices": [
                {"lat": 37.01, "lng": -122.05}, {"lat": 37.01, "lng": -122.01},
                {"lat": 37.05, "lng": -122.01}, {"lat": 37.05, "lng": -122.05},
            ]},
            "altitude_lower": {"value": 50.0, "reference": "W84", "units": "M"},
            "altitude_upper": {"value": 200.0, "reference": "W84", "units": "M"},
        },
        "time_start": {"value": ser.format_time(t0), "format": "RFC3339"},
        "time_end": {"value": ser.format_time(t1), "format": "RFC3339"},
    }


AOI = {"area_of_interest": _scd_extent(T0, T0 + timedelta(hours=6))}
# whole seconds, microseconds, and a fraction format_time trims
_ENDS = (timedelta(hours=2), timedelta(hours=2, microseconds=123456),
         timedelta(hours=3, milliseconds=500))


def _uuid(i: int) -> str:
    return f"00000000-0000-4000-8000-{i:012d}"


def _op_params(i: int, **kw):
    start = T0 + timedelta(minutes=i, microseconds=7 * (i % 2))
    return {
        "extents": [_scd_extent(start, T0 + _ENDS[i % 3])],
        "uss_base_url": URL if i % 2 else "https://uss.example/plain",
        "new_subscription": {"uss_base_url": "https://uss.example/sub"},
        # no OVN key is asked of a state outside REQUIRES_KEY
        "state": "NonConforming", "old_version": 0, "key": [], **kw,
    }


# -- the path this PR replaced, as the reference ---------------------------


def _old_search_operations(svc, params, owner) -> bytes:
    vol4, cells = scd_service._aoi_to_covering(params)
    sv = vol4.spatial_volume
    ops = svc.store.search_operations(
        cells, sv.altitude_lo, sv.altitude_hi, vol4.start_time,
        vol4.end_time, allow_stale=True,
    )
    out = []
    for op in ops:
        if op.owner != owner:
            op.ovn = ""
        out.append(ser.op_to_json(op))
    return json.dumps({"operation_references": out}).encode("utf-8")


def _old_search_isas(svc, area) -> bytes:
    cells = rid_service._area_to_cells(area)
    isas = svc.store.search_isas(
        cells, svc.clock.now(), None, allow_stale=True
    )
    return json.dumps(
        {"service_areas": [ser.isa_to_json(i) for i in isas]}
    ).encode("utf-8")


# -- every store a service can hold ----------------------------------------


class _Served:
    """A writable pair of services and the pair that searches: the same
    pair for a single-process store, the leader's and the worker's for
    the shm front."""

    def __init__(self, write, search, counts, close, sync=lambda: None):
        self.write_rid, self.write_scd = write
        self.rid, self.scd = search
        self.counts, self.close, self.sync = counts, close, sync


def _single(store):
    rid = rid_service.RIDService(store.rid, store.clock)
    scd = scd_service.SCDService(store.scd, store.clock)

    def counts():
        st = store.stats()
        return st["dss_wire_memo_hits"], st["dss_wire_memo_misses"]

    return _Served((rid, scd), (rid, scd), counts, store.close)


def _federated():
    """The federation wrapper has no stored depth: it is served through
    the same encoder from its copies (every record a miss)."""
    store = DSSStore(storage="memory", clock=FakeClock(T0))
    router = fed.FederationRouter(
        fed.FederationMap(
            [fed.RegionEntry("solo")], np.array([], np.int32), "solo"
        ), {},
    )
    store.attach_federation(router)
    router.close()  # no background sync in tests
    assert isinstance(store.scd, fed.FederatedSCDStore)
    return _single(store)


def _shm(tmp_path):
    h = _FrontHarness(tmp_path)
    write = (rid_service.RIDService(h.leader.rid, h.clock),
             scd_service.SCDService(h.leader.scd, h.clock))
    search = (rid_service.RIDService(h.rid, h.clock),
              scd_service.SCDService(h.scd, h.clock))

    def counts():
        st = h.client.stats()
        return st["wire_memo_hits"], st["wire_memo_misses"]

    served = _Served(write, search, counts, h.close, h.sync)
    served.harness = h
    return served


STORES = ("tpu", "memory", "shm", "federated")


@pytest.fixture(params=STORES)
def served(request, tmp_path):
    kind = request.param
    if kind == "shm":
        s = _shm(tmp_path)
    elif kind == "federated":
        s = _federated()
    else:
        s = _single(DSSStore(storage=kind, clock=FakeClock(T0)))
    s.kind = kind
    yield s
    s.close()


def _seed_ops(served, n=6):
    for i in range(n):
        served.write_scd.put_operation(
            _uuid(100 + i), _op_params(i), A if i % 2 else B
        )
    served.sync()


def _seed_isas(served, n=5):
    for i in range(n):
        served.write_rid.create_isa(_uuid(200 + i), {
            "extents": _rid_extents(
                T0 + timedelta(minutes=1 + i), T0 + _ENDS[i % 3]
            ),
            "flights_url": URL if i % 2 else "https://uss.example/f",
        }, A)
    served.sync()


# -- differential ----------------------------------------------------------


@pytest.mark.parametrize("cls", ["operation", "isa"])
def test_body_equals_the_old_dict_dumped(served, cls):
    if cls == "operation":
        _seed_ops(served)
        n, key = 6, "operation_references"
        old = lambda: _old_search_operations(served.scd, AOI, A)  # noqa: E731
        new = lambda: served.scd.search_operations(AOI, A)  # noqa: E731
    else:
        _seed_isas(served)
        n, key = 5, "service_areas"
        old = lambda: _old_search_isas(served.rid, AREA)  # noqa: E731
        new = lambda: served.rid.search_isas(AREA)  # noqa: E731
    want = old()
    assert len(json.loads(want)[key]) == n
    assert b"\\u98de" in want  # the non-ASCII url went through
    h0, m0 = served.counts()
    cold = new()
    h1, m1 = served.counts()
    warm = new()
    h2, m2 = served.counts()
    assert cold == want and warm == want
    assert (h1 - h0, m1 - m0) == (0, n)
    # a store that hands out its records remembers; copies never hit
    remembers = served.kind != "federated"
    assert (h2 - h1, m2 - m1) == ((n, 0) if remembers else (0, n))


_T = T0 + timedelta(hours=1)
_TIMES = {
    "whole-seconds": (_T, _T + timedelta(hours=1)),
    "microseconds": (_T + timedelta(microseconds=1),
                     _T + timedelta(microseconds=999999)),
    "trimmed-fraction": (_T + timedelta(milliseconds=250), _T),
    "no-times": (None, None),
    "no-end": (_T, None),
}


@pytest.mark.parametrize("times", _TIMES)
@pytest.mark.parametrize("cls", ["operation", "isa"])
def test_encoder_equals_json_dumps_of_the_dict(cls, times):
    """Records no store write admits (no times) still replay from a
    WAL: the encoder alone, against `json.dumps` of the old dict."""
    start, end = _TIMES[times]
    if cls == "operation":
        recs = [scdm.Operation(
            id=_uuid(i), owner=(A, B)[i % 2], version=i, ovn=f"ovn-{i}",
            start_time=start, end_time=end, uss_base_url=URL,
            subscription_id=_uuid(900 + i),
        ) for i in range(4)]
        for asker in (A, B, "nobody"):
            docs = []
            for r in recs:
                doc = ser.op_to_json(r)
                if r.owner != asker:
                    doc["ovn"] = ""
                docs.append(doc)
            want = json.dumps({"operation_references": docs}).encode()
            assert ser.operations_body(recs, asker)[0] == want
            assert ser.operations_body(recs, asker) == (want, 4)
    else:
        recs = [ridm.IdentificationServiceArea(
            id=_uuid(i), owner=A, url=URL, start_time=start, end_time=end,
        ) for i in range(4)]
        want = json.dumps(
            {"service_areas": [ser.isa_to_json(r) for r in recs]}
        ).encode()
        assert ser.isas_body(recs) == (want, 0)
        assert ser.isas_body(recs) == (want, 4)
    assert ser.operations_body([], A)[0] == b'{"operation_references": []}'
    assert ser.isas_body([])[0] == b'{"service_areas": []}'


# -- guarantees ------------------------------------------------------------


def _ovns(body: bytes) -> dict:
    return {o["id"]: (o["owner"], o["ovn"])
            for o in body_json(body)["operation_references"]}


@pytest.mark.parametrize("first", [A, B])
def test_ovns_stay_private_whatever_the_order_of_askers(served, first):
    """(a) A sees A's OVNs and blanks for B's, B the reverse, whoever
    asks first (so each form is met cold and warm), and a third party
    sees none."""
    _seed_ops(served)
    second = B if first == A else A
    for asker in (first, second, first, "uss-c", second):
        got = _ovns(served.scd.search_operations(AOI, asker))
        assert len(got) == 6
        for owner, ovn in got.values():
            assert bool(ovn) == (owner == asker), (asker, owner, ovn)
        assert served.scd.search_operations(AOI, asker) == (
            _old_search_operations(served.scd, AOI, asker)
        )


def test_a_write_and_a_delete_are_seen_through_a_warm_memo(served):
    """(b) upsert then search returns the new version and OVN, delete
    then search omits the record: a write installs a new object, which
    remembers nothing."""
    _seed_ops(served)
    oid = _uuid(101)  # A's
    before = _ovns(served.scd.search_operations(AOI, A))  # warm
    assert _ovns(served.scd.search_operations(AOI, A)) == before
    served.write_scd.clock.advance(seconds=2)  # an OVN has 1 s grain
    out = served.write_scd.put_operation(
        oid, _op_params(1, old_version=1), A
    )["operation_reference"]
    assert out["version"] == 2 and out["ovn"] != before[oid][1]
    # no sync(): the shm front waits for the write's WAL seq itself
    body = served.scd.search_operations(AOI, A)
    after = _ovns(body)
    assert after[oid] == (A, out["ovn"])
    refs = {o["id"]: o for o in body_json(body)["operation_references"]}
    assert refs[oid]["version"] == 2
    assert {k: v for k, v in after.items() if k != oid} == (
        {k: v for k, v in before.items() if k != oid}
    )
    assert _ovns(served.scd.search_operations(AOI, B))[oid] == (A, "")
    served.write_scd.delete_operation(oid, A)
    gone = _ovns(served.scd.search_operations(AOI, A))
    assert oid not in gone and len(gone) == 5
    assert served.scd.search_operations(AOI, A) == (
        _old_search_operations(served.scd, AOI, A)
    )


def test_a_record_missing_from_the_replica_is_skipped_and_counted(tmp_path):
    """(c) the index's answer names a record the worker's replica no
    longer (or not yet) holds: left out of the body, counted."""
    served = _shm(tmp_path)
    try:
        _seed_ops(served)
        _seed_isas(served)
        replica = served.harness.replica
        served.scd.search_operations(AOI, A)  # warm
        del replica.scd._ops[_uuid(102)]
        del replica.rid._isas[_uuid(203)]
        got = _ovns(served.scd.search_operations(AOI, A))
        assert _uuid(102) not in got and len(got) == 5
        areas = body_json(served.rid.search_isas(AREA))["service_areas"]
        assert sorted(a["id"] for a in areas) == [
            _uuid(200 + i) for i in (0, 1, 2, 4)
        ]
        assert served.harness.client.stats()["assembly_misses"] == 2
    finally:
        served.close()


def test_a_vanished_record_is_skipped_by_the_single_process_store():
    """(c) for DSSStore: a delete between the index's answer and the
    join (here: the dict entry alone) skips, never KeyErrors."""
    served = _single(DSSStore(storage="memory", clock=FakeClock(T0)))
    _seed_ops(served)
    served.scd.search_operations(AOI, A)
    store = served.scd.store
    del store._ops[_uuid(104)]
    store._cache.invalidate_all()
    got = _ovns(served.scd.search_operations(AOI, A))
    assert _uuid(104) not in got and len(got) == 5


def test_a_caller_of_the_copying_depth_cannot_change_a_later_body(served):
    """(d) the precheck's and the conflict listing's depth hands out
    copies, and a copy carries its original's `__dict__`, remembered
    bytes and all: changing one changes no search, and encoding one
    never answers with (or overwrites) what its original remembers."""
    _seed_ops(served)
    want = served.scd.search_operations(AOI, A)  # warm
    vol4, cells = scd_service._aoi_to_covering(AOI)
    sv = vol4.spatial_volume
    copies = served.scd.store.search_operations(
        cells, sv.altitude_lo, sv.altitude_hi, vol4.start_time, vol4.end_time
    )
    assert len(copies) == 6
    for c in copies:
        c.ovn, c.uss_base_url, c.version = "stolen", "https://evil", 99
    assert served.scd.search_operations(AOI, A) == want
    forged, hits = ser.operations_body(copies, A)
    assert hits == 0 and b"stolen" in forged and b"evil" in forged
    assert served.scd.search_operations(AOI, A) == want
    # a second-generation copy of a record that HAS remembered bytes
    stored = served.scd.store.stored_operations(
        cells, sv.altitude_lo, sv.altitude_hi, vol4.start_time, vol4.end_time
    )
    if served.kind != "federated":
        twin = copy.copy(stored[0])
        assert ser._WIRE in twin.__dict__
        twin.uss_base_url = "https://evil"
        assert b"evil" in ser.operations_body([twin], twin.owner)[0]
        assert ser.operations_body([stored[0]], "nobody")[1] == 1
    assert served.scd.search_operations(AOI, A) == want


def test_stored_depth_hands_out_the_stored_objects_in_assembly_order(served):
    """...where the store keeps its records; the federation wrapper,
    which merges what its peers sent, serves the depth from copies."""
    _seed_ops(served)
    _seed_isas(served)
    uncopied = served.kind != "federated"
    vol4, cells = scd_service._aoi_to_covering(AOI)
    sv = vol4.spatial_volume
    args = (cells, sv.altitude_lo, sv.altitude_hi, vol4.start_time,
            vol4.end_time)
    store = served.scd.store
    ops = getattr(store, "_inner", store)._ops
    stored = store.stored_operations(*args)
    assert [r.id for r in stored] == [
        r.id for r in store.search_operations(*args)
    ] and len(stored) == 6
    assert all((r is ops[r.id]) == uncopied for r in stored)
    assert not any(r is ops[r.id] for r in store.search_operations(*args))
    rstore = served.rid.store
    isas = getattr(rstore, "_inner", rstore)._isas
    rcells = canonical_cells(rid_service._area_to_cells(AREA))
    got = rstore.stored_isas(rcells, T0, None)
    assert [r.id for r in got] == [
        r.id for r in rstore.search_isas(rcells, T0, None)
    ] and len(got) == 5
    assert all((r is isas[r.id]) == uncopied for r in got)


def test_the_http_answer_is_the_joined_body_with_the_old_headers():
    store = DSSStore(storage="memory", clock=FakeClock(T0))
    served = _single(store)
    _seed_ops(served)
    _seed_isas(served)
    srv = LiveServer(build_app(served.rid, served.scd, None, enable_scd=True))
    try:
        for _ in range(2):  # cold, warm
            r = requests.post(
                f"{srv.base}/dss/v1/operation_references/query",
                json=AOI, timeout=30,
            )
            assert r.status_code == 200, r.text
            assert r.content == _old_search_operations(
                served.scd, AOI, "anonymous"
            )
            assert r.headers["Content-Type"] == (
                "application/json; charset=utf-8"
            )
            assert "class=op" in r.headers["X-DSS-Freshness"]
            r = requests.get(
                f"{srv.base}/v1/dss/identification_service_areas",
                params={"area": AREA}, timeout=30,
            )
            assert r.status_code == 200, r.text
            assert r.content == _old_search_isas(served.rid, AREA)
            assert r.headers["Content-Type"] == (
                "application/json; charset=utf-8"
            )
            assert "class=isa" in r.headers["X-DSS-Freshness"]
    finally:
        srv.stop()
        store.close()
