"""RID service tests, modeled on the reference prober scenarios
(monitoring/prober/rid/*)."""

from datetime import timedelta

import pytest

from dss_tpu import errors
from dss_tpu.clock import FakeClock
from dss_tpu.dar.dss_store import DSSStore
from dss_tpu.services.rid import RIDService
from dss_tpu.services.serialization import format_time
from tests.test_store_contract import T0
from tests.wire import body_json

ISA_ID = "11111111-1111-4111-8111-111111111111"
SUB_ID = "22222222-2222-4222-8222-222222222222"
AREA = "37.0,-122.0,37.06,-122.0,37.06,-122.06,37.0,-122.06"


def extents(lat=37.03, lng=-122.03, half=0.02, t0=None, t1=None):
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
        "time_start": format_time(t0 if t0 else T0),
        "time_end": format_time(t1 if t1 else T0 + timedelta(hours=2)),
    }


@pytest.fixture(params=["memory", "tpu"])
def svc(request):
    clock = FakeClock(T0)
    store = DSSStore(storage=request.param, clock=clock)
    s = RIDService(store.rid, clock)
    s.fake_clock = clock
    return s


def isa_params():
    return {"extents": extents(), "flights_url": "https://uss.example.com/flights"}


def sub_params():
    return {
        "extents": extents(),
        "callbacks": {
            "identification_service_area_url": "https://uss2.example.com/isa"
        },
    }


def test_isa_crud_lifecycle(svc):
    created = svc.create_isa(ISA_ID, isa_params(), "uss1")
    isa = created["service_area"]
    assert isa["id"] == ISA_ID and isa["owner"] == "uss1"
    assert isa["version"]
    assert created["subscribers"] == []

    got = svc.get_isa(ISA_ID)["service_area"]
    assert got["version"] == isa["version"]

    found = body_json(svc.search_isas(AREA))
    assert [a["id"] for a in found["service_areas"]] == [ISA_ID]

    updated = svc.update_isa(ISA_ID, isa["version"], isa_params(), "uss1")
    assert updated["service_area"]["version"] != isa["version"]

    # delete with stale version -> 409
    with pytest.raises(errors.StatusError) as ei:
        svc.delete_isa(ISA_ID, isa["version"], "uss1")
    assert ei.value.code == errors.Code.ABORTED
    deleted = svc.delete_isa(ISA_ID, updated["service_area"]["version"], "uss1")
    assert deleted["service_area"]["id"] == ISA_ID
    with pytest.raises(errors.StatusError):
        svc.get_isa(ISA_ID)


def test_isa_create_validations(svc):
    with pytest.raises(errors.StatusError):
        svc.create_isa("not-a-uuid", isa_params(), "uss1")
    p = isa_params()
    p["flights_url"] = ""
    with pytest.raises(errors.StatusError):
        svc.create_isa(ISA_ID, p, "uss1")
    p = isa_params()
    del p["extents"]
    with pytest.raises(errors.StatusError):
        svc.create_isa(ISA_ID, p, "uss1")
    # creating twice -> 409 AlreadyExists
    svc.create_isa(ISA_ID, isa_params(), "uss1")
    with pytest.raises(errors.StatusError) as ei:
        svc.create_isa(ISA_ID, isa_params(), "uss1")
    assert ei.value.code == errors.Code.ALREADY_EXISTS
    # update by another owner -> 403
    v = svc.get_isa(ISA_ID)["service_area"]["version"]
    with pytest.raises(errors.StatusError) as ei:
        svc.update_isa(ISA_ID, v, isa_params(), "intruder")
    assert ei.value.code == errors.Code.PERMISSION_DENIED


def test_isa_time_rules(svc):
    p = isa_params()
    p["extents"]["time_start"] = format_time(T0 - timedelta(hours=1))
    with pytest.raises(errors.StatusError, match="in the past"):
        svc.create_isa(ISA_ID, p, "uss1")
    p = isa_params()
    del p["extents"]["time_end"]
    with pytest.raises(errors.StatusError, match="time_end"):
        svc.create_isa(ISA_ID, p, "uss1")
    # omitted start defaults to now
    p = isa_params()
    del p["extents"]["time_start"]
    out = svc.create_isa(ISA_ID, p, "uss1")
    assert out["service_area"]["time_start"] == format_time(T0)


def test_search_area_validation(svc):
    with pytest.raises(errors.StatusError) as ei:
        svc.search_isas("37.0,-122.0,37.05")
    assert ei.value.code == errors.Code.INVALID_ARGUMENT
    # huge area -> 413
    with pytest.raises(errors.StatusError) as ei:
        svc.search_isas("0,0,0,5,5,5,5,0")
    assert ei.value.code == errors.Code.AREA_TOO_LARGE


def test_subscription_lifecycle_and_isa_interaction(svc):
    sub = svc.create_subscription(SUB_ID, sub_params(), "uss2")
    assert sub["subscription"]["id"] == SUB_ID
    assert sub["subscription"]["notification_index"] == 0
    assert sub["service_areas"] == []

    # creating an ISA in the overlapping area returns the subscriber
    out = svc.create_isa(ISA_ID, isa_params(), "uss1")
    assert len(out["subscribers"]) == 1
    state = out["subscribers"][0]["subscriptions"][0]
    assert state["subscription_id"] == SUB_ID
    assert state["notification_index"] == 1

    # a later subscription in the same area sees the ISA in the response
    sub2 = svc.create_subscription(
        "33333333-3333-4333-8333-333333333333", sub_params(), "uss3"
    )
    assert [a["id"] for a in sub2["service_areas"]] == [ISA_ID]

    # owner search only returns own subscriptions
    mine = svc.search_subscriptions(AREA, "uss2")
    assert [s["id"] for s in mine["subscriptions"]] == [SUB_ID]

    # deleting the ISA also notifies
    v = svc.get_isa(ISA_ID)["service_area"]["version"]
    out = svc.delete_isa(ISA_ID, v, "uss1")
    assert len(out["subscribers"]) == 2  # both live subscriptions

    got = svc.get_subscription(SUB_ID)["subscription"]
    assert got["notification_index"] == 2
    deleted = svc.delete_subscription(SUB_ID, got["version"], "uss2")
    assert deleted["subscription"]["id"] == SUB_ID


def test_subscription_quota(svc):
    for k in range(10):
        svc.create_subscription(
            f"44444444-4444-4444-8444-44444444440{k:x}", sub_params(), "uss2"
        )
    with pytest.raises(errors.StatusError) as ei:
        svc.create_subscription(
            "44444444-4444-4444-8444-4444444444ff", sub_params(), "uss2"
        )
    assert ei.value.code == errors.Code.RESOURCE_EXHAUSTED


def test_subscription_duration_cap(svc):
    p = sub_params()
    p["extents"]["time_end"] = format_time(T0 + timedelta(hours=30))
    with pytest.raises(errors.StatusError, match="24 hours"):
        svc.create_subscription(SUB_ID, p, "uss2")
    # omitted end defaults to start + 24h
    p = sub_params()
    del p["extents"]["time_end"]
    out = svc.create_subscription(SUB_ID, p, "uss2")
    assert out["subscription"]["time_end"] == format_time(T0 + timedelta(hours=24))
