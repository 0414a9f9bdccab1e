"""SCD service tests: op lifecycle + two-USS OVN conflict flows,
modeled on monitoring/prober/scd/test_operations_simple.py and
test_operation_references_*."""

from datetime import timedelta

import pytest

from dss_tpu import errors
from dss_tpu.clock import FakeClock
from dss_tpu.dar.dss_store import DSSStore
from dss_tpu.services.scd import SCDService
from dss_tpu.services.serialization import format_time
from tests.test_store_contract import T0
from tests.wire import body_json

OP1 = "aaaaaaaa-aaaa-4aaa-8aaa-aaaaaaaaaaa1"
OP2 = "aaaaaaaa-aaaa-4aaa-8aaa-aaaaaaaaaaa2"
SUB1 = "bbbbbbbb-bbbb-4bbb-8bbb-bbbbbbbbbbb1"


def scd_extent(lat=40.0, lng=-100.0, half=0.02, alt=(50.0, 200.0), t0=None, t1=None):
    return {
        "volume": {
            "outline_polygon": {
                "vertices": [
                    {"lat": lat - half, "lng": lng - half},
                    {"lat": lat - half, "lng": lng + half},
                    {"lat": lat + half, "lng": lng + half},
                    {"lat": lat + half, "lng": lng - half},
                ]
            },
            "altitude_lower": {"value": alt[0], "reference": "W84", "units": "M"},
            "altitude_upper": {"value": alt[1], "reference": "W84", "units": "M"},
        },
        "time_start": {"value": format_time(t0 or T0), "format": "RFC3339"},
        "time_end": {
            "value": format_time(t1 or (T0 + timedelta(hours=1))),
            "format": "RFC3339",
        },
    }


def op_params(**kw):
    p = {
        "extents": [scd_extent()],
        "uss_base_url": "https://uss1.example.com",
        "new_subscription": {
            "uss_base_url": "https://uss1.example.com",
            "notify_for_constraints": False,
        },
        "state": "Accepted",
        "old_version": 0,
        "key": [],
    }
    p.update(kw)
    return p


@pytest.fixture(params=["memory", "tpu"])
def svc(request):
    clock = FakeClock(T0)
    store = DSSStore(storage=request.param, clock=clock)
    s = SCDService(store.scd, clock)
    s.fake_clock = clock
    return s


def test_op_lifecycle_with_implicit_subscription(svc):
    out = svc.put_operation(OP1, op_params(), "uss1")
    ref = out["operation_reference"]
    assert ref["id"] == OP1 and ref["version"] == 1 and ref["ovn"]
    sub_id = ref["subscription_id"]
    assert sub_id  # implicit subscription created
    # the implicit sub covers the op's volume, so the upsert notified it
    assert len(out["subscribers"]) == 1
    assert out["subscribers"][0]["uss_base_url"] == "https://uss1.example.com"

    got = svc.get_operation(OP1, "uss1")["operation_reference"]
    assert got["ovn"] == ref["ovn"]
    # other owners don't see the OVN
    assert svc.get_operation(OP1, "uss2")["operation_reference"]["ovn"] == ""

    deleted = svc.delete_operation(OP1, "uss1")
    assert deleted["operation_reference"]["id"] == OP1
    with pytest.raises(errors.StatusError):
        svc.get_operation(OP1, "uss1")
    # implicit subscription was GC'd
    with pytest.raises(errors.StatusError):
        svc.get_subscription(sub_id, "uss1")


def test_two_uss_ovn_conflict_flow(svc):
    """USS2 must present USS1's OVN to create an overlapping op."""
    out1 = svc.put_operation(OP1, op_params(), "uss1")
    ovn1 = out1["operation_reference"]["ovn"]

    with pytest.raises(errors.StatusError) as ei:
        svc.put_operation(
            OP2, op_params(uss_base_url="https://uss2.example.com"), "uss2"
        )
    err = ei.value
    assert err.code == errors.Code.MISSING_OVNS
    # the AirspaceConflictResponse body lists the conflicting op with OVN
    conflicts = err.details["entity_conflicts"]
    assert [c["operation_reference"]["id"] for c in conflicts] == [OP1]
    assert conflicts[0]["operation_reference"]["ovn"] == ovn1

    out2 = svc.put_operation(
        OP2,
        op_params(uss_base_url="https://uss2.example.com", key=[ovn1]),
        "uss2",
    )
    assert out2["operation_reference"]["version"] == 1
    # uss2 is notified of uss1's op volume via its implicit sub? No —
    # notification goes the other way: uss1's implicit sub is notified
    urls = {s["uss_base_url"] for s in out2["subscribers"]}
    assert "https://uss1.example.com" in urls


def test_op_update_requires_own_ovn(svc):
    out1 = svc.put_operation(OP1, op_params(), "uss1")
    ovn1 = out1["operation_reference"]["ovn"]
    # update without key -> conflict with own previous version
    with pytest.raises(errors.StatusError) as ei:
        svc.put_operation(OP1, op_params(old_version=1), "uss1")
    assert ei.value.code == errors.Code.MISSING_OVNS
    out2 = svc.put_operation(OP1, op_params(old_version=1, key=[ovn1]), "uss1")
    assert out2["operation_reference"]["version"] == 2


def test_op_search(svc):
    svc.put_operation(OP1, op_params(), "uss1")
    found = body_json(svc.search_operations(
        {"area_of_interest": scd_extent()}, "uss2"
    ))["operation_references"]
    assert [o["id"] for o in found] == [OP1]
    assert found[0]["ovn"] == ""  # stripped for non-owner
    # disjoint area
    found = body_json(svc.search_operations(
        {"area_of_interest": scd_extent(lat=-40.0, lng=100.0)}, "uss2"
    ))["operation_references"]
    assert found == []
    with pytest.raises(errors.StatusError):
        svc.search_operations({}, "uss2")


def test_op_validations(svc):
    with pytest.raises(errors.StatusError, match="UssBaseUrl"):
        svc.put_operation(OP1, op_params(uss_base_url=""), "uss1")
    p = op_params()
    p["extents"][0]["time_start"] = None
    with pytest.raises(errors.StatusError, match="time_start"):
        svc.put_operation(OP1, p, "uss1")
    p = op_params()
    p["new_subscription"]["uss_base_url"] = "http://insecure.example.com"
    with pytest.raises(errors.StatusError, match="TLS"):
        svc.put_operation(OP1, p, "uss1")


def test_scd_subscription_lifecycle(svc):
    params = {
        "extents": scd_extent(),
        "uss_base_url": "https://uss1.example.com",
        "notify_for_operations": True,
        "notify_for_constraints": False,
        "old_version": 0,
    }
    out = svc.put_subscription(SUB1, params, "uss1")
    assert out["subscription"]["id"] == SUB1
    assert out["subscription"]["version"] == 1
    assert out["operations"] == []

    got = svc.get_subscription(SUB1, "uss1")["subscription"]
    assert got["version"] == 1
    with pytest.raises(errors.StatusError):
        svc.get_subscription(SUB1, "uss2")

    q = svc.query_subscriptions({"area_of_interest": scd_extent()}, "uss1")
    assert [s["id"] for s in q["subscriptions"]] == [SUB1]

    # an op created in the area notifies, and appears in a sub update
    svc.put_operation(OP1, op_params(subscription_id=SUB1), "uss1")
    upd = svc.put_subscription(SUB1, dict(params, old_version=1), "uss1")
    assert [o["id"] for o in upd["operations"]] == [OP1]

    # delete blocked while the op depends on it
    with pytest.raises(errors.StatusError):
        svc.delete_subscription(SUB1, "uss1")
    svc.delete_operation(OP1, "uss1")
    out = svc.delete_subscription(SUB1, "uss1")
    assert out["subscription"]["id"] == SUB1


def test_scd_subscription_requires_notify_trigger(svc):
    params = {
        "extents": scd_extent(),
        "uss_base_url": "https://uss1.example.com",
        "notify_for_operations": False,
        "notify_for_constraints": False,
    }
    with pytest.raises(errors.StatusError, match="notification triggers"):
        svc.put_subscription(SUB1, params, "uss1")


def test_dss_report_still_stubbed(svc):
    # constraints are real now (tests/test_scd_constraints.py); the
    # report endpoint remains the reference's stub
    with pytest.raises(errors.StatusError, match="not yet implemented"):
        svc.make_dss_report()
