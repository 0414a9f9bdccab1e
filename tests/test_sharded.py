"""Multi-chip sharded DAR queries vs the exact oracle.

Runs on the virtual 8-device CPU mesh (conftest.py); the driver
separately exercises the same path via __graft_entry__.dryrun_multichip.
"""

import numpy as np
import pytest

import jax

from dss_tpu.dar import oracle
from dss_tpu.dar.oracle import Record
from dss_tpu.parallel import ShardedDar, make_mesh
from dss_tpu.ops.conflict import NO_TIME_HI, NO_TIME_LO

NOW = 1_700_000_000_000_000_000  # unix ns
HOUR = 3_600_000_000_000


def _mk_records(rng, n, key_space=500):
    recs = []
    for i in range(n):
        nk = int(rng.integers(1, 12))
        keys = np.unique(rng.integers(0, key_space, nk).astype(np.int32))
        alo, ahi = sorted(rng.uniform(0, 3000, 2))
        t0 = NOW + int(rng.integers(-5, 5)) * HOUR
        t1 = t0 + int(rng.integers(1, 8)) * HOUR
        recs.append(
            Record(
                entity_id=f"e{i}",
                keys=keys,
                alt_lo=float(alo),
                alt_hi=float(ahi),
                t_start=t0,
                t_end=t1,
                owner_id=int(rng.integers(0, 5)),
            )
        )
    return recs


@pytest.mark.parametrize("dp,sp", [(1, 8), (2, 4), (1, 1)])
def test_sharded_matches_oracle(dp, sp):
    if dp * sp > len(jax.devices()):
        pytest.skip("not enough devices")
    rng = np.random.default_rng(7)
    recs = _mk_records(rng, 300)
    mesh = make_mesh(dp * sp, dp=dp, sp=sp)
    dar = ShardedDar(recs, mesh, max_results=512)

    q = 16
    kw = 32
    keys = np.full((q, kw), -1, np.int32)
    alo = np.full(q, -np.inf, np.float32)
    ahi = np.full(q, np.inf, np.float32)
    ts = np.full(q, NO_TIME_LO, np.int64)
    te = np.full(q, NO_TIME_HI, np.int64)
    for i in range(q):
        nk = int(rng.integers(1, kw))
        uniq = np.unique(rng.integers(0, 500, nk).astype(np.int32))
        keys[i, : len(uniq)] = uniq
        if i % 2:
            a, b = sorted(rng.uniform(0, 3000, 2))
            alo[i], ahi[i] = a, b
        if i % 3:
            ts[i] = NOW - 2 * HOUR
            te[i] = NOW + 2 * HOUR

    got = dar.query_batch(keys, alo, ahi, ts, te, now=NOW)
    recs_map = {i: r for i, r in enumerate(recs)}
    for i in range(q):
        want = oracle.search(
            recs_map,
            keys[i][keys[i] >= 0],
            None if alo[i] == -np.inf else float(alo[i]),
            None if ahi[i] == np.inf else float(ahi[i]),
            None if ts[i] == NO_TIME_LO else int(ts[i]),
            None if te[i] == NO_TIME_HI else int(te[i]),
            NOW,
        )
        assert sorted(got[i]) == sorted(want), f"query {i}"


def test_sharded_overflow_falls_back_exact():
    rng = np.random.default_rng(3)
    # many entities on one hot cell so results overflow max_results=4
    recs = []
    for i in range(40):
        recs.append(
            Record(
                entity_id=f"e{i}",
                keys=np.array([7], np.int32),
                alt_lo=-np.inf,
                alt_hi=np.inf,
                t_start=NOW - HOUR,
                t_end=NOW + HOUR,
                owner_id=0,
            )
        )
    mesh = make_mesh(8, dp=2, sp=4)
    dar = ShardedDar(recs, mesh, max_results=4)
    keys = np.full((2, 4), -1, np.int32)
    keys[0, 0] = 7
    keys[1, 0] = 9  # empty cell
    got = dar.query_batch(
        keys,
        np.full(2, -np.inf, np.float32),
        np.full(2, np.inf, np.float32),
        np.full(2, NO_TIME_LO, np.int64),
        np.full(2, NO_TIME_HI, np.int64),
        now=NOW,
    )
    assert sorted(got[0]) == list(range(40))
    assert got[1] == []


def test_query_batch_pads_to_dp():
    rng = np.random.default_rng(11)
    recs = _mk_records(rng, 50)
    mesh = make_mesh(8, dp=2, sp=4)
    dar = ShardedDar(recs, mesh)
    # odd batch size (3) not divisible by dp=2 — must pad internally
    keys = np.full((3, 8), -1, np.int32)
    keys[:, 0] = [1, 2, 3]
    got = dar.query_batch(
        keys,
        np.full(3, -np.inf, np.float32),
        np.full(3, np.inf, np.float32),
        np.full(3, NO_TIME_LO, np.int64),
        np.full(3, NO_TIME_HI, np.int64),
        now=NOW,
    )
    assert len(got) == 3


# -- replica: WAL tail -> serving ShardedDar (SURVEY §7 step 7) -------------


def _op_params_at(lat):
    import time as _t

    now = _t.time()

    def iso(off):
        import time as _tt

        return _tt.strftime(
            "%Y-%m-%dT%H:%M:%S", _tt.gmtime(now + off)
        ) + "Z"

    return {
        "extents": [
            {
                "volume": {
                    "outline_polygon": {
                        "vertices": [
                            {"lat": lat, "lng": -100.0},
                            {"lat": lat + 0.02, "lng": -100.0},
                            {"lat": lat + 0.02, "lng": -99.98},
                            {"lat": lat, "lng": -99.98},
                        ]
                    },
                    "altitude_lower": {
                        "value": 50.0, "reference": "W84", "units": "M"
                    },
                    "altitude_upper": {
                        "value": 200.0, "reference": "W84", "units": "M"
                    },
                },
                "time_start": {"value": iso(60), "format": "RFC3339"},
                "time_end": {"value": iso(3600), "format": "RFC3339"},
            }
        ],
        "uss_base_url": "https://uss1.example.com",
        "new_subscription": {"uss_base_url": "https://uss1.example.com"},
        "state": "Accepted",
        "old_version": 0,
        "key": [],
    }


def test_replica_tails_live_wal_into_sharded_dar(tmp_path):
    """A live standalone store's WAL replays into a serving ShardedDar
    on the 8-device mesh; reads are consistent across refreshes."""
    import threading
    import time as _t
    import uuid

    from dss_tpu.clock import Clock
    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.geo import covering as geo_covering
    from dss_tpu.geo import s2cell
    from dss_tpu.parallel.replica import ShardedReplica
    from dss_tpu.services.scd import SCDService

    wal = tmp_path / "dss.wal"
    store = DSSStore(storage="memory", wal_path=str(wal))
    scd = SCDService(store.scd, store.clock)

    mesh = make_mesh(8, dp=2, sp=4)
    rep = ShardedReplica(mesh, wal_path=str(wal))

    # first wave of ops
    ids1 = [str(uuid.uuid4()) for _ in range(5)]
    for i, op_id in enumerate(ids1):
        scd.put_operation(op_id, _op_params_at(40.0 + i * 0.1), "uss1")
    rep.sync()

    def area_keys(lat):
        cells = geo_covering.covering_polygon(
            [(lat, -100.0), (lat + 0.02, -100.0),
             (lat + 0.02, -99.98), (lat, -99.98)]
        )
        return s2cell.cell_to_dar_key(cells)

    now = int(_t.time() * 1e9)
    for i, op_id in enumerate(ids1):
        got = rep.query(area_keys(40.0 + i * 0.1), now=now)
        assert op_id in got, (i, got)

    # concurrent reads during a second wave of writes + refreshes only
    # ever see complete snapshots (one of the valid states, no partial)
    valid_counts = {len(ids1), len(ids1) + 1, len(ids1) + 2}
    stop = threading.Event()
    errors_seen = []
    wide = np.unique(
        np.concatenate([area_keys(40.0 + i * 0.1) for i in range(7)])
    )

    def reader():
        while not stop.is_set():
            got = rep.query(wide, now=now)
            if len(got) not in valid_counts:
                errors_seen.append(len(got))

    th = threading.Thread(target=reader)
    th.start()
    ids2 = [str(uuid.uuid4()) for _ in range(2)]
    for j, op_id in enumerate(ids2):
        scd.put_operation(op_id, _op_params_at(40.5 + j * 0.1), "uss1")
        rep.sync()
    stop.set()
    th.join(timeout=10)
    assert not errors_seen, f"partial snapshots observed: {errors_seen}"

    got = rep.query(wide, now=now)
    assert sorted(got) == sorted(ids1 + ids2)

    # deletes propagate too
    scd.delete_operation(ids1[0], "uss1")
    rep.sync()
    got = rep.query(wide, now=now)
    assert ids1[0] not in got and sorted(got) == sorted(ids1[1:] + ids2)

    st = rep.stats()
    assert st["replica_rebuilds"] >= 3
    assert st["replica_ops_snapshot_records"] == len(ids1) - 1 + len(ids2)
    rep.close()
    store.close()


def test_replica_serves_every_entity_class(tmp_path):
    """ISAs, RID subs, and SCD subs replicate to the mesh alongside
    ops (the reference's range sharding covers every table,
    implementation_details.md:11-42)."""
    import time as _t
    import uuid

    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.geo import covering as geo_covering
    from dss_tpu.geo import s2cell
    from dss_tpu.parallel.replica import ShardedReplica
    from dss_tpu.services.rid import RIDService
    from dss_tpu.services.scd import SCDService

    wal = tmp_path / "dss.wal"
    store = DSSStore(storage="memory", wal_path=str(wal))
    rid = RIDService(store.rid, store.clock)
    scd = SCDService(store.scd, store.clock)

    mesh = make_mesh(8, dp=2, sp=4)
    rep = ShardedReplica(mesh, wal_path=str(wal))

    def iso(off):
        return _t.strftime(
            "%Y-%m-%dT%H:%M:%SZ", _t.gmtime(_t.time() + off)
        )

    isa_id = str(uuid.uuid4())
    rid.create_isa(
        isa_id,
        {
            "extents": {
                "spatial_volume": {
                    "footprint": {
                        "vertices": [
                            {"lat": 40.0, "lng": -100.0},
                            {"lat": 40.02, "lng": -100.0},
                            {"lat": 40.02, "lng": -99.98},
                            {"lat": 40.0, "lng": -99.98},
                        ]
                    },
                    "altitude_lo": 10.0,
                    "altitude_hi": 300.0,
                },
                "time_start": iso(60),
                "time_end": iso(3600),
            },
            "flights_url": "https://u1.example.com/f",
        },
        "uss1",
    )
    sub_id = str(uuid.uuid4())
    rid.create_subscription(
        sub_id,
        {
            "extents": {
                "spatial_volume": {
                    "footprint": {
                        "vertices": [
                            {"lat": 40.0, "lng": -100.0},
                            {"lat": 40.02, "lng": -100.0},
                            {"lat": 40.02, "lng": -99.98},
                            {"lat": 40.0, "lng": -99.98},
                        ]
                    },
                    "altitude_lo": 0.0,
                    "altitude_hi": 3000.0,
                },
                "time_start": iso(60),
                "time_end": iso(3600),
            },
            "callbacks": {
                "identification_service_area_url": "https://u1.example.com"
            },
        },
        "uss1",
    )
    op_id = str(uuid.uuid4())
    scd.put_operation(op_id, _op_params_at(40.0), "uss1")
    rep.sync()

    cells = geo_covering.covering_polygon(
        [(40.0, -100.0), (40.02, -100.0), (40.02, -99.98), (40.0, -99.98)]
    )
    keys = s2cell.cell_to_dar_key(cells)
    now = int(_t.time() * 1e9) + int(120e9)
    assert rep.query(keys, now=now, cls="isas") == [isa_id]
    assert rep.query(keys, now=now, cls="rid_subs") == [sub_id]
    # subscription ids are owner-private: scoping filters them
    assert rep.query(keys, now=now, cls="rid_subs", owner="uss1") == [sub_id]
    assert rep.query(keys, now=now, cls="rid_subs", owner="uss2") == []
    assert op_id in rep.query(keys, now=now, cls="ops")
    # the put_operation creates an implicit SCD subscription
    assert len(rep.query(keys, now=now, cls="scd_subs")) == 1
    st = rep.stats()
    assert st["replica_isas_snapshot_records"] == 1
    assert st["replica_rid_subs_snapshot_records"] == 1
    assert st["replica_scd_subs_snapshot_records"] == 1
    # deletes propagate per class
    v = rid.get_isa(isa_id)["service_area"]["version"]
    rid.delete_isa(isa_id, v, "uss1")
    rep.sync()
    assert rep.query(keys, now=now, cls="isas") == []
    rep.close()
    store.close()


def test_mesh_offload_for_oversized_stale_ok_batches(tmp_path):
    """Batches of >= min_batch allow_stale queries route to the mesh
    delegate when fresh; conflict prechecks (allow_stale=False) and
    owner-filtered queries never do."""
    from dss_tpu.dar.coalesce import QueryCoalescer, _Item
    from dss_tpu.dar.snapshot import DarTable

    table = DarTable()
    table.upsert("local", np.asarray([5], np.int32), None, None, 0,
                 10**18, 0)
    co = QueryCoalescer(table)
    calls = []

    def mesh_fn(keys_list, alo, ahi, ts, te, now_arr):
        calls.append(len(keys_list))
        return [["mesh-answer"] for _ in keys_list]

    co.set_mesh_delegate(mesh_fn, lambda: True, min_batch=2)

    def item(allow_stale, owner=None):
        return _Item(
            np.asarray([5], np.int32), None, None, None, None, 1,
            owner, allow_stale,
        )

    # all stale-ok, no owner filter -> offloaded
    b = [item(True), item(True)]
    co._execute(b)
    assert [it.result for it in b] == [["mesh-answer"], ["mesh-answer"]]
    assert co.mesh_offloads == 1
    # one conflict-precheck item (allow_stale=False) -> local
    b = [item(True), item(False)]
    co._execute(b)
    assert [it.result for it in b] == [["local"], ["local"]]
    # owner-filtered -> local
    b = [item(True, owner=0), item(True, owner=0)]
    co._execute(b)
    assert [it.result for it in b] == [["local"], ["local"]]
    # below min_batch -> local
    b = [item(True)]
    co._execute(b)
    assert b[0].result == ["local"]
    assert co.mesh_offloads == 1
    co.close()


def test_replica_demand_paced_refresh(tmp_path):
    """The background loop's rebuild gate (_refresh_due): rebuild
    unconditionally during the boot grace, go idle once the pace
    window passes with no freshness probes, and resume on the next
    fresh() consult — the mesh route's demand signal.  Pace <= 0
    restores the historical always-rebuild loop."""
    import time as _t

    from dss_tpu.parallel.replica import ShardedReplica

    wal = tmp_path / "dss.wal"
    wal.touch()
    mesh = make_mesh(8, dp=2, sp=4)
    rep = ShardedReplica(mesh, wal_path=str(wal))
    rep.demand_pace_s = 5.0
    now = _t.monotonic()

    rep._started_at = now  # inside boot grace
    assert rep._refresh_due()

    rep._started_at = now - 60.0  # grace over, no demand -> idle
    assert not rep._refresh_due()
    assert rep.stats()["replica_demand_idle"] == 1

    rep.fresh()  # a mesh-shaped batch probed freshness -> resume
    assert rep._refresh_due()
    assert rep.stats()["replica_demand_idle"] == 0

    rep.demand_pace_s = 0.0  # pacing disabled -> always rebuild
    rep._demand_last = 0.0
    assert rep._refresh_due()
