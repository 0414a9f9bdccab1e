"""Differential fuzz: the tpu and memory backends must be observably
identical under random operation sequences.

The store-contract tests pin known scenarios; this pins a longer tail:
random interleavings of ISA create/delete, RID search, SCD operation
put (with per-backend OVN keys, alternating constraint-aware)/delete,
SCD search, constraint put/delete/query (the fifth entity class rides
the same differential), and owner-scoped
RID subscription search on FOUR backends — memory, tpu with aggressive
TIERED snapshots (folds forced mid-sequence so queries constantly
cross the L0/L1/overlay split), tpu with tiering DISABLED
(tier_ratio=0: every fold a full rebuild, the pre-tier
single-snapshot path), and memory with the read cache DISABLED.
Outcomes (success vs exact error status/code), result-id sets, and
notified-subscriber sets are compared; versions/OVNs are per-store
commit-timestamp artifacts and are excluded.  The memory backend is a
direct transliteration of the reference's SQL semantics
(dar/oracle.py), so agreement here is agreement with the reference —
tiered agreeing with flat pins the tiering acceptance criterion, and
the CACHED stores (memory, tpu — search areas are quantized to a
small grid so repeat polls actually hit) agreeing with
the UNCACHED ones (memory_nocache, tpu_flat) pins the version-fence
acceptance criterion: a cache hit is bit-identical to the fresh path
under interleaved writes, folds, major compactions, owner-scoped
queries, and tombstones."""

from __future__ import annotations

import uuid
from datetime import datetime, timedelta, timezone

import numpy as np
import pytest

from dss_tpu import chaos, errors
from dss_tpu.dar.dss_store import DSSStore
from dss_tpu.services.rid import RIDService
from dss_tpu.services.scd import SCDService
from dss_tpu.services.serialization import format_time
from tests.wire import body_json

BASE_LAT, BASE_LNG = 40.0, -100.0


def _extents(rng):
    lat = BASE_LAT + float(rng.uniform(0, 0.3))
    lng = BASE_LNG + float(rng.uniform(0, 0.3))
    half = float(rng.uniform(0.005, 0.02))
    now = datetime.now(timezone.utc)
    t0 = now + timedelta(minutes=int(rng.integers(1, 30)))
    t1 = t0 + timedelta(minutes=int(rng.integers(10, 120)))
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
            "altitude_lo": float(rng.uniform(0, 200)),
            "altitude_hi": float(rng.uniform(250, 500)),
        },
        "time_start": format_time(t0),
        "time_end": format_time(t1),
    }


def _search_area(rng):
    # QUANTIZED to a small grid: the poll model is many clients asking
    # for the SAME areas, so fuzz searches repeat and the read cache's
    # hit path is actually exercised (continuous draws would never
    # repeat a covering and the fuzz would only ever test misses)
    lat = BASE_LAT + 0.05 * int(rng.integers(0, 6))
    lng = BASE_LNG + 0.05 * int(rng.integers(0, 6))
    h = (0.02, 0.045)[int(rng.integers(0, 2))]
    return (
        f"{lat},{lng},{lat + h},{lng},{lat + h},{lng + h},{lat},{lng + h}"
    )


def _norm_outcome(fn, *args):
    """-> ('ok', normalized-result) or ('err', status, code)."""
    try:
        out = fn(*args)
        # the record searches answer with the finished body
        return ("ok", body_json(out) if isinstance(out, bytes) else out)
    except errors.StatusError as e:
        return ("err", e.http_status, int(e.code))


def _cst_aoi_at(lat, lng, h):
    """Constraint-query AoI for one grid square (also the recovery
    sweep's request shape — ONE definition for the whole file)."""
    return {
        "area_of_interest": {
            "volume": {
                "outline_polygon": {
                    "vertices": [
                        {"lat": lat, "lng": lng},
                        {"lat": lat + h, "lng": lng},
                        {"lat": lat + h, "lng": lng + h},
                        {"lat": lat, "lng": lng + h},
                    ]
                },
            },
        }
    }


def _cst_aoi(rng):
    """A constraint-query AoI over the same quantized grid as
    _search_area (repeat polls exercise the cache's fifth class)."""
    lat = BASE_LAT + 0.05 * int(rng.integers(0, 6))
    lng = BASE_LNG + 0.05 * int(rng.integers(0, 6))
    return _cst_aoi_at(lat, lng, 0.045)


def _cst_put_body(ext):
    """Constraint PUT params from one _extents draw — shared by both
    fuzz tests so they exercise one request shape."""
    return {
        "extents": [
            {
                "volume": {
                    "outline_polygon": ext["spatial_volume"][
                        "footprint"
                    ],
                },
                "time_start": {
                    "value": ext["time_start"],
                    "format": "RFC3339",
                },
                "time_end": {
                    "value": ext["time_end"],
                    "format": "RFC3339",
                },
            }
        ],
        "uss_base_url": "https://authority.example",
    }


def _index_tables(store):
    out = []
    for index in (
        store.rid._isa_index, store.rid._sub_index,
        store.scd._op_index, store.scd._sub_index,
        store.scd._cst_index,
    ):
        t = getattr(index, "table", None)
        if t is not None:
            out.append(t)
    return out


@pytest.mark.parametrize("seed", list(range(1, 9)))
def test_backends_agree_under_random_ops(seed, monkeypatch):
    # "tpu": tiering forced aggressive (churn ratio 5 -> folds stay
    # minor, so the tier stack is live for most of the sequence);
    # "tpu_flat": tiering disabled (every fold a full single-snapshot
    # rebuild) — the differential pin that tiered == single-snapshot.
    # Cache split: memory + tpu run the version-fenced read cache,
    # memory_nocache + tpu_flat run WITHOUT it — cached answers must
    # be bit-identical to uncached ones on both backends.  Capacity
    # comfortably exceeds the run's distinct-key count so the hits>0
    # assertion below is deterministic: shard placement hashes key
    # bytes with the PYTHONHASHSEED-randomized hash(), so a squeezed
    # capacity would make eviction — and thus whether a repeat still
    # finds its line — vary run to run (eviction behavior itself is
    # pinned deterministically in test_readcache with shards=1).
    monkeypatch.setenv("DSS_CACHE_ENABLE", "1")
    monkeypatch.setenv("DSS_CACHE_CAP", "512")
    monkeypatch.setenv("DSS_TIER_RATIO", "5")
    tiered = DSSStore(storage="tpu")
    mem_cached = DSSStore(storage="memory")
    monkeypatch.setenv("DSS_CACHE_ENABLE", "0")
    monkeypatch.setenv("DSS_TIER_RATIO", "0")
    flat = DSSStore(storage="tpu")
    mem_plain = DSSStore(storage="memory")
    monkeypatch.delenv("DSS_TIER_RATIO")
    monkeypatch.delenv("DSS_CACHE_ENABLE")
    monkeypatch.delenv("DSS_CACHE_CAP")
    stores = {
        "memory": mem_cached,
        "memory_nocache": mem_plain,
        "tpu": tiered,
        "tpu_flat": flat,
    }
    others = [n for n in stores if n != "memory"]
    # the push pipeline rides the TIERED tpu store: its notify-path
    # matching now routes through the planner's rqmatch MatchStage
    # (fused kernel over the live subscription DAR) while the memory
    # oracle keeps the linear scan — so every subscriber-set equality
    # assertion below pins "no missed match, no duplicate match" under
    # interleaved subscription writes, folds, and major compactions
    from dss_tpu.push import PushPipeline

    push = PushPipeline(workers=1, transport=lambda *a: None)
    tiered.attach_push(push)
    push.register_hook("u1", "http://u1.example/notify")
    rid = {n: RIDService(s.rid, s.clock) for n, s in stores.items()}
    scd = {n: SCDService(s.scd, s.clock) for n, s in stores.items()}
    max_tiers = 0

    rng = np.random.default_rng(seed)
    # versions, like OVNs, derive from per-store commit timestamps:
    # track them per backend and hand each store its own token
    isa_versions: dict = {n: {} for n in stores}
    # OVNs are per-store (they derive from each store's commit
    # timestamps), so each backend presents its OWN keys
    op_ovns: dict = {n: {} for n in stores}

    rid_sub_versions: dict = {n: {} for n in stores}
    # constraints: int32 versions are deterministic (same across
    # backends) but tracked per backend anyway, like everything else;
    # OVNs derive from per-store commit timestamps
    cst_versions: dict = {n: {} for n in stores}
    cst_ovns: dict = {n: {} for n in stores}

    for step in range(90):
        op = rng.integers(0, 13)
        sid = str(uuid.UUID(int=int(rng.integers(0, 40)), version=4))
        if op == 0:  # ISA create (fresh id, same for both backends)
            create_id = (
                str(uuid.UUID(int=int(rng.integers(1000, 2000)), version=4))
                if sid in isa_versions["memory"]
                else sid
            )
            body = {"extents": _extents(rng), "flights_url": "https://u/f"}
            outs = {
                n: _norm_outcome(rid[n].create_isa, create_id, body, "u1")
                for n in stores
            }
        elif op == 1:  # ISA delete (maybe-existing, maybe-stale version)
            outs = {
                n: _norm_outcome(
                    rid[n].delete_isa,
                    sid,
                    isa_versions[n].get(sid, "aaaaaaaaaa"),
                    "u1",
                )
                for n in stores
            }
        elif op == 2:  # RID search, polled twice as a display provider does
            area = _search_area(rng)
            outs = {
                n: _norm_outcome(rid[n].search_isas, area)
                for n in stores
            }
            # nothing wrote in between: the repeat is the cached
            # stores' read cache, and it answers what the first did
            for n in stores:
                again = _norm_outcome(rid[n].search_isas, area)
                assert again == outs[n], (step, n, again, outs[n])
        elif op == 3:  # SCD op put (no key -> may 409-conflict)
            ext = _extents(rng)  # ONE draw: coherent volume + window
            body = {
                "extents": [
                    {
                        "volume": {
                            "outline_polygon": ext["spatial_volume"][
                                "footprint"
                            ],
                            "altitude_lower": {
                                "value": 50.0, "reference": "W84",
                                "units": "M",
                            },
                            "altitude_upper": {
                                "value": 200.0, "reference": "W84",
                                "units": "M",
                            },
                        },
                        "time_start": {
                            "value": ext["time_start"],
                            "format": "RFC3339",
                        },
                        "time_end": {
                            "value": ext["time_end"],
                            "format": "RFC3339",
                        },
                    }
                ],
                "uss_base_url": "https://u.example",
                # alternate constraint awareness: aware ops must key
                # against intersecting constraints too, and their
                # conflict payloads carry constraint_reference entries
                # — both sides of the gate run through the differential
                "new_subscription": {
                    "uss_base_url": "https://u.example",
                    "notify_for_constraints": step % 2 == 0,
                },
                "state": "Accepted",
                "old_version": 0,
            }
            outs = {
                n: _norm_outcome(
                    scd[n].put_operation,
                    sid,
                    dict(
                        body,
                        key=list(op_ovns[n].values())
                        + (
                            list(cst_ovns[n].values())
                            if step % 2 == 0
                            else []
                        ),
                    ),
                    "u1",
                )
                for n in stores
            }
        elif op == 4:  # SCD op delete
            outs = {
                n: _norm_outcome(scd[n].delete_operation, sid, "u1")
                for n in stores
            }
        elif op == 6:  # RID subscription create/upsert (quota DSS0050)
            body = {
                "extents": _extents(rng),
                "callbacks": {
                    "identification_service_area_url": "https://u/i"
                },
            }
            # upsert: create when unseen, version-fenced update after
            # (each backend presents its OWN version token)
            outs = {
                n: (
                    _norm_outcome(
                        rid[n].update_subscription,
                        sid,
                        rid_sub_versions[n][sid],
                        body,
                        "u1",
                    )
                    if sid in rid_sub_versions[n]
                    else _norm_outcome(
                        rid[n].create_subscription, sid, body, "u1"
                    )
                )
                for n in stores
            }
        elif op == 7:  # RID subscription delete (maybe-stale version)
            outs = {
                n: _norm_outcome(
                    rid[n].delete_subscription,
                    sid,
                    rid_sub_versions[n].get(sid, "aaaaaaaaaa"),
                    "u1",
                )
                for n in stores
            }
        elif op == 9:  # owner-scoped RID subscription search (the
            #             cache key carries the owner scope; two
            #             owners must never share a line)
            area = _search_area(rng)
            owner = ("u1", "u2")[int(rng.integers(0, 2))]
            outs = {
                n: _norm_outcome(rid[n].search_subscriptions, area, owner)
                for n in stores
            }
        elif op == 8:  # ISA update with the CURRENT version (fencing)
            body = {"extents": _extents(rng), "flights_url": "https://u/f"}
            outs = {
                n: _norm_outcome(
                    rid[n].update_isa,
                    sid,
                    isa_versions[n].get(sid, "aaaaaaaaaa"),
                    body,
                    "u1",
                )
                for n in stores
            }
        elif op == 10:  # constraint put (create, fenced update, or
            #             stale-version rejection — version tracked)
            body = _cst_put_body(_extents(rng))  # ONE coherent draw
            outs = {
                n: _norm_outcome(
                    scd[n].put_constraint,
                    sid,
                    dict(body, old_version=cst_versions[n].get(sid, 0)),
                    "u1",
                )
                for n in stores
            }
        elif op == 11:  # constraint delete (maybe-missing)
            outs = {
                n: _norm_outcome(scd[n].delete_constraint, sid, "u1")
                for n in stores
            }
        elif op == 12:  # constraint query (quantized area, cache-able)
            aoi = _cst_aoi(rng)
            owner = ("u1", "u2")[int(rng.integers(0, 2))]
            outs = {
                n: _norm_outcome(scd[n].query_constraints, aoi, owner)
                for n in stores
            }
        else:  # SCD search
            ext = _extents(rng)  # ONE draw: coherent volume + window
            aoi = {
                "area_of_interest": {
                    "volume": {
                        "outline_polygon": ext["spatial_volume"][
                            "footprint"
                        ],
                    },
                    "time_start": {
                        "value": ext["time_start"],
                        "format": "RFC3339",
                    },
                    "time_end": {
                        "value": ext["time_end"],
                        "format": "RFC3339",
                    },
                }
            }
            outs = {
                n: _norm_outcome(scd[n].search_operations, aoi, "u1")
                for n in stores
            }

        mem = outs["memory"]
        for n in others:
            assert mem[0] == outs[n][0], (step, op, n, mem, outs[n])
        if mem[0] == "err":
            for n in others:
                assert mem[1:] == outs[n][1:], (step, op, n, mem, outs[n])
            continue
        res = {n: o[1] for n, o in outs.items()}
        # normalize: versions/OVNs derive from per-store commit
        # timestamps and legitimately differ; ids and SETS of results
        # must agree exactly
        if op == 2:
            ids = {
                n: sorted(s["id"] for s in r["service_areas"])
                for n, r in res.items()
            }
            for n in others:
                assert ids[n] == ids["memory"], (step, n, ids)
        elif op == 5:
            ids = {
                n: sorted(o["id"] for o in r["operation_references"])
                for n, r in res.items()
            }
            for n in others:
                assert ids[n] == ids["memory"], (step, n, ids)
        elif op == 9:
            ids = {
                n: sorted(s["id"] for s in r["subscriptions"])
                for n, r in res.items()
            }
            for n in others:
                assert ids[n] == ids["memory"], (step, n, ids)
        elif op in (0, 8):
            subs = {
                n: sorted(
                    x["subscriptions"][0]["subscription_id"]
                    for x in r["subscribers"]
                )
                for n, r in res.items()
            }
            for n in others:
                assert subs[n] == subs["memory"], (step, n, subs)
            for n, r in res.items():
                isa_versions[n][r["service_area"]["id"]] = r[
                    "service_area"
                ]["version"]
        elif op == 1:
            for m in isa_versions.values():
                m.pop(sid, None)
        elif op == 3:
            for n, r in res.items():
                op_ovns[n][sid] = r["operation_reference"]["ovn"]
        elif op == 4:
            for m in op_ovns.values():
                m.pop(sid, None)
        elif op == 6:
            for n, r in res.items():
                rid_sub_versions[n][sid] = r["subscription"]["version"]
            # affected ISAs returned on sub create must agree
            ids = {
                n: sorted(x["id"] for x in r.get("service_areas", []))
                for n, r in res.items()
            }
            for n in others:
                assert ids[n] == ids["memory"], (step, n, ids)
        elif op == 7:
            for m in rid_sub_versions.values():
                m.pop(sid, None)
        elif op == 10:
            # int32 versions must agree EXACTLY across backends (they
            # are deterministic counters, unlike the commit-timestamp
            # versions of RID); subscriber fanout sets must agree too
            vers = {
                n: r["constraint_reference"]["version"]
                for n, r in res.items()
            }
            for n in others:
                assert vers[n] == vers["memory"], (step, n, vers)
            # fanout targets are implicit subscriptions whose ids are
            # per-store uuid4s: compare the (url, count) shape of the
            # fanout, not the ids themselves
            subs = {
                n: sorted(
                    (x["uss_base_url"], len(x["subscriptions"]))
                    for x in r["subscribers"]
                )
                for n, r in res.items()
            }
            for n in others:
                assert subs[n] == subs["memory"], (step, n, subs)
            for n, r in res.items():
                cst_versions[n][sid] = r["constraint_reference"]["version"]
                cst_ovns[n][sid] = r["constraint_reference"]["ovn"]
        elif op == 11:
            for m in cst_versions.values():
                m.pop(sid, None)
            for m in cst_ovns.values():
                m.pop(sid, None)
        elif op == 12:
            ids = {
                n: sorted(
                    c["id"] for c in r["constraint_references"]
                )
                for n, r in res.items()
            }
            for n in others:
                assert ids[n] == ids["memory"], (step, n, ids)

        if step % 6 == 5:
            # force folds mid-sequence so later queries cross the tier
            # split (tiered) and the rebuilt snapshot (flat) — the
            # overlay-only easy path must not be all the fuzz sees.
            # Every other round is a forced MAJOR compaction: cached
            # entries must survive the full L0 rebuild untouched (the
            # cell clock lives on the table, not in the snapshots).
            major = (step // 6) % 2 == 1
            for n in stores:
                for t in _index_tables(stores[n]):
                    if major:
                        t.compact()
                    else:
                        t.fold()
            max_tiers = max(
                max_tiers,
                max(
                    t.stats()["tier_count"]
                    for t in _index_tables(stores["tpu"])
                ),
            )

    # the tiered backend must actually have served from >= 2 tiers
    assert max_tiers >= 2, "fuzz never exercised the tier stack"
    # the CACHED stores must actually have served hits (quantized
    # areas repeat), or the differential proved nothing about the
    # fence; the uncached twins must never have consulted theirs
    for n in ("memory", "tpu"):
        assert stores[n].cache.stats()["hits"] > 0, (
            n, stores[n].cache.stats(),
        )
    for n in ("memory_nocache", "tpu_flat"):
        assert stores[n].cache.stats()["hits"] == 0
    # the push differential must actually have exercised the rqmatch
    # route (ISA writes occur in every seed's sequence), fan-out must
    # have enqueued without shedding, and the no-op transport must
    # have acked everything the writes produced
    tpu_stats = stores["tpu"].stats()
    assert tpu_stats["dss_dar_rid_sub_co_plan_rqmatch"] > 0
    assert push.drain(10.0)
    pst = push.stats()
    assert pst["dss_push_enqueued_total"] > 0
    assert pst["dss_push_dropped_total"] == 0
    assert pst["dss_push_acked_total"] == pst["dss_push_enqueued_total"]
    for s in stores.values():
        s.close()


@pytest.mark.parametrize("seed", [3, 11])
def test_fuzz_with_fault_schedule(seed, monkeypatch):
    """The fault-schedule dimension (ISSUE 11): a SEEDED FaultPlan is
    injected mid-sequence against the tpu store — device loss at the
    dispatch seam, dropped cache populations — while the memory store
    (uncached, deviceless: no instrumented seam fires there) runs as
    the no-fault oracle.  Every outcome must stay identical THROUGH
    the fault window (the coalescer absorbs device loss onto the host
    route; population failures degrade to misses), and after the plan
    clears and the degradation ladder walks back down, a full search
    sweep must be bit-identical to the oracle with zero acked-write
    loss (every write acked during the window is still served)."""
    chaos.clear_plan()
    chaos.registry().reset_counters()
    monkeypatch.setenv("DSS_CACHE_ENABLE", "1")
    monkeypatch.setenv("DSS_CACHE_CAP", "512")
    monkeypatch.setenv("DSS_TIER_RATIO", "5")
    tpu = DSSStore(storage="tpu")
    monkeypatch.setenv("DSS_CACHE_ENABLE", "0")
    mem = DSSStore(storage="memory")
    stores = {"memory": mem, "tpu": tpu}
    rid = {n: RIDService(s.rid, s.clock) for n, s in stores.items()}
    scd = {n: SCDService(s.scd, s.clock) for n, s in stores.items()}
    rng = np.random.default_rng(seed)
    isa_versions: dict = {n: {} for n in stores}
    op_ovns: dict = {n: {} for n in stores}
    cst_versions: dict = {n: {} for n in stores}
    acked_isas: set = set()  # ids acked DURING the fault window
    acked_csts: set = set()  # constraint ids acked DURING the window

    plan = chaos.FaultPlan.from_dict(
        {
            "seed": seed,
            "events": [
                # two device-loss episodes: the first mid-window hit,
                # another after a few more dispatch attempts
                {"site": "device.dispatch", "action": "device_lost",
                 "count": 2},
                {"site": "device.dispatch", "action": "device_lost",
                 "after": 5, "count": 2},
                # dropped cache populations (best-effort contract)
                {"site": "cache.populate", "action": "error",
                 "count": 3},
                # and a deterministic thinning of later populations
                {"site": "cache.populate", "action": "error",
                 "after": 3, "count": 4, "p": 0.5},
            ],
        }
    )

    try:
        for step in range(72):
            if step == 12:
                chaos.install_plan(plan)  # fault window opens
            if step == 56:
                # fault clearance + explicit recovery: the ladder
                # walks back down (re-warm runs before re-admission)
                chaos.clear_plan()
                tpu.health.exit("device_lost")
            in_window = 12 <= step < 56
            op = rng.integers(0, 8)
            sid = str(uuid.UUID(int=int(rng.integers(0, 24)), version=4))
            if op == 0:  # ISA create
                create_id = (
                    str(uuid.UUID(int=int(rng.integers(1000, 2000)),
                                  version=4))
                    if sid in isa_versions["memory"]
                    else sid
                )
                body = {
                    "extents": _extents(rng),
                    "flights_url": "https://u/f",
                }
                outs = {
                    n: _norm_outcome(
                        rid[n].create_isa, create_id, body, "u1"
                    )
                    for n in stores
                }
            elif op == 1:  # ISA delete
                outs = {
                    n: _norm_outcome(
                        rid[n].delete_isa, sid,
                        isa_versions[n].get(sid, "aaaaaaaaaa"), "u1",
                    )
                    for n in stores
                }
            elif op in (2, 3):  # RID search (the device-route seam)
                area = _search_area(rng)
                outs = {
                    n: _norm_outcome(rid[n].search_isas, area)
                    for n in stores
                }
            elif op == 4:  # SCD op put
                ext = _extents(rng)
                body = {
                    "extents": [
                        {
                            "volume": {
                                "outline_polygon": ext[
                                    "spatial_volume"
                                ]["footprint"],
                                "altitude_lower": {
                                    "value": 50.0, "reference": "W84",
                                    "units": "M",
                                },
                                "altitude_upper": {
                                    "value": 200.0, "reference": "W84",
                                    "units": "M",
                                },
                            },
                            "time_start": {
                                "value": ext["time_start"],
                                "format": "RFC3339",
                            },
                            "time_end": {
                                "value": ext["time_end"],
                                "format": "RFC3339",
                            },
                        }
                    ],
                    "uss_base_url": "https://u.example",
                    "new_subscription": {
                        "uss_base_url": "https://u.example"
                    },
                    "state": "Accepted",
                    "old_version": 0,
                }
                outs = {
                    n: _norm_outcome(
                        scd[n].put_operation, sid,
                        dict(body, key=list(op_ovns[n].values())), "u1",
                    )
                    for n in stores
                }
            elif op == 6:  # constraint put (fifth class through the
                #            fault window: WAL + cache.populate seams)
                body = _cst_put_body(_extents(rng))
                outs = {
                    n: _norm_outcome(
                        scd[n].put_constraint, sid,
                        dict(
                            body,
                            old_version=cst_versions[n].get(sid, 0),
                        ),
                        "u1",
                    )
                    for n in stores
                }
            elif op == 7:  # constraint query
                aoi = _cst_aoi(rng)
                outs = {
                    n: _norm_outcome(scd[n].query_constraints, aoi, "u1")
                    for n in stores
                }
            else:  # SCD search
                ext = _extents(rng)
                aoi = {
                    "area_of_interest": {
                        "volume": {
                            "outline_polygon": ext["spatial_volume"][
                                "footprint"
                            ],
                        },
                        "time_start": {
                            "value": ext["time_start"],
                            "format": "RFC3339",
                        },
                        "time_end": {
                            "value": ext["time_end"],
                            "format": "RFC3339",
                        },
                    }
                }
                outs = {
                    n: _norm_outcome(scd[n].search_operations, aoi, "u1")
                    for n in stores
                }

            mem_out = outs["memory"]
            assert mem_out[0] == outs["tpu"][0], (
                step, op, mem_out, outs["tpu"],
            )
            if mem_out[0] == "err":
                assert mem_out[1:] == outs["tpu"][1:], (step, op, outs)
                continue
            res = {n: o[1] for n, o in outs.items()}
            if op in (2, 3):
                ids = {
                    n: sorted(s["id"] for s in r["service_areas"])
                    for n, r in res.items()
                }
                assert ids["tpu"] == ids["memory"], (step, ids)
            elif op == 5:
                ids = {
                    n: sorted(
                        o["id"] for o in r["operation_references"]
                    )
                    for n, r in res.items()
                }
                assert ids["tpu"] == ids["memory"], (step, ids)
            elif op == 0:
                for n, r in res.items():
                    isa_versions[n][r["service_area"]["id"]] = r[
                        "service_area"
                    ]["version"]
                if in_window:
                    acked_isas.add(res["memory"]["service_area"]["id"])
            elif op == 1:
                for m in isa_versions.values():
                    m.pop(sid, None)
                acked_isas.discard(sid)
            elif op == 4:
                for n, r in res.items():
                    op_ovns[n][sid] = r["operation_reference"]["ovn"]
            elif op == 6:
                vers = {
                    n: r["constraint_reference"]["version"]
                    for n, r in res.items()
                }
                assert vers["tpu"] == vers["memory"], (step, vers)
                for n, r in res.items():
                    cst_versions[n][sid] = r["constraint_reference"][
                        "version"
                    ]
                if in_window:
                    acked_csts.add(sid)
            elif op == 7:
                ids = {
                    n: sorted(
                        c["id"] for c in r["constraint_references"]
                    )
                    for n, r in res.items()
                }
                assert ids["tpu"] == ids["memory"], (step, ids)

            if step % 8 == 7:
                # folds/compactions mid-window: recovery state must be
                # identical across the tier churn too
                major = (step // 8) % 2 == 1
                for n in stores:
                    for t in _index_tables(stores[n]):
                        if major:
                            t.compact()
                        else:
                            t.fold()

        # the schedule actually exercised both seams, and the absorbed
        # device losses never surfaced (all outcomes matched above)
        injected = chaos.registry().injected_by_site()
        assert injected.get("device.dispatch", 0) >= 1, injected
        assert injected.get("cache.populate", 0) >= 1, injected
        # recovery: ladder fully walked down
        assert tpu.health.mode() == chaos.HEALTHY

        # post-recovery sweep: bit-identical to the no-fault oracle
        # across every quantized poll area; zero acked-write loss (the
        # writes acked during the window are still served)
        seen_tpu: set = set()
        seen_cst_tpu: set = set()
        for i in range(6):
            for j in range(6):
                for h in (0.02, 0.045):
                    lat = BASE_LAT + 0.05 * i
                    lng = BASE_LNG + 0.05 * j
                    area = (
                        f"{lat},{lng},{lat + h},{lng},"
                        f"{lat + h},{lng + h},{lat},{lng + h}"
                    )
                    a = _norm_outcome(rid["memory"].search_isas, area)
                    b = _norm_outcome(rid["tpu"].search_isas, area)
                    assert a[0] == b[0] == "ok", (area, a, b)
                    am = sorted(
                        s["id"] for s in a[1]["service_areas"]
                    )
                    bm = sorted(
                        s["id"] for s in b[1]["service_areas"]
                    )
                    assert am == bm, (area, am, bm)
                    seen_tpu.update(bm)
                    # the fifth class sweeps the same grid: constraint
                    # answers must also be bit-identical post-recovery
                    aoi = _cst_aoi_at(lat, lng, h)
                    ca = _norm_outcome(
                        scd["memory"].query_constraints, aoi, "u1"
                    )
                    cb = _norm_outcome(
                        scd["tpu"].query_constraints, aoi, "u1"
                    )
                    assert ca[0] == cb[0] == "ok", (area, ca, cb)
                    cam = sorted(
                        c["id"] for c in ca[1]["constraint_references"]
                    )
                    cbm = sorted(
                        c["id"] for c in cb[1]["constraint_references"]
                    )
                    assert cam == cbm, (area, cam, cbm)
                    seen_cst_tpu.update(cbm)
        still_live = {
            i for i in acked_isas if i in isa_versions["memory"]
        }
        assert still_live <= seen_tpu, (
            "acked-write loss after recovery",
            still_live - seen_tpu,
        )
        still_live_csts = {
            i for i in acked_csts if i in cst_versions["memory"]
        }
        assert still_live_csts <= seen_cst_tpu, (
            "acked constraint loss after recovery",
            still_live_csts - seen_cst_tpu,
        )
    finally:
        chaos.clear_plan()
        chaos.registry().reset_counters()
        for s in stores.values():
            s.close()
