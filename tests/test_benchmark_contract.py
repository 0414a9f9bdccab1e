"""What the benchmark reads, the program still writes.

`dssbench/metrics/*.json` name counter and gauge families on `/metrics`
and lines of the leader's boot log; a PR that deletes one of them used
to find out from a `null` in the ledger a day later.  One case per
metric file whose reader is `scrape_ratio`, `scrape_rate`, `stage_mean`,
`stage_cover` or `bootlog`, against one boot of the served topology on
the CPU backend (with `--push` and tokens, as the write cells'
deployments), started
and awaited by the harness's own code (`dssbench.run.start_server`,
`dssbench.deploy.wait_ready`: the leader's "resident AOT warm:" line is
part of the contract, the harness sends nothing before it) and scraped
by its parser.  Every family pattern the file names has to match a key
of the process it names (`leader`: the device owner's loopback port;
`front`: the public port), by the rule of `readers/scrape_ratio.delta`;
a `stage_mean` file's (route, stage) row has to be there, which the
program exports once it has observed it: the boot has answered
searches and planned flights, each flight's 200 a notifying write; a
`bootlog` file is handed to its reader and has to read a number.
"""

from __future__ import annotations

import asyncio
import fnmatch
import glob
import json
import os
import time
from unittest import mock

import numpy as np
import pytest

from dssbench import deploy, run, traffic as tr
from dssbench.readers import bootlog

READERS = ("scrape_ratio", "scrape_rate", "stage_mean", "stage_cover",
           "bootlog")


def _metric_files() -> list:
    out = []
    for path in sorted(glob.glob(
            os.path.join(deploy.REPO, "dssbench", "metrics", "*.json"))):
        with open(path) as fh:
            m = json.load(fh)
        if m["reader"] in READERS:
            out.append(pytest.param(m, id=m["name"]))
    return out


# 16 x 16 cells at the rehearsal's footprints, a tenth of its records:
# boots in ~10 s; the AOT grid pinned to one small bucket, as the
# benchmark's configurations pin theirs
CONFIG = {
    "generator": {
        "grid": 16, "owners": 8, "strata": 60, "stratum_m": 50,
        "classes": {
            "op": {"n": 3000, "cells": [2, 12]},
            "isa": {"n": 40, "cells": [4, 12]},
            "rid_sub": {"n": 10, "cells": [4, 12]},
            "scd_sub": {"n": 10, "cells": [4, 12]},
        },
    },
    # the write cells' server: every caller a USS with a token (one
    # anonymous writer would meet the quota of 10 subscriptions a cell),
    # and the push pipeline attached, no webhook registered
    "server": {
        "flags": ["--enable_scd", "--push"], "workers": 2,
        "env": {"DSS_RES_BATCH_BUCKETS": "16",
                "DSS_RES_WINDOW_BUCKETS": "1024"},
        "auth": {"owners": 8, "audience": "localhost", "ttl_s": 3000,
                 "scope": "utm.strategic_coordination "
                          "dss.read.identification_service_areas"},
    },
}
# a RID poll, a small op-intent check and a planned flight (PUT, 409 ->
# key -> 200) in turn: every stage of a search through the ring and of
# a notifying write through the ring's write lane is observed; none
# reaches the device route but the write's match (rqmatch)
TRAFFIC = {"components": [
    {"share": 0.4, "endpoint": "rid_search",
     "w_cells": [1, 2], "h_cells": [1, 2]},
    {"share": 0.4, "endpoint": "scd_query",
     "w_cells": [2, 3], "h_cells": [2, 3],
     "alt_band_m": 60, "alt_ceiling_m": 2900, "timed_every": 2,
     "opens_in_s": [7200, 14400], "lasts_s": [900, 3600]},
    {"share": 0.2, "endpoint": "scd_put",
     "w_cells": [1, 2], "h_cells": [1, 2],
     "alt_band_m": 40, "alt_ceiling_m": 2900,
     "opens_in_s": [7200, 14400], "lasts_s": [900, 3600]},
]}
BOOT_TIMEOUT_S = 300


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """{'leader': keys, 'front': keys, 'bootlog': records} of one boot
    that has answered 16 searches and 4 planned flights."""
    work = str(tmp_path_factory.mktemp("served"))
    wal = os.path.join(work, "dss.wal")
    t_gen = int(time.time())
    metro, ref = deploy.generate(5, CONFIG["generator"], t_gen, wal)
    cores = sorted(os.sched_getaffinity(0))
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    with mock.patch.dict(os.environ, env, clear=True):
        # conftest's 8 virtual devices are the tests', not the server's
        srv = run.start_server(CONFIG, wal, work, "cpu", False, cores, cores)
    try:
        deploy.wait_ready(srv, CONFIG["server"]["workers"], BOOT_TIMEOUT_S)
        tr.TOKENS[:] = srv.tokens
        requests = tr.build(TRAFFIC, metro, ref, {},
                            np.random.default_rng(1), t_gen, 10, 2.0)

        async def ask():
            # as the harness's own prefill lanes: connections as they
            # come (a worker may still be binding; the merged families
            # do not care which one answered)
            client = tr.Client(srv.port)
            out = await tr.prefill(client, requests, 2)
            await client.close()
            return out

        out = asyncio.run(ask())
        got = run.scrape_all(srv)
        got["bootlog"], _ = srv.log_records()
        got["ended"] = [int(c[-1].status) for c in out.chain if c]
        got["statuses"] = [int(st) for st in out.status]
    finally:
        tr.TOKENS.clear()
        srv.stop()
    return got


def test_the_boot_answered_searches_and_notifying_writes(served):
    """What the cases below rest on: every request answered, every
    planned flight accepted (its 200 names at least its own implicit
    subscription, so each is a notifying write on the rqmatch route)."""
    assert served["statuses"] == [200] * 20
    assert served["ended"] == [200] * 4
    leader = served["leader"]
    assert leader["dss_scd_notifying_writes_total"] == 4
    assert leader["dss_scd_subscribers_notified_total"] >= 4
    assert leader["dss_push_offers_total"] == 4
    assert leader["dss_dar_scd_sub_co_plan_rqmatch"] == 4
    # every PUT rode the ring to the owner's write lane
    assert served["front"]["dss_shm_write_served_total"] >= 4
    assert served["front"]["dss_shm_worker_write_proxied"] == 0


def _allocator_peak(monkeypatch) -> bool:
    """`dss_device_peak_bytes_in_use`: the CPU's allocator reports
    nothing, so no CPU boot exports it; checked where it is made
    (`dss_tpu.ops.device_memory_stats`, which `DSSStore.stats` merges
    into every scrape) with a device that reports as the chip does."""
    import jax

    from dss_tpu import ops

    class Chip:
        def memory_stats(self):
            return {"bytes_in_use": 3, "peak_bytes_in_use": 7}

    monkeypatch.setattr(jax, "local_devices", lambda: [Chip()])
    return ops.device_memory_stats().get("dss_device_peak_bytes_in_use") == 7


# families that no CPU boot can export, and where each is checked instead
ELSEWHERE = {"dss_device_peak_bytes_in_use": _allocator_peak}


def _match_without_a_pipeline(monkeypatch) -> bool:
    """Stage `sub_match_ms`: a notifying write's match through the
    subscription index, which only a store WITHOUT `--push` runs
    (`write-mixed`'s deployment; this boot has the pipeline, whose
    match marks `push_match_ms`).  Checked where it is marked."""
    import numpy as np

    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.obs import stages
    from dss_tpu.obs.metrics import stage_name

    store = DSSStore(storage="memory")
    sink = {}
    stages.set_sink(sink)
    try:
        store.scd._notify_subs_locked(np.asarray([1], np.uint64))
    finally:
        stages.set_sink(None)
        store.close()
    return "sub_match_ms" in sink and stage_name("sub_match_ms") != "other"


def _exec_hops_without_the_ring(monkeypatch) -> bool:
    """Stage `exec_wait_ms` of route `write`: the executor's two hops
    around a PUT's service call, which a single-process server (and a
    PUT that took the loopback proxy) pays; behind this boot's front a
    PUT rides the ring to the owner's write lane, which has no
    executor.  Checked on an app over a store of its own."""
    import requests

    from dss_tpu.api.app import build_app
    from dss_tpu.clock import Clock
    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.services.scd import SCDService
    from tests.live_server import LiveServer
    from tests.test_write_stages import OP1, _flight, _stages_of

    store = DSSStore(storage="memory")
    srv = LiveServer(build_app(None, SCDService(store.scd, Clock()), None,
                               enable_scd=True, trace_requests=True))
    try:
        r = requests.put(f"{srv.base}/dss/v1/operation_references/{OP1}",
                         json=_flight(), timeout=30)
        return r.status_code == 200 and "exec_wait_ms" in _stages_of(r)
    finally:
        srv.stop()
        store.close()


def _affected_on_a_subscription_put(monkeypatch) -> bool:
    """Stage `sub_affected_ms`: a subscription PUT's closing search for
    the operations its volume meets (`upsert_subscription`, route class
    `write`).  A planned flight makes its implicit subscription in one
    transaction with the op and runs no such search, so the flights
    this boot files never mark it.  Checked where it is marked."""
    import numpy as np
    from datetime import datetime, timedelta, timezone

    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.geo import s2cell
    from dss_tpu.models import scd as scdm
    from dss_tpu.obs import stages
    from dss_tpu.obs.metrics import stage_name

    store = DSSStore(storage="memory")
    t0 = datetime.now(timezone.utc)
    cell = s2cell.cell_parent(s2cell.cell_id_from_latlng(40.0, -100.0, 30), 13)
    sink = {}
    stages.set_sink(sink)
    try:
        store.scd.upsert_subscription(scdm.Subscription(
            id="00000000-0000-4000-8000-000000000001", owner="uss1",
            start_time=t0, end_time=t0 + timedelta(hours=1),
            cells=np.asarray([cell], np.uint64)))
    finally:
        stages.set_sink(None)
        store.close()
    return "sub_affected_ms" in sink and stage_name(
        "sub_affected_ms") != "other"


# stages this boot's deployment never runs, and where each is checked
STAGES_ELSEWHERE = {"sub_match_ms": _match_without_a_pipeline,
                    "exec_wait_ms": _exec_hops_without_the_ring,
                    "sub_affected_ms": _affected_on_a_subscription_put}


@pytest.mark.parametrize("metric", _metric_files())
def test_the_program_exports_what_the_metric_reads(metric, served,
                                                   monkeypatch):
    name, args = metric["name"], metric["args"]
    if metric["reader"] == "bootlog":
        assert bootlog.read({"bootlog": served["bootlog"]}, **args) is not None, (
            f"dssbench/metrics/{name}.json reads the leader's boot log "
            f"for {args}: no such line was logged"
        )
        return
    keys = served[args.get("proc", "front")]
    if metric["reader"] in ("stage_mean", "stage_cover"):
        # a stage_cover file reads the sums of its whole and of every
        # leaf: a leaf that is gone would read as time gone dark
        stages = ([args["stage"]] if metric["reader"] == "stage_mean"
                  else [args["whole"]] + args["leaves"])
        for stage in stages:
            if stage in STAGES_ELSEWHERE:
                assert STAGES_ELSEWHERE[stage](monkeypatch), stage
                continue
            for fam in ("sum", "count"):
                row = (f'dss_stage_duration_seconds_{fam}{{route="'
                       f'{args["route"]}",stage="{stage}"}}')
                assert keys.get(row, 0) > 0, (
                    f"dssbench/metrics/{name}.json reads {row} from the "
                    "front's /metrics: no such row was observed")
        return
    for pat in args.get("num", []) + args.get("den", []) + args.get(
            "names", []):
        found = pat in keys or (
            any(c in pat for c in "*?[") and fnmatch.filter(keys, pat))
        if not found and pat in ELSEWHERE:
            found = ELSEWHERE[pat](monkeypatch)
        assert found, (
            f"dssbench/metrics/{name}.json reads {pat} from the "
            f"{args['proc']}'s /metrics: the program exports no such family"
        )


@pytest.mark.parametrize("stage", ["store_ms", "serialize_ms"])
def test_a_search_is_still_timed_around_its_store_and_its_encoder(
        stage, served):
    """`http_host_ms_mean` reads `serialize_ms`, and PERF.md's
    breakdowns `store_ms` less the ring: both stages stay around what
    they named when a search answer became a join of remembered bytes
    (the records found; the body made), observed on every search."""
    fam = "dss_stage_duration_seconds_count"
    n = served["front"].get(f'{fam}{{route="search",stage="{stage}"}}', 0)
    assert n > 0 and n == served["front"].get(
        f'{fam}{{route="search",stage="service_ms"}}'
    ), (stage, n)
