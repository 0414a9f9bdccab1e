"""scd-dense-urban-1M and its cell: the flagship at its published size.

The configuration is scd-dense-urban-125k's with two numbers of the
generator changed, and the cell's traffic is query-mixed's, so that the
pair of cells reads what the table's size alone costs.  These tests
hold the files to that; nothing is generated or booted here (the log of
the deployment is 475 MiB).
"""

from __future__ import annotations

import json
import math
import os

import pytest

from dssbench import deploy, run
from dssbench.readers import bootlog

CELL = "scd-dense-urban-1M.query-mixed-1M"
SMALL = "scd-dense-urban-125k.query-mixed"
BOOT_METRICS = ("boot_parse_s", "boot_build_s", "worker_boot_s",
                "boot_records_per_s")


def test_the_configuration_is_the_125k_one_at_the_published_size():
    bench, big, _ = run.load_cell(CELL)
    _, small, _ = run.load_cell(SMALL)
    gen = json.loads(json.dumps(big["generator"]))
    assert gen["grid"] == 96 and gen["classes"]["op"]["n"] == 1_000_000
    gen["grid"] = small["generator"]["grid"]
    gen["classes"]["op"]["n"] = small["generator"]["classes"]["op"]["n"]
    assert gen == small["generator"]
    # the server: the same command, workers and pinned AOT grid; a boot
    # deadline short enough that a build which cannot boot the log in
    # bulk fails by the harness's own BenchFailure inside the run's limit
    assert dict(big["server"], boot_timeout_s=0) == dict(
        small["server"], boot_timeout_s=0)
    assert big["server"]["boot_timeout_s"] <= 300
    assert big["cores"] == small["cores"]
    # nothing is cut: the file's sizes are the published ones
    for key, val in big["published"].items():
        if key != "note":
            assert big[key] == val, key
    assert big["published"] == small["published"]
    entry = next(c for c in bench["configs"] if c["name"] == big["name"])
    assert entry["reduced"] == [] and entry["source"] == big["source"]
    records = sum(c["n"] for c in big["generator"]["classes"].values())
    assert f"{records:,} records" in big["guarantees"]["reads"]
    assert {k: v for k, v in big["guarantees"].items() if k != "reads"} == {
        k: v for k, v in small["guarantees"].items() if k != "reads"}


def test_the_traffic_is_query_mixed_key_for_key():
    _, _, big = run.load_cell(CELL)
    _, _, small = run.load_cell(SMALL)
    for key in ("components", "warmup", "connections_per_worker",
                "arrivals"):
        assert big[key] == small[key], key
    # its own knee, from its own climb; the rate is the quarter, and
    # never above query-mixed's, so that where the knee allows the two
    # cells offer the same requests at the same rate
    assert big["knee_rps"] in big["ladder_rps"]
    assert big["rate_rps"] == min(small["rate_rps"],
                                  math.floor(0.25 * big["knee_rps"]))
    assert big["sweep"]["device"]["platform"] == "tpu"


def test_the_cell_reads_every_list_less_metric_and_its_four_boot_metrics():
    mine = {m["name"]: m for m in run.load_metrics(CELL)}
    for name in BOOT_METRICS:
        assert mine[name]["workloads"] == [CELL]
        assert mine[name]["layer"] == "boot"
        assert mine[name]["moves"] == "setup_s"
        assert mine[name]["reader"] == "bootlog"
    rest = {n for n in mine if n not in BOOT_METRICS}
    assert rest and all("workloads" not in mine[n] for n in rest)
    assert {"replay_s", "aot_warm_s", "device_idle_pct",
            "compiles_in_window"} <= rest
    # and no other cell reads the four
    assert not set(BOOT_METRICS) & {
        m["name"] for m in run.load_metrics(SMALL)}


# the boot log of a --workers 2 server as cmds/server.py writes it (the
# lines are held against a real boot in tests/test_benchmark_contract.py)
BOOTLOG = [
    {"ts": 10.0, "logger": "dss.server", "msg": "backend: {...}"},
    {"ts": 51.5, "logger": "dss.server", "msg":
     "boot parse: 1001000 records read, decoded and resolved in 24.25 s"},
    {"ts": 51.5, "logger": "dss.server", "msg":
     "boot build: 6512345 postings in tables of 156500000 bytes on the "
     "device, built and uploaded in 16.50 s (24564 records/s over both "
     "stages)"},
    {"ts": 51.6, "logger": "dss.server", "msg": "store ready: storage=tpu"},
    {"ts": 95.0, "logger": "dss.worker", "msg":
     "worker replica ready: 1001000 records in 41.02 s (bulk)"},
    {"ts": 96.0, "logger": "dss.worker", "msg":
     "worker replica ready: 1001000 records in 42.10 s (bulk)"},
]


@pytest.mark.parametrize("name,value", [
    ("boot_parse_s", 24.25), ("boot_build_s", 16.5),
    ("worker_boot_s", 41.02), ("boot_records_per_s", 24564.0),
])
def test_a_boot_metric_reads_its_line(name, value):
    with open(os.path.join(deploy.REPO, "dssbench", "metrics",
                           name + ".json")) as fh:
        metric = json.load(fh)
    assert bootlog.read({"bootlog": BOOTLOG}, **metric["args"]) == value
    # a build without the line (the parent commit): nothing, no error
    old = [r for r in BOOTLOG if r["msg"].startswith(("backend", "store"))]
    assert bootlog.read({"bootlog": old}, **metric["args"]) is None
