"""The subscription storm (scd-fanout-storm-125k.write-fanout) at the
rehearsal's size: that the cell is write-mixed's traffic on a city of
subscriptions and nothing else, that the generator's own count of the
subscribers a 200 names reads what the reference says, and that a 200
which drops one subscriber reads `correct: false`.  CPU only; one test
boots a server with --push and is `slow`:

    JAX_PLATFORMS=cpu python -m pytest dssbench/tests/test_fanout.py -q
"""

from __future__ import annotations

import json
import math
import os

import numpy as np
import pytest

from dssbench import check, deploy, run, traffic as tr
from dssbench.readers import subscribers as subs_reader

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(os.path.dirname(HERE), "testdata")
T_GEN = 1_800_000_000
CELL = "scd-fanout-storm-125k.write-fanout"
WRITE_CELL = "scd-write-mixed-125k.write-mixed"
FANOUT_METRICS = {
    "fanout_subscribers_mean", "fanout_match_ms_mean", "fanout_bump_ms_mean",
    "fanout_offer_ms_mean", "fanout_rqmatch_pct", "fanout_notified_per_write",
    "fanout_match_device_pct",
    "fanout_keyed_put_p50_ms", "fanout_write_service_ms_mean"}


def _json(name):
    with open(os.path.join(DATA, name)) as fh:
        return json.load(fh)


def tiny_storm() -> dict:
    """testdata/tiny-config.json with the storm's subscriptions at the
    deployment's density: 11,000 on 1,156 cells are 2,440 on 256."""
    config = _json("tiny-config.json")
    config["generator"]["classes"]["scd_sub"] = {"n": 2440, "cells": [2, 12]}
    return config


@pytest.fixture(scope="module")
def storm(tmp_path_factory):
    """(traffic, metro, reference, a window's requests, the stand-in's
    answers to them) on the tiny storm."""
    wal = tmp_path_factory.mktemp("storm") / "dss.wal"
    metro, ref = deploy.generate(11, tiny_storm()["generator"], T_GEN,
                                 str(wal))
    traffic = _json("tiny-traffic-write-mixed.json")
    reqs = tr.build(traffic, metro, ref, {}, np.random.default_rng([11, 1]),
                    T_GEN, 30, 10.0)
    return traffic, metro, ref, reqs


def _answered(storm):
    traffic, metro, ref, reqs = storm
    out = check.answers_of(traffic, reqs, metro, ref)
    written = deploy.Written()
    written.absorb(reqs, out)
    return out, written


def _drop_a_subscriber(out, own: bool = False) -> int:
    """Take one subscription (the flight's own, or the first other) out
    of the first 200 that names at least two.  -> the chain's index."""
    for k, chain in enumerate(out.chain):
        if not chain or chain[-1].status != 200:
            continue
        doc = json.loads(chain[-1].body)
        mine = doc["operation_reference"]["subscription_id"]
        named = [s for g in doc["subscribers"] for s in g["subscriptions"]]
        if len(named) < 2:
            continue
        for g in doc["subscribers"]:
            keep = [s for s in g["subscriptions"]
                    if (s["subscription_id"] == mine) is not own]
            if len(keep) < len(g["subscriptions"]):
                g["subscriptions"] = keep if own else g["subscriptions"][1:]
                chain[-1].body = out.body[k] = json.dumps(doc).encode()
                return k
    raise AssertionError("no 200 named two subscribers")


def drop_subscriber(requests, out, written=None) -> None:
    """The fault the rehearsal plants (in `run.alter_answer`'s place)."""
    _drop_a_subscriber(out)


# ---------------------------------------------------------------------------
# the files: write-mixed's traffic on a city of subscriptions
# ---------------------------------------------------------------------------


def test_the_storm_is_write_mixed_but_for_the_subscription_leg():
    _, config, traffic = run.load_cell(CELL)
    _, mixed_config, mixed = run.load_cell(WRITE_CELL)
    for key in ("components", "warmup", "connections_per_worker", "arrivals"):
        assert traffic[key] == mixed[key], key
    assert traffic["components"] == _json(
        "tiny-traffic-write-mixed.json")["components"]
    gen, theirs = config["generator"], mixed_config["generator"]
    assert gen["classes"]["scd_sub"] == {"n": 11000, "cells": [2, 12]}
    assert dict(gen, classes=0) == dict(theirs, classes=0)
    assert {k: v for k, v in gen["classes"].items() if k != "scd_sub"} == {
        k: v for k, v in theirs["classes"].items() if k != "scd_sub"}
    assert config["server"]["flags"] == ["--enable_scd", "--wal_fsync",
                                         "--push"]
    assert dict(config["server"], flags=0) == dict(mixed_config["server"],
                                                   flags=0)
    assert not any(k.startswith("DSS_PUSH") for k in config["server"]["env"])
    assert set(mixed_config["guarantees"]) | {"subscribers"} == set(
        config["guarantees"])
    # the quota's cut stands as a number beside the source's
    assert config["subscriptions_per_owner_cell"] < config["published"][
        "subscriptions_per_owner_cell"] == 10
    if traffic["knee_rps"]:
        assert traffic["rate_rps"] in {
            math.floor(f * traffic["knee_rps"]) for f in (0.4, 0.25, 0.125)}
        assert traffic["ladder_rps"][0] == 20.0


def test_the_cell_reports_the_flights_median_and_its_own_per_layer_metrics():
    with open(os.path.join(deploy.REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    assert [m["name"] for m in run.end_to_end_readers(bench, CELL)] == [
        "search_p50_ms", "search_p95_ms", "goodput_rps", "setup_s",
        "write_p50_ms"]
    layer = {m["name"]: m for m in run.load_metrics(CELL)}
    assert FANOUT_METRICS <= set(layer)
    assert all(layer[n]["workloads"] == [CELL] and
               layer[n]["moves"] == "write_p50_ms" for n in FANOUT_METRICS)
    # the metrics that list no cell read here as everywhere
    assert {"device_idle_pct", "compiles_in_window", "device_route_pct",
            "replay_s", "aot_warm_s", "http_host_ms_mean", "gen_late_p95_ms",
            "tail_p90_ms", "tail_p99_ms"} <= set(layer)
    # and nothing of this cell's is read in the cell it is held against
    assert not FANOUT_METRICS & {m["name"]
                                 for m in run.load_metrics(WRITE_CELL)}


# ---------------------------------------------------------------------------
# the generator's own count, and a 200 that leaves a subscriber out
# ---------------------------------------------------------------------------


def test_the_generator_counts_what_the_reference_says(storm):
    traffic, metro, ref, reqs = storm
    out, written = _answered(storm)
    chains = [(r, c) for r, c in zip(reqs, out.chain)
              if c and c[-1].status == 200]
    assert len(chains) >= 100
    static = [len(ref["scd_sub"].search(metro.rect_flat(*r.rect),
                                        now=T_GEN * deploy.NS))
              for r, _ in chains]
    named = [len(check.notified(c[-1].body)[0]) for _, c in chains]
    got = subs_reader.read({"out": out})
    assert got == pytest.approx(np.mean(named))
    # the WAL's subscriptions, the flight's own, and those of earlier
    # flights of the run in its cells on top
    assert all(n >= s + 1 for n, s in zip(named, static))
    assert np.mean(static) + 1 <= got <= np.mean(static) + 1 + len(chains)
    assert np.mean(static) > 20  # a storm, not write-mixed's ~5
    # no chain ended 200, or no chain at all: nothing to read, never 0
    reads = [r for r in reqs if r.kind == "search"]
    assert subs_reader.read({"out": tr.Outcome(
        None, None, np.full(len(reads), 200), [])}) is None
    with pytest.raises(ValueError):
        subs_reader.read({"out": out}, "median")


@pytest.mark.parametrize("own", [False, True], ids=["another", "its_own"])
def test_a_200_that_drops_a_subscriber_is_wrong(storm, own):
    traffic, metro, ref, reqs = storm
    out, written = _answered(storm)
    sound = check.compare(traffic, reqs, out, metro, ref, written)
    assert sound["numbers"] == {"wrong_answers": 0, "never_answered": 0}
    k = _drop_a_subscriber(out, own)
    cmp = check.compare(traffic, reqs, out, metro, ref, written)
    assert cmp["numbers"]["wrong_answers"] == 1 and not cmp["good"][k]
    assert "subscribers" in cmp["first_wrong"]
    assert check.verdict(cmp["numbers"], cmp["facts"]["compared"])[0] is False


# ---------------------------------------------------------------------------
# the whole cell command, on the CPU rehearsal, with --push
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["", "drop_subscriber"])
def test_the_storm_runs_whole_on_the_cpu_rehearsal(fault, monkeypatch):
    """Boot with --push, warm-up with writes in it, window, comparison
    of every 200's subscribers, and the readers of every per-layer
    metric that needs no trace; with one subscriber taken out of one
    200 on its way out, `correct` comes out false."""
    bench, cell_config, _ = run.load_cell(CELL)
    config = tiny_storm()
    config["server"].update({k: cell_config["server"][k]
                             for k in ("flags", "auth")})
    assert "--push" in config["server"]["flags"]
    traffic = _json("tiny-traffic-write-mixed.json")
    layer = [m for m in run.load_metrics(CELL) if m["name"] in FANOUT_METRICS]
    # a fault of the test's own, planted where run.py plants its own
    monkeypatch.setattr(run, "alter_answer", drop_subscriber)
    result, facts = run.run_cell(
        CELL, 2**31 + 11, 8.0, False, config=config, traffic=traffic,
        metrics=[], end_to_end=run.end_to_end_readers(bench, CELL) + layer,
        platform="cpu", fault="alter_answer" if fault else "")
    assert result["attempted"] == 240
    assert result["correct"] is (fault == ""), facts["first_wrong"]
    if fault:
        assert result["checks"]["wrong_answers"]["value"] == 1
        assert "subscribers" in facts["first_wrong"]
        return
    assert result["failed"] == 0 and facts["refused"] == 0
    assert facts["chains"]["ended"] == {"200": 120}
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert {"search_p50_ms", "search_p95_ms", "goodput_rps", "setup_s",
            "write_p50_ms"} | FANOUT_METRICS <= set(got)
    assert got["fanout_subscribers_mean"] > 20
    assert got["fanout_notified_per_write"] == pytest.approx(
        got["fanout_subscribers_mean"], rel=0.01)
    # --push: the planner names the device route for every match, and
    # the table under it answers a flight's ~1,000 candidate postings
    # from its host copy: no match launches the kernel
    assert got["fanout_rqmatch_pct"] == 100.0
    assert got["fanout_match_device_pct"] == 0.0
    assert got["fanout_bump_ms_mean"] > 0 and got["fanout_match_ms_mean"] > 0
