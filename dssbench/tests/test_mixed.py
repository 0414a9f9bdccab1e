"""The planning mix (scd-dense-urban-125k.query-mixed) at the
rehearsal's size: testdata/tiny-traffic-mixed.json holds the cell's
three components unchanged, over tiny-config.json's 16 x 16 metro at
the flagship's density.  CPU only, boots nothing:

    JAX_PLATFORMS=cpu python -m pytest dssbench/tests/test_mixed.py -q
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from dssbench import check, deploy, run, traffic as tr
from dssbench.readers import population

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(os.path.dirname(HERE), "testdata")
T_GEN = 1_800_000_000
CELL = "scd-dense-urban-125k.query-mixed"


def _json(name):
    with open(os.path.join(DATA, name)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    config, traffic = _json("tiny-config.json"), _json(
        "tiny-traffic-mixed.json")
    wal = tmp_path_factory.mktemp("wal") / "dss.wal"
    metro, ref = deploy.generate(7, config["generator"], T_GEN, str(wal))
    return traffic, metro, ref


def _build(tiny, seed, rate=30, seconds=20):
    traffic, metro, ref = tiny
    return tr.build(traffic, metro, ref, {},
                    np.random.default_rng([seed, 1]), T_GEN, rate, seconds)


def test_the_rehearsal_holds_the_cells_own_components():
    _, _, traffic = run.load_cell(CELL)
    assert _json("tiny-traffic-mixed.json")["components"] == traffic[
        "components"]
    assert [c["share"] for c in traffic["components"]] == [0.70, 0.18, 0.12]
    assert traffic["rate_rps"] <= 466  # the wide share alone stays <= 56
    # every metric the cell lists, and every one without a list, has a
    # reader the harness can import
    names = {m["name"] for m in run.load_metrics(CELL)}
    assert {"small_p50_ms", "small_p95_ms", "district_p50_ms",
            "wide_p50_ms", "host_scan_ms_mean", "host_scan_candidates_mean",
            "queued_pct", "dragged_small_pct", "mixed_drain_pct",
            "serve_host_ms_mean", "serve_device_ms_mean",
            "device_idle_pct", "compiles_in_window", "device_route_pct",
            "http_host_ms_mean", "gen_late_p95_ms", "tail_p90_ms",
            "tail_p99_ms", "aot_warm_s", "replay_s"} <= names
    assert "shm_ring_ms_mean" not in names  # query-wide's, by its list


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_build_deals_the_three_populations_in_their_shares(tiny, seed):
    from dss_tpu.ops.fastpath import FastTable

    traffic, metro, ref = tiny
    reqs = _build(tiny, seed)
    assert len(reqs) == 600
    which = np.array([r.comp for r in reqs])
    assert [int((which == c).sum()) for c in range(3)] == [420, 108, 72]
    # the same multiset of sides and of timed requests on every seed
    def shapes(rs):
        return (sorted((r.comp, r.rect[2], r.rect[3]) for r in rs),
                sorted((r.comp, r.when is None) for r in rs))

    assert shapes(reqs) == shapes(_build(tiny, 99))
    cap = FastTable.HOST_MAX_CANDIDATES
    for r in reqs:
        comp = traffic["components"][r.comp]
        n = ref["op"].candidates(metro.rect_flat(*r.rect))
        assert comp.get("min_candidates", 0) <= n
        assert n <= comp.get("max_candidates", n)
        # small and district under the host scan's cap, wide 1.25 x over
        assert (n <= cap) if r.comp < 2 else (n >= 1.25 * cap)
        assert comp["w_cells"][0] <= r.rect[2] <= comp["w_cells"][1]
        assert r.alt[1] - r.alt[0] == comp["alt_band_m"]
    # no pool: every volume is its own, so no cache can answer
    assert len({(r.rect, r.alt, r.when) for r in reqs}) == len(reqs)


def test_population_reader_agrees_with_the_facts_line(tiny):
    traffic, _, _ = tiny
    reqs = _build(tiny, 3)
    rng = np.random.default_rng(3)
    n = len(reqs)
    due = tr.due_times(reqs)
    took = rng.gamma(2.0, 0.004, n) * (1 + np.array([r.comp for r in reqs]))
    out = tr.Outcome(due.copy(), due + took, np.full(n, 200, np.int32),
                     [b"{}"] * n)
    good = np.ones(n, bool)
    good[rng.integers(0, n, 5)] = False  # wrong answers: the deadline
    out.done[rng.integers(0, n, 3)] = np.nan  # never answered
    ctx = {"requests": reqs, "out": out, "good": good, "traffic": traffic}
    # run.run_cell's own arithmetic for facts.latency_ms_by_component
    lat = tr.latencies_ms(reqs, out, good)
    which = np.array([r.comp for r in reqs])
    for c in range(3):
        for q in (50, 95):
            assert population.read(ctx, component=c, q=q) == tr.percentile(
                lat[which == c], q)
    assert population.read(ctx, component=0) == tr.percentile(
        lat[which == 0], 50)
    # medians in the populations' order, as the fake latencies were made
    assert (population.read(ctx, 0, 50) < population.read(ctx, 1, 50)
            < population.read(ctx, 2, 50))
    assert population.read(ctx, component=3, q=50) is None  # no such one


@pytest.mark.parametrize("served_by", ["sound", *check.CONTROLS])
def test_controls_on_the_mixed_traffic(tiny, served_by):
    traffic, metro, ref = tiny
    reqs = _build(tiny, 7)
    served = ref if served_by == "sound" else check.CONTROLS[served_by](ref)
    correct, checks = check.judge_control(traffic, reqs, metro, ref, served)
    sound = served_by not in check.controls_for(traffic)
    assert correct is sound
    assert checks["answers_compared"]["value"] == len(reqs)
    assert (checks["wrong_answers"]["value"] > 0) is (not sound)


# ---------------------------------------------------------------------------
# the same mix on the VLL deployment (scd-vll-delivery-125k.query-mixed-vll)
# ---------------------------------------------------------------------------

VLL = "scd-vll-delivery-125k.query-mixed-vll"
SLOT_IDS = 32768 // 46  # a ring slot's answer: 8 B end time + 2 + 36 B id


@pytest.fixture(scope="module")
def tiny_vll(tmp_path_factory):
    """tiny-config.json's metro with the VLL deployment's altitudes,
    under the VLL cell's own components."""
    _, vll_config, traffic = run.load_cell(VLL)
    config = _json("tiny-config.json")
    for key in ("strata", "stratum_m"):
        config["generator"][key] = vll_config["generator"][key]
    wal = tmp_path_factory.mktemp("wal") / "dss.wal"
    metro, ref = deploy.generate(7, config["generator"], T_GEN, str(wal))
    return traffic, metro, ref


def test_the_vll_deployment_differs_by_its_altitudes_alone():
    _, dense, mixed = run.load_cell(CELL)
    bench, vll, traffic = run.load_cell(VLL)
    gen = dict(vll["generator"], strata=dense["generator"]["strata"],
               stratum_m=dense["generator"]["stratum_m"])
    assert gen == dense["generator"]
    # the server: the same command and grid, the ring's slot sized to
    # the answers (a slot of the default size holds SLOT_IDS ids)
    env = dict(vll["server"]["env"])
    assert int(env.pop("DSS_SHM_SLOT_BYTES")) // 46 > 20000
    assert dict(vll["server"], env=env) == dense["server"]
    # every op intent under the ceiling the file states
    g = vll["generator"]
    assert (g["strata"] - 1) * g["stratum_m"] + 20 + 45 <= vll[
        "vll_ceiling_m"]
    assert vll["flights_per_hour"] == round(g["classes"]["op"]["n"] / 12)
    entry = next(c for c in bench["configs"] if c["name"] == vll["name"])
    assert entry["source"] == vll["source"]
    assert entry["reduced"] == ["flights_per_hour"]
    # the mix's populations and shares, but for the band and the windows
    def shape(c):
        return {k: v for k, v in c.items()
                if k not in ("alt_band_m", "alt_ceiling_m", "timed_every",
                             "opens_in_s")}

    assert [shape(c) for c in traffic["components"]] == [
        shape(c) for c in mixed["components"]]
    # flights are checked 2-4 h ahead, districts and wide areas 8-9 h
    assert [c["opens_in_s"] for c in traffic["components"]] == [
        mixed["components"][0]["opens_in_s"], [28800, 32400], [28800, 32400]]
    for c in traffic["components"]:
        assert c["alt_ceiling_m"] + c["alt_band_m"] <= vll["vll_ceiling_m"]
    assert {m["name"] for m in run.load_metrics(VLL)} == {
        m["name"] for m in run.load_metrics(CELL)}


def test_vll_answers_outgrow_a_ring_slot(tiny, tiny_vll):
    """What the deployment is for: the same shapes, answers an order
    larger where the flights are, past what a default ring slot holds."""
    traffic, metro, ref = tiny_vll
    reqs = _build(tiny_vll, 5)
    assert sum(r.when is None for r in reqs) == 3  # one of each population
    now = T_GEN * deploy.NS
    ids = [[], [], []]
    for r in reqs:
        ids[r.comp].append(len(check.expected(
            r, traffic["components"][r.comp], metro, ref, now)))
    dense_traffic, dense_metro, dense_ref = tiny
    dense = [[], [], []]
    for r in _build(tiny, 5):
        dense[r.comp].append(len(check.expected(
            r, dense_traffic["components"][r.comp], dense_metro, dense_ref,
            now)))
    # flights' checks hold an order more ids; the day-ahead district
    # and wide checks, where few intents are filed yet, about as many
    assert np.mean(ids[0]) > 8 * np.mean(dense[0])
    assert np.mean(ids[1]) > np.mean(dense[1])
    assert np.mean(ids[2]) > np.mean(dense[2])
    # some of every population over what a slot of the default size
    # holds, and the untimed first ones far over
    over = [float(np.mean(np.array(v) > SLOT_IDS)) for v in ids]
    assert 0.02 < over[0] < 0.25 and over[2] > 0.05
    assert max(ids[2]) > 10 * SLOT_IDS


@pytest.mark.parametrize("served_by", ["sound", *check.CONTROLS])
def test_controls_on_the_vll_traffic(tiny_vll, served_by):
    traffic, metro, ref = tiny_vll
    reqs = _build(tiny_vll, 7, rate=10)
    served = ref if served_by == "sound" else check.CONTROLS[served_by](ref)
    correct, checks = check.judge_control(traffic, reqs, metro, ref, served)
    sound = served_by not in check.controls_for(traffic)
    assert correct is sound
    assert (checks["wrong_answers"]["value"] > 0) is (not sound)
