"""Tests of the yardstick itself.  CPU only:

    JAX_PLATFORMS=cpu python -m pytest dssbench/tests -q            # ~20 s
    JAX_PLATFORMS=cpu python -m pytest dssbench/tests -q -m slow    # boots servers

The quick ones boot nothing.  The slow ones skip the harness's look
for a chip and drive the rest of a run on the CPU backend at the
rehearsal's size: a sound run reads correct, and a run with a fault
planted under it reads not correct.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from dssbench import check, deploy, run, schema, traffic as tr
from dssbench.readers import generator as gen_reader, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(os.path.dirname(HERE), "testdata")
T_GEN = 1_800_000_000


def _tiny():
    with open(os.path.join(DATA, "tiny-config.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(DATA, "tiny-traffic.json")) as fh:
        traffic = json.load(fh)
    return config, traffic


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(deploy.REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def flagship(tmp_path_factory):
    """The flagship deployment's data at its own size, from one seed."""
    _, config, _ = run.load_cell("scd-dense-urban-125k.query-wide")
    wal = tmp_path_factory.mktemp("wal") / "dss.wal"
    metro, ref = deploy.generate(11, config["generator"], T_GEN, str(wal))
    return metro, ref


def test_benchmark_json_and_its_files_pass_the_schema(bench):
    assert schema.problems(bench) == []
    for w in bench["workloads"]:
        _, config, traffic = run.load_cell(w["name"])
        assert config["generator"]["classes"]["op"]["n"] == config["op_intents"]
        assert config["generator"]["grid"] ** 2 == config["metro_cells"]
        assert traffic["rate_rps"] >= 1
        assert run.load_metrics(w["name"])
        assert run.end_to_end_readers(bench, w["name"])


@pytest.mark.parametrize("bad", [
    {"name": "has space"}, {"unit": "tokens per second"},
    {"better": "faster"}, {"source": "guess"}, {"moves": "nothing"},
])
def test_schema_refuses(bench, bad):
    doc = json.loads(json.dumps(bench))
    doc["per_layer"][0].update(bad)
    assert schema.problems(doc)


def test_a_new_cell_metric_and_traffic_are_new_files_only(bench, tmp_path):
    """What README.md's worked example does: nothing that exists is
    edited, the harness finds the new files by name."""
    (tmp_path / "x.json").write_text(json.dumps({
        "name": "x", "layer": "shm front", "unit": "ms", "better": "lower",
        "source": "program_counter", "moves": "search_p50_ms",
        "reader": "scrape_rate",
        "args": {"proc": "front", "names": ["dss_shm_served_total"]},
        "workloads": ["some.cell"]}))
    (tmp_path / "y.json").write_text(json.dumps({
        "name": "y", "layer": "device", "unit": "ms", "better": "lower",
        "source": "program_counter", "moves": "search_p50_ms",
        "reader": "scrape_rate", "args": {"proc": "front", "names": []}}))
    assert [m["name"] for m in run.load_metrics("some.cell", str(tmp_path))
            ] == ["x", "y"]
    assert [m["name"] for m in run.load_metrics("other", str(tmp_path))
            ] == ["y"]


def test_s2_arithmetic_agrees_with_the_programs():
    from dss_tpu import geo
    from dss_tpu.geo import s2cell

    metro = deploy.Metro(8)
    leaf = s2cell.cell_id_from_latlng(np.array([deploy.Metro.LAT]),
                                      np.array([deploy.Metro.LNG]))
    face, i, j, _ = s2cell.to_face_ij(leaf)
    assert (int(face[0]), int(i[0]), int(j[0])) == deploy.latlng_to_face_ij(
        deploy.Metro.LAT, deploy.Metro.LNG)
    got = geo.covering_polygon(
        [(v["lat"], v["lng"]) for v in metro.rect(2, 1, 3, 4)])
    want = metro.cells[metro.rect_flat(2, 1, 3, 4)]
    assert sorted(int(c) for c in got) == sorted(int(c) for c in want)


def test_generation_is_a_function_of_the_seed(tmp_path):
    config, traffic = _tiny()
    gen = dict(config["generator"])
    gen["classes"] = {k: dict(v, n=v["n"] // 10)
                      for k, v in gen["classes"].items()}
    for comp in traffic["components"]:  # a tenth of the density
        comp.pop("min_candidates", None)
    wals = []
    for name, seed in (("a", 2**31 + 11), ("b", 2**31 + 11), ("c", 12)):
        path = tmp_path / name
        metro, ref = deploy.generate(seed, gen, T_GEN, str(path))
        reqs = tr.build(traffic, metro, ref,
                        tr.pools(traffic, metro, ref, seed, T_GEN),
                        np.random.default_rng([seed, 1]), T_GEN, 20, 5)
        wals.append((path.read_bytes(), [(r.due, r.wire) for r in reqs]))
    assert wals[0] == wals[1]
    assert wals[0][0] != wals[2][0] and wals[0][1] != wals[2][1]


def test_every_seed_offers_the_same_set_of_work(flagship):
    metro, ref = flagship
    _, _, traffic = run.load_cell("scd-dense-urban-125k.poll")
    shapes = []
    for seed in (1, 2):
        reqs = tr.build(traffic, metro, ref,
                        tr.pools(traffic, metro, ref, seed, T_GEN),
                        np.random.default_rng([seed, 1]), T_GEN, 200, 10)
        assert len(reqs) == 2000
        shapes.append((
            sorted((r.comp, r.rect[2], r.rect[3]) for r in reqs),
            sorted((r.comp, r.when is None) for r in reqs)))
    assert shapes[0] == shapes[1]
    assert sum(1 for s in shapes[0][0] if s[0] == 1) == 5  # 1 in 400 wide
    # and each pooled area's answer holds the ids its sides are given
    polls = traffic["components"][0]
    for seed in (1, 2):
        pool = tr.pools(traffic, metro, ref, seed, T_GEN)[0]
        assert len(set(pool)) == polls["pool"]
        miss = [abs(len(ref["isa"].search(metro.rect_flat(*r),
                                          now=T_GEN * deploy.NS))
                    - polls["answer_ids"][f"{r[2]}x{r[3]}"]) for r in pool]
        assert max(miss[:64]) == 0 and max(miss) <= 3


def test_wide_rectangles_overflow_the_host_scan_and_polls_never_do(flagship):
    from dss_tpu.ops.fastpath import FastTable

    metro, ref = flagship
    cap = FastTable.HOST_MAX_CANDIDATES
    for cell in ("scd-dense-urban-125k.query-wide",
                 "scd-dense-urban-125k.poll"):
        _, _, traffic = run.load_cell(cell)
        reqs = tr.build(traffic, metro, ref,
                        tr.pools(traffic, metro, ref, 3, T_GEN),
                        np.random.default_rng([3, 1]), T_GEN,
                        traffic["rate_rps"], 10)
        for r in reqs:
            comp = traffic["components"][r.comp]
            cls = tr.ENDPOINTS[comp["endpoint"]]["class"]
            n = ref[cls].candidates(metro.rect_flat(*r.rect))
            if comp["endpoint"] == "scd_query":
                assert n >= comp["min_candidates"] >= 1.25 * cap
            else:
                assert n <= cap


def test_arrivals_are_poisson_at_the_fixed_rate(flagship):
    metro, ref = flagship
    _, _, traffic = run.load_cell("scd-dense-urban-125k.query-wide")
    reqs = tr.build(traffic, metro, ref, {}, np.random.default_rng([5, 1]),
                    T_GEN, 50, 40)
    due = np.array([r.due for r in reqs])
    assert len(due) == 2000 and (np.diff(due) >= 0).all()
    assert 0 <= due[0] and due[-1] < 40
    gaps = np.diff(due)
    assert abs(gaps.mean() - 1 / 50) < 0.002
    assert 0.85 < gaps.std() / gaps.mean() < 1.15  # exponential gaps


def test_percentiles_count_a_failed_request_as_the_deadline():
    reqs = [tr.Request(float(k), 0, (0, 0, 1, 1), None, None)
            for k in range(20)]
    out = tr.Outcome(np.arange(20.0), np.arange(20.0) + 0.010,
                     np.full(20, 200, np.int32), [b"{}"] * 20)
    good = np.ones(20, bool)
    good[3] = False  # a wrong answer
    out.done[7] = np.nan  # never answered
    out.done[9] = 9 + 11.0  # answered after the deadline
    lat = tr.latencies_ms(reqs, out, good)
    assert sorted(np.flatnonzero(lat == 10_000.0)) == [3, 7, 9]
    ctx = {"requests": reqs, "out": out, "good": good, "seconds": 20.0}
    assert gen_reader.read(ctx, "latency_percentile_ms", q=50) == pytest.approx(10.0)
    assert gen_reader.read(ctx, "latency_percentile_ms", q=95) == 10_000.0
    assert gen_reader.read(ctx, "goodput_rps") == pytest.approx(17 / 20)
    assert tr.percentile([1, 2, 3, 4], 50) == 2 and tr.percentile([5], 95) == 5


def test_roofline_bytes_on_a_hand_worked_case():
    # 100,000 candidate postings of 24 B and 250 ids of 8 B
    assert check.needed_bytes(100_000, 250) == 2_402_000
    # at 819 GB/s that is 2.933 us; over 0.2 ms of device time: 1.466 %
    assert xplane.peak_bytes_per_s("TPU v5 lite") == 819e9
    assert 100 * (2_402_000 / 819e9) / 0.0002 == pytest.approx(1.4664, rel=1e-3)
    with pytest.raises(KeyError):
        xplane.peak_bytes_per_s("TPU v9")


def test_trace_reduction_on_the_recorded_trace():
    """dssbench/testdata/v5e-trimmed.xplane.pb: the first device ops of
    a capture of scd-dense-urban-125k.query-wide on a TPU v5 lite,
    re-encoded by testdata/trim_xplane.py; the numbers are in
    v5e-trimmed.json beside it."""
    with open(os.path.join(DATA, "v5e-trimmed.json")) as fh:
        want = json.load(fh)
    path = os.path.join(DATA, "v5e-trimmed.xplane.pb")
    red = xplane.reduce_trace(path)
    # the capture's own span, first event to last; the window is the
    # part of it in which the device was given work, and lead and tail
    # are what is left at its ends (worked out by hand from the file:
    # events from 0.778369314 s to 2.706030009 s, device ops from
    # 0.778369321 s to 2.276167208 s)
    assert red["capture_s"] == pytest.approx(2.706030009 - 0.778369314)
    assert red["window_s"] == pytest.approx(2.276167208 - 0.778369321)
    assert red["lead_s"] == pytest.approx(7e-9, abs=1e-12)
    assert red["tail_s"] == pytest.approx(2.706030009 - 2.276167208)
    for key in ("capture_s", "window_s", "lead_s", "tail_s", "busy_s"):
        assert red[key] == pytest.approx(want[key], rel=1e-9, abs=1e-12)
    assert set(red["modules"]) == set(want["modules"])
    for name, mod in want["modules"].items():
        assert red["modules"][name] == pytest.approx(mod)
    assert red["breakdown"]["device_ops"][0][0] == want["top_op"]
    assert len(red["breakdown"]["idle_gaps"]) <= 10
    # and the stats the metric files ask for, on 14 requests of one
    # right 100,000-candidate answer of 250 ids each
    ctx = {"trace_file": path, "device_kind": "TPU v5 lite",
           "traced": {"requests": [None] * 14}}
    assert xplane.read(ctx, "device_idle_pct") == pytest.approx(
        want["idle_pct"], rel=1e-9)
    kernel = {"kernel": "jit_fused_window_filter"}
    # this capture began in the middle of traffic (no quiet lead): its
    # runs are not a stretch's requests', so per request nothing is read
    assert xplane.read(ctx, "kernel_launches_per_request", **kernel) is None
    kernel["margin_s"] = 0.0
    assert xplane.read(ctx, "kernel_launches_per_request", **kernel) == 0.5
    assert xplane.read(ctx, "kernel_ms_per_launch", **kernel
                       ) == pytest.approx(1.211344 / 7, rel=1e-9)
    # the roofline share, on stand-ins for the reference: 13 right
    # answers (one wrong) of 100,000 candidates and 250 ids each are
    # 13 x 2,402,000 B = 38.127 us at 819 GB/s, of 1,211.344 us run
    class Flat:
        def rect_flat(self, *rect):
            return None

        def candidates(self, flat):
            return 100_000

    body = json.dumps({"operation_references":
                       [{"id": str(i)} for i in range(250)]}).encode()
    ctx.update(traffic={"components": [{"endpoint": "scd_query"}]},
               metro=Flat(), ref={"op": Flat()})
    ctx["traced"].update(
        requests=[tr.Request(0.0, 0, (0, 0, 1, 1), None, None)] * 14,
        good=[True] * 13 + [False],
        out=tr.Outcome(None, None, None, [body] * 14))
    assert xplane.read(ctx, "kernel_roofline_pct", min_candidates=65537,
                       **kernel) == pytest.approx(
        100 * (13 * 2_402_000 / 819e9) / 0.001211344, rel=1e-9)
    assert xplane.read(ctx, "kernel_roofline_pct", min_candidates=100_001,
                       **kernel) is None  # no such request: nothing, not 0
    # a kernel that did not run under the capture gives nothing, never 0
    assert xplane.read(ctx, "kernel_ms_per_launch", kernel="jit_other") is None
    assert xplane.read(ctx, "kernel_roofline_pct", kernel="jit_other") is None


@pytest.mark.parametrize("served_by", ["sound", *check.CONTROLS])
def test_controls_read_not_correct_at_the_test_size(served_by, tmp_path):
    """The reference with a guarantee broken, put in the program's
    place, goes through the comparison and the verdict of a run and
    comes out not correct; the sound reference comes out correct."""
    config, traffic = _tiny()
    metro, ref = deploy.generate(7, config["generator"], T_GEN,
                                 str(tmp_path / "wal"))
    reqs = tr.build(traffic, metro, ref,
                    tr.pools(traffic, metro, ref, 7, T_GEN),
                    np.random.default_rng([7, 1]), T_GEN, 20, 20)
    served = ref if served_by == "sound" else check.CONTROLS[served_by](ref)
    correct, checks = check.judge_control(traffic, reqs, metro, ref, served)
    # `lost_write` has nothing to lose where no request writes
    sound = served_by not in check.controls_for(traffic)
    assert correct is sound
    assert checks["answers_compared"]["value"] == len(reqs)
    wrong = checks["wrong_answers"]
    assert (wrong["value"] > wrong["limit"]) is (not sound)


_fake_outcome = check.answers_of


def test_compare_catches_an_altered_and_a_missing_answer(tmp_path):
    config, traffic = _tiny()
    metro, ref = deploy.generate(9, config["generator"], T_GEN,
                                 str(tmp_path / "wal"))
    reqs = tr.build(traffic, metro, ref,
                    tr.pools(traffic, metro, ref, 9, T_GEN),
                    np.random.default_rng([9, 1]), T_GEN, 20, 5)
    out = _fake_outcome(traffic, reqs, metro, ref)
    cmp = check.compare(traffic, reqs, out, metro, ref)
    assert check.verdict(cmp["numbers"], cmp["facts"]["compared"])[0]
    run.alter_answer(reqs, out)
    cmp = check.compare(traffic, reqs, out, metro, ref)
    assert cmp["numbers"]["wrong_answers"] == 1
    assert not check.verdict(cmp["numbers"], cmp["facts"]["compared"])[0]
    out = _fake_outcome(traffic, reqs, metro, ref)
    out.status[2], out.body[2] = 0, None  # never came
    out.status[4] = 503  # refused: late, not wrong
    cmp = check.compare(traffic, reqs, out, metro, ref)
    assert cmp["numbers"] == {"wrong_answers": 0, "never_answered": 1}
    assert cmp["facts"]["refused"] == 1 and not cmp["good"][4]


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["", "lose_tail", "alter_answer"])
def test_a_run_reads_correct_only_when_nothing_is_broken(fault, bench):
    """The rest of a run, on the CPU backend, at the rehearsal's size:
    with the WAL's tail lost under the server, or an answer altered on
    its way out, `correct` comes out false."""
    config, traffic = _tiny()
    result, facts = run.run_cell(
        "tiny", 2**31 + 5, 6.0, False, config=config, traffic=traffic,
        metrics=[], end_to_end=run.end_to_end_readers(bench, "tiny"),
        platform="cpu", fault=fault)
    assert result["attempted"] == 120
    assert result["correct"] is (fault == "")
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {
        m["name"] for m in bench["end_to_end"] if "workloads" not in m}
    assert result["device"]["platform"] == "cpu"  # never under a TPU's name
