"""readers/spans.py: the program's dss.* spans read beside the device's
ops — on a capture worked out by hand, and on one recorded on the chip.

    JAX_PLATFORMS=cpu python -m pytest dssbench/tests/test_spans.py -q
"""

from __future__ import annotations

import importlib.util
import json
import os

import pytest

from dssbench.readers import spans, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(os.path.dirname(HERE), "testdata")
MS = 1_000_000  # the capture's clock counts nanoseconds


def _encode(planes: list) -> bytes:
    spec = importlib.util.spec_from_file_location(
        "trim_xplane", os.path.join(DATA, "trim_xplane.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.encode(planes)


def _ev(name: str, a_ms: float, b_ms: float) -> tuple:
    return (name, int(a_ms * MS), int((b_ms - a_ms) * MS))


@pytest.fixture()
def worked(tmp_path):
    """Two serve threads and the scan loop beside three device ops.
    The device's window is 10..41 ms, busy for 4 ms of it."""
    path = tmp_path / "worked.xplane.pb"
    path.write_bytes(_encode([
        ("/device:TPU:0", [(xplane.OPS_LINE, [
            _ev("fusion.1", 10, 11), _ev("fusion.2", 20, 22),
            _ev("fusion.1", 40, 41)])]),
        ("/host:CPU", [
            ("shm-serve-0", [
                _ev("dss.owner.idle", 0, 8),
                _ev("dss.owner.serve", 8, 24),
                _ev("dss.device.dispatch", 9, 12),
                _ev("PjitFunction(fused_window_filter)", 10, 11),
                _ev("dss.collect", 12, 23),
                _ev("dss.owner.idle", 24, 50)]),
            ("shm-serve-1", [
                _ev("dss.owner.idle", 0, 18),
                _ev("dss.owner.serve", 18, 30),
                _ev("dss.owner.idle", 30, 50)]),
            ("shm-scan", [_ev("dss.owner.scan_idle", 5, 7)]),
        ]),
    ]))
    return str(path)


def test_owner_busy_and_idle_with_work_on_a_hand_worked_capture(worked):
    ctx = {"trace_file": worked}
    assert xplane.reduction(ctx)["window_s"] == pytest.approx(0.031)
    # a serve was open over 8..30 ms: 10..30 of the window's 31 ms
    assert spans.read(ctx, "owner_busy_pct") == pytest.approx(
        100 * 20 / 31)
    # of those 20 ms the device ran for 10..11 and 20..22: idle for 17
    assert spans.read(ctx, "idle_with_work_pct") == pytest.approx(
        100 * 17 / 31)
    with pytest.raises(ValueError):
        spans.read(ctx, "no_such_stat")
    assert spans.read({"trace_file": None}, "owner_busy_pct") is None


def test_self_time_and_idle_blame_on_a_hand_worked_capture(worked):
    cap = spans.load(worked)
    own = spans.self_times(cap["spans"])
    # serve-0's 16 ms hold a dispatch of 3 and a collect of 11: 2 of
    # its own; serve-1's 12 ms are all its own.  An event that is not
    # the program's (PjitFunction) is no child.
    assert own["dss.owner.serve"] == pytest.approx([2, 0.028, 0.014])
    assert own["dss.device.dispatch"] == pytest.approx([1, 0.003, 0.003])
    assert own["dss.collect"] == pytest.approx([1, 0.011, 0.011])
    assert own["dss.owner.idle"] == pytest.approx([4, 0.072, 0.072])
    # the device idles over 11..20 and 22..40.  Each instant goes to
    # the working span opened last on any thread: dispatch 11..12,
    # collect 12..18, serve-1's envelope 18..20 and 22..30; then only
    # waits are open: 30..40 to owner.idle
    blame = spans.idle_by_span(cap)
    assert blame == pytest.approx({
        "dss.device.dispatch": 0.001, "dss.collect": 0.006,
        "dss.owner.serve": 0.010, "dss.owner.idle": 0.010})
    assert sum(blame.values()) == pytest.approx(0.027)
    assert spans.main.__doc__  # the table's printer exists; run below


def test_the_table_prints(worked, capsys, monkeypatch):
    monkeypatch.setattr("sys.argv", ["spans", worked])
    assert spans.main() == 0
    out = capsys.readouterr().out
    assert "dss.owner.serve" in out and "device idle under" in out


def test_a_capture_without_program_spans_reads_nothing():
    """The parent commit's program has no dss.* event (PR 26's
    recorded capture is one such): the reader returns nothing and does
    not raise, and the line leaves the metric out."""
    ctx = {"trace_file": os.path.join(DATA, "v5e-trimmed.xplane.pb")}
    assert spans.read(ctx, "owner_busy_pct") is None
    assert spans.read(ctx, "idle_with_work_pct") is None


def test_spans_on_the_capture_recorded_on_the_chip():
    """dssbench/testdata/v5e-spans-trimmed.xplane.pb: the first device
    ops of a capture of scd-dense-urban-125k.query-wide on a TPU v5
    lite, taken through /debug/profile with the program of PR 27 (no
    Python tracer) and re-encoded by testdata/trim_xplane.py, which
    keeps the host events of a millisecond or more; what both readers
    make of it is in v5e-spans-trimmed.json and
    v5e-spans-trimmed.spans.json beside it."""
    path = os.path.join(DATA, "v5e-spans-trimmed.xplane.pb")
    with open(path.replace(".xplane.pb", ".json")) as fh:
        red_want = json.load(fh)
    with open(path.replace(".xplane.pb", ".spans.json")) as fh:
        want = json.load(fh)
    ctx = {"trace_file": path}
    red = xplane.reduction(ctx)
    assert red["window_s"] == pytest.approx(red_want["window_s"], rel=1e-9)
    cap = spans.load(path)
    names = {name for _a, _b, name, _line in cap["spans"]}
    assert "dss.owner.serve" in names and "dss.owner.idle" in names
    # every gap between device ops of a millisecond or more (this cut
    # holds six; the rest are the 2 ns between a kernel's two fusions)
    # is blamed on one of the program's spans, and none on a lock wait
    # that the Python tracer used to name (`acquire`)
    gaps = red["breakdown"]["idle_gaps"]
    assert [who for who, s in gaps if s >= 1e-3] and all(
        who.startswith("host:python3:dss.") for who, s in gaps if s >= 1e-3)
    assert not any("acquire" in who or "$" in who for who, _s in gaps)
    own = spans.self_times(cap["spans"])
    for name, row in want["self_times"].items():
        assert own[name] == pytest.approx(row, rel=1e-9), name
    assert set(own) == set(want["self_times"])
    assert spans.read(ctx, "owner_busy_pct") == pytest.approx(
        want["owner_busy_pct"], rel=1e-9)
    assert spans.read(ctx, "idle_with_work_pct") == pytest.approx(
        want["idle_with_work_pct"], rel=1e-9)
    # the device idles nearly all the time a request sits in the owner
    assert 0 < want["idle_with_work_pct"] <= want["owner_busy_pct"] <= 100
    blame = spans.idle_by_span(cap)
    assert blame == pytest.approx(want["idle_by_span"], rel=1e-9)
    lo, hi = spans.device_window(cap["ops"])
    assert sum(blame.values()) == pytest.approx(
        (hi - lo) / 1e9 - red["busy_s"], rel=1e-6)
