"""MetricsRegistry exposition (RED metrics + gauges + info pattern)."""

from __future__ import annotations

import pytest

from dss_tpu.obs.metrics import (
    STAGE_BUCKETS,
    STAGE_NAMES,
    MetricsRegistry,
    stage_hist_quantile,
)


def test_render_counters_gauges_and_info():
    m = MetricsRegistry()
    isa_id = "dddddddd-dddd-4ddd-8ddd-ddddddddddd1"
    path = f"/v1/dss/identification_service_areas/{isa_id}"
    m.observe_request("GET", path, 200, 0.012)
    m.observe_request("GET", path, 200, 0.5)
    m.set_gauge("dss_dar_op_live_records", 42)
    m.set_info("dss_build_info", {"commit": "deadbeef", "host": "unit"})
    text = m.render()
    assert 'dss_build_info{commit="deadbeef",host="unit"} 1' in text
    assert "dss_requests_total" in text and 'status="200"' in text
    assert "dss_dar_op_live_records 42" in text
    # route templating: the UUID segment must not mint a label series
    assert isa_id not in text


def test_info_overwrites_not_accumulates():
    m = MetricsRegistry()
    m.set_info("dss_build_info", {"commit": "a"})
    m.set_info("dss_build_info", {"commit": "b"})
    text = m.render()
    assert 'commit="b"' in text and 'commit="a"' not in text


def test_label_values_escaped_everywhere():
    """Route labels come from request paths (remotely supplied): a
    quote/backslash/newline in any label value must be escaped, never
    break the whole exposition."""
    m = MetricsRegistry()
    m.observe_request("GET", '/v1/dss/a"b\\c', 200, 0.01)
    m.set_info("dss_build_info", {"t": 'x"y\nz'})
    text = m.render()
    assert '\\"' in text and "\\n" in text and "\\\\" in text
    for line in text.splitlines():
        # balanced quotes on every line (escaped ones excluded)
        assert line.replace('\\"', "").count('"') % 2 == 0, line


# -- the stage family across the front ---------------------------------------


def _front_registry(tmp_path, worker: int = 0):
    """A worker's registry wired as cmds/server.py wires it: its stage
    observations mirrored into its shm block, its exposition the
    family merged across the front's blocks."""
    from dss_tpu.parallel import shmring

    region = shmring.ShmRegion.create(
        str(tmp_path / "front.shm"), nworkers=2, depth=4
    )
    m = MetricsRegistry(proc=f"worker-{worker}:1")
    m.attach_stage_writer(shmring.StageHistWriter(region, worker))
    m.set_stage_agg(lambda: shmring.shm_stage_hist(region))
    return region, m


@pytest.mark.parametrize(
    "stage", [s for s in STAGE_NAMES if s != "other"]
)
def test_every_stage_name_reaches_the_front_family(tmp_path, stage):
    """A mark under any name of STAGE_NAMES in a worker lands in the
    front-merged dss_stage_duration_seconds family under THAT name —
    the shm blocks are laid out from the tuple, so a name added to it
    needs nothing else — and not under `other`."""
    from dss_tpu.obs import stages

    region, m = _front_registry(tmp_path)
    try:
        sink = {}
        stages.set_sink(sink)
        try:
            stages.mark(stage, 1.5, span=False)
        finally:
            stages.set_sink(None)
        route = "/v1/dss/identification_service_areas"
        for st, ms in sink.items():  # as the access log does
            m.observe_stage(route, st, ms / 1000.0)
        text = m.render()
        want = f'route="search",stage="{stage}"'
        assert f"dss_stage_duration_seconds_count{{{want}}} 1" in text
        assert f"dss_stage_duration_seconds_sum{{{want}}} 0.001500" in text
        assert 'stage="other"' not in text
    finally:
        region.close()


def test_unknown_stage_collapses_to_other_and_loop_route_to_other(tmp_path):
    from dss_tpu.obs.metrics import LOOP_ROUTE

    region, m = _front_registry(tmp_path)
    try:
        m.observe_stage("/healthy", "made_up_ms", 0.002)
        m.observe_stage(LOOP_ROUTE, "loop_lag_ms", 0.0004)
        text = m.render()
        assert (
            'dss_stage_duration_seconds_count{route="other",stage="other"} 1'
            in text
        )
        assert (
            'dss_stage_duration_seconds_count'
            '{route="other",stage="loop_lag_ms"} 1' in text
        )
    finally:
        region.close()


def test_legacy_stage_summary_is_gone():
    m = MetricsRegistry()
    m.observe_stage("/v1/dss/identification_service_areas", "store_ms", 0.004)
    text = m.render()
    assert "dss_stage_duration_seconds_count" in text
    # the summary family rendered beside it until PR 27 was the
    # exposition's only `summary`
    assert " summary" not in text


class _Dev:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("devices,want", [
    # one chip: the allocator's two numbers
    ([_Dev({"bytes_in_use": 10, "peak_bytes_in_use": 30, "x": 1})],
     {"dss_device_bytes_in_use": 10, "dss_device_peak_bytes_in_use": 30}),
    # four: in use summed, the highest single peak
    ([_Dev({"bytes_in_use": 10, "peak_bytes_in_use": 30}),
      _Dev({"bytes_in_use": 5, "peak_bytes_in_use": 40})],
     {"dss_device_bytes_in_use": 15, "dss_device_peak_bytes_in_use": 40}),
    # a backend that reports nothing (the CPU), or lacks a key: no
    # series for it — never a 0
    ([_Dev(None)], {}),
    ([_Dev({"bytes_in_use": 7})], {"dss_device_bytes_in_use": 7}),
])
def test_device_memory_gauges(monkeypatch, devices, want):
    import jax

    from dss_tpu.ops import device_memory_stats

    monkeypatch.setattr(jax, "local_devices", lambda: devices)
    assert device_memory_stats() == want


# -- stage_hist_quantile: the interpolation's edge cases -----------------


def _hist_row(durations_ms):
    """Cumulative stage-histogram row (counts, sum_s, cnt) exactly as
    MetricsRegistry.observe_stage accumulates it."""
    counts = [0] * len(STAGE_BUCKETS)
    total = 0.0
    for ms in durations_ms:
        s = ms / 1000.0
        for i, edge in enumerate(STAGE_BUCKETS):
            if s <= edge:
                counts[i] += 1
        total += s
    return tuple(counts), total, len(durations_ms)


def test_quantile_empty_histogram_returns_none():
    assert stage_hist_quantile((0,) * len(STAGE_BUCKETS), 0, 0.5) is None
    assert stage_hist_quantile((), 0, 0.99) is None


def test_quantile_single_occupied_bucket_interpolates():
    """All mass in one bucket: quantiles interpolate linearly from the
    previous edge, exactly like any other bucket."""
    counts, _, cnt = _hist_row([3.0] * 100)  # all in (0.0025, 0.005]
    q50 = stage_hist_quantile(counts, cnt, 0.50)
    q99 = stage_hist_quantile(counts, cnt, 0.99)
    assert 0.0025 < q50 < q99 <= 0.005
    assert q50 == pytest.approx(0.0025 + 0.5 * 0.0025)


def test_quantile_all_overflow_returns_last_edge_floor():
    """Durations past the last bucket edge land in no bucket; the
    quantile reports the last edge as a FLOOR rather than inventing a
    number beyond the histogram's resolution."""
    counts, _, cnt = _hist_row([5000.0] * 10)  # 5 s >> 1 s last edge
    assert all(c == 0 for c in counts)
    assert cnt == 10
    assert stage_hist_quantile(counts, cnt, 0.99) == STAGE_BUCKETS[-1]
    assert stage_hist_quantile(counts, cnt, 0.50) == STAGE_BUCKETS[-1]
