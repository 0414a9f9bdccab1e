"""Deploy-tier observability artifacts reference REAL metrics: every
metric name used in deploy/prometheus/rules.yaml and
deploy/grafana/dss-dashboard.json must be one the server actually
exports (obs/metrics.py + the stats gauges)."""

from __future__ import annotations

import json
import os
import re

import pytest

yaml = pytest.importorskip("yaml")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# metrics emitted outside this process's control
_EXTERNAL = {"up"}

_PROMQL_FUNCS = {
    "rate", "increase", "sum", "histogram_quantile", "by", "le",
    "route", "stage", "status", "job", "dss", "m", "s", "version",
    "commit",
}


def _exported_metric_names() -> set:
    """Every metric name the serving stack can export."""
    from dss_tpu.clock import Clock
    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.obs.metrics import MetricsRegistry

    names = {
        "dss_requests_total",
        "dss_request_duration_seconds",
        "dss_stage_duration_seconds",
        "dss_build_info",
    }
    store = DSSStore(storage="memory", clock=Clock())
    names |= set(store.stats())
    # region coordinator gauges
    names |= {
        "region_applied", "region_dirty", "region_resyncs",
        "region_rollbacks", "region_failovers", "region_client_retries",
    }
    # region log server (primary/mirror) metrics — the exported-name
    # tuple lives next to the code that renders them
    from dss_tpu.region.mirror import REGION_SERVER_METRICS

    names |= set(REGION_SERVER_METRICS)
    # multi-host mesh gauge family (stable name tuple next to the code)
    from dss_tpu.parallel.multihost import MULTIHOST_METRICS

    names |= set(MULTIHOST_METRICS)
    # follower + replica gauges (stats key sets are stable)
    from dss_tpu.parallel.replica import CLASSES

    names |= {"follower_applied_seq", "follower_apply_errors"}
    names |= {
        "replica_applied_records", "replica_apply_errors",
        "replica_tail_errors", "replica_rebuilds", "replica_staleness_s",
        "replica_demand_idle",
    }
    for c in CLASSES:
        names |= {
            f"replica_{c}_records",
            f"replica_{c}_snapshot_records",
            f"replica_{c}_overflow_fallbacks",
            f"replica_{c}_dirty",
        }
    # skew-aware shard placement gauges (ShardedReplica.shard_stats;
    # dss_shard_load renders as a labeled per-shard family)
    names |= {
        "dss_shard_load",
        "dss_shard_imbalance_factor",
        "dss_shard_boundary_moves",
        "dss_shard_moved_bytes",
        "dss_shard_members",
        "dss_shard_results_cap",
    }
    # tpu-storage DAR gauges (memory backend exports fewer)
    tpu = DSSStore(storage="tpu", clock=Clock())
    names |= set(tpu.stats())
    # set directly on the registry by cmds/server.py build() (not a
    # store stats key): boot-profile staleness
    names.add("dss_autotune_profile_age_s")
    return names


def _names_in_expr(expr: str) -> set:
    toks = set(re.findall(r"[a-zA-Z_][a-zA-Z0-9_]*", expr))
    out = set()
    for t in toks - _PROMQL_FUNCS:
        base = re.sub(r"_(bucket|sum|count|total)$", "", t)
        if t.startswith(("dss_", "region_", "replica_", "follower_")):
            out.add(t)
        elif base != t and base.startswith(
            ("dss_", "region_", "replica_", "follower_")
        ):
            out.add(t)
        elif t in _EXTERNAL:
            out.add(t)
    return out


def _resolve(name: str, exported: set) -> bool:
    if name in _EXTERNAL or name in exported:
        return True
    base = re.sub(r"_(bucket|sum|count)$", "", name)
    return base in exported


def test_prometheus_rules_reference_real_metrics():
    exported = _exported_metric_names()
    rules = yaml.safe_load(
        open(os.path.join(ROOT, "deploy/prometheus/rules.yaml"))
    )
    missing = []
    for g in rules["groups"]:
        for r in g["rules"]:
            for name in _names_in_expr(r["expr"]):
                if not _resolve(name, exported):
                    missing.append((r.get("alert"), name))
    assert not missing, f"rules reference unknown metrics: {missing}"


def test_grafana_dashboard_references_real_metrics():
    exported = _exported_metric_names()
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    missing = []
    for p in dash["panels"]:
        for t in p.get("targets", []):
            for name in _names_in_expr(t["expr"]):
                if not _resolve(name, exported):
                    missing.append((p["title"], name))
    assert not missing, f"dashboard references unknown metrics: {missing}"
    assert len(dash["panels"]) >= 8


def test_grafana_dashboard_has_tier_panels():
    """The tiered-snapshot subsystem (dar/tiers.py) must stay visible:
    the dashboard carries panels over the dss_dar_*_tier_* gauges
    (tier sizes, shadowed rows, minor-fold vs major-compaction time)."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    for needed in (
        "tier_l0_records",
        "tier_l1_records",
        "tier_shadowed_rows",
        "tier_minor_fold_ms_total",
        "tier_compact_ms_total",
    ):
        assert any(needed in e for e in exprs), needed


def test_grafana_and_rules_cover_multihost():
    """The multi-host mesh must stay observable: dashboard panels over
    the dss_multihost_* family and a paging alert on degradation."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    for needed in (
        "dss_multihost_degraded",
        "dss_multihost_processes",
        "dss_multihost_refresh_bytes",
        "dss_multihost_last_barrier_age_s",
    ):
        assert any(needed in e for e in exprs), needed
    rules = yaml.safe_load(
        open(os.path.join(ROOT, "deploy/prometheus/rules.yaml"))
    )
    alerts = {
        r.get("alert"): r["expr"]
        for g in rules["groups"]
        for r in g["rules"]
    }
    assert "DssMultihostDegraded" in alerts
    assert "dss_multihost_degraded" in alerts["DssMultihostDegraded"]


def test_grafana_and_rules_cover_deadline_routing():
    """The deadline router must stay observable: dashboard panels over
    the route-mix counters + cost estimates, and a paging rule on
    sustained deadline-shedding (the 504 fast-shed path)."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    for needed in (
        "co_route_hostchunk_batches",
        "co_route_device_batches",
        "co_deadline_shed",
        "co_est_device_floor_ms",
        "co_est_host_chunk_ms",
    ):
        assert any(needed in e for e in exprs), needed
    rules = yaml.safe_load(
        open(os.path.join(ROOT, "deploy/prometheus/rules.yaml"))
    )
    alerts = {
        r.get("alert"): r["expr"]
        for g in rules["groups"]
        for r in g["rules"]
    }
    assert "DssDeadlineShedding" in alerts
    assert "co_deadline_shed" in alerts["DssDeadlineShedding"]


def test_grafana_covers_planner_decision_mix():
    """The query planner must stay observable: a dashboard panel over
    the co_plan_* decision-mix counters (all six routes + ring-full
    fallback demotions) and the boundary-aware result-capacity gauge."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    for needed in (
        "co_plan_cache",
        "co_plan_inline",
        "co_plan_hostchunk",
        "co_plan_device",
        "co_plan_resident",
        "co_plan_mesh",
        "co_plan_fallbacks",
        "dss_shard_results_cap",
    ):
        assert any(needed in e for e in exprs), needed


def test_grafana_and_rules_cover_resident_kernel():
    """The resident serving kernel must stay observable: dashboard
    panels over the route counter, ring depth/occupancy gauges, the
    per-bucket AOT cache hit/miss counters, and the learned resident
    floor — plus a paging rule on sustained ring-full rejections (the
    cold-dispatch fallback burning the floor the loop exists to
    amortize)."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    for needed in (
        "co_route_resident_batches",
        "co_res_ring_depth",
        "co_res_ring_cap",
        "co_res_inflight",
        "co_res_rejected",
        "co_res_aot_hits",
        "co_res_aot_misses",
        "co_res_aot_buckets",
        "co_est_resident_floor_ms",
    ):
        assert any(needed in e for e in exprs), needed
    rules = yaml.safe_load(
        open(os.path.join(ROOT, "deploy/prometheus/rules.yaml"))
    )
    alerts = {
        r.get("alert"): r["expr"]
        for g in rules["groups"]
        for r in g["rules"]
    }
    assert "DssResidentRingSaturated" in alerts
    assert "co_res_rejected" in alerts["DssResidentRingSaturated"]


def test_grafana_and_rules_cover_read_cache():
    """The version-fenced read cache must stay observable: a hit-rate
    panel over the co_cache_* / dss_cache_* gauges, a churn panel
    (entries/bytes/evictions/invalidations), and a DssCacheThrashing
    alert on sustained invalidation rate ~ miss rate (writes killing
    entries as fast as polls repopulate them)."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    for needed in (
        "dss_cache_hits",
        "dss_cache_misses",
        "dss_cache_evictions",
        "dss_cache_invalidations",
        "dss_cache_entries",
        "dss_cache_bytes",
        "co_cache_hits",
    ):
        assert any(needed in e for e in exprs), needed
    rules = yaml.safe_load(
        open(os.path.join(ROOT, "deploy/prometheus/rules.yaml"))
    )
    alerts = {
        r.get("alert"): r["expr"]
        for g in rules["groups"]
        for r in g["rules"]
    }
    assert "DssCacheThrashing" in alerts
    assert "dss_cache_invalidations" in alerts["DssCacheThrashing"]
    assert "dss_cache_misses" in alerts["DssCacheThrashing"]


def test_make_certs_provisions_trust_material(tmp_path):
    """deploy/make_certs.py (the reference's build/make-certs.py +
    apply-certs.sh analog): JWT keypair, region token, TLS CA chain,
    and valid k8s Secret manifests."""
    import subprocess
    import sys

    pytest.importorskip("cryptography")  # make_certs signs with RSA

    out = tmp_path / "trust"
    r = subprocess.run(
        [
            sys.executable,
            os.path.join(ROOT, "deploy/make_certs.py"),
            "--out", str(out),
            "--hosts", "region-log.test.svc",
        ],
        capture_output=True,
        timeout=120,
    )
    assert r.returncode == 0, r.stderr.decode()
    for f in ("oauth.key", "oauth.pem", "region.token", "ca.crt",
              "server.crt", "server.key"):
        assert (out / f).exists(), f
    # private material is 0600
    assert (out / "oauth.key").stat().st_mode & 0o077 == 0
    # the JWT keypair actually signs/verifies (the dummy-oauth flow)
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.hazmat.primitives.asymmetric import padding

    priv = serialization.load_pem_private_key(
        (out / "oauth.key").read_bytes(), None
    )
    pub = serialization.load_pem_public_key((out / "oauth.pem").read_bytes())
    sig = priv.sign(b"claims", padding.PKCS1v15(), hashes.SHA256())
    pub.verify(sig, b"claims", padding.PKCS1v15(), hashes.SHA256())
    # k8s manifests parse as Secrets
    for f in (out / "k8s").iterdir():
        d = yaml.safe_load(f.read_text())
        assert d["kind"] == "Secret", f


def test_openapi_spec_covers_every_route():
    """docs/openapi.yaml is the wire contract (the reference's
    interfaces/ OpenAPI analog): every route the server registers must
    appear in the spec with the right method, and vice versa."""
    from dss_tpu.api.app import build_app
    from dss_tpu.clock import Clock
    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.obs.metrics import MetricsRegistry
    from dss_tpu.services.rid import RIDService
    from dss_tpu.services.scd import SCDService

    with open(os.path.join(ROOT, "docs/openapi.yaml")) as f:
        spec = yaml.safe_load(f)
    spec_ops = {
        (m.upper(), path)
        for path, methods in spec["paths"].items()
        for m in methods
        if m in ("get", "put", "post", "delete")
    }

    clock = Clock()
    store = DSSStore(storage="memory", clock=clock)

    class _FakeReplica:
        def query(self, *a, **k):
            return []

        def stats(self):
            return {}

    app = build_app(
        RIDService(store.rid, clock),
        SCDService(store.scd, clock),
        None,
        metrics=MetricsRegistry(),
        profile_dir="/tmp/profiles",
        replica=_FakeReplica(),
        # any non-None router/pipeline registers the federation and
        # push surfaces (handlers consult them only at request time)
        federation=object(),
        push=object(),
    )
    app_ops = set()
    for route in app.router.routes():
        if route.method in ("GET", "PUT", "POST", "DELETE"):
            app_ops.add((route.method, route.resource.canonical))
    missing_from_spec = app_ops - spec_ops
    stale_in_spec = spec_ops - app_ops
    assert not missing_from_spec, missing_from_spec
    assert not stale_in_spec, stale_in_spec


def test_k8s_manifests_are_structurally_sound():
    """Parse every deploy/k8s manifest: Secrets/ConfigMaps carry only
    string data, the region-log StatefulSet keeps its WAL PVC, and
    every volumeMount has a backing volume."""
    import glob

    for path in glob.glob(os.path.join(ROOT, "deploy/k8s/*.yaml")):
        with open(path) as f:
            docs = [d for d in yaml.safe_load_all(f) if d]
        for d in docs:
            if d["kind"] in ("ConfigMap", "Secret"):
                for k, v in d.get("data", {}).items():
                    assert isinstance(v, str), (path, d["kind"], k)
            if d["kind"] in ("Deployment", "StatefulSet"):
                spec = d["spec"]["template"]["spec"]
                vols = {v["name"] for v in spec.get("volumes", [])}
                if d["kind"] == "StatefulSet":
                    vols |= {
                        t["metadata"]["name"]
                        for t in d["spec"].get("volumeClaimTemplates", [])
                    }
                for c in spec["containers"]:
                    for m in c.get("volumeMounts", []):
                        assert m["name"] in vols, (path, c["name"], m)
    # the region WAL must be PVC-backed (it IS the region's history)
    with open(os.path.join(ROOT, "deploy/k8s/region-log.yaml")) as f:
        sts = [
            d for d in yaml.safe_load_all(f)
            if d and d["kind"] == "StatefulSet"
        ][0]
    assert sts["spec"]["volumeClaimTemplates"], "region WAL lost its PVC"


def test_dockerfile_ships_native_kernels():
    """The runtime image is toolchain-less (python:slim), so the
    Dockerfile must compile libdsscover.so in a build stage and copy
    it in — otherwise the deployed binary silently serves from the
    numpy fallbacks (3-26x slower hot paths).  Also pins that
    packaging ships the kernel sources + prebuilt .so, and that the
    staged compile covers exactly the sources the lazy in-process
    builder uses (the two lists must stay in lockstep)."""
    with open(os.path.join(ROOT, "Dockerfile")) as f:
        df = f.read()
    assert "AS native-build" in df
    # one builder: the stage runs the same stdlib-only _buildlib the
    # lazy in-process path uses, so the source list cannot desync
    assert "_buildlib.py" in df
    assert re.search(
        r"COPY --from=native-build[\s\S]*libdsscover\.so[\s\S]*"
        r"libdsscover\.so\.sha", df
    )
    with open(os.path.join(ROOT, "pyproject.toml")) as f:
        py = f.read()
    assert '"dss_tpu.native" = ["*.cc", "*.so", "*.so.sha"]' in py


def test_native_freshness_is_content_based(tmp_path):
    """The loader must reject a stale .so whose sources changed after
    it was built, regardless of file mtimes (pip stamps installed
    files with extraction time, so mtime rules are meaningless in a
    wheel install)."""
    import shutil

    from dss_tpu.native import _buildlib

    if shutil.which("g++") is None:
        pytest.skip("needs a C++ toolchain")
    d = tmp_path / "native"
    d.mkdir()
    src_dir = os.path.join(ROOT, "dss_tpu", "native")
    for name in _buildlib.SOURCE_NAMES:
        shutil.copy(os.path.join(src_dir, name), d / name)
    assert not _buildlib.so_fresh(str(d))  # nothing built yet
    assert _buildlib.build(str(d))
    assert _buildlib.so_fresh(str(d))
    # edit a source: the digest no longer matches -> stale, even
    # though we ALSO give the .so the newest mtime in the directory
    with open(d / _buildlib.SOURCE_NAMES[0], "a") as f:
        f.write("\n// changed\n")
    os.utime(d / _buildlib.SO_NAME, None)
    assert not _buildlib.so_fresh(str(d))
    # rebuild restores freshness
    assert _buildlib.build(str(d))
    assert _buildlib.so_fresh(str(d))


def test_grafana_and_rules_cover_shard_placement():
    """Skew-aware shard placement must stay observable: a per-shard
    load heat panel plus imbalance/boundary-move/membership series,
    and a warning rule on sustained imbalance above the rebalance
    threshold (a hot spot the rebalancer is NOT shedding)."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    for needed in (
        "dss_shard_load",
        "dss_shard_imbalance_factor",
        "dss_shard_boundary_moves",
        "dss_shard_moved_bytes",
        "dss_shard_members",
    ):
        assert any(needed in e for e in exprs), needed
    rules = yaml.safe_load(
        open(os.path.join(ROOT, "deploy/prometheus/rules.yaml"))
    )
    alerts = {
        r.get("alert"): r["expr"]
        for g in rules["groups"]
        for r in g["rules"]
    }
    assert "DssShardHotspot" in alerts
    assert "dss_shard_imbalance_factor" in alerts["DssShardHotspot"]
    assert "DssShardRebalanceThrash" in alerts
    assert (
        "dss_shard_boundary_moves" in alerts["DssShardRebalanceThrash"]
    )


def test_grafana_and_rules_cover_degradation():
    """The fault-injection + degradation-ladder subsystem must stay
    observable: a dashboard panel over dss_degraded_mode /
    dss_breaker_state{remote} / dss_fault_injected_total{site} /
    region_mirror_backoff_s, plus the DssDegradedMode page and the
    DssBreakerOpen warning registered in the alert rules."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    for needed in (
        "dss_degraded_mode",
        "dss_breaker_state",
        "dss_fault_injected_total",
        "dss_degraded_transitions",
        "co_device_loss_absorbed",
        "co_device_ok",
        "region_mirror_backoff_s",
    ):
        assert any(needed in e for e in exprs), needed
    rules = yaml.safe_load(
        open(os.path.join(ROOT, "deploy/prometheus/rules.yaml"))
    )
    alerts = {
        r.get("alert"): r["expr"]
        for g in rules["groups"]
        for r in g["rules"]
    }
    assert "DssDegradedMode" in alerts
    assert "dss_degraded_mode" in alerts["DssDegradedMode"]
    assert "DssBreakerOpen" in alerts
    assert "dss_breaker_state" in alerts["DssBreakerOpen"]


def test_degradation_gauges_render_as_labeled_families():
    """dss_breaker_state and dss_fault_injected_total are keyed gauge
    families with their OWN label names (remote / site), routed through
    the metrics handler's per-metric label map."""
    from dss_tpu.api.app import _GAUGE_VEC_LABELS
    from dss_tpu.obs.metrics import MetricsRegistry

    assert _GAUGE_VEC_LABELS["dss_breaker_state"] == "remote"
    assert _GAUGE_VEC_LABELS["dss_fault_injected_total"] == "site"
    reg = MetricsRegistry()
    reg.set_gauge_vec(
        "dss_breaker_state", "remote", {"http://a:1": 2.0}
    )
    reg.set_gauge_vec(
        "dss_fault_injected_total", "site", {"wal.fsync": 3.0}
    )
    text = reg.render()
    assert 'dss_breaker_state{remote="http://a:1"} 2.0' in text
    assert 'dss_fault_injected_total{site="wal.fsync"} 3.0' in text


def test_shard_gauges_render_as_labeled_family():
    """dss_shard_load is a per-shard labeled gauge family: the /metrics
    exposition must carry one series per shard so the heat panel can
    render without per-shard metric names."""
    from dss_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.set_gauge_vec(
        "dss_shard_load", "shard", {"0": 10.0, "1": 3.0}
    )
    reg.set_gauge("dss_shard_imbalance_factor", 1.54)
    text = reg.render()
    assert 'dss_shard_load{shard="0"} 10.0' in text
    assert 'dss_shard_load{shard="1"} 3.0' in text
    assert "# TYPE dss_shard_load gauge" in text
    assert "dss_shard_imbalance_factor 1.54" in text


def test_grafana_and_rules_cover_federation():
    """The multi-region federation must stay observable: dashboard
    panels over dss_fed_peer_state{region} / dss_fed_mirror_lag_s /
    dss_fed_partitioned and the federated query mix, plus the
    DssFederationPartitioned page and the mirror-lag warning
    registered in the alert rules."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    for needed in (
        "dss_fed_peer_state",
        "dss_fed_mirror_lag_s",
        "dss_fed_partitioned",
        "dss_fed_stale_served",
        "dss_fed_shed",
        "dss_fed_sync_failures",
    ):
        assert any(needed in e for e in exprs), needed
    rules = yaml.safe_load(
        open(os.path.join(ROOT, "deploy/prometheus/rules.yaml"))
    )
    alerts = {
        r.get("alert"): r["expr"]
        for g in rules["groups"]
        for r in g["rules"]
    }
    assert "DssFederationPartitioned" in alerts
    assert "dss_fed_partitioned" in alerts["DssFederationPartitioned"]
    assert "DssFederationMirrorLagHigh" in alerts
    assert (
        "dss_fed_mirror_lag_s" in alerts["DssFederationMirrorLagHigh"]
    )


def test_federation_gauges_render_as_labeled_families():
    """dss_fed_peer_state and dss_fed_mirror_lag_s are keyed gauge
    families labeled by region, and the stable dss_fed_* key set is
    exported even with no federation attached (dashboards never miss
    the series)."""
    from dss_tpu.api.app import _GAUGE_VEC_LABELS
    from dss_tpu.obs.metrics import MetricsRegistry

    assert _GAUGE_VEC_LABELS["dss_fed_peer_state"] == "region"
    assert _GAUGE_VEC_LABELS["dss_fed_mirror_lag_s"] == "region"
    reg = MetricsRegistry()
    reg.set_gauge_vec("dss_fed_peer_state", "region", {"b": 2.0})
    reg.set_gauge_vec("dss_fed_mirror_lag_s", "region", {"b": 1.5})
    text = reg.render()
    assert 'dss_fed_peer_state{region="b"} 2.0' in text
    assert 'dss_fed_mirror_lag_s{region="b"} 1.5' in text


def test_grafana_and_rules_cover_shm_front():
    """The shared-memory serving front must stay observable: dashboard
    panels over ring saturation / slots in flight / served rate and the
    per-worker counter families (cache hits, ring trips, proxy
    fallbacks), plus a DssShmRingSaturated alert on sustained
    saturation or ring-full fallback rate (a saturated ring silently
    degrades every search to the loopback-proxy cost)."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    for needed in (
        "dss_shm_saturation",
        "dss_shm_slots_in_flight",
        "dss_shm_served_total",
        "dss_shm_ring_full_total",
        "dss_shm_reclaimed_total",
        "dss_shm_worker_cache_hits",
        "dss_shm_worker_enqueued",
        "dss_shm_worker_proxy_fallbacks",
        "dss_shm_workers",
    ):
        assert any(needed in e for e in exprs), needed
    rules = yaml.safe_load(
        open(os.path.join(ROOT, "deploy/prometheus/rules.yaml"))
    )
    alerts = {
        r.get("alert"): r["expr"]
        for g in rules["groups"]
        for r in g["rules"]
    }
    assert "DssShmRingSaturated" in alerts
    assert "dss_shm_saturation" in alerts["DssShmRingSaturated"]
    assert "DssShmWorkerDead" in alerts
    assert "dss_shm_reclaimed_total" in alerts["DssShmWorkerDead"]


def test_grafana_and_rules_cover_push():
    """The reverse-query push pipeline must stay observable: dashboard
    panels over queue depth / delivery lag / oldest unacked and the
    match->enqueue->deliver flow counters (including the per-USS
    breaker family), plus the DssPushDeliveryLagHigh warning and the
    DssPushQueueSaturated page registered in the alert rules (a
    saturated queue is already shedding bulk notifications and has
    flipped the ladder to PUSH_DEGRADED)."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    for needed in (
        "dss_push_queue_depth",
        "dss_push_delivery_lag_p50_ms",
        "dss_push_delivery_lag_p99_ms",
        "dss_push_oldest_pending_s",
        "dss_push_match_queries_total",
        "dss_push_match_absorbed_total",
        "dss_push_enqueued_total",
        "dss_push_delivered_total",
        "dss_push_requeued_total",
        "dss_push_parked_total",
        "dss_push_dropped_total",
        "dss_push_breaker_state",
        "dss_push_fed_forwarded_total",
    ):
        assert any(needed in e for e in exprs), needed
    rules = yaml.safe_load(
        open(os.path.join(ROOT, "deploy/prometheus/rules.yaml"))
    )
    alerts = {
        r.get("alert"): r["expr"]
        for g in rules["groups"]
        for r in g["rules"]
    }
    assert "DssPushDeliveryLagHigh" in alerts
    assert "dss_push_delivery_lag_p99_ms" in alerts["DssPushDeliveryLagHigh"]
    assert "dss_push_oldest_pending_s" in alerts["DssPushDeliveryLagHigh"]
    assert "DssPushQueueSaturated" in alerts
    assert "dss_push_queue_depth" in alerts["DssPushQueueSaturated"]
    assert "dss_push_dropped_total" in alerts["DssPushQueueSaturated"]


def test_push_breaker_gauge_renders_as_labeled_family():
    """dss_push_breaker_state is a keyed gauge family labeled by the
    subscriber USS (the delivery-side analog of dss_breaker_state's
    `remote`), routed through the metrics handler's per-metric label
    map."""
    from dss_tpu.api.app import _GAUGE_VEC_LABELS
    from dss_tpu.obs.metrics import MetricsRegistry

    assert _GAUGE_VEC_LABELS["dss_push_breaker_state"] == "uss"
    reg = MetricsRegistry()
    reg.set_gauge_vec(
        "dss_push_breaker_state", "uss", {"uss1": 2.0}
    )
    text = reg.render()
    assert 'dss_push_breaker_state{uss="uss1"} 2.0' in text


def test_grafana_and_rules_cover_tracing():
    """The distributed-tracing subsystem must stay observable: a
    per-stage latency heatmap over the dss_stage_duration_seconds
    histogram, a slow-trace-rate panel over the trace recorder
    counters, plus the DssTraceRecorderSaturated warning and the
    DssStageLatencyRegression per-stage p99 regression rule."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    for needed in (
        "dss_stage_duration_seconds_bucket",
        "dss_trace_kept_slow_total",
        "dss_trace_kept_sampled_total",
        "dss_trace_dropped_total",
        "dss_trace_ring_depth",
    ):
        assert any(needed in e for e in exprs), needed
    rules = yaml.safe_load(
        open(os.path.join(ROOT, "deploy/prometheus/rules.yaml"))
    )
    alerts = {
        r.get("alert"): r["expr"]
        for g in rules["groups"]
        for r in g["rules"]
    }
    assert "DssTraceRecorderSaturated" in alerts
    assert "dss_trace_dropped_total" in alerts["DssTraceRecorderSaturated"]
    assert "DssStageLatencyRegression" in alerts
    assert (
        "dss_stage_duration_seconds_bucket"
        in alerts["DssStageLatencyRegression"]
    )


def test_grafana_covers_the_autotune_profile_age():
    """The boot profile's provenance stays on the dashboard (its
    panel outlived the self-tuner's, PR 31), and no panel or alert
    reads a family the program no longer exports."""
    dash = json.load(
        open(os.path.join(ROOT, "deploy/grafana/dss-dashboard.json"))
    )
    exprs = [
        t["expr"]
        for p in dash["panels"]
        for t in p.get("targets", [])
    ]
    assert any("dss_autotune_profile_age_s" in e for e in exprs)
    rules = yaml.safe_load(
        open(os.path.join(ROOT, "deploy/prometheus/rules.yaml"))
    )
    exprs += [r["expr"] for g in rules["groups"] for r in g["rules"]]
    assert not [e for e in exprs if "dss_tune_" in e]


def test_dict_valued_stats_render_as_labeled_families():
    """A dict-valued stats key is exploded by the metrics handler's
    per-metric label map into a gauge family; a store exports no
    dss_tune_* key (the self-tuner is gone, PR 31)."""
    from dss_tpu.api.app import _GAUGE_VEC_LABELS
    from dss_tpu.clock import Clock
    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.obs.metrics import MetricsRegistry

    assert _GAUGE_VEC_LABELS["dss_push_breaker_state"] == "uss"
    store = DSSStore(storage="memory", clock=Clock())
    stats = store.stats()
    assert stats["dss_push_breaker_state"] == {}
    assert not [k for k in stats if k.startswith("dss_tune_")]
    assert not [k for k in _GAUGE_VEC_LABELS if k.startswith("dss_tune_")]
    reg = MetricsRegistry()
    reg.set_gauge_vec("dss_push_breaker_state", "uss", {"uss1": 2})
    assert 'dss_push_breaker_state{uss="uss1"} 2.0' in reg.render()


def test_stage_histogram_renders_as_labeled_family():
    """dss_stage_duration_seconds is a labeled histogram family
    ({stage,route}, bounded cardinality: stage names collapse onto the
    STAGE_NAMES allowlist); per-process registries stamp the constant
    process label on the local series."""
    from dss_tpu.obs.metrics import MetricsRegistry, STAGE_BUCKETS

    reg = MetricsRegistry(proc="worker-0:42")
    reg.observe_stage(
        "/v1/dss/identification_service_areas", "store_ms", 0.004
    )
    reg.observe_stage(
        "/v1/dss/identification_service_areas", "made_up_stage_ms", 0.2
    )
    text = reg.render()
    assert "# TYPE dss_stage_duration_seconds histogram" in text
    assert (
        'dss_stage_duration_seconds_bucket{'
        'route="/v1/dss/identification_service_areas",'
        f'stage="store_ms",process="worker-0:42",le="{STAGE_BUCKETS[0]}"'
        in text or
        'stage="store_ms"' in text
    )
    # unknown stage collapsed to the bounded label (the legacy
    # summary family keeps raw names; the histogram must not)
    hist_lines = [
        l for l in text.splitlines()
        if l.startswith("dss_stage_duration_seconds")
    ]
    assert any('stage="other"' in l for l in hist_lines)
    assert not any('stage="made_up_stage_ms"' in l for l in hist_lines)
    assert (
        'dss_stage_duration_seconds_count{'
        'route="/v1/dss/identification_service_areas",'
        'stage="store_ms",process="worker-0:42"} 1' in text
    )


def test_shm_worker_gauges_render_as_process_family():
    """dss_shm_worker_* are keyed gauge families labeled by the
    worker's process id — and because every multi-process registry
    already stamps a constant process="..." label on its own series,
    the renderer must NOT duplicate it on these families (a duplicate
    label name invalidates the whole scrape)."""
    from dss_tpu.api.app import _GAUGE_VEC_LABELS
    from dss_tpu.obs.metrics import MetricsRegistry

    assert _GAUGE_VEC_LABELS["dss_shm_worker_cache_hits"] == "process"
    reg = MetricsRegistry(proc="leader:123")
    reg.set_gauge_vec(
        "dss_shm_worker_cache_hits", "process", {"worker-0": 7.0}
    )
    reg.set_gauge("dss_shm_saturation", 0.25)
    text = reg.render()
    assert (
        'dss_shm_worker_cache_hits{process="worker-0"} 7.0' in text
    )
    # the leader's own gauges keep the constant label
    assert 'dss_shm_saturation{process="leader:123"} 0.25' in text
    for line in text.splitlines():
        assert line.count('process="') <= 1, line


def test_multi_process_scrape_coherence_labels():
    """Under SO_REUSEPORT consecutive scrapes land on different
    processes: every series a worker or leader exports must carry the
    distinguishing `process` label so the series never appear to
    reset across scrapes (obs/metrics.py)."""
    from dss_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry(proc="worker-1:999")
    reg.observe_request("GET", "/v1/dss/subscriptions", 200, 0.01)
    reg.set_gauge("follower_applied_seq", 42)
    reg.set_counter("dss_shm_worker_plan_shm_total", 3)
    text = reg.render()
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        assert 'process="worker-1:999"' in line, line
