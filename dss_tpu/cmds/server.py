"""The DSS server binary: flags, store bootstrap, auth setup, serve.

Collapses the reference's two processes (cmds/grpc-backend
RunGRPCServer, main.go:90-222 + cmds/http-gateway RunHTTPProxy) into
one REST server; the flag inventory mirrors grpc-backend main.go:42-73.

Run: python -m dss_tpu.cmds.server --addr :8082 --enable_scd \
         --public_key_files build/test-certs/oauth.pem \
         --accepted_jwt_audiences localhost --storage tpu
"""

from __future__ import annotations

import argparse
import os
import threading
import time

from aiohttp import web

from dss_tpu.api.app import (
    RID_SCOPES, SCD_SCOPES, build_app, make_ring_write_fn,
)
from dss_tpu.auth.authorizer import (
    Authorizer,
    JWKSResolver,
    StaticKeyResolver,
)
from dss_tpu.clock import Clock
from dss_tpu.dar.dss_store import DSSStore
from dss_tpu.services.rid import RIDService
from dss_tpu.services.scd import SCDService


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="TPU-native DSS server")
    p.add_argument("--addr", default=":8082", help="address to listen on")
    p.add_argument(
        "--storage",
        default="tpu",
        choices=["memory", "tpu"],
        help="spatial index backend (memory = host linear scan)",
    )
    p.add_argument(
        "--wal_path", default="", help="write-ahead log file (durability)"
    )
    p.add_argument("--wal_fsync", action="store_true")
    p.add_argument("--enable_scd", action="store_true")
    p.add_argument(
        "--public_key_files",
        default="",
        help="comma-separated PEM files with JWT verification keys",
    )
    p.add_argument("--jwks_endpoint", default="")
    p.add_argument("--jwks_key_ids", default="")
    p.add_argument(
        "--key_refresh_timer",
        type=float,
        default=0.0,
        help="seconds between JWKS refreshes (0 = no refresh)",
    )
    p.add_argument(
        "--accepted_jwt_audiences",
        default="",
        help="comma-separated accepted `aud` claims",
    )
    p.add_argument(
        "--insecure_no_auth",
        action="store_true",
        help="disable auth entirely (local testing only)",
    )
    p.add_argument(
        "--dump_requests",
        action="store_true",
        help="log request bodies (reference --dump_requests)",
    )
    p.add_argument(
        "--trace_requests",
        action="store_true",
        help="per-request tracing: X-Request-Id propagation + "
        "auth/service stage timings in the access log (reference "
        "--trace-requests, pkg/logging/http.go:36-55)",
    )
    p.add_argument(
        "--profile_dir",
        default="",
        help="enable POST /debug/profile?seconds=N: capture a JAX/XLA "
        "device trace into this directory under live traffic "
        "(reference --gcp_prof_service_name analog)",
    )
    p.add_argument(
        "--autotune_profile",
        default=os.environ.get("DSS_AUTOTUNE_PROFILE", ""),
        help="autotune profile JSON (dss_tpu/plan/autotune.py; "
        "emitted by `bench.py --leg autotune` into deploy/autotune/"
        "<host-class>.json): seeds the planner's cost models, the "
        "resident ring/stream depth, the AOT bucket grids, and the "
        "sharded replica's per-shard result capacity from MEASURED "
        "microbenchmarks, so a fresh process serves with converged "
        "estimates instead of paying the EWMA learning window under "
        "live traffic.  Knob precedence: explicit DSS_* env > "
        "profile > built-in defaults.  Env fallback "
        "DSS_AUTOTUNE_PROFILE",
    )
    p.add_argument(
        "--region_url",
        default="",
        help="region log server URL(s), comma-separated primary + "
        "mirrors; joins this instance to a multi-instance DSS Region "
        "(replaces the local WAL).  With mirrors listed, the client "
        "fails over on connection errors / 503 not-primary",
    )
    p.add_argument(
        "--region_token_file",
        default="",
        help="file holding the shared region secret (env "
        "DSS_REGION_TOKEN overrides)",
    )
    p.add_argument(
        "--region_poll_interval",
        type=float,
        default=0.05,
        help="seconds between region log tail polls (read staleness "
        "bound on non-writing instances)",
    )
    p.add_argument(
        "--region_snapshot_every",
        type=int,
        default=512,
        help="upload a state snapshot to the region log every N "
        "entries (bounds late-join/resync replay; the log compacts "
        "below the snapshot)",
    )
    p.add_argument(
        "--instance_id",
        default="",
        help="stable identity of this DSS instance within the region",
    )
    p.add_argument(
        "--federation_map",
        default=os.environ.get("DSS_FED_MAP", ""),
        help="path to the format-versioned multi-region federation "
        "map (S2-key-range -> region ownership + peer URLs, "
        "region/federation.py).  Joins this region to the federation: "
        "locality routing serves owned coverings locally, fans "
        "cross-region slices out to peers, and serves bounded-stale "
        "follower reads during partitions.  Env fallback DSS_FED_MAP; "
        "DSS_FED_* knobs in docs/OPERATIONS.md",
    )
    p.add_argument(
        "--federation_region",
        default=os.environ.get("DSS_FED_REGION", ""),
        help="this deployment's region id in the federation map "
        "(overrides the map's 'local' field; env DSS_FED_REGION)",
    )
    p.add_argument(
        "--push",
        action="store_true",
        default=os.environ.get("DSS_PUSH", "") == "1",
        help="enable the reverse-query push pipeline (dss_tpu/push): "
        "writes are matched against the subscription DAR through the "
        "planner's rqmatch route and fanned out to registered USS "
        "webhooks through a WAL-backed durable delivery queue "
        "(per-USS breakers/backoff, emergency-over-bulk QoS).  Env "
        "fallback DSS_PUSH=1; DSS_PUSH_* knobs in docs/OPERATIONS.md",
    )
    p.add_argument(
        "--virtual_cpu_devices",
        type=int,
        default=0,
        help="force an N-virtual-device CPU backend (testing the "
        "multi-chip path without chips; the driver's dryrun analog)",
    )
    p.add_argument(
        "--jax_coordinator",
        default="",
        help="host:port of process 0's jax.distributed coordination "
        "service: joins this server to a PROCESS-SPANNING mesh (the "
        "multi-host DCN seam, parallel/multihost.py).  Env fallback "
        "DSS_JAX_COORDINATOR.  Requires --process_id + "
        "--num_processes on every process",
    )
    p.add_argument(
        "--process_id",
        type=int,
        default=None,
        help="this process's index in the multi-host mesh (0 = "
        "leader: serves mesh queries and paces refreshes; >0 = "
        "follower compute peer).  Env fallback DSS_PROCESS_ID",
    )
    p.add_argument(
        "--num_processes",
        type=int,
        default=None,
        help="total processes in the multi-host mesh.  Env fallback "
        "DSS_NUM_PROCESSES",
    )
    p.add_argument(
        "--multihost_dryrun",
        type=int,
        default=0,
        help="CPU device override for the multi-host path: each "
        "process gets N virtual CPU devices and cross-process "
        "collectives run over gloo TCP (the DCN program without "
        "TPUs).  Env fallback DSS_MULTIHOST_DRYRUN",
    )
    p.add_argument(
        "--sharded_replica",
        default="",
        help="'dp,sp' mesh shape: serve multi-chip ShardedDar read "
        "replicas of ALL entity classes (SCD operations + "
        "subscriptions, RID ISAs + subscriptions), refreshed from the "
        "WAL (standalone) or region log tail; oversized "
        "bounded-staleness search batches offload to the mesh, and "
        "/aux/v1/replica/operations serves the ops class directly "
        "(SURVEY §7 step 7)",
    )
    p.add_argument(
        "--replica_refresh_interval",
        type=float,
        default=0.5,
        help="seconds between replica log polls / snapshot rebuilds",
    )
    p.add_argument(
        "--no_shard_rebalance",
        action="store_true",
        help="pin the sharded replica to the static equal-count "
        "postings split: disable the load-weighted boundary search "
        "(equivalent to DSS_SHARD_REBALANCE_RATIO=0).  By default the "
        "replica measures per-key-range query load and moves shard "
        "boundaries at fold cuts when imbalance exceeds "
        "DSS_SHARD_REBALANCE_RATIO (docs/OPERATIONS.md)",
    )
    p.add_argument(
        "--no_warmup",
        action="store_true",
        help="skip the background fused-kernel compile at startup",
    )
    p.add_argument(
        "--no_resident",
        action="store_true",
        help="disable the resident serving kernel (ops/resident.py): "
        "the persistent device-feeder loop with AOT-compiled shape "
        "buckets and donated I/O that amortizes the device dispatch "
        "floor across in-flight batches.  On by default for --storage "
        "tpu; the deadline router then learns a separate resident "
        "floor (DSS_CO_EST_RES_FLOOR_MS seed) and routes device-class "
        "batches through the loop",
    )
    p.add_argument(
        "--workers",
        type=int,
        default=0,
        help="spawn N read-worker processes sharing the listen port "
        "via SO_REUSEPORT: the leader owns the TPU + all mutations "
        "(journaled to the WAL), workers serve searches from a "
        "WAL-tail replica and proxy everything else to the leader "
        "(the goroutine-per-RPC scale-out analog, grpc-backend "
        "main.go:201-214).  0 = single process.  Standalone mode only.",
    )
    p.add_argument(
        "--worker_reader",
        action="store_true",
        help=argparse.SUPPRESS,  # internal: this process is a read worker
    )
    p.add_argument(
        "--leader_url",
        default="",
        help=argparse.SUPPRESS,  # internal: leader base URL for proxying
    )
    p.add_argument(
        "--shm_region",
        default="",
        help=argparse.SUPPRESS,  # internal: shared-memory ring region path
    )
    p.add_argument(
        "--shm_worker_index",
        type=int,
        default=-1,
        help=argparse.SUPPRESS,  # internal: this worker's ring index
    )
    p.add_argument(
        "--follower_poll_interval",
        type=float,
        default=0.02,
        help="read-worker WAL tail interval in seconds (staleness bound)",
    )
    p.add_argument(
        "--inline_reads",
        default="auto",
        choices=["auto", "on", "off"],
        help="run read handlers directly on the event loop instead of "
        "the thread-pool executor.  'auto' enables it on single-core "
        "hosts, where the two executor handoffs are pure overhead "
        "(reads are lock-free and sub-millisecond)",
    )
    p.add_argument(
        "--default_timeout",
        type=float,
        default=10.0,
        help="per-request deadline in seconds; exceeding it returns 504 "
        "(reference: 10s default RPC timeout, grpc-backend main.go:48). "
        "0 disables.",
    )
    p.add_argument(
        "--shutdown_grace",
        type=float,
        default=25.0,
        help="seconds SIGTERM waits for in-flight requests to complete "
        "before closing connections (reference: GracefulStop, "
        "grpc-backend main.go:217-221)",
    )
    p.add_argument(
        "--tls_cert",
        default="",
        help="TLS certificate chain (PEM) — serve HTTPS directly "
        "(deploy/make_certs.py emits server.crt/server.key; leave "
        "unset when an ingress/mesh terminates TLS)",
    )
    p.add_argument(
        "--tls_key",
        default="",
        help="TLS private key (PEM); required with --tls_cert",
    )
    return p


def resolve_backend(storage: str) -> dict:
    """Resolve the JAX backend ONCE, before the store touches it, and
    return the labels the boot log and the dss_build_info gauge carry:
    platform / device_kind / device_count as JAX reports them.

    The tpu storage backend runs on whatever jax.devices() returns,
    and JAX falls back to the CPU quietly when JAX_PLATFORMS is unset
    and no accelerator initializes.  That fallback is refused here: the
    CPU backend serves `--storage tpu` only when it was asked for by
    name (JAX_PLATFORMS / jax_platforms containing "cpu").  With
    JAX_PLATFORMS=tpu JAX itself raises when no chip is found.  The
    memory backend never touches JAX, so it resolves nothing (a
    resolve would grab the chip for a process that does not use it)."""
    if storage != "tpu":
        return {"storage": storage}
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    asked = [p for p in (jax.config.jax_platforms or "").split(",") if p]
    if platform == "cpu" and "cpu" not in asked:
        raise SystemExit(
            "--storage tpu found no accelerator (JAX resolved the cpu "
            "backend on its own); refusing to serve the device path "
            "from the CPU silently — set JAX_PLATFORMS=cpu to run it "
            "on the CPU backend on purpose, or JAX_PLATFORMS=tpu to "
            "fail inside JAX when the chip is missing"
        )
    return {
        "storage": storage,
        "platform": platform,
        "device_kind": devs[0].device_kind,
        "device_count": str(len(devs)),
    }


def _log_boot(log, boot: dict) -> None:
    """The leader's replay by stage, between `backend:` and `store
    ready:` (dssbench/metrics/boot_*.json read the numbers; the same
    are dss_boot_records and dss_boot_seconds{stage} on /metrics)."""
    if boot.get("mode") == "bulk":
        log.info(
            "boot parse: %d records read, decoded and resolved in %.2f s",
            boot["records"], boot["parse_s"],
        )
        log.info(
            "boot build: %d postings in tables of %d bytes on the device,"
            " built and uploaded in %.2f s (%.0f records/s over both"
            " stages)",
            boot["postings"], boot["device_bytes"], boot["build_s"],
            boot["records"] / max(boot["parse_s"] + boot["build_s"], 1e-9),
        )
    elif boot:
        log.info(
            "boot loop: %d records applied one by one (the bulk path"
            " refused the log: see the warning above)", boot["records"],
        )


def build_worker(args) -> web.Application:
    """A read worker: local WAL-tail replica serves searches; every
    other route proxies to the leader.  Runs on the CPU backend — the
    leader owns the (single-client) TPU; worker store queries take the
    host path, which is exact and fast at serving batch sizes."""
    from dss_tpu.api.app import make_worker_proxy_middleware
    from dss_tpu.dar.follower import WalFollower
    from dss_tpu.obs.logging import configure_logging, get_logger
    from dss_tpu.obs.metrics import MetricsRegistry

    configure_logging()
    log = get_logger("dss.worker")
    if not args.wal_path or not args.leader_url:
        raise SystemExit("--worker_reader needs --wal_path and --leader_url")
    if args.federation_map:
        raise SystemExit(
            "--worker_reader cannot serve a federated region (see the"
            " --federation_map/--workers refusal in the leader)"
        )
    import jax

    jax.config.update("jax_platforms", "cpu")
    backend = resolve_backend(args.storage)
    log.info("backend: %s", backend)
    clock = Clock()
    store = DSSStore(storage=args.storage, clock=clock)
    follower = WalFollower(
        store, args.wal_path, interval_s=args.follower_poll_interval
    )
    t_replica = time.perf_counter()
    follower.start()
    # the first catch-up is the worker's boot: the whole log as one
    # batch (mode bulk), or record by record where that was refused
    log.info(
        "worker replica ready: %d records in %.2f s (%s)",
        store.boot_stats.get("records", 0),
        time.perf_counter() - t_replica,
        store.boot_stats.get("mode", "empty log"),
    )
    log.info(
        "read worker up: replica from %s every %.0f ms, leader %s",
        args.wal_path, args.follower_poll_interval * 1000, args.leader_url,
    )
    rid_store, scd_store = store.rid, store.scd
    front = None
    if args.shm_region:
        # shared-memory serving front (parallel/shmring.py): searches
        # ride the query ring to the device owner — with a worker-
        # local version-fenced read cache answering repeat polls in
        # microseconds — instead of re-scanning the WAL-tail replica.
        # The replica stays: record assembly + proxy-fallback serving.
        from dss_tpu.dar.shmfront import (
            ShmRIDStore, ShmSCDStore, ShmSearchFront,
        )
        from dss_tpu.parallel import shmring

        region = shmring.ShmRegion.open_existing(args.shm_region)
        client = shmring.ShmWorkerClient(
            region, args.shm_worker_index
        )
        front = ShmSearchFront(
            region, client, follower, clock,
            catchup_s=float(os.environ.get("DSS_SHM_CATCHUP_S", 1.0)),
            owner_ttl_s=float(
                os.environ.get("DSS_SHM_OWNER_TTL_S", 5.0)
            ),
            owner_threads=int(
                os.environ.get("DSS_SHM_OWNER_THREADS", 0)
            ) or min(4, max(2, os.cpu_count() or 2)),
        )
        rid_store = ShmRIDStore(store.rid, front)
        scd_store = ShmSCDStore(store.scd, front)
        log.info(
            "shm front: worker %d of %d on %s (depth %d, slot %d B)",
            args.shm_worker_index, region.nworkers, args.shm_region,
            region.depth, region.slot_bytes,
        )
    rid = RIDService(rid_store, clock)
    scd = SCDService(scd_store, clock) if args.enable_scd else None
    authorizer = _make_authorizer(args)
    metrics = MetricsRegistry(
        proc=f"worker-{args.shm_worker_index}:{os.getpid()}"
        if args.shm_region else f"worker:{os.getpid()}"
    )
    if front is not None:
        # per-stage histograms across the whole front: this worker's
        # stage observations land in its shared block, and its
        # /metrics renders the MERGED dss_stage_duration_seconds
        # family (any process's scrape shows the front's tails)
        from dss_tpu.parallel.shmring import (
            StageHistWriter, shm_stage_hist,
        )

        metrics.attach_stage_writer(
            StageHistWriter(front.region, args.shm_worker_index)
        )
        metrics.set_stage_agg(
            lambda _r=front.region: shm_stage_hist(_r)
        )
    from dss_tpu.build_info import build_info

    metrics.set_info("dss_build_info", {**build_info(), **backend})

    leader_wait_s = 0.0

    def stats_fn():
        out = store.stats()
        out["dss_boot_seconds"]["leader_wait"] = leader_wait_s
        out.update(follower.stats())
        if front is not None:
            out.update(front.stats())
        return out

    def wait_for_leader() -> None:
        """Block until the leader serves: its loopback answers /healthy
        and the owner's heartbeat in the region is fresh (no region
        with DSS_SHM_ENABLE=0: the loopback's answer alone).  main()
        binds the public port only after this, so /healthy there means
        the whole front is up however the processes' boots fall.  No
        time-out of its own: the loopback has listened since before
        this worker was born, so a probe sent early waits in its
        backlog and is answered the moment the leader serves, and a
        REFUSED one means the leader is gone (as _watch_parent, which
        cannot see a leader that died before this process could look
        for it)."""
        nonlocal leader_wait_s
        import http.client
        from urllib.parse import urlsplit

        t0 = time.perf_counter()
        netloc = urlsplit(args.leader_url).netloc
        while True:
            conn = http.client.HTTPConnection(netloc, timeout=5.0)
            try:
                conn.request("GET", "/healthy")
                up = conn.getresponse().status == 200
            except ConnectionRefusedError:
                raise SystemExit(
                    f"leader {args.leader_url} is gone: worker exits"
                ) from None
            except (OSError, http.client.HTTPException):
                up = False  # still booting: the probe timed out
            finally:
                conn.close()
            if up and (
                front is None
                or front.region.owner_heartbeat_age_s() < front.owner_ttl_s
            ):
                break
            time.sleep(0.05)
        leader_wait_s = time.perf_counter() - t0
        log.info("worker waited for the leader: %.2f s", leader_wait_s)

    app = build_app(
        rid,
        scd,
        authorizer,
        enable_scd=args.enable_scd,
        metrics=metrics,
        dump_requests=args.dump_requests,
        stats_fn=stats_fn,
        status_fn=lambda: {**store.freshness_status(), "backend": backend},
        health_fn=store.health.mode_name,
        default_timeout_s=args.default_timeout,
        trace_requests=args.trace_requests,
        # ring waits block their thread: searches must stay on the
        # executor, never the event loop, when the front is attached
        # shm-front workers run optimistic inline reads regardless of
        # core count: a worker-cache hit is microseconds on the event
        # loop, and the front raises NeedsDevice before anything that
        # blocks (ring round trip, replica catchup) so misses re-run
        # on the executor — see ShmSearchFront.serve
        inline_reads=(
            args.inline_reads != "off" if args.shm_region
            else _inline_reads(args)
        ),
        worker_proxy=make_worker_proxy_middleware(
            args.leader_url, follower=follower,
            costs=front.costs if front is not None else None,
            # a PUT of an op reference rides the ring to the owner's
            # write lane, authenticated here as the leader would
            ring=front,
            authorizer=authorizer,
        ),
    )
    # the worker's boot heap is the initially-replayed WAL; tail
    # records arriving later stay in normal generations
    from dss_tpu.runtime import freeze_boot_heap

    freeze_boot_heap()
    app["dss_wait_for_leader"] = wait_for_leader
    return app


def _inline_reads(args) -> bool:
    if args.inline_reads == "on":
        return True
    if args.inline_reads == "off":
        return False
    return (os.cpu_count() or 2) == 1


def _make_authorizer(args):
    if args.insecure_no_auth:
        return None
    if args.public_key_files:
        resolver = StaticKeyResolver.from_files(
            [f for f in args.public_key_files.split(",") if f]
        )
    elif args.jwks_endpoint:
        resolver = JWKSResolver(
            args.jwks_endpoint,
            [k for k in args.jwks_key_ids.split(",") if k] or None,
        )
    else:
        raise SystemExit(
            "one of --public_key_files / --jwks_endpoint is required "
            "(or --insecure_no_auth)"
        )
    audiences = [a for a in args.accepted_jwt_audiences.split(",") if a]
    if not audiences:
        raise SystemExit(
            "--accepted_jwt_audiences is required when auth is enabled "
            "(every token would be rejected otherwise)"
        )
    scopes = dict(RID_SCOPES)
    scopes.update(SCD_SCOPES)
    return Authorizer(
        resolver,
        audiences=audiences,
        scopes_table=scopes,
        refresh_interval_s=args.key_refresh_timer or None,
    )


def build(args) -> web.Application:
    from dss_tpu.obs.logging import configure_logging, get_logger
    from dss_tpu.obs.metrics import MetricsRegistry

    if args.worker_reader:
        return build_worker(args)

    configure_logging()
    log = get_logger("dss.server")
    from dss_tpu.build_info import build_info

    log.info("build: %s", build_info())
    if args.virtual_cpu_devices:
        # must land before the first backend initialization
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
            f"{args.virtual_cpu_devices}"
        ).strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
    backend = resolve_backend(args.storage)
    log.info("backend: %s", backend)
    clock = Clock()
    region_token = os.environ.get("DSS_REGION_TOKEN", "")
    if not region_token and args.region_token_file:
        with open(args.region_token_file, "r", encoding="utf-8") as fh:
            region_token = fh.read().strip()
    from dss_tpu.ops import compile_site as _compile_site

    # the replay's build compiles on this thread before any request
    # can wait for it: the boot's (dss_jax_compiles_boot_warm)
    with _compile_site("boot_warm"):
        store = DSSStore(
            storage=args.storage,
            clock=clock,
            wal_path=args.wal_path or None,
            wal_fsync=args.wal_fsync,
            region_url=args.region_url or None,
            region_token=region_token or None,
            region_poll_interval_s=args.region_poll_interval,
            region_snapshot_every=args.region_snapshot_every,
            instance_id=args.instance_id or None,
        )
    _log_boot(log, store.boot_stats)
    log.info(
        "store ready: storage=%s wal=%s scd=%s region=%s",
        args.storage,
        args.wal_path or "(none)",
        args.enable_scd,
        args.region_url or "(standalone)",
    )
    log.info(
        "read cache: %s (cap=%d entries, stale_lag=%d gens; "
        "DSS_CACHE_* / configure_serving(cache=) to change)",
        "enabled" if store.cache.enabled else "disabled",
        store.cache.capacity,
        store.cache.stale_lag,
    )
    fed_router = None
    if args.federation_map and args.workers > 0:
        # worker readers serve searches from a plain WAL-tail replica
        # with no federation layer: a cross-region covering landing on
        # a worker would return a silently PARTIAL answer, and peer
        # federation calls would 404.  Refuse the combination until
        # workers grow federation-aware routing (ROADMAP item 1's
        # scale-out front is where that lands).
        raise SystemExit(
            "--federation_map with --workers > 0 is not supported yet:"
            " read workers would serve cross-region coverings"
            " partially; run federated instances single-process"
        )
    if args.federation_map:
        # multi-region federation: attach BEFORE building services so
        # they see the federated store wrappers (locality routing +
        # ownership-guarded writes + bounded-stale remote reads)
        from dss_tpu.region import federation as fedmod

        fmap = fedmod.FederationMap.load(
            args.federation_map, local=args.federation_region or None
        )
        fed_router = fedmod.FederationRouter.from_map(
            fmap,
            token=os.environ.get("DSS_FED_TOKEN") or None,
            **fedmod.env_knobs(),
        )
        store.attach_federation(fed_router)
        log.info(
            "federation: region %s of %s (stale lag bound %.1fs, "
            "sync every %.2fs)",
            fmap.local, fmap.region_ids, fed_router.stale_lag_s,
            fed_router.sync_interval_s,
        )
    rid = RIDService(store.rid, clock)
    scd = SCDService(store.scd, clock) if args.enable_scd else None

    # resident serving kernel: on by default on the tpu backend — the
    # coalescers grow the persistent device-feeder route and install
    # fold-time AOT warm hooks; the bucket-grid boot warm runs on the
    # warm thread below so the multi-second XLA compiles never race a
    # request deadline
    use_resident = args.storage == "tpu" and not args.no_resident
    if use_resident:
        store.configure_serving(resident=True)

    warm_thread = None
    if args.storage == "tpu" and not args.no_warmup:
        # compile the fused kernel's point-lookup executable in the
        # background so the first real request after boot doesn't burn
        # its 10 s deadline on the XLA compile (an early request still
        # waits on the same in-flight compile — never a double compile)
        from dss_tpu.ops.fastpath import warmup as _fastpath_warmup

        # this thread's compiles are the boot's, no request's
        # (dss_jax_compiles_boot_warm)
        @_compile_site("boot_warm")
        def _warm():
            try:
                t0 = time.perf_counter()
                _fastpath_warmup()
                log.info(
                    "fastpath warmup done in %.1fs",
                    time.perf_counter() - t0,
                )
            except Exception:  # noqa: BLE001 — warmup is best-effort
                log.exception("fastpath warmup failed")
            if use_resident:
                try:
                    t0 = time.perf_counter()
                    n = store.warm_resident()
                    log.info(
                        "resident AOT warm: %d bucket executables "
                        "in %.1fs",
                        n, time.perf_counter() - t0,
                    )
                except Exception:  # noqa: BLE001 — best-effort
                    log.exception("resident warm failed")

        warm_thread = threading.Thread(
            target=_warm, name="fastpath-warmup", daemon=True
        )
        warm_thread.start()

    authorizer = _make_authorizer(args)

    metrics = MetricsRegistry(
        proc=f"leader:{os.getpid()}" if args.workers > 0 else None
    )
    metrics.set_info("dss_build_info", {**build_info(), **backend})

    mh_runtime = getattr(args, "_mh_runtime", None)
    if mh_runtime is not None:
        # peer loss climbs the degradation ladder: the mesh route is
        # already refused via replica freshness, this makes the mode
        # explicit stack-wide (/status, X-DSS-Freshness, the
        # dss_degraded_mode gauge + DssDegradedMode alert)
        mh_runtime.on_degraded(
            lambda: store.health.enter(
                "mesh_degraded", mh_runtime.degraded_reason
            )
        )
    replica = None
    if args.sharded_replica:
        import jax
        import numpy as _np

        from dss_tpu.parallel.replica import ShardedReplica
        from jax.sharding import Mesh

        try:
            dp, sp = (int(x) for x in args.sharded_replica.split(","))
        except ValueError:
            raise SystemExit(
                f"--sharded_replica must be 'dp,sp' (got "
                f"{args.sharded_replica!r})"
            )
        region_client = None
        if args.region_url:
            from dss_tpu.region.client import RegionClient

            region_client = RegionClient(
                args.region_url,
                (args.instance_id or "dss") + "-replica",
                auth_token=region_token or None,
            )
        elif not args.wal_path:
            raise SystemExit(
                "--sharded_replica needs --wal_path or --region_url "
                "(a log to tail)"
            )
        # every bucket a mesh-offloaded chunk can land in (chunks are
        # <= 64; remainders bucket to 16/32): the first offload must
        # never stall on a compile
        warm = (1, 32, 64)
        if mh_runtime is not None:
            # process-spanning mesh: dp,sp names the GLOBAL shape
            from dss_tpu.parallel.mesh import make_global_mesh
            from dss_tpu.parallel.multihost import MultihostReplica

            try:
                placement = make_global_mesh(dp=dp, sp=sp)
            except ValueError as e:
                raise SystemExit(f"--sharded_replica {dp},{sp}: {e}")
            replica = MultihostReplica(
                mh_runtime,
                placement,
                wal_path=args.wal_path or None,
                region_client=region_client,
                warm_batches=warm,
            )
            if args.no_shard_rebalance:
                replica._inner.rebalance_ratio = 0.0
            if mh_runtime.is_leader:
                replica.start(args.replica_refresh_interval)
                store.attach_mesh_replica(replica)
            else:
                # compute peer: replay the leader's command stream;
                # its own HTTP reads answer exactly from the host map
                threading.Thread(
                    target=replica.run_follower,
                    name="multihost-follower",
                    daemon=True,
                ).start()
            log.info(
                "multi-host sharded replica: process %d/%d, global "
                "%dx%d mesh, placement %s (%s)",
                mh_runtime.process_id, mh_runtime.num_processes,
                dp, sp, placement.describe(),
                "region log" if args.region_url else "wal",
            )
        else:
            devs = jax.devices()
            if len(devs) < dp * sp:
                raise SystemExit(
                    f"--sharded_replica {dp},{sp} needs {dp * sp} "
                    f"devices, have {len(devs)}"
                )
            mesh = Mesh(
                _np.array(devs[: dp * sp]).reshape(dp, sp), ("dp", "sp")
            )
            if region_client is not None:
                replica = ShardedReplica(
                    mesh, region_client=region_client, warm_batches=warm
                )
            else:
                replica = ShardedReplica(
                    mesh, wal_path=args.wal_path, warm_batches=warm
                )
            if args.no_shard_rebalance:
                replica.rebalance_ratio = 0.0
            replica.start(args.replica_refresh_interval)
            # oversized bounded-staleness search batches ride the mesh
            store.attach_mesh_replica(replica)
            log.info(
                "sharded replica serving all entity classes on a "
                "%dx%d mesh (%s)",
                dp, sp, "region log" if args.region_url else "wal",
            )

    push = None
    if args.push:
        from dss_tpu.push import PushPipeline
        from dss_tpu.push.pipeline import env_knobs as _push_knobs

        push = PushPipeline(metrics=metrics, **_push_knobs())
        store.attach_push(push)
        log.info(
            "push pipeline: %d delivery workers, queue bound %d, log "
            "%s (DSS_PUSH_* knobs in docs/OPERATIONS.md)",
            push.pool._workers, push.log.max_depth,
            os.environ.get("DSS_PUSH_LOG") or "(in-memory)",
        )

    def stats_fn():
        out = store.stats()
        if replica is not None:
            out.update(replica.stats())
        elif mh_runtime is not None:
            out.update(mh_runtime.stats())
        return out

    app = build_app(
        rid,
        scd,
        authorizer,
        enable_scd=args.enable_scd,
        metrics=metrics,
        dump_requests=args.dump_requests,
        stats_fn=stats_fn,
        status_fn=lambda: {**store.freshness_status(), "backend": backend},
        health_fn=store.health.mode_name,
        default_timeout_s=args.default_timeout,
        replica=replica,
        federation=fed_router,
        push=push,
        trace_requests=args.trace_requests,
        profile_dir=args.profile_dir,
        inline_reads=_inline_reads(args),
        # workers wait on this seq for read-your-writes after a
        # proxied mutation
        wal_seq_fn=(lambda: store.wal.seq) if args.workers > 0 else None,
    )
    # main() attaches the shared-memory front to the store (workers
    # mode) after the listen sockets exist, with this write lane
    app["dss_store"] = store
    app["dss_metrics"] = metrics
    app["dss_ring_write"] = make_ring_write_fn({"scd": scd}, metrics)

    # autotune profile provenance: stable gauge whether or not a
    # profile was loaded — 0.0 means "no profile or no timestamp",
    # the alertable case is large
    metrics.set_gauge(
        "dss_autotune_profile_age_s",
        float(getattr(args, "_autotune_profile_age_s", 0.0)),
    )

    from dss_tpu.obs import trace as _trace

    if _trace.enabled():
        cfg = _trace.env_config()
        log.info(
            "tracing: sample=%g slow_ms=%g ring=%d "
            "(/aux/v1/debug/traces; DSS_TRACE_* in OPERATIONS.md)",
            cfg["sample"], cfg["slow_ms"], cfg["ring"],
        )

    # park the boot heap outside GC scans once boot actually finishes:
    # after the background warmup compile (its caches are part of the
    # boot heap; freezing mid-compile would pin transients instead)
    # and after the sharded replica's first full log sync (its record
    # maps are the largest heap in replica mode).  When neither is
    # pending the freeze runs synchronously, before serving starts.
    from dss_tpu.runtime import freeze_boot_heap

    def _freeze_after_boot():
        if warm_thread is not None:
            warm_thread.join()
        if replica is not None:
            deadline = time.monotonic() + 300.0
            while (
                replica.staleness_s() == float("inf")
                and time.monotonic() < deadline
            ):
                time.sleep(0.5)
        # a handful of requests may be in flight by now; collect()
        # first so only their live frames (bounded, one-time) can pin
        freeze_boot_heap()

    if warm_thread is None and replica is None:
        freeze_boot_heap()
    else:
        threading.Thread(
            target=_freeze_after_boot, name="gc-freeze", daemon=True
        ).start()
    return app


def _public_socket(addr: str, reuse_port: bool):
    import socket

    host, _, port = addr.rpartition(":")
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    if reuse_port:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
    s.bind((host or "0.0.0.0", int(port)))
    s.listen(1024)
    return s


def _process_age_s() -> float:
    """Seconds since the kernel started this process (interpreter
    start-up and this module's imports, most of what precedes main(),
    included)."""
    with open("/proc/self/stat", "r", encoding="ascii") as fh:
        start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
    return (
        time.clock_gettime(time.CLOCK_BOOTTIME)
        - start_ticks / os.sysconf("SC_CLK_TCK")
    )


def _watch_parent():
    """Read workers exit when the leader dies (no orphaned listeners
    competing on the port)."""
    import threading
    import time as _time

    parent = os.getppid()

    def loop():
        while True:
            if os.getppid() != parent:
                os._exit(0)
            _time.sleep(1.0)

    threading.Thread(target=loop, name="parent-watch", daemon=True).start()


def _forward_args(args, leader_url: str, worker_index: int = -1):
    """argv for a read-worker child."""
    out = [
        "--worker_reader",
        "--leader_url", leader_url,
        "--addr", args.addr,
        "--storage", args.storage,
        "--wal_path", args.wal_path,
        "--default_timeout", str(args.default_timeout),
        "--shutdown_grace", str(args.shutdown_grace),
        "--follower_poll_interval", str(args.follower_poll_interval),
        "--inline_reads", args.inline_reads,
    ]
    if getattr(args, "_shm_path", ""):
        out += [
            "--shm_region", args._shm_path,
            "--shm_worker_index", str(worker_index),
        ]
    if args.enable_scd:
        out.append("--enable_scd")
    if args.insecure_no_auth:
        out.append("--insecure_no_auth")
    if args.public_key_files:
        out += ["--public_key_files", args.public_key_files]
    if args.jwks_endpoint:
        out += ["--jwks_endpoint", args.jwks_endpoint]
    if args.jwks_key_ids:
        out += ["--jwks_key_ids", args.jwks_key_ids]
    if args.key_refresh_timer:
        out += ["--key_refresh_timer", str(args.key_refresh_timer)]
    if args.accepted_jwt_audiences:
        out += ["--accepted_jwt_audiences", args.accepted_jwt_audiences]
    if args.dump_requests:
        out.append("--dump_requests")
    if args.trace_requests:
        out.append("--trace_requests")
    return out


def main():
    import atexit
    import socket
    import subprocess
    import sys
    import tempfile

    args = make_parser().parse_args()

    if args.autotune_profile:
        # seed serving knobs from the measured host profile BEFORE any
        # store/coalescer construction reads the env (env > profile >
        # defaults; worker children inherit the seeded environment)
        from dss_tpu.plan import autotune as _autotune

        from dss_tpu.obs.logging import get_logger

        profile = _autotune.load_profile(args.autotune_profile)
        applied = _autotune.apply_profile(profile)
        _plog = get_logger("dss.server")
        _plog.info(
            "autotune profile %s (host class %s): seeded %s",
            args.autotune_profile,
            profile.get("host_class", "?"),
            ", ".join(f"{k}={v}" for k, v in sorted(applied.items()))
            or "nothing (env overrides everything)",
        )
        stale = _autotune.profile_staleness(profile)
        if not stale["host_class_match"]:
            _plog.warning(
                "AUTOTUNE PROFILE HOST-CLASS MISMATCH: profile "
                "measured on %r, this host is %r — the seeded cost "
                "models describe a DIFFERENT machine; re-run "
                "`bench.py --leg autotune` here",
                stale["profile_host_class"], stale["host_class"],
            )
        if not stale["has_timestamp"]:
            _plog.warning(
                "autotune profile %s has no measured_at timestamp "
                "(pre-provenance format): age unknown, treating as "
                "fresh; re-run `bench.py --leg autotune` to stamp it",
                args.autotune_profile,
            )
        elif stale["age_s"] > 30 * 86400.0:
            _plog.warning(
                "autotune profile %s is %.0f days old: the measured "
                "cost models may no longer describe this host; "
                "re-run `bench.py --leg autotune`",
                args.autotune_profile, stale["age_s"] / 86400.0,
            )
        # build() exports the age as dss_autotune_profile_age_s
        args._autotune_profile_age_s = stale["age_s"]

    from dss_tpu.cmds import make_ssl_context

    ssl_ctx = make_ssl_context(args.tls_cert, args.tls_key)

    # multi-host mesh: join BEFORE any jax backend touch (flags with
    # DSS_JAX_COORDINATOR / DSS_PROCESS_ID / DSS_NUM_PROCESSES /
    # DSS_MULTIHOST_DRYRUN env fallbacks)
    from dss_tpu.parallel.multihost import MultihostConfig
    from dss_tpu.parallel import multihost as _mh

    mh_cfg = MultihostConfig.from_flags(
        args.jax_coordinator,
        args.process_id,
        args.num_processes,
        args.multihost_dryrun,
    )
    if mh_cfg is not None:
        if args.workers > 0:
            raise SystemExit(
                "--workers and --jax_coordinator are mutually "
                "exclusive (one process per host in a multi-host mesh)"
            )
        if args.worker_reader:
            raise SystemExit(
                "--worker_reader cannot join a multi-host mesh"
            )
        args._mh_runtime = _mh.initialize(mh_cfg)

    if args.worker_reader:
        _watch_parent()
        app = build(args)
        # replica caught up, ring opened: nothing listens on the public
        # port until the leader serves too
        app["dss_wait_for_leader"]()
        sock = _public_socket(args.addr, reuse_port=True)
        web.run_app(
            app,
            sock=sock,
            shutdown_timeout=args.shutdown_grace,
            ssl_context=ssl_ctx,
        )
        return

    if args.workers > 0:
        if args.region_url:
            raise SystemExit(
                "--workers is standalone-only (region instances already "
                "scale horizontally; run more instances instead)"
            )
        if ssl_ctx is not None:
            raise SystemExit(
                "--tls_cert is single-process only: the worker fleet "
                "shares one leader loopback that must stay plaintext — "
                "terminate TLS at the ingress for --workers deployments "
                "(docs/OPERATIONS.md)"
            )
        if not args.wal_path:
            args.wal_path = os.path.join(
                tempfile.mkdtemp(prefix="dss-wal-"), "wal.jsonl"
            )
        # shared-memory serving front (parallel/shmring.py), on by
        # default: the region file must exist BEFORE workers boot.
        # DSS_SHM_ENABLE=0 falls back to plain WAL-tail workers.
        from dss_tpu.dar.coalesce import _env_bool
        from dss_tpu.parallel import shmring

        shm_raw = os.environ.get("DSS_SHM_ENABLE")
        shm_enable = True if shm_raw is None else _env_bool(shm_raw)
        shm_path = ""
        region = None
        if shm_enable:
            shm_path = os.path.join(
                tempfile.mkdtemp(prefix="dss-shm-"), "ring.shm"
            )
            region = shmring.ShmRegion.create(
                shm_path, nworkers=args.workers, **shmring.env_knobs()
            )
        args._shm_path = shm_path
        # the loopback the workers proxy writes to: bound and listening
        # before they are born, served once build() has an app
        internal = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        internal.bind(("127.0.0.1", 0))
        internal.listen(1024)
        leader_url = f"http://127.0.0.1:{internal.getsockname()[1]}"

        def spawn_worker(i):
            return subprocess.Popen(
                [sys.executable, "-m", "dss_tpu.cmds.server"]
                + _forward_args(args, leader_url, worker_index=i)
            )

        from dss_tpu.obs.logging import get_logger

        children: list = []
        stopping = threading.Event()

        def reap():
            stopping.set()
            for c in children:
                if c.poll() is None:
                    c.terminate()
            for c in children:
                try:
                    c.wait(timeout=args.shutdown_grace + 5)
                except subprocess.TimeoutExpired:
                    c.kill()

        # The front boots side by side: the workers are born BEFORE
        # the leader's build(), so the three processes import, read
        # the log and build their state at the same time.  A worker
        # needs nothing the leader computes (the log's path, the
        # region and the loopback URL all exist by now), and one that
        # is ready first keeps the public port closed until the leader
        # serves (build_worker's wait_for_leader), so /healthy still
        # means the whole front is up.  reap is registered first: a
        # leader that exits in build() takes its workers with it.
        atexit.register(reap)
        children.extend(spawn_worker(i) for i in range(args.workers))
        owner = None  # the ring's drain, once build() has a store

        # a dead worker's in-flight ring slots are reclaimed the
        # moment the leader reaps it (the heartbeat TTL is the
        # backstop for a wedged-but-alive worker), and the worker is
        # RESPAWNED: with the shm front on, the leader leaves the
        # public port entirely to the workers, so an unreplaced crash
        # would permanently shrink — and at zero workers eliminate —
        # the service's public listeners.  A crash-looping worker
        # (died within 10s of spawn) backs off exponentially to 30s;
        # one that served a while restarts on the next tick.  The
        # watch runs through the leader's own boot too.
        def watch_children():
            import time as _time

            log = get_logger("dss.server")
            backoff = [0.5] * len(children)
            respawn_at = [0.0] * len(children)
            spawned_at = [_time.monotonic()] * len(children)
            dead: set = set()
            while not stopping.is_set():
                now = _time.monotonic()
                for i, c in enumerate(children):
                    if c.poll() is None:
                        continue
                    if i not in dead:
                        dead.add(i)
                        freed = (
                            owner.reclaim_worker(i)
                            if owner is not None else 0
                        )
                        if now - spawned_at[i] < 10.0:
                            backoff[i] = min(backoff[i] * 2, 30.0)
                        else:
                            backoff[i] = 0.5
                        respawn_at[i] = now + backoff[i]
                        log.warning(
                            "worker %d exited (rc=%s); reclaimed %d "
                            "in-flight shm slots; respawn in %.1fs",
                            i, c.returncode, freed, backoff[i],
                        )
                    elif now >= respawn_at[i] and not stopping.is_set():
                        children[i] = spawn_worker(i)
                        spawned_at[i] = _time.monotonic()
                        dead.discard(i)
                        log.warning(
                            "worker %d respawned (pid %d)",
                            i, children[i].pid,
                        )
                _time.sleep(0.5)

        threading.Thread(
            target=watch_children, name="worker-watch", daemon=True
        ).start()
        get_logger("dss.server").info(
            "workers spawned: %d at %.2f s",
            args.workers, _process_age_s(),
        )

        app = build(args)
        if region is not None:
            owner = app["dss_store"].attach_shm_front(
                region,
                threads=int(
                    os.environ.get("DSS_SHM_OWNER_THREADS", 0)
                ) or None,
                worker_ttl_s=float(
                    os.environ.get("DSS_SHM_WORKER_TTL_S", 5.0)
                ),
                write_fn=app["dss_ring_write"],
            )
            # the leader's stage observations (writes on the ring's
            # write lane or the loopback proxy) land in block N; its
            # /metrics also renders the merged whole-front stage
            # histograms
            app["dss_metrics"].attach_stage_writer(
                shmring.StageHistWriter(region, args.workers)
            )
            app["dss_metrics"].set_stage_agg(
                lambda _r=region: shmring.shm_stage_hist(_r)
            )
            # a write is observed twice, by the worker around its wait
            # for the leader and here: the owner's own interval gets a
            # name of its own beside the merged handler_ms
            app["dss_metrics"].handler_stages = (
                "handler_ms", "leader_handler_ms",
            )
        # With the shm front attached the leader is a PURE device
        # owner: it serves the ring plus the loopback port, and leaves
        # the public port entirely to the workers.  A public
        # connection landing on the leader would be served at
        # single-process latency AND steal owner CPU from the ring
        # drain — measured, that one topology leak capped the whole
        # front near the r06 ceiling.  Plain SO_REUSEPORT mode
        # (DSS_SHM_ENABLE=0) keeps the historical shared public bind.
        if region is not None:
            leader_socks = [internal]
        else:
            leader_socks = [
                _public_socket(args.addr, reuse_port=True), internal,
            ]
        web.run_app(
            app,
            sock=leader_socks,
            shutdown_timeout=args.shutdown_grace,
        )
        return

    app = build(args)
    host, _, port = args.addr.rpartition(":")
    # run_app installs SIGINT/SIGTERM handlers: the listener stops
    # accepting, in-flight requests get shutdown_timeout to finish,
    # then connections close (the GracefulStop analog)
    web.run_app(
        app,
        host=host or "0.0.0.0",
        port=int(port),
        shutdown_timeout=args.shutdown_grace,
        ssl_context=ssl_ctx,
    )


if __name__ == "__main__":
    main()
