"""North-star benchmark: SCD conflict queries/sec against a 1M-intent DAR.

The table under test is a real serving-stack DarTable (dar/snapshot.py)
populated via bulk_load — the same immutable-snapshot object the DSS
service reads — so the headline number runs against the snapshot the
service would serve, and a second leg measures the full serving path
(DarTable.query_many via the QueryCoalescer, request-per-thread).

This replaces the reference's per-query SQL conflict scan
(pkg/scd/store/cockroach/operations.go:374-435); the reference itself
publishes no numbers (BASELINE.md), so vs_baseline is against the
BASELINE.json north star of 100k conflict queries/sec.

Legs:
  - headline pipelined: submit all batches (async) against the
    DarTable's device snapshot, collect in order — steady-state
    conflict-check throughput; device work + transfers of batch i+1
    overlap the host decode of batch i.
  - single-batch latency: one submit+collect with a full sync — the
    cold request-to-result latency (one full dispatch round trip;
    see dispatch_floor_ms).
  - kernel-only: the fused device kernel re-invoked on device-resident
    inputs — the pure device throughput ceiling.
  - serving path: N closed-loop client threads issuing single conflict
    queries through the QueryCoalescer (continuous micro-batching) ->
    honest p50/p99 + qps through DarTable.query_many, overlay/dead-slot
    filtering included.  Coalesced batches <= 64 answer exactly from
    the host postings copy (FastTable.query_host) — no device round
    trip — which is what puts the p50 under the 5 ms north-star bound;
    bigger bursts amortize the device trip on the fused kernel.
    dispatch_floor_ms is the measured minimal device round trip of
    the backend this process runs on (not measured on the chip yet).

Prints ONE JSON line:
  {"metric": ..., "value": qps, "unit": "queries/s", "vs_baseline": x}
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

import dss_tpu.ops.conflict as C  # noqa: F401  (enables x64 before jax init)
from dss_tpu import errors
from dss_tpu.dar.coalesce import QueryCoalescer
from dss_tpu.dar.oracle import Record
from dss_tpu.dar.snapshot import DarTable

import jax
import jax.numpy as jnp

from dss_tpu.ops import fastpath

HOUR = 3_600_000_000_000
NOW = 1_700_000_000_000_000_000


def build_table(n_entities: int, n_cells: int, kpe: int, seed: int = 0):
    """Synthetic dense-urban DAR: n_entities intents, kpe level-13
    cells each, over an n_cells metro region — loaded into a real
    serving DarTable."""
    rng = np.random.default_rng(seed)
    keys = np.sort(
        rng.integers(0, n_cells, (n_entities, kpe)).astype(np.int32), axis=1
    )
    alt_lo = rng.uniform(0, 3000, n_entities).astype(np.float32)
    alt_hi = alt_lo + rng.uniform(10, 600, n_entities).astype(np.float32)
    t0 = NOW + rng.integers(-4, 4, n_entities) * HOUR
    t1 = t0 + rng.integers(1, 6, n_entities) * HOUR
    records = [
        Record(
            entity_id=f"e{i}",
            keys=keys[i],
            alt_lo=float(alt_lo[i]),
            alt_hi=float(alt_hi[i]),
            t_start=int(t0[i]),
            t_end=int(t1[i]),
            owner_id=i & 0xFFFF,
        )
        for i in range(n_entities)
    ]
    table = DarTable(delta_capacity=8192)
    table.bulk_load(records)
    return table


def make_batch(seed, batch, n_cells, width):
    """A typical op-intent conflict check: the intent's own covering
    (~width contiguous level-13 cells), a ~300 m altitude band, a ~1 h
    window."""
    r = np.random.default_rng(seed)
    start = r.integers(0, n_cells - width, batch)
    keys = (start[:, None] + np.arange(width)[None, :]).astype(np.int32)
    alo = r.uniform(0, 3000, batch).astype(np.float32)
    t0 = NOW + r.integers(-2, 2, batch) * HOUR
    return (
        keys,
        alo,
        (alo + 300.0).astype(np.float32),
        t0.astype(np.int64),
        (t0 + HOUR).astype(np.int64),
    )


def headline(ft, batch, reps, n_cells, width):
    """Pipelined fused-path throughput against the serving snapshot."""
    q0 = make_batch(100, batch, n_cells, width)
    qidx, slots = ft.query_fused(*q0, now=NOW)  # compile + warmup
    n_hits = len(slots)
    batches = [make_batch(200 + i, batch, n_cells, width) for i in range(reps)]

    # two producer threads submit (host work: searchsorted + window
    # packing) while two collector threads drain (D2H wait + decode):
    # the big numpy ops release the GIL, so host stages of different
    # batches genuinely overlap on top of the device overlap
    import queue as _queue

    in_q: _queue.Queue = _queue.Queue()
    pend_q: _queue.Queue = _queue.Queue(maxsize=8)
    _DONE = object()  # distinct from submit()'s None (empty batch)
    n_done = [0, 0]  # per-collector (no shared += race)

    def producer():
        while True:
            try:
                qb = in_q.get_nowait()
            except _queue.Empty:
                return
            pend_q.put(ft.submit(*qb, now=NOW))

    def collector(slot):
        while True:
            p = pend_q.get()
            if p is _DONE:
                return
            ft.collect(p)
            n_done[slot] += 1

    def one_pass():
        for qb in batches:
            in_q.put(qb)
        n_done[0] = n_done[1] = 0
        t0 = time.perf_counter()
        prods = [threading.Thread(target=producer) for _ in range(2)]
        colls = [
            threading.Thread(target=collector, args=(i,)) for i in range(2)
        ]
        for t in prods + colls:
            t.start()
        for t in prods:
            t.join()
        for _ in colls:
            pend_q.put(_DONE)
        for t in colls:
            t.join()
        dt = time.perf_counter() - t0
        assert sum(n_done) == reps
        return dt

    # kernel-only: stage one batch's device inputs once, then chain
    # executions of the fused kernel (no H2D, no host decode)
    qb = batches[0]
    packed, windows, *_ = ft._pack_query(*qb, NOW)
    t0_eff = np.maximum(qb[3], np.int64(NOW))
    mw = fastpath.max_words_for(windows)

    def staged(i):
        # vary the time bound by 1ns per rep: defeats any result
        # memoization while keeping the compiled executable and result
        # shapes identical
        fastpath.pack_bounds(
            packed, windows, qb[1], qb[2], t0_eff + i, qb[4]
        )
        return jax.block_until_ready(jnp.asarray(packed))

    def kernel(dev_packed):
        return ft._fused_xla(
            ft.b_alo, ft.b_ahi, ft.b_t0, ft.b_t1, dev_packed,
            windows=windows, max_words=mw,
        )

    int(kernel(staged(0))[0])
    kreps = reps * 4
    inputs = [staged(i) for i in range(kreps)]
    t0 = time.perf_counter()
    outs = [kernel(x) for x in inputs]
    # chain the executions, then force completion by fetching the last
    # output's count word (the fetch cannot return before the compute)
    int(outs[-1][0])
    dt_kernel = time.perf_counter() - t0

    # five passes, the MEDIAN reported; the worst pass rides along so
    # the run-to-run spread of this host is visible in the record
    passes = sorted(one_pass() for _ in range(5))
    dt_pipe = passes[len(passes) // 2]

    # single-batch latency (full sync per batch)
    lat = []
    for qb in batches[: min(4, reps)]:
        t0 = time.perf_counter()
        ft.query_fused(*qb, now=NOW)
        lat.append(time.perf_counter() - t0)
    lat_ms = sorted(lat)[len(lat) // 2] * 1000
    return {
        "qps": batch * reps / dt_pipe,
        "pipelined_batch_ms": dt_pipe / reps * 1000,
        "worst_pass_batch_ms": passes[-1] / reps * 1000,
        "single_batch_latency_ms": lat_ms,
        "kernel_only_qps": batch * kreps / dt_kernel,
        "warmup_hits_per_query": n_hits / batch,
    }


def dispatch_floor_ms() -> float:
    """Median minimal device round trip (tiny op + host fetch) — the
    backend's per-request latency floor, independent of this
    framework."""
    x = jnp.zeros(8, jnp.float32)
    float(jnp.sum(x))  # compile
    ts = []
    for i in range(10):
        t0 = time.perf_counter()
        float(jnp.sum(x + i))
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2] * 1000


def dispatch_floor_split(ft, n_cells, stream: int = 24) -> dict:
    """The r6 tentpole's honesty split: the SAME minimal fused-kernel
    batch measured two ways through the REAL serving kernel —

      cold_dispatch_ms     — synchronous submit+collect per batch (one
                             full dispatch round trip each: what every
                             pre-resident device batch paid);
      resident_dispatch_ms — amortized per-batch cost with `stream`
                             batches pipelined through the resident
                             path (AOT bucket + donated I/O, submits
                             issued back-to-back before any collect —
                             exactly the feeder loop's steady state).

    The ratio is the measured resident floor cut.  The batch is tiny
    (128 single-cell queries) so compute is negligible and both
    numbers are dispatch, not kernel time."""
    from dss_tpu.ops.resident import ResidentKernel

    qb = make_batch(7, 128, n_cells, 1)
    # warm both paths: shared jit (cold) + the AOT bucket (resident);
    # nw <= 128 -> window bucket 256, batch bucket 128
    kern = ResidentKernel()
    kern.warm(ft, batch_buckets=(128,), window_buckets=(256,))
    ft.query_fused(*qb, now=NOW)
    ft.collect(ft.submit(*qb, now=NOW, kernel=kern))

    cold = []
    for i in range(6):
        t0 = time.perf_counter()
        ft.collect(ft.submit(qb[0], qb[1], qb[2], qb[3] + i, qb[4] + i,
                             now=NOW))
        cold.append(time.perf_counter() - t0)
    cold_ms = sorted(cold)[len(cold) // 2] * 1000

    t0 = time.perf_counter()
    pend = [
        ft.submit(qb[0], qb[1], qb[2], qb[3] + i, qb[4] + i, now=NOW,
                  kernel=kern)
        for i in range(stream)
    ]
    for p in pend:
        ft.collect(p)
    res_ms = (time.perf_counter() - t0) / stream * 1000
    return {
        "cold_dispatch_ms": round(cold_ms, 2),
        "resident_dispatch_ms": round(res_ms, 2),
        "resident_stream": stream,
        "resident_floor_cut": round(cold_ms / max(res_ms, 1e-6), 1),
        "aot_hits": kern.hits,
        "aot_misses": kern.misses,
    }


def _bench_slo_ms() -> float:
    """The serving SLO the bench legs run with: the deadline router
    only engages under deadline pressure, so the qps/latency claim is
    made WITH an explicit per-query SLO (DSS_BENCH_SLO_MS, default
    50 ms; DSS_CO_SLO_MS also honored)."""
    return float(
        os.environ.get(
            "DSS_BENCH_SLO_MS", os.environ.get("DSS_CO_SLO_MS", "50")
        )
    )


def _bench_resident() -> bool:
    """Serving legs run with the resident loop attached (the serving
    default, cmds/server.py); DSS_CO_RESIDENT=0 measures without it."""
    return os.environ.get("DSS_CO_RESIDENT", "1") not in ("0", "false")


def _serving_coalescer(table, **kw) -> QueryCoalescer:
    """The coalescer every serving leg drives: SLO + resident loop as
    the server boots it, with the resident bucket grid AOT-warmed for
    the table's current tiers (what the boot warm thread does) so the
    measured window never includes a grid compile."""
    co = QueryCoalescer(
        table, slo_ms=_bench_slo_ms(), resident=_bench_resident(), **kw
    )
    loop = co.resident_loop()
    if loop is not None and hasattr(table, "warm_resident"):
        # focused grid: only the buckets device-routed drains land in
        # (small drains answer on the host path regardless) — each
        # bucket is an XLA compile, and misses self-heal via the
        # cache's background compiler anyway
        table.warm_resident(
            loop.kernel,
            batch_buckets=(128, 1024, 4096),
            window_buckets=(4096, 16384, 65536),
        )
    return co


def _stage_breakdown(st0: dict, st1: dict) -> dict:
    """Per-stage pipeline report from two QueryCoalescer.stats()
    snapshots: avg pack/device/collect ms per batch over the window,
    batching/shed counters, and the deadline router's per-window route
    mix (host-chunk vs device batches, deadline sheds) plus its live
    cost estimates — the direct view of both tentpoles (pipeline
    overlap + measured-cost routing)."""
    batches = st1["co_batches"] - st0["co_batches"]
    d = max(1, batches)
    return {
        "batches": batches,
        "batched_items": st1["co_items"] - st0["co_items"],
        "inline": st1["co_inline"] - st0["co_inline"],
        "shed": st1["co_shed"] - st0["co_shed"],
        "deadline_shed": (
            st1["co_deadline_shed"] - st0["co_deadline_shed"]
        ),
        "route_host_batches": (
            st1["co_route_host_batches"] - st0["co_route_host_batches"]
        ),
        "route_hostchunk_batches": (
            st1["co_route_hostchunk_batches"]
            - st0["co_route_hostchunk_batches"]
        ),
        "route_device_batches": (
            st1["co_route_device_batches"]
            - st0["co_route_device_batches"]
        ),
        "route_resident_batches": (
            st1["co_route_resident_batches"]
            - st0["co_route_resident_batches"]
        ),
        "est_device_floor_ms": st1["co_est_device_floor_ms"],
        "est_host_chunk_ms": st1["co_est_host_chunk_ms"],
        "est_resident_floor_ms": st1["co_est_resident_floor_ms"],
        "pack_ms_avg": round(
            (st1["co_pack_ms_total"] - st0["co_pack_ms_total"]) / d, 3
        ),
        "device_ms_avg": round(
            (st1["co_device_ms_total"] - st0["co_device_ms_total"]) / d, 3
        ),
        "collect_ms_avg": round(
            (st1["co_collect_ms_total"] - st0["co_collect_ms_total"]) / d, 3
        ),
        "batch_size_end": st1["co_batch_size"],
        "batch_grows": st1["co_batch_grows"] - st0["co_batch_grows"],
        "batch_shrinks": st1["co_batch_shrinks"] - st0["co_batch_shrinks"],
    }


def serving_leg(table, n_cells, width, threads, warm_s, run_s):
    """Closed-loop clients through the QueryCoalescer: the full
    serving read path (query_many: fused kernel + overlay scan +
    dead-slot filter + id assembly), pipelined continuous
    micro-batching with per-stage (pack/device/collect) timings, the
    deadline router active (DSS_BENCH_SLO_MS), and the resident loop
    attached (DSS_CO_RESIDENT=0 opts out)."""
    co = _serving_coalescer(table)
    stop = threading.Event()
    warm_until = time.perf_counter() + warm_s
    lats: list = [[] for _ in range(threads)]
    sheds = [0] * threads
    dl_sheds = [0] * threads
    client_errors: list = []  # re-raised after join: a plain Thread
    #                           target's exception is otherwise
    #                           printed and swallowed
    st_warm = {}

    def client(i):
        r = np.random.default_rng(1000 + i)
        while not stop.is_set():
            start = int(r.integers(0, n_cells - width))
            keys = (start + np.arange(width)).astype(np.int32)
            alo = float(r.uniform(0, 3000))
            t0 = NOW + int(r.integers(-2, 2)) * HOUR
            t_req = time.perf_counter()
            try:
                co.query(keys, alo, alo + 300.0, t0, t0 + HOUR, now=NOW)
            except errors.OverloadedError:
                # closed-loop clients self-throttle, so sheds are rare;
                # count them rather than crash the client thread
                if t_req >= warm_until:
                    sheds[i] += 1
                continue
            except errors.StatusError as e:
                if e.code != errors.Code.DEADLINE_EXCEEDED:
                    # a real server error must fail the leg
                    client_errors.append(e)
                    return
                # deadline expired in queue (fast-shed -> HTTP 504):
                # counted against the leg, client keeps offering load
                if t_req >= warm_until:
                    dl_sheds[i] += 1
                continue
            t_done = time.perf_counter()
            if t_done >= warm_until:
                lats[i].append(t_done - t_req)

    ths = [threading.Thread(target=client, args=(i,)) for i in range(threads)]
    for t in ths:
        t.start()
    time.sleep(warm_s)
    st_warm = co.stats()  # stage accounting for the measured window only
    time.sleep(run_s)
    stop.set()
    for t in ths:
        t.join()
    st_end = co.stats()
    co.close()
    if client_errors:
        raise RuntimeError(
            f"serving leg hit server errors: {client_errors[:3]}"
        )
    all_lats = np.sort(np.concatenate([np.asarray(l) for l in lats]))
    if len(all_lats) == 0:
        return {"error": "no samples"}
    return {
        "qps": len(all_lats) / run_s,
        "p50_ms": float(all_lats[len(all_lats) // 2] * 1000),
        "p99_ms": float(all_lats[int(len(all_lats) * 0.99)] * 1000),
        "p999_ms": float(all_lats[int(len(all_lats) * 0.999)] * 1000),
        "threads": threads,
        "samples": int(len(all_lats)),
        "shed": int(sum(sheds)),
        "deadline_shed": int(sum(dl_sheds)),
        # shed requests are excluded from the latency percentiles, so
        # the rate rides along — a nonzero value means the qps/p50/p99
        # above describe only the surviving fraction of traffic
        "shed_rate": round(
            (sum(sheds) + sum(dl_sheds))
            / max(1, sum(sheds) + sum(dl_sheds) + len(all_lats)),
            4,
        ),
        "slo_ms": _bench_slo_ms(),
        "host_cpus": os.cpu_count(),
        "stages": _stage_breakdown(st_warm, st_end),
    }


def curve_leg(table, n_cells, width, rates, secs, warm_s=1.0):
    """Open-loop qps/latency curve (VERDICT r4 #3): drive the serving
    path at FIXED offered rates and report achieved qps + p50/p99/p99.9
    measured from the SCHEDULED send time (coordinated omission safe),
    plus the per-point route mix (host-chunk vs resident vs cold
    device batches, deadline sheds) so the deadline router's behavior
    at the knee is directly visible.  The north-star claim is then
    stated jointly: the max offered load at which p50 stays under
    5 ms."""
    co = _serving_coalescer(table)
    rows = []
    for offered in rates:
        # thread count scales with offered load: a GIL-sharing python
        # client thread sustains ~350-450 qps, so the old 16-thread cap
        # silently ceilinged the GENERATOR at ~7k offered and reported
        # the client's scheduling debt as server latency right where
        # the knee claim matters
        k = int(min(64, max(4, offered // 250)))
        per_thread = offered / k
        stop_at = time.perf_counter() + warm_s + secs
        warm_until = time.perf_counter() + warm_s
        lats: list = [[] for _ in range(k)]
        sheds = [0] * k
        dl_sheds = [0] * k
        client_errors: list = []  # re-raised after join (thread
        #                           targets swallow exceptions)

        def client(i):
            r = np.random.default_rng(5000 + i)
            # pregenerate the query stream: per-query RNG + arange in
            # the hot loop billed ~0.05 ms of client CPU to every
            # request — on a 1-core host that is server capacity
            n_pre = 4096
            starts = r.integers(0, n_cells - width, n_pre)
            pre_keys = (
                starts[:, None] + np.arange(width)[None, :]
            ).astype(np.int32)
            pre_alo = r.uniform(0, 3000, n_pre).astype(np.float32)
            pre_t0 = (
                NOW + r.integers(-2, 2, n_pre) * HOUR
            ).astype(np.int64)
            interval = 1.0 / per_thread
            next_t = time.perf_counter() + r.uniform(0, interval)
            qi = 0
            while True:
                now_t = time.perf_counter()
                if now_t >= stop_at:
                    return
                if now_t < next_t:
                    time.sleep(min(next_t - now_t, 0.02))
                    continue
                qi = (qi + 1) % n_pre
                alo = float(pre_alo[qi])
                t0 = int(pre_t0[qi])
                try:
                    co.query(
                        pre_keys[qi], alo, alo + 300.0, t0, t0 + HOUR,
                        now=NOW,
                    )
                except errors.OverloadedError:
                    # backpressure shed: admitted requests keep bounded
                    # latency, this one is counted against the curve
                    if time.perf_counter() >= warm_until:
                        sheds[i] += 1
                    next_t += interval
                    continue
                except errors.StatusError as e:
                    if e.code != errors.Code.DEADLINE_EXCEEDED:
                        # a real server error must fail the leg
                        client_errors.append(e)
                        return
                    # deadline expired in queue (fast-shed -> 504)
                    if time.perf_counter() >= warm_until:
                        dl_sheds[i] += 1
                    next_t += interval
                    continue
                done = time.perf_counter()
                if done >= warm_until:
                    # latency from the scheduled send time: queueing
                    # delay when we fall behind the offered rate counts
                    lats[i].append(done - next_t)
                next_t += interval

        ths = [
            threading.Thread(target=client, args=(i,)) for i in range(k)
        ]
        t_run0 = time.perf_counter()
        for t in ths:
            t.start()
        # stage accounting for the measured window only, matching the
        # warm_until filter on latencies/sheds (first-batch jit compile
        # and warm-up shrinks would otherwise skew the averages)
        time.sleep(max(0.0, warm_until - time.perf_counter()))
        st0 = co.stats()
        for t in ths:
            t.join()
        if client_errors:
            co.close()
            raise RuntimeError(
                f"curve leg hit server errors: {client_errors[:3]}"
            )
        span = time.perf_counter() - t_run0 - warm_s
        st1 = co.stats()
        all_l = np.sort(np.concatenate([np.asarray(x) for x in lats]))
        if len(all_l) == 0:
            continue
        n_shed = int(sum(sheds))
        n_dl = int(sum(dl_sheds))
        stages = _stage_breakdown(st0, st1)
        row = {
            "offered_qps": offered,
            "achieved_qps": round(len(all_l) / max(span, 1e-9), 1),
            "p50_ms": round(float(all_l[len(all_l) // 2]) * 1000, 2),
            "p99_ms": round(
                float(all_l[int(len(all_l) * 0.99)]) * 1000, 2
            ),
            "p999_ms": round(
                float(all_l[int(len(all_l) * 0.999)]) * 1000, 2
            ),
            "threads": k,
            "samples": int(len(all_l)),
            "shed": n_shed,
            # fraction of offered traffic NOT served: admission 429s
            # plus deadline 504s (both excluded from the percentiles)
            "shed_rate": round(
                (n_shed + n_dl) / max(1, n_shed + n_dl + len(all_l)), 4
            ),
            "deadline_shed": n_dl,
            # the router's per-point decision mix: what served this
            # offered load (chunked host scans vs fused device kernel).
            # These counters are popped from `stages` below so the row
            # carries ONE canonical copy.
            "route_mix": {
                "host_batches": stages.pop("route_host_batches"),
                "hostchunk_batches": stages.pop(
                    "route_hostchunk_batches"
                ),
                "device_batches": stages.pop("route_device_batches"),
                "resident_batches": stages.pop(
                    "route_resident_batches"
                ),
                "deadline_sheds": stages.pop("deadline_shed"),
            },
            "stages": stages,
        }
        rows.append(row)
        # no early saturation break: the recorded curve must cover the
        # FULL configured sweep (the r05 JSON stopped at 12k while the
        # default sweep said 16k — a saturated point is a result, not
        # a reason to stop measuring; each point's cost is bounded by
        # warm_s + secs anyway)
    co.close()
    # a point qualifies for the joint SLO claim only if it served its
    # load: p50 under the bound, >=90% of offered achieved, AND the
    # shed tail (admission 429s + deadline 504s) under 1% — shedding
    # the slow tail must not be able to manufacture the headline
    ok = [
        r["offered_qps"]
        for r in rows
        if r["p50_ms"] < 5.0
        and r["achieved_qps"] >= r["offered_qps"] * 0.9
        and (r["shed"] + r["deadline_shed"])
        <= 0.01 * max(1, r["samples"])
    ]
    return rows, (max(ok) if ok else 0)


def workers_leg():
    """Multi-worker scaling smoke (`bench.py --leg workers`): boots the
    REAL server binary with --workers 0 (single process) and
    --workers N (leader + N SO_REUSEPORT read workers) on this host
    and measures closed-loop RID search throughput through the full
    HTTP stack — out-of-process raw-socket clients, so client CPU is
    never billed to the server.  The measured speedup is what sizes
    --workers in docs/OPERATIONS.md; run it on YOUR host shape, the
    ratio is core-count dependent.  Prints one JSON line."""
    from benchmarks.bench_rid_search import (
        _drive,
        _free_port,
        boot_server,
        populate_isas,
        wait_for_healthy,
    )

    cpus = os.cpu_count() or 1
    workers_n = int(
        os.environ.get("DSS_BENCH_WORKERS", max(1, min(cpus - 1, 4)))
    )
    # full ladder override (VERDICT ask #3: N in {0,2,4} on the CI
    # runner, so the OPERATIONS sizing table is measured, not guessed)
    ladder_env = os.environ.get("DSS_BENCH_WORKERS_SET", "")
    if ladder_env:
        ladder = sorted({int(x) for x in ladder_env.split(",") if x != ""})
    else:
        ladder = sorted({0, workers_n})
    n_isas = int(os.environ.get("DSS_BENCH_ISAS", 300))
    secs = float(os.environ.get("DSS_BENCH_SECS", 6))
    procs = int(os.environ.get("DSS_BENCH_PROCS", min(4, max(2, cpus))))
    threads = int(os.environ.get("DSS_BENCH_THREADS", 3))
    # memory storage: the leg isolates the WORKER fan-out (HTTP +
    # covering + index scan on every worker), not device placement
    storage = os.environ.get("DSS_BENCH_STORAGE", "memory")

    import subprocess

    rows = []
    for w in ladder:
        port = _free_port()
        base = f"http://127.0.0.1:{port}"
        srv = boot_server(port, storage, w)
        try:
            wait_for_healthy(base)
            populate_isas(base, n_isas)
            time.sleep(1.0)  # worker replicas catch the populate tail
            qps, p50, p99, n, _ = _drive(
                base, procs=procs, threads=threads, warm_s=2.0, run_s=secs
            )
            rows.append(
                {
                    "workers": w,
                    "qps": round(qps, 1),
                    "p50_ms": round(p50, 2),
                    "p99_ms": round(p99, 2),
                    "samples": n,
                }
            )
        finally:
            srv.terminate()
            try:
                srv.wait(timeout=30)
            except subprocess.TimeoutExpired:
                srv.kill()
    single = rows[0]
    for r in rows:
        r["speedup_vs_single"] = (
            round(r["qps"] / single["qps"], 3) if single["qps"] else None
        )
    # headline: the BEST worker count on this host (the measured
    # sizing answer), not blindly the largest N
    multi = max(rows[1:] or rows, key=lambda r: r["qps"])
    speedup = multi["speedup_vs_single"]
    print(
        json.dumps(
            {
                "metric": "rid_search_worker_scaling",
                "value": multi["qps"],
                "unit": "searches/s",
                # scaling factor over the single-process server ON THIS
                # HOST — the number the --workers sizing advice cites
                "vs_baseline": speedup,
                "detail": {
                    "host_cpus": cpus,
                    "workers": multi["workers"],
                    "workers_ladder": ladder,
                    "single_process_qps": single["qps"],
                    "speedup_vs_single_process": speedup,
                    "rows": rows,
                    "isas": n_isas,
                    "client_procs": procs,
                    "client_threads_per_proc": threads,
                    "storage": storage,
                    "note": (
                        "closed-loop RID area search via SO_REUSEPORT "
                        "read workers (WAL-tail replicas); on 1-core "
                        "hosts expect speedup <= 1 (context switching "
                        "only) — size --workers from the measured "
                        "speedup, not a cores heuristic"
                    ),
                },
            }
        )
    )


def _poll_store(n_isas: int, n_areas: int, cells_per_area: int,
                storage: str):
    """A DSSStore populated for the poll workload: `n_areas` disjoint
    metro-area coverings, `n_isas` ISAs spread across them.  Returns
    (store, areas, versions) where areas[i] is the uint64 covering of
    area i and versions maps isa id -> current Version (for fenced
    update writes)."""
    from datetime import datetime, timedelta, timezone

    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.geo.s2cell import dar_key_to_cell
    from dss_tpu.models import rid as ridm

    store = DSSStore(storage=storage)
    t0 = datetime.now(timezone.utc) + timedelta(minutes=5)
    t1 = t0 + timedelta(hours=24)
    areas = [
        dar_key_to_cell(
            np.arange(
                i * cells_per_area, (i + 1) * cells_per_area, dtype=np.int64
            )
        )
        for i in range(n_areas)
    ]
    versions = {}
    for k in range(n_isas):
        area = areas[k % n_areas]
        isa = ridm.IdentificationServiceArea(
            id=str(__import__("uuid").UUID(int=k + 1, version=4)),
            owner="bench",
            url="https://uss.example/flights",
            cells=area,
            start_time=t0,
            end_time=t1,
            altitude_lo=0.0,
            altitude_hi=3000.0,
        )
        stored = store.rid.insert_isa(isa)
        versions[stored.id] = (stored.version, area)
    # park the populated heap outside gen2 GC scans, as the server
    # does after boot (cmds/server.py): the poll loop's p99 must
    # measure the cache, not cyclic-GC pauses over the record heap
    from dss_tpu.runtime import freeze_boot_heap

    freeze_boot_heap()
    return store, areas, (t0, t1), versions


def _poll_pass(store, areas, window, versions, *, ratio, secs, threads,
               zipf_a, seed=7):
    """One closed-loop poll run against store.rid.search_isas: every
    thread polls Zipf-favored areas and issues one fenced ISA update
    per `ratio` polls (the writer side of the 100:1 model).  A serial
    warm pass touches every area first (jit warm on the uncached run,
    steady-state population on the cached one — the measured window is
    the fleet's steady state, not 512 cold-start misses).  ->
    (served_qps, p50_ms, p99_ms, polls, writes)."""
    t0, _ = window
    n_areas = len(areas)
    for area in areas:
        store.rid.search_isas(area, t0, None)
    # Zipf-ranked area popularity, deterministic per seed
    ranks = np.arange(1, n_areas + 1, dtype=np.float64)
    probs = ranks ** (-zipf_a)
    probs /= probs.sum()
    stop = threading.Event()
    lats: list = [[] for _ in range(threads)]
    writes = [0] * threads
    errs: list = []
    ids = list(versions)

    def client(i):
        rng = np.random.default_rng(seed * 1000 + i)
        pick = rng.choice(n_areas, size=4096, p=probs)
        qi = 0
        ops = 0
        while not stop.is_set():
            area = areas[int(pick[qi])]
            qi = (qi + 1) % len(pick)
            ops += 1
            try:
                if ratio > 0 and ops % (ratio + 1) == ratio:
                    # fenced update of one ISA (same covering — the
                    # write path that invalidates its area's entries)
                    import dataclasses as _dc

                    eid = ids[(i * 7919 + ops) % len(ids)]
                    ver, a = versions[eid]
                    upd = _dc.replace(
                        store.rid.get_isa(eid), version=ver, cells=a
                    )
                    stored = store.rid.insert_isa(upd)
                    if stored is not None:
                        versions[eid] = (stored.version, a)
                    writes[i] += 1
                    continue
                t_req = time.perf_counter()
                store.rid.search_isas(area, t0, None)
                lats[i].append(time.perf_counter() - t_req)
            except Exception as e:  # noqa: BLE001 — fail the leg
                errs.append(e)
                return

    ths = [threading.Thread(target=client, args=(i,)) for i in range(threads)]
    t_run = time.perf_counter()
    for t in ths:
        t.start()
    time.sleep(secs)
    stop.set()
    for t in ths:
        t.join()
    span = time.perf_counter() - t_run
    if errs:
        raise RuntimeError(f"poll leg hit errors: {errs[:3]}")
    all_l = np.sort(np.concatenate([np.asarray(x) for x in lats]))
    return {
        "served_qps": round(len(all_l) / span, 1),
        "p50_ms": round(float(all_l[len(all_l) // 2]) * 1000, 3),
        "p99_ms": round(float(all_l[int(len(all_l) * 0.99)]) * 1000, 3),
        "polls": int(len(all_l)),
        "writes": int(sum(writes)),
    }


def poll_leg(emit: bool = True):
    """Repeat-poll workload (`bench.py --leg poll`; also folded into
    the default north-star output): DSS_BENCH_POLL_RATIO polls per
    write (default 100:1) over Zipf-distributed metro areas, measured
    twice through the REAL store search path — version-fenced cache ON
    vs OFF on the same populated store — reporting served qps, hit
    rate, and p99 for both.  The acceptance bar is >=10x served qps at
    equal-or-better p99 with the cache on."""
    ratio = int(os.environ.get("DSS_BENCH_POLL_RATIO", 100))
    n_isas = int(os.environ.get("DSS_BENCH_POLL_ISAS", 4000))
    n_areas = int(os.environ.get("DSS_BENCH_POLL_AREAS", 512))
    cpa = int(os.environ.get("DSS_BENCH_POLL_CELLS", 64))
    secs = float(os.environ.get("DSS_BENCH_POLL_SECS", 5.0))
    # client threads scale with cores (same hygiene as the curve leg's
    # offered-load scaling): on a 1-2 core host, 8 GIL-sharing client
    # threads measure scheduler thrash, not the server's read path
    threads = int(
        os.environ.get(
            "DSS_BENCH_POLL_THREADS",
            min(8, max(4, 2 * (os.cpu_count() or 2))),
        )
    )
    zipf_a = float(os.environ.get("DSS_BENCH_POLL_ZIPF", 1.1))
    storage = os.environ.get("DSS_BENCH_POLL_STORAGE", "tpu")

    passes = max(1, int(os.environ.get("DSS_BENCH_POLL_PASSES", 2)))
    store, areas, window, versions = _poll_store(
        n_isas, n_areas, cpa, storage
    )
    try:
        # interleaved best-of-N passes per mode: a shared host can
        # slow an entire pass 2-3x, and interleaving + best-of keeps
        # one slow stretch from landing entirely on one mode
        base = cached = None
        s0 = s1 = store.cache.stats()
        for p in range(passes):
            store.configure_serving(cache=False)
            b = _poll_pass(
                store, areas, window, versions, ratio=ratio, secs=secs,
                threads=threads, zipf_a=zipf_a, seed=11 + 2 * p,
            )
            if base is None or b["served_qps"] > base["served_qps"]:
                base = b
            # cached pass: the version fence serves repeat polls;
            # writes keep invalidating areas at the configured ratio
            store.configure_serving(cache=True)
            c0 = store.cache.stats()
            c = _poll_pass(
                store, areas, window, versions, ratio=ratio, secs=secs,
                threads=threads, zipf_a=zipf_a, seed=12 + 2 * p,
            )
            if cached is None or c["served_qps"] > cached["served_qps"]:
                cached = c
                s0, s1 = c0, store.cache.stats()
    finally:
        store.close()
    hits = s1["hits"] - s0["hits"]
    misses = s1["misses"] - s0["misses"]
    result = {
        "poll_ratio": ratio,
        "areas": n_areas,
        "zipf_a": zipf_a,
        "isas": n_isas,
        "threads": threads,
        "storage": storage,
        "cached": cached,
        "uncached": base,
        "hit_rate": round(hits / max(1, hits + misses), 4),
        "invalidations": s1["invalidations"] - s0["invalidations"],
        "served_qps_speedup": round(
            cached["served_qps"] / max(1e-9, base["served_qps"]), 2
        ),
        "p99_ratio": round(
            cached["p99_ms"] / max(1e-9, base["p99_ms"]), 3
        ),
    }
    if emit:
        print(
            json.dumps(
                {
                    "metric": "poll_served_qps_speedup",
                    "value": result["served_qps_speedup"],
                    "unit": "x",
                    "detail": result,
                }
            )
        )
    return result


def cache_smoke_leg():
    """CI read-cache smoke (`bench.py --leg cache-smoke`): the
    deterministic hit -> write-invalidate -> miss -> repopulate cycle
    through the real store, asserting the acceptance contract — a hit
    is bit-identical to the fresh path AND performs zero coalescer
    enqueues and zero device dispatches (co_* counters frozen across
    the hit).  Exits nonzero if the hit path goes unexercised."""
    from datetime import timedelta

    store, areas, window, versions = _poll_store(
        n_isas=64, n_areas=8, cells_per_area=32,
        storage=os.environ.get("DSS_BENCH_POLL_STORAGE", "tpu"),
    )
    t0, _ = window
    try:
        area = areas[0]

        def co_counters():
            st = store.stats()
            return {
                k: v
                for k, v in st.items()
                if k.endswith(("co_batches", "co_items", "co_inline"))
            }

        def ids_of(res):
            return sorted(x.id for x in res)

        # miss -> populate
        fresh = ids_of(store.rid.search_isas(area, t0, None))
        assert fresh, "poll area unexpectedly empty"
        pre = co_counters()
        pre_cache = store.cache.stats()
        # hit: bit-identical, zero coalescer enqueues, zero dispatches
        hit = ids_of(store.rid.search_isas(area, t0, None))
        post = co_counters()
        post_cache = store.cache.stats()
        assert hit == fresh, f"cache hit diverged: {hit} != {fresh}"
        assert post_cache["hits"] == pre_cache["hits"] + 1, (
            pre_cache, post_cache,
        )
        assert post == pre, (
            f"a cache hit touched the coalescer: {pre} -> {post}"
        )
        # write-invalidate: a fenced update in the polled area
        import dataclasses as _dc

        eid = next(i for i, (_, a) in versions.items() if a is areas[0])
        ver, a = versions[eid]
        upd = _dc.replace(store.rid.get_isa(eid), version=ver)
        upd.end_time = upd.end_time + timedelta(hours=1)
        assert store.rid.insert_isa(upd) is not None
        # miss (fence rejected) -> fresh answer -> repopulate
        c0 = store.cache.stats()
        after = ids_of(store.rid.search_isas(area, t0, None))
        c1 = store.cache.stats()
        assert after == fresh, f"post-write answer diverged: {after}"
        assert c1["invalidations"] == c0["invalidations"] + 1, (c0, c1)
        assert c1["misses"] == c0["misses"] + 1, (c0, c1)
        # repopulated: the next poll hits again
        c2 = store.cache.stats()
        again = ids_of(store.rid.search_isas(area, t0, None))
        c3 = store.cache.stats()
        assert again == after
        assert c3["hits"] == c2["hits"] + 1, (c2, c3)
        final = store.cache.stats()
    finally:
        store.close()
    assert final["hits"] >= 2, f"hit path unexercised: {final}"
    print(
        json.dumps(
            {
                "metric": "read_cache_smoke",
                "value": 1,
                "unit": "ok",
                "detail": {
                    "hits": final["hits"],
                    "misses": final["misses"],
                    "invalidations": final["invalidations"],
                    "entries": final["entries"],
                },
            }
        )
    )


def curve_smoke_leg():
    """CI router smoke (`bench.py --leg curve-smoke`): a short
    DSS_BENCH_CURVE_QPS sweep on a small table, then two deterministic
    bursts that pin BOTH router outcomes — a fresh tight-SLO burst
    served as forced host chunks, and a bulk stale-ok burst that rides
    the device path.  Exits nonzero if either route went unexercised,
    so the deadline router cannot silently rot into a one-route
    scheduler.  Runs on CPU (JAX_PLATFORMS=cpu in CI)."""
    n_cells = int(os.environ.get("DSS_BENCH_CELLS", 2000))
    width = 4
    table = build_table(
        int(os.environ.get("DSS_BENCH_ENTITIES", 5000)), n_cells, 4
    )
    rates = [
        int(x)
        for x in os.environ.get("DSS_BENCH_CURVE_QPS", "200,800").split(",")
        if x.strip()
    ]
    rows, max_ok = curve_leg(
        table, n_cells, width, rates,
        secs=float(os.environ.get("DSS_BENCH_CURVE_SECS", 1.5)),
        warm_s=0.5,
    )
    assert rows, "curve sweep produced no points"

    # burst A — fresh queries under a tight SLO with the device seeded
    # slow: the router must serve them as forced host chunks
    co = QueryCoalescer(
        table, min_batch=1, inline=False, slo_ms=50.0,
        est_floor_ms=10_000.0, est_item_ms=0.0, est_chunk_ms=0.01,
    )
    from concurrent.futures import ThreadPoolExecutor

    # pregenerated on the main thread: np.random.Generator is not
    # thread-safe, and these bursts fan out across a pool
    starts = np.random.default_rng(0).integers(0, n_cells - width, 256)

    def one(i, stale=False):
        start = int(starts[i % len(starts)])
        keys = (start + np.arange(width)).astype(np.int32)
        try:
            return co.query(
                keys, None, None, NOW - HOUR, NOW + HOUR, now=NOW,
                allow_stale=stale,
            )
        except errors.StatusError as e:
            if e.code != errors.Code.DEADLINE_EXCEEDED:
                raise
            # an expected router outcome on a stalled shared runner
            # (real 50 ms SLO + a >50 ms scheduler pause): the burst
            # asserts on route counters, not on zero sheds
            return None

    with ThreadPoolExecutor(max_workers=32) as pool:
        list(pool.map(one, range(96)))
    st = co.stats()
    assert st["co_route_hostchunk_batches"] >= 1, (
        f"tight-SLO burst never took the forced host route: {st}"
    )

    # burst B — bulk stale-ok drain (no fresh deadlines): the router
    # must keep the fused device path.  A brief submit gate queues the
    # burst into ONE >64 drain (min_batch raised so the AIMD size
    # cannot cap the drain below the host cutoff) so the outcome is
    # deterministic.
    co.configure(slo_ms=0.0, min_batch=128)
    gate = threading.Event()
    orig_submit = table.query_many_submit

    def gated_submit(*a, **kw):
        gate.wait(10.0)
        return orig_submit(*a, **kw)

    table.query_many_submit = gated_submit
    try:
        with ThreadPoolExecutor(max_workers=128) as pool:
            futs = [
                pool.submit(one, i, stale=True) for i in range(128)
            ]
            deadline = time.perf_counter() + 5.0
            while (
                co.stats()["co_queue_depth"] < 80
                and time.perf_counter() < deadline
            ):
                time.sleep(0.01)
            gate.set()
            for f in futs:
                f.result()
    finally:
        table.query_many_submit = orig_submit
        gate.set()
    # route counters are bumped by the collect thread AFTER caller
    # events fire — wait for the pipeline to fully drain before
    # asserting, or a healthy run can read the stats a beat early
    deadline = time.perf_counter() + 5.0
    st = co.stats()
    while (
        st["co_route_device_batches"] < 1
        and (st["co_inflight"] > 0 or time.perf_counter() < deadline)
    ):
        time.sleep(0.01)
        st = co.stats()
    assert st["co_route_device_batches"] >= 1, (
        f"bulk stale burst never rode the device path: {st}"
    )
    co.close()
    table.close()
    print(
        json.dumps(
            {
                "metric": "deadline_router_smoke",
                "value": 1,
                "unit": "ok",
                "detail": {
                    "curve": rows,
                    "max_serving_qps_p50_under_5ms": max_ok,
                    "route_hostchunk_batches": st[
                        "co_route_hostchunk_batches"
                    ],
                    "route_device_batches": st["co_route_device_batches"],
                    "deadline_shed": st["co_deadline_shed"],
                },
            }
        )
    )


def resident_smoke_leg():
    """CI resident-loop smoke (`bench.py --leg resident-smoke`, CPU):
    boots the resident loop, AOT-warms a small grid, pushes a
    deterministic burst through it, asserts the resident route was
    exercised (nonzero co_route_resident_batches) with answers
    bit-identical to the serial path, then closes the coalescer WHILE
    batches are still queued in the ring and asserts the shutdown
    drains them cleanly (every admitted caller resolves, both loop
    threads exit).  Exits nonzero on any miss."""
    from concurrent.futures import ThreadPoolExecutor

    n_cells = int(os.environ.get("DSS_BENCH_CELLS", 500))
    table = build_table(
        int(os.environ.get("DSS_BENCH_ENTITIES", 2000)), n_cells, 4
    )
    # seeds make the resident stream the obvious device-class choice
    # (cold floor huge, chunks huge) so routing is deterministic
    co = QueryCoalescer(
        table, min_batch=1, max_batch=256, inline=False, queue_depth=64,
        slo_ms=0.0, resident=True,
        est_floor_ms=10_000.0, est_res_floor_ms=0.05, est_chunk_ms=1e6,
    )
    loop = co.resident_loop()
    assert loop is not None, "resident loop failed to attach"
    warmed = table.warm_resident(
        loop.kernel, batch_buckets=(16, 32, 64, 128),
        window_buckets=(256, 1024),
    )

    rng = np.random.default_rng(3)
    width = 4
    starts = rng.integers(0, n_cells - width, 256)

    def one(i):
        keys = (int(starts[i % len(starts)]) + np.arange(width)).astype(
            np.int32
        )
        return keys, co.query(keys, None, None, NOW - HOUR, NOW + HOUR,
                              now=NOW)

    with ThreadPoolExecutor(max_workers=32) as pool:
        got = list(pool.map(one, range(128)))
    deadline = time.perf_counter() + 10.0
    while (
        co.stats()["co_inflight"] > 0 and time.perf_counter() < deadline
    ):
        time.sleep(0.01)
    st = co.stats()
    assert st["co_route_resident_batches"] >= 1, (
        f"burst never rode the resident loop: {st}"
    )
    for keys, res in got:
        ref = table.query(keys, None, None, NOW - HOUR, NOW + HOUR,
                          now=NOW)
        assert res == ref, f"resident mismatch: {res} != {ref}"

    # shutdown with batches still queued in the ring: gate the table's
    # submit so the feeder stalls, refill the ring, then close() while
    # it is non-empty — the drain contract says every caller resolves
    gate = threading.Event()
    orig_submit = table.query_many_submit

    def gated_submit(*a, **kw):
        gate.wait(10.0)
        return orig_submit(*a, **kw)

    table.query_many_submit = gated_submit
    outcomes = []

    def client(i):
        try:
            outcomes.append(one(i)[1])
        except Exception as e:  # noqa: BLE001 — counted, not raised
            outcomes.append(e)

    try:
        ths = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        for t in ths:
            t.start()
        deadline = time.perf_counter() + 10.0
        while (
            loop.stats()["ring_depth"] < 1
            and time.perf_counter() < deadline
        ):
            time.sleep(0.005)
        ring_at_close = loop.stats()["ring_depth"]
        closer = threading.Thread(target=co.close)
        closer.start()
        time.sleep(0.1)
        gate.set()
        closer.join(30.0)
        for t in ths:
            t.join(10.0)
    finally:
        table.query_many_submit = orig_submit
        gate.set()
    assert len(outcomes) == 8, f"lost callers at shutdown: {outcomes}"
    bad = [o for o in outcomes if isinstance(o, Exception)]
    assert not bad, f"shutdown errored callers: {bad[:3]}"
    final = loop.stats()
    assert final["ring_depth"] == 0, f"ring not drained: {final}"
    table.close()
    print(
        json.dumps(
            {
                "metric": "resident_smoke",
                "value": 1,
                "unit": "ok",
                "detail": {
                    "route_resident_batches": st[
                        "co_route_resident_batches"
                    ],
                    "est_resident_floor_ms": st[
                        "co_est_resident_floor_ms"
                    ],
                    "aot_warmed": warmed,
                    "aot_hits": final["aot_hits"],
                    "aot_misses": final["aot_misses"],
                    "ring_at_close": ring_at_close,
                    "ring_drained": True,
                },
            }
        )
    )


# -- chaos: deterministic fault injection + degradation ladder ----------------


def _chaos_free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _chaos_wait_http(url: str, deadline_s: float = 30.0):
    import requests

    end = time.time() + deadline_s
    last = None
    while time.time() < end:
        try:
            r = requests.get(url, timeout=2)
            if r.status_code < 500:
                return r
            last = r.status_code
        except Exception as e:  # noqa: BLE001 — still booting
            last = e
        time.sleep(0.1)
    raise RuntimeError(f"server at {url} never came up ({last})")


def chaos_smoke_leg():
    """CI chaos smoke (`bench.py --leg chaos-smoke`): the deterministic
    device-loss scenario through the real store.  A seeded FaultPlan
    kills the device at the dispatch seam mid-burst; the acceptance
    contract is asserted end to end — the planner serves every search
    via the host class (hostchunk plans, zero device plans beyond the
    absorbed batch), ZERO unexpected 5xx (any shed is 429/503 WITH
    Retry-After), the degradation ladder reads DEVICE_LOST, and after
    fault clearance + recovery the answers are bit-identical to the
    no-fault oracle with the device class re-admitted.  Exits nonzero
    on any miss."""
    from concurrent.futures import ThreadPoolExecutor

    from dss_tpu import chaos

    chaos.clear_plan()
    chaos.registry().reset_counters()
    store, areas, (t0, _t1), _versions = _poll_store(
        n_isas=64, n_areas=8, cells_per_area=32, storage="tpu"
    )
    try:
        def ids(area):
            return sorted(
                x.id for x in store.rid.search_isas(area, t0, None)
            )

        # the no-fault oracle
        oracle = [ids(a) for a in areas]
        assert any(oracle), "poll areas unexpectedly empty"
        # every search must traverse the coalescer during the fault
        # window: no cache hits, no lone-caller inline shortcut — the
        # drained batches are what the planner routes
        store.configure_serving(cache=False, inline=False)
        co = store.rid._isa_index.coalescer
        pre = co.stats()

        chaos.install_plan(
            {"seed": 1, "events": [
                {"site": "device.dispatch", "action": "device_lost",
                 "count": 1},
            ]}
        )
        t_fault = time.perf_counter()
        served = 0
        shed_with_retry_after = 0
        unexpected_5xx = 0

        def one(k):
            nonlocal served, shed_with_retry_after, unexpected_5xx
            i = k % len(areas)
            try:
                got = ids(areas[i])
            except errors.StatusError as e:
                if (
                    e.http_status in (429, 503)
                    and getattr(e, "retry_after_s", None)
                ):
                    shed_with_retry_after += 1
                    return
                unexpected_5xx += 1
                return
            assert got == oracle[i], (i, got, oracle[i])
            served += 1

        with ThreadPoolExecutor(max_workers=16) as pool:
            list(pool.map(one, range(96)))
        mid = co.stats()
        assert unexpected_5xx == 0, (
            f"{unexpected_5xx} unexpected 5xx under device loss"
        )
        assert served >= 1
        assert store.health.is_active("device_lost"), (
            "ladder never flipped DEVICE_LOST"
        )
        assert mid["co_device_loss_absorbed"] >= 1, mid
        assert mid["co_device_ok"] == 0, mid
        host_plans = (
            mid["co_plan_hostchunk"] - pre["co_plan_hostchunk"]
        )
        dev_plans = mid["co_plan_device"] - pre["co_plan_device"]
        assert host_plans >= 1, (
            f"device loss never exercised hostchunk plans: {mid}"
        )
        # at most the one absorbed batch ever planned the device
        assert dev_plans <= 1, (pre, mid)
        injected = chaos.registry().injected_by_site()
        assert injected.get("device.dispatch", 0) == 1, injected
        dwell_s = store.health.dwell_s("device_lost")
        burn = unexpected_5xx / max(
            1, served + shed_with_retry_after + unexpected_5xx
        )

        # fault clearance + recovery: re-warm runs before re-admission
        chaos.clear_plan()
        t_rec = time.perf_counter()
        store.health.exit("device_lost")
        assert co.stats()["co_device_ok"] == 1, "device not re-admitted"
        store.configure_serving(cache=True, inline=True)
        for i, a in enumerate(areas):
            got = ids(a)
            assert got == oracle[i], (
                f"post-recovery divergence on area {i}: "
                f"{got} != {oracle[i]}"
            )
        recovery_s = time.perf_counter() - t_rec
        assert store.health.mode() == chaos.HEALTHY
    finally:
        chaos.clear_plan()
        chaos.registry().reset_counters()
        store.close()
    print(
        json.dumps(
            {
                "metric": "chaos_smoke",
                "value": 1,
                "unit": "ok",
                "detail": {
                    "served_during_loss": served,
                    "shed_with_retry_after": shed_with_retry_after,
                    "unexpected_5xx": unexpected_5xx,
                    "error_budget_burn": round(burn, 4),
                    "hostchunk_plans_during_loss": host_plans,
                    "device_plans_during_loss": dev_plans,
                    "degraded_dwell_s": round(dwell_s, 3),
                    "recovery_to_identical_s": round(recovery_s, 3),
                    "fault_window_s": round(
                        time.perf_counter() - t_fault, 3
                    ),
                },
            }
        )
    )
    return 0


def _fanout_store(n_subs: int, n_uss: int, cells_per_area: int,
                  *, storage: str = "tpu", **pipe_kw):
    """A DSSStore with an attached PushPipeline, `n_uss` registered
    webhooks, and `n_subs` RID subscriptions spread over the USSs, all
    intersecting one shared metro covering.  -> (store, pipe, area,
    delivered) where `delivered` is the thread-safe list the counting
    transport appends (uss, body) tuples to."""
    from datetime import datetime, timedelta, timezone

    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.geo.s2cell import dar_key_to_cell
    from dss_tpu.models import rid as ridm
    from dss_tpu.push import PushPipeline

    delivered: list = []
    dlock = threading.Lock()

    def transport(url, body, headers):
        with dlock:
            delivered.append((url, body))

    store = DSSStore(storage=storage)
    pipe = PushPipeline(
        workers=pipe_kw.pop("workers", 4),
        transport=pipe_kw.pop("transport", transport),
        **pipe_kw,
    )
    store.attach_push(pipe)
    for u in range(n_uss):
        pipe.register_hook(f"uss{u:03d}", f"https://uss{u:03d}.example/notify")
    area = dar_key_to_cell(
        np.arange(cells_per_area, dtype=np.int64)
    )
    t0 = datetime.now(timezone.utc) + timedelta(minutes=5)
    t1 = t0 + timedelta(hours=23)
    for k in range(n_subs):
        # a small slice of the shared covering per subscription: the
        # one write intersects every one of them
        lo = k % max(1, cells_per_area - 8)
        sub = ridm.Subscription(
            id=str(__import__("uuid").UUID(int=10_000 + k, version=4)),
            owner=f"uss{k % n_uss:03d}",
            url=f"https://uss{k % n_uss:03d}.example/notify",
            cells=area[lo:lo + 8],
            start_time=t0,
            end_time=t1,
            altitude_lo=0.0,
            altitude_hi=3000.0,
        )
        assert store.rid.insert_subscription(sub) is not None
    return store, pipe, area, delivered


def fanout_push_leg():
    """Headline push fan-out (`bench.py --leg fanout-push`): ONE write
    matched against 10k+ subscriptions through the planner's rqmatch
    route — the fused device kernel with the query and data roles
    swapped — then fanned out as durable webhook deliveries by the
    pool, off the write path.  Reports write-side match qps (bumps/s
    through the rqmatch kernel), matched subscriber-pairs/s, and the
    delivery-lag p50/p99 from enqueue to webhook completion.  Emits
    FANOUT_r01.json next to this file."""
    from datetime import datetime, timezone

    n_subs = int(os.environ.get("DSS_BENCH_PUSH_SUBS", 10_240))
    n_uss = int(os.environ.get("DSS_BENCH_PUSH_USS", 32))
    writes = int(os.environ.get("DSS_BENCH_PUSH_WRITES", 8))
    store, pipe, area, delivered = _fanout_store(
        n_subs, n_uss, cells_per_area=256,
        max_depth=(writes + 2) * n_subs + 1024,
    )
    try:
        from dss_tpu.models import rid as ridm
        from dss_tpu.runtime import freeze_boot_heap

        freeze_boot_heap()
        from datetime import timedelta

        t0 = datetime.now(timezone.utc)
        isa = ridm.IdentificationServiceArea(
            id=str(__import__("uuid").UUID(int=1, version=4)),
            owner="bench", url="https://uss.example/flights",
            cells=area, start_time=t0,
            end_time=t0 + timedelta(hours=24),
            altitude_lo=0.0, altitude_hi=3000.0,
        )
        isa = store.rid.insert_isa(isa)
        pre = store.stats()
        # warm pass: jit/trace warm on the rqmatch route, and the
        # headline single-write assertion — one write, 10k+ matched
        bumped = store.rid.update_notification_idxs_in_cells(
            area, entity=isa
        )
        assert len(bumped) == n_subs, (len(bumped), n_subs)
        assert len(bumped) >= 10_000, (
            f"fan-out below the acceptance floor: {len(bumped)}"
        )
        t_run = time.perf_counter()
        for _ in range(writes):
            out = store.rid.update_notification_idxs_in_cells(
                area, entity=isa
            )
            assert len(out) == n_subs
        match_s = time.perf_counter() - t_run
        assert pipe.drain(timeout_s=300.0), (
            f"delivery queue never drained: depth={pipe.log.depth()}"
        )
        drain_s = time.perf_counter() - t_run
        post = store.stats()
        rq_plans = (
            post["dss_dar_rid_sub_co_plan_rqmatch"]
            - pre["dss_dar_rid_sub_co_plan_rqmatch"]
        )
        assert rq_plans >= 1, (
            "write-side matching never planned the rqmatch device "
            f"route: {rq_plans}"
        )
        ps = pipe.stats()
        assert ps["dss_push_dropped_total"] == 0, ps
        assert ps["dss_push_parked_total"] == 0, ps
        assert ps["dss_push_acked_total"] == (writes + 1) * n_subs, ps
        assert len(delivered) == (writes + 1) * n_subs
        lag = pipe.pool.lag_percentiles_ms()
    finally:
        store.close()
    result = {
        "metric": "fanout_push",
        "value": round((writes * n_subs) / match_s, 1),
        "unit": "matched_pairs_per_s",
        "detail": {
            "subscriptions": n_subs,
            "uss_hooks": n_uss,
            "timed_writes": writes,
            "matched_per_write": n_subs,
            "match_write_qps": round(writes / match_s, 2),
            "matched_pairs_per_s": round((writes * n_subs) / match_s, 1),
            "rqmatch_plans": int(rq_plans),
            "delivered": len(delivered),
            "delivery_lag_p50_ms": lag["p50"],
            "delivery_lag_p99_ms": lag["p99"],
            "drain_s": round(drain_s, 3),
        },
    }
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "FANOUT_r01.json"
    )
    with open(out_path, "w", encoding="utf-8") as fh:
        json.dump(result, fh, indent=1)
    print(json.dumps(result))
    return 0


_FANOUT_CHILD_SRC = """
import json, sys, time
from dss_tpu.push.deliver import DeliveryPool
from dss_tpu.push.queue import DeliveryLog

wal, sink = sys.argv[1], sys.argv[2]
log = DeliveryLog(wal, fsync=False)
fh = open(sink, "a", encoding="utf-8", buffering=1)

def transport(url, body, headers):
    # deliver slowly enough that the parent's SIGKILL lands mid-drain
    fh.write(json.dumps({"nid": body["nid"]}) + chr(10))
    fh.flush()
    time.sleep(0.005)

pool = DeliveryPool(log, workers=1, transport=transport)
pool.start()
print("READY", flush=True)
while True:
    time.sleep(0.1)
"""


def fanout_smoke_leg():
    """CI push smoke (`bench.py --leg fanout-smoke`): three
    deterministic phases.  (1) a seeded FaultPlan at push.match and
    push.deliver — the match fault is absorbed onto the bit-identical
    host oracle (same bumped-subscriber ids as the no-fault write) and
    the delivery faults recover via retry with nothing parked.  (2)
    the delivery-worker SIGKILL drill over a real child process and a
    shared WAL: every acked notification was actually delivered
    before the kill (zero acked loss), every unacked one is
    redelivered after reopen, and the union covers all notifications
    at-least-once.  (3) queue saturation flips the ladder to
    PUSH_DEGRADED (the mildest rung) and draining under the low-water
    mark recovers it to HEALTHY.  Exits nonzero on any miss."""
    import signal
    import subprocess
    import tempfile

    from dss_tpu import chaos

    chaos.clear_plan()
    chaos.registry().reset_counters()
    detail = {}

    # -- phase 1: seeded faults on the match + deliver seams ----------
    store, pipe, area, delivered = _fanout_store(
        n_subs=64, n_uss=8, cells_per_area=64, workers=2,
    )
    try:
        oracle = sorted(
            s.id for s in store.rid.update_notification_idxs_in_cells(
                area
            )
        )
        assert len(oracle) == 64, len(oracle)
        assert pipe.drain(10.0)
        base_acked = pipe.log.acked
        chaos.install_plan(
            {"seed": 17, "events": [
                {"site": "push.match", "action": "error", "count": 1},
                {"site": "push.deliver", "action": "error", "count": 2},
            ]}
        )
        got = sorted(
            s.id for s in store.rid.update_notification_idxs_in_cells(
                area
            )
        )
        assert got == oracle, (
            "faulted match diverged from the no-fault oracle"
        )
        assert pipe.stage("rid_sub").absorbed >= 1, (
            "push.match fault was not absorbed onto the host oracle"
        )
        assert pipe.drain(30.0), (
            f"faulted deliveries never drained: {pipe.log.depth()}"
        )
        injected = chaos.registry().injected_by_site()
        assert injected.get("push.match", 0) == 1, injected
        assert injected.get("push.deliver", 0) == 2, injected
        ps = pipe.stats()
        assert ps["dss_push_parked_total"] == 0, ps
        assert ps["dss_push_acked_total"] == base_acked + 64, ps
        assert store.health.mode() == chaos.HEALTHY
        detail["fault_injected"] = injected
        detail["fault_retries"] = ps["dss_push_requeued_total"]
    finally:
        chaos.clear_plan()
        store.close()

    # -- phase 2: SIGKILL a delivery worker process mid-drain ---------
    n_evt = 200
    with tempfile.TemporaryDirectory() as td:
        wal = os.path.join(td, "push.wal")
        sink = os.path.join(td, "delivered.jsonl")
        from dss_tpu.push.queue import DeliveryLog

        log = DeliveryLog(wal, fsync=False)
        log.register_hook("u1", "https://u1.example/notify")
        for i in range(n_evt):
            assert log.enqueue(
                "u1", "https://u1.example/notify", {"nid": i + 1}
            ) is not None
        log.close()

        def read_sink():
            if not os.path.exists(sink):
                return []
            out = []
            with open(sink, "r", encoding="utf-8") as fh:
                for line in fh:
                    try:
                        out.append(json.loads(line)["nid"])
                    except (ValueError, KeyError):
                        pass  # torn tail write racing the reader
            return out

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        child = subprocess.Popen(
            [sys.executable, "-c", _FANOUT_CHILD_SRC, wal, sink],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
        t_kill = time.perf_counter()
        try:
            while len(read_sink()) < n_evt // 4:
                assert child.poll() is None, "child died before kill"
                assert time.perf_counter() - t_kill < 120.0, (
                    "child never started delivering"
                )
                time.sleep(0.01)
            os.kill(child.pid, signal.SIGKILL)
        finally:
            if child.poll() is None:
                child.kill()
            child.wait(timeout=10.0)
        before_kill = read_sink()
        assert len(before_kill) >= n_evt // 4

        # reopen the WAL: acked ⊆ delivered (zero acked loss), and
        # everything unacked replays for redelivery
        log2 = DeliveryLog(wal, fsync=False)
        all_nids = set(range(1, n_evt + 1))
        pending = set(
            n.body["nid"] for n in log2._open.values()
        )
        acked = all_nids - pending
        lost = acked - set(before_kill)
        assert not lost, (
            f"SIGKILL lost {len(lost)} ACKED notifications: "
            f"{sorted(lost)[:10]}"
        )
        assert log2.depth() == n_evt - len(acked)

        from dss_tpu.push.deliver import DeliveryPool

        def transport2(url, body, headers):
            with open(sink, "a", encoding="utf-8") as fh:
                fh.write(json.dumps({"nid": body["nid"]}) + "\n")

        pool2 = DeliveryPool(log2, workers=2, transport=transport2)
        pool2.start()
        t_rec = time.perf_counter()
        while log2.depth() > 0:
            assert time.perf_counter() - t_rec < 60.0, (
                f"redelivery never drained: {log2.depth()}"
            )
            time.sleep(0.01)
        recovery_s = time.perf_counter() - t_rec
        pool2.close()
        final = read_sink()
        assert set(final) == all_nids, (
            f"at-least-once miss: {sorted(all_nids - set(final))[:10]}"
        )
        assert pool2.parked == 0
        log2.close()
        detail.update(
            delivered_before_kill=len(before_kill),
            acked_before_kill=len(acked),
            acked_lost=0,
            redelivered=len(final) - len(before_kill),
            redeliver_drain_s=round(recovery_s, 3),
        )

    # -- phase 3: saturation -> PUSH_DEGRADED -> drain -> HEALTHY -----
    store, pipe, area, _ = _fanout_store(
        n_subs=50, n_uss=1, cells_per_area=64, workers=1,
        max_depth=50,
    )
    try:
        pipe.pool.close()  # keep the queue full: no drain race
        store.rid.update_notification_idxs_in_cells(area)
        assert pipe.log.depth() == 50
        assert store.health.is_active("push_degraded"), (
            "saturated queue never flipped the ladder"
        )
        assert store.health.mode() == chaos.PUSH_DEGRADED
        t_rec = time.perf_counter()
        while pipe.log.depth() > 20:
            n = pipe.log.take(timeout_s=1.0)
            assert n is not None
            pipe.log.ack(n.nid)
        pipe._update_health()
        assert store.health.mode() == chaos.HEALTHY, (
            store.health.mode_name()
        )
        detail["ladder_recovery_s"] = round(
            time.perf_counter() - t_rec, 3
        )
    finally:
        store.close()

    print(
        json.dumps(
            {
                "metric": "fanout_smoke",
                "value": 1,
                "unit": "ok",
                "detail": detail,
            }
        )
    )
    return 0


def _chaos_device_lost_mid_stream() -> dict:
    """Named scenario: the resident stream loses its device with
    batches in flight.  Every admitted caller still resolves with the
    right answer (host re-run), the ladder flips, and recovery
    re-warms the AOT grid before the stream serves again."""
    from concurrent.futures import ThreadPoolExecutor

    from dss_tpu import chaos

    chaos.clear_plan()
    chaos.registry().reset_counters()
    n_cells = 500
    width = 4
    table = build_table(2000, n_cells, 4)
    co = QueryCoalescer(
        table, min_batch=1, max_batch=256, inline=False, queue_depth=64,
        slo_ms=0.0, resident=True,
        est_floor_ms=10_000.0, est_res_floor_ms=0.05, est_chunk_ms=1e6,
    )
    lad = chaos.DegradationLadder()
    co.set_health(lad)
    loop = co.resident_loop()
    table.warm_resident(
        loop.kernel, batch_buckets=(16, 32, 64, 128),
        window_buckets=(256, 1024),
    )
    starts = np.random.default_rng(3).integers(0, n_cells - width, 256)

    def one(i):
        keys = (
            int(starts[i % len(starts)]) + np.arange(width)
        ).astype(np.int32)
        return keys, co.query(
            keys, None, None, NOW - HOUR, NOW + HOUR, now=NOW
        )

    def check(pairs):
        for keys, res in pairs:
            ref = table.query(
                keys, None, None, NOW - HOUR, NOW + HOUR, now=NOW
            )
            assert res == ref, f"divergence: {res} != {ref}"

    try:
        with ThreadPoolExecutor(max_workers=16) as pool:
            warm = list(pool.map(one, range(64)))
        check(warm)
        st0 = co.stats()
        assert st0["co_route_resident_batches"] >= 1, st0

        chaos.install_plan(
            {"seed": 2, "events": [
                {"site": "resident.submit", "action": "device_lost",
                 "count": 1},
                {"site": "device.dispatch", "action": "device_lost",
                 "count": 1},
            ]}
        )
        with ThreadPoolExecutor(max_workers=16) as pool:
            during = list(pool.map(one, range(128)))
        check(during)  # zero errors, zero divergence through the loss
        assert lad.is_active("device_lost")
        st1 = co.stats()
        assert st1["co_device_loss_absorbed"] >= 1, st1
        dwell_s = lad.dwell_s("device_lost")

        chaos.clear_plan()
        t_rec = time.perf_counter()
        lad.exit("device_lost")
        with ThreadPoolExecutor(max_workers=16) as pool:
            after = list(pool.map(one, range(64)))
        check(after)
        recovery_s = time.perf_counter() - t_rec
        st2 = co.stats()
        assert (
            st2["co_route_resident_batches"]
            > st1["co_route_resident_batches"]
        ), "stream never re-admitted after recovery"
        injected = chaos.registry().injected_by_site()
        return {
            "ok": True,
            "absorbed": st1["co_device_loss_absorbed"],
            "degraded_dwell_s": round(dwell_s, 3),
            "recovery_to_slo_s": round(recovery_s, 3),
            "error_budget_burn": 0.0,
            "injected": injected,
        }
    finally:
        chaos.clear_plan()
        chaos.registry().reset_counters()
        co.close()
        table.close()


def _chaos_wal_fsync_stall(tmpdir: str) -> dict:
    """Named scenario: the WAL's fsync stalls (slow disk).  Writes pay
    the stall honestly (latency, not loss); after the stall clears,
    a fresh boot replays EVERY acked write."""
    import uuid as _uuid
    from datetime import datetime, timedelta, timezone

    from dss_tpu import chaos
    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.geo.s2cell import dar_key_to_cell
    from dss_tpu.models import rid as ridm

    chaos.clear_plan()
    chaos.registry().reset_counters()
    path = os.path.join(tmpdir, "chaos_wal.log")
    store = DSSStore(storage="memory", wal_path=path, wal_fsync=True)
    t0 = datetime.now(timezone.utc) + timedelta(minutes=5)
    t1 = t0 + timedelta(hours=24)

    def put(k):
        isa = ridm.IdentificationServiceArea(
            id=str(_uuid.UUID(int=k + 1, version=4)), owner="bench",
            url="https://uss.example/flights",
            cells=dar_key_to_cell(
                np.arange(k * 4, (k + 1) * 4, dtype=np.int64)
            ),
            start_time=t0, end_time=t1,
            altitude_lo=0.0, altitude_hi=3000.0,
        )
        t = time.perf_counter()
        assert store.rid.insert_isa(isa) is not None
        return (time.perf_counter() - t) * 1000

    try:
        base = [put(k) for k in range(40)]
        chaos.install_plan(
            {"seed": 4, "events": [
                {"site": "wal.fsync", "action": "delay",
                 "delay_s": 0.02, "count": -1},
            ]}
        )
        stalled = [put(k) for k in range(40, 80)]
        injected = chaos.registry().injected_by_site().get("wal.fsync", 0)
        chaos.clear_plan()
    finally:
        chaos.clear_plan()
        store.close()
    # zero acked-write loss: a fresh boot replays everything
    re = DSSStore(storage="memory", wal_path=path)
    replayed = len(re.rid._isas)
    re.close()
    chaos.registry().reset_counters()
    assert replayed == 80, f"acked-write loss: {replayed}/80 after replay"
    p50 = lambda xs: float(np.percentile(xs, 50))  # noqa: E731
    assert injected >= 40
    assert p50(stalled) > p50(base), (
        "stall never showed in write latency"
    )
    return {
        "ok": True,
        "write_p50_ms_clean": round(p50(base), 3),
        "write_p50_ms_stalled": round(p50(stalled), 3),
        "write_p99_ms_stalled": round(float(np.percentile(stalled, 99)), 3),
        "acked_writes_after_replay": replayed,
        "fsync_stalls_injected": injected,
    }


def _chaos_region_partition(tmpdir: str) -> dict:
    """Named scenario: the region log partitions away from this
    instance.  Writes shed 503 with an honest Retry-After (breaker
    cooldown), reads keep serving the stale-but-consistent state with
    the mode surfaced, and the ladder walks back down on its own once
    the link heals (the tail poller's first success)."""
    import subprocess
    import sys
    import uuid as _uuid
    from datetime import datetime, timedelta, timezone

    from dss_tpu import chaos
    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.geo.s2cell import dar_key_to_cell
    from dss_tpu.models import rid as ridm

    chaos.clear_plan()
    chaos.registry().reset_counters()
    port = _chaos_free_port()
    url = f"http://127.0.0.1:{port}"
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "dss_tpu.cmds.region_server",
            "--addr", f"127.0.0.1:{port}",
            "--wal_path", os.path.join(tmpdir, "region.wal"),
        ],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    store = None
    try:
        _chaos_wait_http(url + "/status")
        store = DSSStore(storage="memory", region_url=url)
        t0 = datetime.now(timezone.utc) + timedelta(minutes=5)
        t1 = t0 + timedelta(hours=24)

        def put(k):
            isa = ridm.IdentificationServiceArea(
                id=str(_uuid.UUID(int=k + 1, version=4)), owner="bench",
                url="https://uss.example/flights",
                cells=dar_key_to_cell(
                    np.arange(k * 4, (k + 1) * 4, dtype=np.int64)
                ),
                start_time=t0, end_time=t1,
                altitude_lo=0.0, altitude_hi=3000.0,
            )
            return store.rid.insert_isa(isa)

        for k in range(5):
            assert put(k) is not None
        area = dar_key_to_cell(np.arange(0, 4, dtype=np.int64))
        pre_reads = sorted(
            x.id for x in store.rid.search_isas(area, t0, None)
        )
        assert pre_reads

        # PARTITION: every region-log request fails at the transport
        chaos.install_plan(
            {"seed": 6, "events": [
                {"site": "region.client.request",
                 "action": "partition", "count": -1},
            ]}
        )
        shed = None
        try:
            put(100)
        except errors.StatusError as e:
            shed = e
        assert shed is not None and shed.http_status == 503, shed
        retry_after = getattr(shed, "retry_after_s", None)
        assert retry_after and retry_after > 0, (
            "region-down 503 carried no Retry-After"
        )
        assert store.health.is_active("region_log_down")
        assert (
            store.freshness_status()["degraded_mode"]
            == "region_log_down"
        )
        # reads keep serving the fenced stale-but-consistent state
        during_reads = sorted(
            x.id for x in store.rid.search_isas(area, t0, None)
        )
        assert during_reads == pre_reads
        breakers = store.stats()["dss_breaker_state"]
        assert any(v == 2 for v in breakers.values()), breakers

        # HEAL: the tail poller's first success exits the condition;
        # writes resume
        chaos.clear_plan()
        t_rec = time.perf_counter()
        deadline = t_rec + 30.0
        wrote = False
        while time.perf_counter() < deadline:
            try:
                if put(101) is not None:
                    wrote = True
                    break
            except errors.StatusError:
                time.sleep(0.2)
        assert wrote, "writes never recovered after the partition healed"
        recovery_s = time.perf_counter() - t_rec
        deadline = time.perf_counter() + 10.0
        while (
            store.health.mode() != chaos.HEALTHY
            and time.perf_counter() < deadline
        ):
            time.sleep(0.05)
        assert store.health.mode() == chaos.HEALTHY
        return {
            "ok": True,
            "write_shed_status": shed.http_status,
            "write_shed_retry_after_s": round(retry_after, 3),
            "reads_served_during_partition": len(during_reads),
            "degraded_dwell_s": round(
                store.health.dwell_s("region_log_down"), 3
            ),
            "recovery_to_first_write_s": round(recovery_s, 3),
        }
    finally:
        chaos.clear_plan()
        chaos.registry().reset_counters()
        if store is not None:
            store.close()
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except Exception:  # noqa: BLE001
            proc.kill()


def _chaos_mirror_link_flap(tmpdir: str) -> dict:
    """Named scenario: the primary->mirror replication link flaps
    (drops, then delays).  The fault plan ships via DSS_FAULT_PLAN in
    the PRIMARY process's environment — the cross-process injection
    path operators use.  The flap is visible in
    region_mirror_backoff_s BEFORE lag accumulates, and the mirror
    converges to the full head once the link heals."""
    import subprocess
    import sys

    import requests

    pport, mport = _chaos_free_port(), _chaos_free_port()
    purl = f"http://127.0.0.1:{pport}"
    murl = f"http://127.0.0.1:{mport}"
    plan = json.dumps(
        {"seed": 3, "events": [
            {"site": "region.mirror.replicate", "match": "/replicate",
             "action": "error", "count": 8},
            {"site": "region.mirror.replicate", "match": "/replicate",
             "action": "delay", "delay_s": 0.15, "after": 8,
             "count": 12},
        ]}
    )
    primary = subprocess.Popen(
        [
            sys.executable, "-m", "dss_tpu.cmds.region_server",
            "--addr", f"127.0.0.1:{pport}",
            "--wal_path", os.path.join(tmpdir, "flap_p.wal"),
        ],
        env=dict(os.environ, DSS_FAULT_PLAN=plan, JAX_PLATFORMS="cpu"),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    mirror = subprocess.Popen(
        [
            sys.executable, "-m", "dss_tpu.cmds.region_server",
            "--addr", f"127.0.0.1:{mport}",
            "--wal_path", os.path.join(tmpdir, "flap_m.wal"),
            "--mirror_of", purl,
            "--advertise_url", murl,
        ],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )
    try:
        _chaos_wait_http(purl + "/status")
        _chaos_wait_http(murl + "/status")
        from dss_tpu.region.client import RegionClient

        c = RegionClient(purl, "chaos-bench")
        n = 12
        for i in range(4):
            tok, _ = c.acquire_lease()
            c.append(tok, [{"t": "e", "i": i}], release=True)

        # the flap must be VISIBLE while it happens: poll the backoff
        # gauge during the drop window
        backoff_seen = 0.0
        deadline = time.time() + 8.0
        while time.time() < deadline and backoff_seen == 0.0:
            text = requests.get(purl + "/metrics", timeout=5).text
            for line in text.splitlines():
                if line.startswith("region_mirror_backoff_s"):
                    backoff_seen = max(
                        backoff_seen, float(line.split()[-1])
                    )
            time.sleep(0.02)
        assert backoff_seen > 0.0, (
            "flap never visible in region_mirror_backoff_s"
        )
        for i in range(4, n):
            tok, _ = c.acquire_lease()
            c.append(tok, [{"t": "e", "i": i}], release=True)

        # after the seeded plan exhausts, the link heals and the
        # mirror converges to the full head
        t_rec = time.time()
        deadline = time.time() + 60.0
        lag = None
        while time.time() < deadline:
            st = requests.get(purl + "/status", timeout=5).json()
            lag = st["lag_entries"]
            if st["mirrors"] and lag == 0:
                break
            time.sleep(0.2)
        assert lag == 0, f"mirror never converged (lag={lag})"
        mh = requests.get(murl + "/status", timeout=5).json()["head"]
        assert mh == n, f"mirror head {mh} != {n} after recovery"
        return {
            "ok": True,
            "entries": n,
            "max_backoff_seen_s": round(backoff_seen, 3),
            "converge_after_heal_s": round(time.time() - t_rec, 3),
        }
    finally:
        for p in (primary, mirror):
            p.terminate()
        for p in (primary, mirror):
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                p.kill()


def chaos_leg():
    """`bench.py --leg chaos`: the four named fault scenarios, each a
    seeded, replayable schedule — device-lost-mid-stream,
    WAL-fsync-stall, region-partition, mirror-link-flap — reporting
    error-budget burn, degraded-mode dwell time, and recovery time.
    One JSON line; nonzero exit if any scenario's contract breaks."""
    import tempfile

    detail = {}
    with tempfile.TemporaryDirectory(prefix="dss-chaos-") as tmpdir:
        detail["device-lost-mid-stream"] = _chaos_device_lost_mid_stream()
        detail["wal-fsync-stall"] = _chaos_wal_fsync_stall(tmpdir)
        detail["region-partition"] = _chaos_region_partition(tmpdir)
        detail["mirror-link-flap"] = _chaos_mirror_link_flap(tmpdir)
    print(
        json.dumps(
            {
                "metric": "chaos",
                "value": len(detail),
                "unit": "scenarios_ok",
                "detail": detail,
            }
        )
    )
    return 0


def federation_leg() -> int:
    """`bench.py --leg federation`: the two-region partition drill
    (cmds/federation_dryrun.py — seeded-FaultPlan leg + the SIGKILL
    leg over four real processes), emitting a MULTICHIP-style
    FED_r01.json with partition dwell, error-budget burn, and
    recovery time.  Nonzero exit if any contract breaks: global-query
    bit-identity vs the merged oracle, zero local 5xx through the
    partition, stale reads marked and bounded, remote-owned writes
    shed 503 with honest Retry-After, zero acked-write loss after
    heal."""
    import tempfile

    from dss_tpu.cmds.federation_dryrun import run_dryrun

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="dss-fedbench-") as td:
        verdict = run_dryrun(td)
    wall = round(time.perf_counter() - t0, 2)
    sk = verdict.get("sigkill", {})
    doc = {
        "bench": "federation",
        "format": 1,
        "ok": bool(verdict.get("ok")),
        "wall_s": wall,
        "regions": 2,
        "bit_identical": bool(sk.get("bit_identical")),
        "partition_dwell_s": sk.get("partition_dwell_s"),
        "recovery_s": sk.get("recovery_s"),
        "error_budget": {
            "requests": sk.get("requests_total"),
            "unexpected_statuses": sk.get("unexpected_statuses"),
            "burn": sk.get("error_budget_burn"),
            "local_5xx_during_partition": sk.get(
                "partition", {}
            ).get("local_5xx"),
        },
        "faultplan": verdict.get("faultplan"),
        "sigkill": sk,
    }
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "FED_r01.json"
    )
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True, default=str)
        f.write("\n")
    print(
        json.dumps(
            {
                "metric": "federation",
                "value": 1 if doc["ok"] else 0,
                "unit": "ok",
                "detail": {
                    "partition_dwell_s": doc["partition_dwell_s"],
                    "recovery_s": doc["recovery_s"],
                    "error_budget_burn": doc["error_budget"]["burn"],
                    "bit_identical": doc["bit_identical"],
                    "wall_s": wall,
                    "artifact": os.path.basename(out_path),
                },
            }
        )
    )
    return 0 if doc["ok"] else 1


def _skew_reexec(leg: str):
    """The skew legs need the dp=1 x sp=8 mesh, which exists only as
    8 virtual CPU devices (the hardware at hand has 1 or 4 chips).
    Unless this process already IS that CPU mesh, re-exec the leg in a
    subprocess with the virtual-device env and relay its JSON verdict.
    Returns the parsed result dict, or None when this process can run
    the leg inline.  Decided from the environment alone: asking JAX
    for its devices would make this parent take the chip it is about
    to not use."""
    import re
    import subprocess

    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if (
        os.environ.get("JAX_PLATFORMS") == "cpu"
        and m is not None and int(m.group(1)) >= 8
    ):
        return None

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    want = "--xla_force_host_platform_device_count=8"
    if m is not None:
        # REPLACE an inherited smaller count (same pattern as
        # multihost.initialize): merely appending would leave the
        # child under 8 devices and re-execing forever
        env["XLA_FLAGS"] = flags.replace(m.group(0), want)
    else:
        env["XLA_FLAGS"] = (flags + " " + want).strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--leg", leg],
        env=env, capture_output=True, text=True, timeout=1800,
    )
    line = ""
    for ln in proc.stdout.splitlines():
        if ln.startswith("{"):
            line = ln
    if proc.returncode != 0 or not line:
        raise RuntimeError(
            f"skew subprocess failed (rc={proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
        )
    return json.loads(line)


def _skew_replica(records, *, max_results=256, shard_results=48,
                  load_shift=2, rebalance_ratio=1.5):
    """A ShardedReplica over an 8-virtual-device (dp=1, sp=8) mesh
    with `records` injected directly as the isas class (the leg
    measures the mesh query path + placement search, not WAL codec
    ingestion).  shard_results < max_results on purpose: it is the
    per-shard result capacity a hot range can blow when placement
    concentrates it on one shard — per-query exact host fallback, the
    real latency cliff skew-aware splitting removes."""
    import tempfile

    from dss_tpu.dar.tiers import RangeLoad
    from dss_tpu.parallel import make_mesh
    from dss_tpu.parallel.replica import ShardedReplica

    mesh = make_mesh(8, dp=1, sp=8)
    wal = os.path.join(
        tempfile.mkdtemp(prefix="dss-skew-"), "empty.wal"
    )
    open(wal, "w").close()
    rep = ShardedReplica(
        mesh,
        wal_path=wal,
        max_results=max_results,
        shard_results=shard_results,
        rebalance_ratio=rebalance_ratio,
        move_interval_s=0.0,
    )
    rep.load = RangeLoad(shift=load_shift, decay_factor=0.5)
    with rep._mu:
        rep._records["isas"] = {r.entity_id: r for r in records}
        rep._dirty["isas"] = True
    rep.refresh(plan=False)
    return rep


def _mk_skew_fixture(n_cold, n_hot, n_areas, seed=7):
    """Cold entities uniform over a wide key space plus one hot metro:
    n_hot entities concentrated in a narrow contiguous key range.
    Areas: rank-0 covers the hot range; the rest are uniform cold
    windows.  Returns (records, areas)."""
    from dss_tpu.dar.oracle import Record

    rng = np.random.default_rng(seed)
    key_space = 50_000
    hot_lo = 21_000  # mid-space: inside one equal-count shard's range
    hot_w = 64
    recs = []
    for i in range(n_cold):
        k0 = int(rng.integers(0, key_space - 16))
        keys = np.unique(
            rng.integers(k0, k0 + 16, 4).astype(np.int32)
        )
        recs.append(Record(
            entity_id=f"c{i}", keys=keys, alt_lo=0.0, alt_hi=3000.0,
            t_start=-(2**62), t_end=2**62, owner_id=0,
        ))
    for i in range(n_hot):
        k0 = hot_lo + int(rng.integers(0, hot_w - 4))
        keys = np.unique(
            rng.integers(k0, k0 + 4, 3).astype(np.int32)
        )
        recs.append(Record(
            entity_id=f"h{i}", keys=keys, alt_lo=0.0, alt_hi=3000.0,
            t_start=-(2**62), t_end=2**62, owner_id=0,
        ))
    areas = [np.arange(hot_lo, hot_lo + hot_w, dtype=np.int32)]
    for _ in range(n_areas - 1):
        k0 = int(rng.integers(0, key_space - 24))
        areas.append(np.arange(k0, k0 + 24, dtype=np.int32))
    return recs, areas


def _zipf_ranks(rng, n_areas, alpha, n):
    """n area indices, rank-biased: P(rank r) ~ (r+1)^-alpha (alpha=0
    = uniform; the hot metro is rank 0)."""
    p = (np.arange(1, n_areas + 1, dtype=np.float64)) ** (-alpha)
    p /= p.sum()
    return rng.choice(n_areas, size=n, p=p)


def _skew_pass(rep, areas, picks, *, now=0):
    """Serial single-query pass (each query is one mesh dispatch —
    the per-query latency distribution is the point); -> latencies ms,
    overflow fallbacks incurred, measured per-shard hit work."""
    lat = []
    snap = rep._snapshots["isas"]
    ovf0 = sum(
        d.overflow_fallbacks
        for d in (snap.base, snap.delta) if d is not None
    )
    hits0 = rep.measured_shard_loads().copy()
    for a in picks:
        t0 = time.perf_counter()
        rep.query_batch(
            [areas[a]],
            np.full(1, -np.inf, np.float32),
            np.full(1, np.inf, np.float32),
            np.full(1, -(2**62), np.int64),
            np.full(1, 2**62, np.int64),
            now=now, cls="isas",
        )
        lat.append((time.perf_counter() - t0) * 1000)
    snap = rep._snapshots["isas"]
    ovf = sum(
        d.overflow_fallbacks
        for d in (snap.base, snap.delta) if d is not None
    ) - ovf0
    work = rep.measured_shard_loads() - hits0
    return np.asarray(lat), ovf, work


def skew_leg(emit: bool = True):
    """Zipf hot-spot sweep (`bench.py --leg skew`; also folded into
    the north-star JSON): per-query mesh latency at
    DSS_BENCH_ZIPF_ALPHAS (default 0, 0.8, 1.2) with load-weighted
    shard rebalancing ON vs OFF on the SAME store.  Reports p50/p99
    per alpha per mode plus the measured per-shard imbalance factor
    (from the kernels' per-shard hit counts).  The acceptance bar:
    rebalancing-ON p99 at alpha=1.2 within 1.5x of the uniform-load
    p99, with static placement measurably worse (the hot range
    concentrated on one shard blows the per-shard result capacity and
    falls back to exact host scans)."""
    sub = _skew_reexec("skew")
    if sub is not None:
        if emit:
            print(json.dumps(sub))
        return sub["detail"]
    from dss_tpu.dar.tiers import RangeLoad
    from dss_tpu.parallel.sharded import imbalance_factor

    alphas = [
        float(x)
        for x in os.environ.get(
            "DSS_BENCH_ZIPF_ALPHAS", "0,0.8,1.2"
        ).split(",")
    ]
    n_cold = int(os.environ.get("DSS_BENCH_SKEW_COLD", 3000))
    n_hot = int(os.environ.get("DSS_BENCH_SKEW_HOT", 120))
    n_areas = int(os.environ.get("DSS_BENCH_SKEW_AREAS", 64))
    n_q = int(os.environ.get("DSS_BENCH_SKEW_QUERIES", 250))
    recs, areas = _mk_skew_fixture(n_cold, n_hot, n_areas)
    rep = _skew_replica(recs)
    per_alpha = {}
    try:
        for alpha in alphas:
            rng = np.random.default_rng(int(alpha * 10) + 1)
            picks = _zipf_ranks(rng, n_areas, alpha, n_q)

            # -- OFF: static equal-count placement --------------------
            rep.load = RangeLoad(shift=2, decay_factor=0.5)
            rep.rebalance_ratio = 0.0
            if rep.boundaries is not None:
                rep.boundaries = None
                with rep._mu:
                    rep._force_major["isas"] = True
                    rep._dirty["isas"] = True
                rep.refresh(plan=False)
            warm = _zipf_ranks(rng, n_areas, alpha, 16)
            _skew_pass(rep, areas, warm)  # jit warm, not measured
            lat_off, ovf_off, work_off = _skew_pass(rep, areas, picks)

            # -- ON: measure load, rebalance at the fold, re-measure --
            rep.load = RangeLoad(shift=2, decay_factor=0.5)
            rep.rebalance_ratio = 1.5
            _skew_pass(rep, areas, picks)  # the load-measurement pass
            moves0 = rep.boundary_moves
            rep.plan_rebalance()
            imb_before = rep._imbalance
            rep.refresh(plan=False)
            _skew_pass(rep, areas, warm)  # warm the new split's jit
            lat_on, ovf_on, work_on = _skew_pass(rep, areas, picks)
            rep.plan_rebalance()  # recompute under the new boundaries

            per_alpha[str(alpha)] = {
                "off": {
                    "p50_ms": round(float(np.percentile(lat_off, 50)), 3),
                    "p99_ms": round(float(np.percentile(lat_off, 99)), 3),
                    "overflow_fallbacks": int(ovf_off),
                    "measured_imbalance": round(
                        imbalance_factor(work_off), 3
                    ),
                },
                "on": {
                    "p50_ms": round(float(np.percentile(lat_on, 50)), 3),
                    "p99_ms": round(float(np.percentile(lat_on, 99)), 3),
                    "overflow_fallbacks": int(ovf_on),
                    "measured_imbalance": round(
                        imbalance_factor(work_on), 3
                    ),
                    "boundary_moves": rep.boundary_moves - moves0,
                    "imbalance_before_move": round(imb_before, 3),
                    "imbalance_after_move": round(rep._imbalance, 3),
                },
            }
    finally:
        rep.close()
    uni = per_alpha.get("0.0") or per_alpha.get(str(alphas[0]))
    hotk = str(alphas[-1])
    result = {
        "alphas": alphas,
        "cold_entities": n_cold,
        "hot_entities": n_hot,
        "areas": n_areas,
        "queries_per_pass": n_q,
        # this process ran the mesh (see _skew_reexec): its own devices
        "backend": jax.devices()[0].platform,
        "devices": len(jax.devices()),
        "per_alpha": per_alpha,
        # the acceptance ratios, stated directly
        "on_p99_vs_uniform": round(
            per_alpha[hotk]["on"]["p99_ms"]
            / max(uni["on"]["p99_ms"], 1e-9), 3,
        ),
        "off_p99_vs_on_at_hot": round(
            per_alpha[hotk]["off"]["p99_ms"]
            / max(per_alpha[hotk]["on"]["p99_ms"], 1e-9), 3,
        ),
    }
    if emit:
        print(json.dumps({
            "metric": "skew_on_p99_vs_uniform",
            "value": result["on_p99_vs_uniform"],
            "unit": "x",
            "detail": result,
        }))
    return result


def skew_smoke_leg():
    """CI skew smoke (`bench.py --leg skew-smoke`): the deterministic
    hot-spot chain — one hot key range hammered -> imbalance detected
    above DSS_SHARD_REBALANCE_RATIO -> boundaries move at the fold ->
    measured imbalance recovers -> answers bit-identical before and
    after the move, and the static run pays overflow fallbacks the
    rebalanced run does not.  Exits nonzero if any link fails."""
    sub = _skew_reexec("skew-smoke")
    if sub is not None:
        print(json.dumps(sub))
        return 0 if sub.get("value") == 1 else 1
    from dss_tpu.dar.tiers import RangeLoad

    recs, areas = _mk_skew_fixture(1200, 100, 16)
    rep = _skew_replica(recs, shard_results=32)
    errors = []
    try:
        hot = areas[0]

        def run_hot():
            return rep.query_batch(
                [hot],
                np.full(1, -np.inf, np.float32),
                np.full(1, np.inf, np.float32),
                np.full(1, -(2**62), np.int64),
                np.full(1, 2**62, np.int64),
                now=0, cls="isas",
            )

        before = run_hot()
        if not before[0]:
            errors.append("hot query returned nothing")
        snap = rep._snapshots["isas"]
        ovf_static = snap.base.overflow_fallbacks
        if ovf_static == 0:
            errors.append(
                "static placement never overflowed the per-shard "
                "capacity: the smoke fixture is too small to prove "
                "the cliff"
            )
        # hammer the hot range (the load the rebalancer plans from)
        rep.load = RangeLoad(shift=2, decay_factor=0.5)
        for _ in range(30):
            rep.load.record(hot, work=100.0)
        moved = rep.plan_rebalance()
        imb_before = rep._imbalance
        if not moved:
            errors.append(
                f"no boundary move (imbalance {imb_before:.2f})"
            )
        if rep.boundary_moves != 1:
            errors.append(f"boundary_moves {rep.boundary_moves} != 1")
        rep.refresh(plan=False)
        after = run_hot()
        if before != after:
            errors.append("answers changed across the boundary move")
        snap = rep._snapshots["isas"]
        ovf0 = snap.base.overflow_fallbacks
        run_hot()
        if snap.base.overflow_fallbacks != ovf0:
            errors.append(
                "rebalanced placement still pays exact-host overflow "
                "fallbacks on the hot range"
            )
        rep.plan_rebalance()
        if not rep._imbalance < imb_before:
            errors.append(
                f"imbalance did not recover: {imb_before:.2f} -> "
                f"{rep._imbalance:.2f}"
            )
        # uniform load must NOT move boundaries (hysteresis)
        rep.load = RangeLoad(shift=2, decay_factor=0.5)
        rng = np.random.default_rng(3)
        for _ in range(64):
            a = areas[int(rng.integers(0, len(areas)))]
            rep.load.record(a, work=2.0)
        gen0 = rep.boundary_moves
        rep.plan_rebalance()
        if rep.boundary_moves != gen0:
            errors.append("uniform load moved boundaries (no hysteresis)")
    finally:
        rep.close()
    ok = not errors
    print(json.dumps({
        "metric": "skew_smoke",
        "value": 1 if ok else 0,
        "unit": "ok",
        "detail": {
            "errors": errors,
            "boundary_moves": rep.boundary_moves,
            "imbalance_before": round(imb_before, 3),
            "imbalance_after": round(rep._imbalance, 3),
        },
    }))
    return 0 if ok else 1



# -- autotune: offline mapping-space search + cold-start comparison ------------


def _seeds_from_profile(profile: dict) -> dict:
    """QueryCoalescer constructor seeds from an autotune profile's
    knobs (what a profile-loaded boot passes through env_knobs)."""
    k = profile["knobs"]
    return {
        "est_floor_ms": float(k["DSS_CO_EST_FLOOR_MS"]),
        "est_item_ms": float(k["DSS_CO_EST_ITEM_MS"]),
        "est_chunk_ms": float(k["DSS_CO_EST_CHUNK_MS"]),
        "est_res_floor_ms": float(k["DSS_CO_EST_RES_FLOOR_MS"]),
        "est_res_lat_ms": float(k["DSS_CO_EST_RES_LAT_MS"]),
        "res_ring": int(k["DSS_CO_RES_RING"]),
        "res_inflight": int(k["DSS_CO_RES_INFLIGHT"]),
    }


def _cold_start_pass(table, n_cells, width, seeds, secs, threads,
                     early_frac=0.4):
    """One cold-start serving window: a FRESH coalescer (its cost
    models reset to `seeds`) under closed-loop deadline-carrying load,
    with per-sample timestamps so the EARLY window — where boot-seed
    quality is the whole story — reports its own p99.  XLA compiles
    are process-warm by construction (the caller prewarms), so this
    measures routing quality, not compile luck."""
    co = QueryCoalescer(
        table, slo_ms=_bench_slo_ms(), resident=_bench_resident(),
        **seeds,
    )
    loop = co.resident_loop()
    if loop is not None and hasattr(table, "warm_resident"):
        table.warm_resident(
            loop.kernel, batch_buckets=(128,), window_buckets=(4096,),
        )
    st0 = co.stats()
    stop = threading.Event()
    samples: list = [[] for _ in range(threads)]  # (t_rel, lat_ms)
    sheds = [0] * threads
    t_start = time.perf_counter()

    def client(i):
        r = np.random.default_rng(7000 + i)
        while not stop.is_set():
            start = int(r.integers(0, n_cells - width))
            keys = (start + np.arange(width)).astype(np.int32)
            alo = float(r.uniform(0, 3000))
            t0 = NOW + int(r.integers(-2, 2)) * HOUR
            t_req = time.perf_counter()
            try:
                co.query(keys, alo, alo + 300.0, t0, t0 + HOUR, now=NOW)
            except errors.StatusError:
                sheds[i] += 1
                continue
            t_done = time.perf_counter()
            samples[i].append((t_req - t_start, (t_done - t_req) * 1e3))

    ths = [
        threading.Thread(target=client, args=(i,)) for i in range(threads)
    ]
    for t in ths:
        t.start()
    time.sleep(secs)
    stop.set()
    for t in ths:
        t.join()
    st1 = co.stats()
    co.close()
    all_s = sorted(
        (t, l) for part in samples for (t, l) in part
    )
    lat = np.asarray([l for _, l in all_s])
    early = np.asarray([l for t, l in all_s if t <= early_frac * secs])
    late = np.asarray([l for t, l in all_s if t > early_frac * secs])

    def _p(a, q):
        return float(np.percentile(a, q)) if len(a) else None

    d = max(1, st1["co_batches"] - st0["co_batches"])
    mix = {
        "hostchunk": st1["co_plan_hostchunk"] - st0["co_plan_hostchunk"],
        "device": st1["co_plan_device"] - st0["co_plan_device"],
        "resident": st1["co_plan_resident"] - st0["co_plan_resident"],
        "inline": st1["co_plan_inline"] - st0["co_plan_inline"],
    }
    return {
        "samples": int(len(lat)),
        "sheds": int(sum(sheds)),
        "p50_ms": round(_p(lat, 50) or 0, 3),
        "p99_ms": round(_p(lat, 99) or 0, 3),
        "early_p99_ms": round(_p(early, 99) or 0, 3),
        "early_samples": int(len(early)),
        "late_p99_ms": round(_p(late, 99) or 0, 3),
        "plan_mix": mix,
        "plan_mix_per_batch": {
            k: round(v / d, 3) for k, v in mix.items()
        },
        "est_floor_ms_end": st1["co_est_device_floor_ms"],
        "est_chunk_ms_end": st1["co_est_host_chunk_ms"],
        "seeds": {k: round(float(v), 4) for k, v in seeds.items()},
    }


def autotune_leg(emit: bool = True, smoke: bool = False):
    """The offline autotuner (`bench.py --leg autotune`): run the
    measured mapping-space sweep (dss_tpu/plan/autotune.py) on THIS
    host, write the seed profile to deploy/autotune/<host-class>.json,
    then make the cold-start case: two fresh coalescers over one
    warmed table — default boot seeds vs the profile's measured seeds
    — under identical deadline-carrying load.  The early window (first
    40%% of the run) is where seed quality is the whole story: the
    profiled boot should hold a visibly lower early p99 and reach its
    steady route mix immediately instead of mis-routing until the
    EWMAs converge.  Folded into the default north-star JSON as
    detail.autotune."""
    from dss_tpu.plan import autotune as at

    profile = at.autotune(quick=smoke)
    if smoke:
        import tempfile

        path = at.save_profile(
            profile,
            os.path.join(
                tempfile.mkdtemp(prefix="dss-autotune-"),
                f"{at.host_class()}.json",
            ),
        )
    else:
        path = at.save_profile(profile)
    # reload round trip: the boot path consumes exactly this file
    profile = at.load_profile(path)

    n_ent = int(
        os.environ.get("DSS_BENCH_AUTOTUNE_ENTITIES",
                       3_000 if smoke else 100_000)
    )
    n_cel = int(
        os.environ.get("DSS_BENCH_AUTOTUNE_CELLS",
                       2_000 if smoke else 40_000)
    )
    secs = float(
        os.environ.get("DSS_BENCH_AUTOTUNE_SECS", 2.0 if smoke else 8.0)
    )
    threads = int(os.environ.get("DSS_BENCH_AUTOTUNE_THREADS", 8))
    width = 8
    table = build_table(n_ent, n_cel, 8, seed=3)
    try:
        ft = table._state.tiers[0].snap.fast
        # prewarm every executable BOTH passes can touch: the compare
        # isolates seed quality, not compile luck (compile caches are
        # process-wide, so whichever pass ran first would otherwise
        # donate its compiles to the second)
        qb = make_batch(31, 128, n_cel, width)
        ft.query_fused(*qb, now=NOW)
        default_seeds: dict = {}
        prof_seeds = _seeds_from_profile(profile)
        cold_default = _cold_start_pass(
            table, n_cel, width, default_seeds, secs, threads
        )
        cold_profiled = _cold_start_pass(
            table, n_cel, width, prof_seeds, secs, threads
        )
    finally:
        table.close()
    e_def = cold_default["early_p99_ms"] or 0
    e_prof = cold_profiled["early_p99_ms"] or 0
    result = {
        "metric": "autotune_cold_start_early_p99",
        "value": e_prof,
        "unit": "ms",
        "vs_baseline": round(e_prof / e_def, 3) if e_def else None,
        "detail": {
            "profile_path": path,
            "host_class": profile["host_class"],
            "knobs": profile["knobs"],
            "sweep_s": profile["sweep_s"],
            "capacity_weight": profile["capacity_weight"],
            "cold_start": {
                "secs": secs,
                "threads": threads,
                "entities": n_ent,
                "default_seeds": cold_default,
                "profiled_seeds": cold_profiled,
                # the headline: profile-seeded boot's early-window p99
                # vs the default boot's (lower is the win)
                "early_p99_default_ms": e_def,
                "early_p99_profiled_ms": e_prof,
                "early_p99_cut": (
                    round(e_def / e_prof, 2) if e_prof else None
                ),
            },
        },
    }
    if emit:
        print(json.dumps(result))
    return result


def autotune_smoke_leg() -> int:
    """CI plan smoke (`bench.py --leg autotune-smoke`): tiny
    deterministic grid -> profile emitted -> cold-start mini-compare
    -> every route reachable by some plan -> the six routes exercised
    through a live store (cache / inline / hostchunk / device /
    resident in-process; mesh via the planner's reachability check —
    no multi-chip mesh in this smoke) -> the REAL server binary boots
    with --autotune_profile and exports co_plan_* in /metrics.
    Nonzero exit on any miss."""
    from dss_tpu.plan import BatchShape, ModelState, Planner, ROUTES
    from dss_tpu.plan import autotune as at

    failures = []

    def check(name, ok, detail=""):
        print(f"  {'ok ' if ok else 'FAIL'} {name} {detail}")
        if not ok:
            failures.append(name)

    r = autotune_leg(emit=False, smoke=True)
    path = r["detail"]["profile_path"]
    check("profile_emitted", os.path.exists(path), path)
    prof = at.load_profile(path)
    check(
        "profile_knobs_complete",
        set(at.KNOB_KEYS) <= set(prof["knobs"]),
        sorted(set(at.KNOB_KEYS) - set(prof["knobs"])),
    )
    cs = r["detail"]["cold_start"]
    check(
        "cold_start_measured",
        cs["default_seeds"]["samples"] > 0
        and cs["profiled_seeds"]["samples"] > 0,
        f"default early p99 {cs['early_p99_default_ms']} ms, "
        f"profiled {cs['early_p99_profiled_ms']} ms",
    )

    # -- every route reachable by SOME plan (unreachable = dead route)
    pl = Planner()

    def st(**kw):
        base = dict(
            est_floor_ms=100.0, est_item_ms=0.01, est_chunk_ms=0.2,
            est_res_floor_ms=25.0, est_res_lat_ms=100.0, chunk=64,
        )
        base.update(kw)
        return ModelState(**base)

    reach = {
        "device": pl.plan(
            BatchShape(n=256, all_stale=True), st(), None
        ).route,
        "resident": pl.plan(
            BatchShape(n=256, all_stale=True),
            st(resident_ready=True, est_res_floor_ms=1.0), None,
        ).route,
        "hostchunk": pl.plan(BatchShape(n=256), st(), 8.0).route,
        "mesh": pl.plan(
            BatchShape(n=128, all_stale=True), st(mesh_ready=True),
            None,
        ).route,
        "inline": pl.plan(
            BatchShape(n=1, inline=True), st(), 1000.0
        ).route,
    }
    for route, got in reach.items():
        check(f"route_reachable_{route}", got == route, got)
    check("route_reachable_cache", "cache" in ROUTES)

    # -- live store: the plan counters move under real traffic
    from datetime import datetime, timedelta, timezone

    from dss_tpu.dar.dss_store import DSSStore
    from dss_tpu.geo import covering as geo_covering
    from dss_tpu.models import rid as ridm

    store = DSSStore(storage="tpu")
    try:
        now = datetime.now(timezone.utc)
        cells = geo_covering.covering_polygon(
            [(40.0, -100.0), (40.02, -100.0),
             (40.02, -99.98), (40.0, -99.98)]
        )
        for i in range(8):
            store.rid.insert_isa(
                ridm.IdentificationServiceArea(
                    id=f"00000000-0000-4000-8000-0000000000{i:02x}",
                    owner="smoke",
                    url="https://uss.example/f",
                    cells=np.asarray(cells, np.uint64),
                    start_time=now - timedelta(minutes=1),
                    end_time=now + timedelta(hours=1),
                    altitude_lo=0.0,
                    altitude_hi=3000.0,
                )
            )
        co = store.rid._isa_index.coalescer
        # inline + cache: a lone search populates, the repeat hits
        store.rid.search_isas(cells, now, None)
        store.rid.search_isas(cells, now, None)
        st1 = co.stats()
        check("live_plan_inline", st1["co_plan_inline"] >= 1,
              st1["co_plan_inline"])
        check("live_plan_cache", st1["co_plan_cache"] >= 1,
              st1["co_plan_cache"])
        check(
            "metrics_plan_keys",
            all(f"co_plan_{rt}" in st1 for rt in ROUTES),
        )
    finally:
        store.close()

    # -- the real binary boots with the profile and exports co_plan_*
    import subprocess

    import requests as _requests

    sys.path.insert(0, os.path.join(os.path.dirname(__file__)))
    from benchmarks.bench_rid_search import (
        _free_port,
        boot_server,
        wait_for_healthy,
    )

    port = _free_port()
    srv = boot_server(
        port, "tpu", 0, extra=["--autotune_profile", path]
    )
    try:
        base = f"http://127.0.0.1:{port}"
        wait_for_healthy(base)
        body = _requests.get(f"{base}/metrics", timeout=10).text
        check("server_metrics_co_plan", "co_plan_" in body)
        check(
            "server_metrics_all_routes",
            all(f"co_plan_{rt}" in body for rt in ROUTES),
        )
    finally:
        srv.terminate()
        try:
            srv.wait(timeout=30)
        except subprocess.TimeoutExpired:
            srv.kill()

    print(
        json.dumps(
            {
                "metric": "autotune_smoke",
                "ok": not failures,
                "failures": failures,
                "profile": path,
                "early_p99_default_ms": cs["early_p99_default_ms"],
                "early_p99_profiled_ms": cs["early_p99_profiled_ms"],
            }
        )
    )
    return 1 if failures else 0


# ---------------------------------------------------------------------------
# scenario harness (dss_tpu/scenario): city-scale named workloads through
# the REAL HTTP stack, per-phase SLO reporting (`--leg scenario`), plus the
# deterministic CI replay gate (`--leg scenario-smoke`)
# ---------------------------------------------------------------------------


def _boot_scd_server(port, storage, *, platform, extra=(),
                     env_extra=None, no_warmup=True):
    """Boot the real server binary with SCD enabled; callers own
    terminate/kill.  `platform` is the caller's statement of where the
    child serves from: a JAX platform name ("cpu" for the CI drills)
    is written into the child's JAX_PLATFORMS; None leaves the
    caller's own environment untouched — the server then refuses to
    fall back to the CPU on its own (cmds/server.resolve_backend).
    Never a default: a leg that measures must not serve from a CPU
    child behind its caller's back.  The host platform is given 8
    virtual devices so --sharded_replica shapes fit on the CPU (the
    flag does nothing on an accelerator).  no_warmup=False keeps the
    boot-time background kernel warm (the http-curve leg needs it:
    first-use XLA compiles mid-measurement wedge a small host for
    seconds)."""
    import subprocess

    argv = [
        sys.executable, "-m", "dss_tpu.cmds.server",
        "--addr", f":{port}",
        "--storage", storage,
        "--insecure_no_auth",
        "--enable_scd",
    ]
    if no_warmup:
        argv.append("--no_warmup")
    argv += list(extra)
    env = dict(os.environ, DSS_LOG_LEVEL="error")
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    if env_extra:
        env.update(env_extra)
    # keep the leg's stdout pure (one JSON line): the server's banner
    # and access log go to /dev/null, errors surface via wait/healthy
    return subprocess.Popen(
        argv, env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


_PLAN_ROUTES = ("cache", "inline", "hostchunk", "device", "resident", "mesh")


# ---------------------------------------------------------------------------
# shm-smoke: the shared-memory serving front CI drill (`--leg shm-smoke`)
# ---------------------------------------------------------------------------


def _shm_metric(base_or_sess, name) -> dict:
    """Scrape one dss_shm_* family; scalar -> {'': v}, labeled ->
    {label_value: v}."""
    import re

    import requests as _rq

    sess = (
        base_or_sess
        if hasattr(base_or_sess, "get") else _rq
    )
    base = getattr(sess, "_dss_base", base_or_sess)
    txt = sess.get(f"{base}/metrics", timeout=10).text
    out = {}
    pat = re.compile(
        rf"^{re.escape(name)}(?:\{{([^}}]*)\}})?\s+([0-9.eE+-]+)$"
    )
    for line in txt.splitlines():
        m = pat.match(line)
        if not m:
            continue
        labels = m.group(1) or ""
        key = ""
        for part in labels.split(","):
            if part.startswith('process="worker-'):
                key = part.split('"')[1]
        out[key] = float(m.group(2))
    return out


def _shm_leader_url(port: int) -> str:
    """The device owner's internal loopback URL, read from a live
    worker's argv (--leader_url): with the shm front attached the
    leader serves NO public-port connections, so it is only reachable
    there.  Matches only workers of the front bound to `port` — a
    stray worker from an earlier aborted run must never pin the
    drill's leader session to a different store.  '' until a worker
    process exists."""
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as fh:
                cmd = fh.read().decode(errors="replace").split("\0")
        except OSError:
            continue
        if (
            "--shm_worker_index" in cmd
            and "--leader_url" in cmd
            and f":{port}" in cmd
        ):
            return cmd[cmd.index("--leader_url") + 1]
    return ""


class _LeaderPinned:
    """Session pinned to the device owner.  The owner binds only its
    internal loopback listener (workers own the public port), so
    leader-side calls rewrite URLs built against the public base onto
    the leader URL — the smoke/curve legs keep one URL namespace and
    this adapter picks the process."""

    def __init__(self, base, leader_url):
        import requests as _rq

        self._public_base = base.rstrip("/")
        self._dss_base = leader_url.rstrip("/")  # _shm_metric scrapes here
        self._sess = _rq.Session()

    def _rw(self, url):
        if url.startswith(self._public_base):
            return self._dss_base + url[len(self._public_base):]
        return url

    def close(self):
        self._sess.close()

    def get(self, url, **kw):
        return self._sess.get(self._rw(url), **kw)

    def put(self, url, **kw):
        return self._sess.put(self._rw(url), **kw)

    def post(self, url, **kw):
        return self._sess.post(self._rw(url), **kw)

    def delete(self, url, **kw):
        return self._sess.delete(self._rw(url), **kw)


def _shm_sessions(base, *, want_workers: int, deadline_s: float = 120.0):
    """-> {'leader': _LeaderPinned, 'worker-N': Session, ...}.  Worker
    sessions are keep-alive connections to the public port opened
    until `want_workers` distinct workers have answered (SO_REUSEPORT
    hashes fresh connections across the worker processes — the leader
    no longer listens there); serial use of a session stays on its
    process.  The leader session targets its internal loopback URL."""
    import re

    import requests as _rq

    port = int(base.rsplit(":", 1)[1].split("/")[0])
    sessions = {}
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if "leader" not in sessions:
            lurl = _shm_leader_url(port)
            if lurl:
                sessions["leader"] = _LeaderPinned(base, lurl)
        have_workers = sum(1 for k in sessions if k.startswith("worker"))
        if have_workers >= want_workers and "leader" in sessions:
            return sessions
        s = _rq.Session()
        s._dss_base = base
        try:
            txt = s.get(f"{base}/metrics", timeout=5).text
        except _rq.RequestException:
            time.sleep(0.5)
            continue
        procs = {
            x for x in re.findall(r'process="([^"]+)"', txt)
            if ":" in x
        }
        placed = False
        for p in procs:
            key = p.split(":")[0]
            if key.startswith("worker") and key not in sessions:
                sessions[key] = s
                placed = True
        if not placed:
            s.close()
        time.sleep(0.05)
    raise RuntimeError(
        f"never reached leader + {want_workers} workers; have "
        f"{sorted(sessions)}"
    )


def _shm_worker_pids(port: int) -> dict:
    """{worker_index: pid} of live read-worker processes of the front
    bound to `port` (the drill's SIGKILL target), from /proc cmdlines;
    the port filter keeps strays from an earlier aborted run out."""
    out = {}
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as fh:
                cmd = fh.read().decode(errors="replace").split("\0")
        except OSError:
            continue
        if "--shm_worker_index" in cmd and f":{port}" in cmd:
            out[int(cmd[cmd.index("--shm_worker_index") + 1])] = int(pid)
    return out


def _shm_iso(base_epoch, off):
    return time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(base_epoch + off)
    )


def _shm_isa_body(lat, lng, t0s, t1s, *, d=0.01):
    return {
        "extents": {
            "spatial_volume": {
                "footprint": {"vertices": [
                    {"lat": lat - d, "lng": lng - d},
                    {"lat": lat - d, "lng": lng + d},
                    {"lat": lat + d, "lng": lng + d},
                    {"lat": lat + d, "lng": lng - d},
                ]},
                "altitude_lo": 0.0,
                "altitude_hi": 120.0,
            },
            "time_start": t0s,
            "time_end": t1s,
        },
        "flights_url": "https://shm.uss.example/flights",
    }


def shm_smoke_leg() -> int:
    """`bench.py --leg shm-smoke` (CI job shm-front-smoke): boot the
    real binary as leader + 2 shm-front workers and drill the whole
    acceptance surface — deterministic burst through the ring,
    worker-served answers bit-identical to leader-served, worker-local
    fenced cache hits (and exact write invalidation), read-your-writes
    on a worker session right after a proxied write, a SIGKILL-one-
    worker drill with zero 5xx from survivors + the leader reclaiming
    the dead worker + the ladder never leaving HEALTHY, and a clean
    SIGTERM shutdown with searches still in flight."""
    import signal as _signal
    import uuid as _uuid

    import requests as _rq

    from benchmarks.bench_rid_search import _free_port, wait_for_healthy

    storage = os.environ.get("DSS_BENCH_SHM_STORAGE", "memory")
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    srv = _boot_scd_server(
        port, storage, platform="cpu", extra=["--workers", "2"],
    )
    failures = []

    def check(name, ok, detail=""):
        print(f"  {'ok ' if ok else 'FAIL'} {name} {detail}")
        if not ok:
            failures.append(name)

    now = time.time()
    area_pts = [(47.5 + 0.04 * i, -122.5 + 0.05 * i) for i in range(4)]

    def area_str(lat, lng, d=0.01):
        return ",".join(
            f"{a:.5f},{b:.5f}" for a, b in [
                (lat - d, lng - d), (lat - d, lng + d),
                (lat + d, lng + d), (lat + d, lng - d),
            ]
        )

    try:
        wait_for_healthy(base, deadline_s=120.0)
        sessions = _shm_sessions(base, want_workers=2)
        worker_keys = sorted(k for k in sessions if k.startswith("worker"))
        lsess = sessions["leader"]
        w0, w1 = (sessions[k] for k in worker_keys[:2])
        print(f"  sessions: leader + {worker_keys}")

        # populate over the quantized pool (through the leader session)
        for i, (lat, lng) in enumerate(area_pts):
            r = lsess.put(
                f"{base}/v1/dss/identification_service_areas/"
                f"{_uuid.UUID(int=(21 << 64) | i, version=4)}",
                json=_shm_isa_body(
                    lat, lng, _shm_iso(now, 30), _shm_iso(now, 7200)
                ),
                timeout=30,
            )
            r.raise_for_status()

        et = _shm_iso(now, 60)
        urls = [
            f"{base}/v1/dss/identification_service_areas"
            f"?area={area_str(lat, lng)}&earliest_time={et}"
            for lat, lng in area_pts
        ]

        # 1. deterministic burst: worker-served bit-identical to
        #    leader-served, every poll 200, the ring actually used
        bodies = {}
        statuses = set()
        for name, sess in (("leader", lsess), ("w0", w0), ("w1", w1)):
            got = []
            for u in urls * 4:
                r = sess.get(u, timeout=30)
                statuses.add(r.status_code)
                got.append(r.json())
            bodies[name] = got
        check("burst_all_200", statuses == {200}, statuses)
        check(
            "worker_bit_identical_to_leader",
            bodies["w0"] == bodies["leader"]
            and bodies["w1"] == bodies["leader"],
        )
        served = _shm_metric(lsess, "dss_shm_served_total").get("", 0)
        check("ring_served_nonzero", served > 0, served)
        hits = _shm_metric(lsess, "dss_shm_worker_cache_hits")
        check(
            "worker_cache_hits_nonzero",
            sum(hits.values()) > 0, hits,
        )
        fallbacks = _shm_metric(
            lsess, "dss_shm_worker_proxy_fallbacks"
        )
        check(
            "zero_proxy_fallbacks",
            sum(fallbacks.values()) == 0, fallbacks,
        )

        # 2. exact invalidation: a write in area 0 fences exactly that
        #    worker-cached answer; the repeat poll sees the new record
        lat, lng = area_pts[0]
        wid = _uuid.UUID(int=(22 << 64) | 1, version=4)
        r = w0.put(
            f"{base}/v1/dss/identification_service_areas/{wid}",
            json=_shm_isa_body(
                lat, lng, _shm_iso(now, 30), _shm_iso(now, 7200),
                d=0.006,
            ),
            timeout=30,
        )
        check("proxied_write_200", r.status_code == 200, r.status_code)
        r = w0.get(urls[0], timeout=30)
        got_ids = {x["id"] for x in r.json()["service_areas"]}
        check("invalidated_poll_sees_write", str(wid) in got_ids)
        check(
            "invalidated_poll_matches_leader",
            r.json() == lsess.get(urls[0], timeout=30).json(),
        )

        # 3. read-your-writes on the SAME worker session: write ->
        #    immediate search must include it, every time
        ryw_ok = True
        for i in range(8):
            rid = _uuid.UUID(int=(23 << 64) | i, version=4)
            lat, lng = area_pts[i % len(area_pts)]
            w1.put(
                f"{base}/v1/dss/identification_service_areas/{rid}",
                json=_shm_isa_body(
                    lat, lng, _shm_iso(now, 30), _shm_iso(now, 7200),
                    d=0.004,
                ),
                timeout=30,
            ).raise_for_status()
            r = w1.get(
                f"{base}/v1/dss/identification_service_areas"
                f"?area={area_str(lat, lng, d=0.004)}"
                f"&earliest_time={et}",
                timeout=30,
            )
            if str(rid) not in {
                x["id"] for x in r.json()["service_areas"]
            }:
                ryw_ok = False
                break
        check("read_your_writes_on_worker", ryw_ok)

        # 4. worker-kill drill: SIGKILL one worker mid-burst; the
        #    survivors serve every request with zero 5xx, the leader
        #    reclaims the dead worker, the ladder stays HEALTHY
        pids = _shm_worker_pids(port)
        kill_idx = int(worker_keys[0].split("-")[1])
        check("worker_pids_found", set(pids) == {0, 1}, pids)
        err: dict = {"n5xx": 0, "done": 0}
        stop = threading.Event()

        def survivor_burst(sess):
            i = 0
            while not stop.is_set():
                r = sess.get(urls[i % len(urls)], timeout=30)
                if r.status_code >= 500:
                    err["n5xx"] += 1
                err["done"] += 1
                i += 1

        ths = [
            threading.Thread(target=survivor_burst, args=(s,))
            for s in (lsess, w1)
        ]
        for t in ths:
            t.start()
        time.sleep(0.5)  # mid-burst
        os.kill(pids[kill_idx], _signal.SIGKILL)
        time.sleep(2.5)  # leader reaps at 0.5s cadence
        stop.set()
        for t in ths:
            t.join(timeout=30)
        check(
            "survivors_zero_5xx",
            err["n5xx"] == 0 and err["done"] > 20, err,
        )
        dead = _shm_metric(lsess, "dss_shm_dead_workers").get("", 0)
        check("leader_reclaimed_dead_worker", dead == 1, dead)
        st = lsess.get(f"{base}/status", timeout=10).json()
        check(
            "ladder_stays_healthy",
            st.get("degraded_mode", "healthy") == "healthy",
            st.get("degraded_mode"),
        )
        # the survivor keeps serving through its ring after the kill
        r = w1.get(urls[1], timeout=30)
        check("survivor_serves_after_kill", r.status_code == 200)
        # the leader RESPAWNS the killed worker (the public port
        # belongs to the workers — an unreplaced crash would shrink
        # the front forever) and the owner revives it on its first
        # fresh heartbeat, draining dss_shm_dead_workers back to 0
        respawned = False
        t_end = time.monotonic() + 90
        while time.monotonic() < t_end:
            now_pids = _shm_worker_pids(port)
            if (
                now_pids.get(kill_idx) not in (None, pids[kill_idx])
                and _shm_metric(
                    lsess, "dss_shm_dead_workers"
                ).get("", 1) == 0
            ):
                respawned = True
                break
            time.sleep(0.5)
        check("worker_respawned_and_revived", respawned)

        # 5. clean shutdown with searches still in flight (a racing
        # request may see connection-reset: that's the SIGTERM, not
        # a failure)
        def _fire(u):
            try:
                _rq.get(u, timeout=5)
            except _rq.RequestException:
                pass

        flight = [
            threading.Thread(target=_fire, args=(u,)) for u in urls
        ]
        for t in flight:
            t.start()
        srv.terminate()
        try:
            rc = srv.wait(timeout=40)
        except Exception:  # noqa: BLE001
            srv.kill()
            rc = None
        for t in flight:
            t.join(timeout=10)
        check("clean_sigterm_shutdown", rc == 0, rc)
    finally:
        if srv.poll() is None:
            srv.terminate()
            try:
                srv.wait(timeout=30)
            except Exception:  # noqa: BLE001
                srv.kill()

    result = {
        "metric": "shm_front_smoke",
        "value": 0 if failures else 1,
        "unit": "pass",
        "detail": {"storage": storage, "failures": failures},
    }
    print(json.dumps(result))
    return 1 if failures else 0


def _co_plan_totals(base, sess=None) -> dict:
    """Sum the per-class planner decision counters (plus cache hits)
    from /metrics — the route-mix currency of the HTTP legs.  Under
    --workers pass a leader-pinned session: a fresh connection lands
    on a random process and only the leader runs the coalescer."""
    import re

    import requests as _rq

    out = {r: 0 for r in _PLAN_ROUTES}
    out["cache_hits"] = 0
    try:
        txt = (sess or _rq).get(f"{base}/metrics", timeout=10).text
    except _rq.RequestException:
        return out
    pat = re.compile(
        r"^dss_dar_\w+_co_plan_(\w+)(?:\{[^}]*\})?\s+([0-9.eE+-]+)"
    )
    hits = re.compile(r"^dss_cache_hits(?:\{[^}]*\})?\s+([0-9.eE+-]+)")
    for line in txt.splitlines():
        m = pat.match(line)
        if m and m.group(1) in out:
            out[m.group(1)] += int(float(m.group(2)))
            continue
        h = hits.match(line)
        if h:
            out["cache_hits"] += int(float(h.group(1)))
    return out


def _mix_delta(m0: dict, m1: dict) -> dict:
    return {k: m1.get(k, 0) - m0.get(k, 0) for k in m1}


def _run_scenario_phase(base, phase, t0_epoch, threads):
    """Drive one phase's timed request stream open-loop: senders pace
    each request by its scheduled offset, latency is measured from the
    SCHEDULED send time (coordinated-omission safe).  Returns
    (results, captured) where captured holds the parsed bodies of the
    reporting-tagged responses (closure_put, intent_census)."""
    import requests as _rq

    from dss_tpu.scenario import materialize_body

    reqs = sorted(phase.requests, key=lambda r: r.t)
    results = []
    captured = {}
    lock = threading.Lock()
    start = time.perf_counter()

    def worker(wi):
        sess = _rq.Session()
        for r in reqs[wi::threads]:
            sched = start + r.t
            while True:
                now = time.perf_counter()
                if now >= sched:
                    break
                time.sleep(min(sched - now, 0.05))
            body = (
                None if r.body is None
                else materialize_body(r.body, t0_epoch)
            )
            try:
                resp = sess.request(
                    r.method, base + r.path, json=body, timeout=60
                )
                status = resp.status_code
            except _rq.RequestException:
                status = -1
            done = time.perf_counter()
            ok = status in r.expect
            # a 429/504 is an excusable overload shed ONLY for plain
            # traffic: a request that carries an assertion (non-default
            # expect, e.g. the emergency blocked_put's 409) or feeds
            # the report (closure_put, intent_census) must actually
            # run, or the gate would pass without verifying anything
            must = r.expect != (200,) or r.tag in (
                "closure_put", "intent_census",
            )
            shed = status in (429, 504) and not ok and not must
            with lock:
                results.append((r.tag, status, done - sched, ok, shed))
                if ok and r.tag in ("closure_put", "intent_census"):
                    try:
                        captured[r.tag] = resp.json()
                    except ValueError:
                        pass

    ths = [
        threading.Thread(target=worker, args=(i,))
        for i in range(max(1, threads))
    ]
    for t in ths:
        t.start()
    for t in ths:
        t.join()
    return results, captured


def _phase_slo_row(phase_name, results, mix) -> dict:
    lats = np.sort(np.array(
        [l for (_, _, l, ok, shed) in results if ok and not shed]
    ))
    n = len(results)
    n_shed = sum(1 for x in results if x[4])
    n_unexpected = sum(1 for x in results if not x[3] and not x[4])
    by_tag = {}
    for tag, *_ in results:
        by_tag[tag] = by_tag.get(tag, 0) + 1
    bad = sorted(
        {(t, s) for (t, s, _, ok, shed) in results if not ok and not shed}
    )
    return {
        "phase": phase_name,
        "requests": n,
        "p50_ms": (
            round(float(lats[len(lats) // 2]) * 1000, 2) if len(lats) else None
        ),
        "p99_ms": (
            round(float(lats[int(len(lats) * 0.99)]) * 1000, 2)
            if len(lats) else None
        ),
        "shed": n_shed,
        "shed_rate": round(n_shed / max(1, n), 4),
        "unexpected": n_unexpected,
        **({"unexpected_samples": bad[:5]} if bad else {}),
        "route_mix": mix,
        "by_tag": by_tag,
    }


def _scrape_scalar(sess, name) -> float:
    """One scalar gauge from this session's process (/metrics; the
    constant process label is tolerated)."""
    import re

    base = getattr(sess, "_dss_base", None)
    txt = sess.get(f"{base}/metrics", timeout=10).text
    pat = re.compile(
        rf"^{re.escape(name)}(?:\{{[^}}]*\}})?\s+([0-9.eE+-]+)$"
    )
    for line in txt.splitlines():
        m = pat.match(line)
        if m:
            return float(m.group(1))
    return float("nan")


def trace_smoke_leg() -> int:
    """`bench.py --leg trace-smoke` (CI job trace-smoke): the
    end-to-end tracing acceptance drill over the REAL binary as
    leader + 2 shm-front workers, in two boots.

    Boot A (tracing disabled — the default): drive populate + polls
    through the front and assert the recorder performed ZERO
    allocations in EVERY process (dss_trace_allocs_total, counter-
    verified — the one-branch-per-seam contract).

    Boot B (DSS_TRACE_SAMPLE=0, DSS_TRACE_SLOW_MS armed, a seeded
    DSS_FAULT_PLAN delaying every `device.dispatch`): a fresh-area
    search rides worker -> shm ring -> owner -> dispatch, breaches the
    slow bound, and must be TAIL-CAPTURED on the worker that served it
    with the injected stage dominating its span tree — stitched across
    both processes from the slot's trace words.  A repeat poll (worker
    cache hit, fast) must NOT be captured."""
    import json as _json
    import uuid as _uuid

    from benchmarks.bench_rid_search import _free_port, wait_for_healthy

    failures = []

    def check(name, ok, detail=""):
        print(f"  {'ok ' if ok else 'FAIL'} {name} {detail}")
        if not ok:
            failures.append(name)

    now = time.time()
    lat, lng = 47.61, -122.33

    def area_str(d=0.01):
        return ",".join(
            f"{a:.5f},{b:.5f}" for a, b in [
                (lat - d, lng - d), (lat - d, lng + d),
                (lat + d, lng + d), (lat + d, lng - d),
            ]
        )

    search_url_tail = (
        "/v1/dss/identification_service_areas"
        f"?area={area_str()}&earliest_time={_shm_iso(now, 60)}"
    )

    # ---- boot A: tracing disabled, zero recorder allocations ----
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    srv = _boot_scd_server(
        port, "tpu", platform="cpu", extra=["--workers", "2"]
    )
    try:
        wait_for_healthy(base, deadline_s=120.0)
        sessions = _shm_sessions(base, want_workers=2)
        w0 = sessions[sorted(
            k for k in sessions if k.startswith("worker")
        )[0]]
        r = w0.put(
            f"{base}/v1/dss/identification_service_areas/"
            f"{_uuid.UUID(int=(31 << 64) | 1, version=4)}",
            json=_shm_isa_body(
                lat, lng, _shm_iso(now, 30), _shm_iso(now, 7200)
            ),
            timeout=30,
        )
        check("disabled_write_200", r.status_code == 200, r.status_code)
        for _ in range(6):
            r = w0.get(base + search_url_tail, timeout=30)
            check("disabled_search_200", r.status_code == 200,
                  r.status_code) if r.status_code != 200 else None
        allocs = {
            name: _scrape_scalar(s, "dss_trace_allocs_total")
            for name, s in sessions.items()
        }
        check(
            "disabled_zero_recorder_allocs",
            all(v == 0 for v in allocs.values()), allocs,
        )
        started = {
            name: _scrape_scalar(s, "dss_trace_started_total")
            for name, s in sessions.items()
        }
        check(
            "disabled_zero_traces_started",
            all(v == 0 for v in started.values()), started,
        )
        for s in sessions.values():
            s.close()
    finally:
        srv.terminate()
        try:
            srv.wait(timeout=40)
        except Exception:  # noqa: BLE001
            srv.kill()

    # ---- boot B: tail capture of an injected-slow dispatch ----
    delay_s = float(os.environ.get("DSS_BENCH_TRACE_DELAY_S", 0.3))
    slow_ms = float(os.environ.get("DSS_BENCH_TRACE_SLOW_MS", 150.0))
    plan = {"seed": 11, "events": [{
        "site": "device.dispatch", "action": "delay",
        "delay_s": delay_s, "count": -1,
    }]}
    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    srv = _boot_scd_server(
        port, "tpu", platform="cpu",
        extra=["--workers", "2", "--no_resident"],
        env_extra={
            "DSS_TRACE_SAMPLE": "0",
            "DSS_TRACE_SLOW_MS": str(slow_ms),
            "DSS_FAULT_PLAN": _json.dumps(plan),
        },
    )
    try:
        wait_for_healthy(base, deadline_s=120.0)
        sessions = _shm_sessions(base, want_workers=2)
        w0 = sessions[sorted(
            k for k in sessions if k.startswith("worker")
        )[0]]
        r = w0.put(
            f"{base}/v1/dss/identification_service_areas/"
            f"{_uuid.UUID(int=(32 << 64) | 1, version=4)}",
            json=_shm_isa_body(
                lat, lng, _shm_iso(now, 30), _shm_iso(now, 7200)
            ),
            timeout=30,
        )
        check("write_200", r.status_code == 200, r.status_code)
        # the slow one: fresh-area search -> worker miss -> ring ->
        # owner -> delayed dispatch; wall time must breach slow_ms
        t0 = time.perf_counter()
        r = w0.get(base + search_url_tail, timeout=30)
        slow_wall_ms = (time.perf_counter() - t0) * 1000
        check("slow_search_200", r.status_code == 200, r.status_code)
        check(
            "slow_search_breaches_bound",
            slow_wall_ms >= slow_ms,
            f"{slow_wall_ms:.0f}ms",
        )
        slow_tid = r.headers.get("X-Request-Id", "")
        # the fast one: repeat poll = worker cache hit, microseconds
        t0 = time.perf_counter()
        r = w0.get(base + search_url_tail, timeout=30)
        fast_wall_ms = (time.perf_counter() - t0) * 1000
        check("fast_poll_200", r.status_code == 200, r.status_code)
        check(
            "fast_poll_under_bound", fast_wall_ms < slow_ms,
            f"{fast_wall_ms:.0f}ms",
        )
        fast_tid = r.headers.get("X-Request-Id", "")
        # the worker that served both holds exactly the slow trace
        d = w0.get(f"{base}/aux/v1/debug/traces", timeout=10).json()
        get_traces = [
            t for t in d["traces"]
            if "GET /v1/dss/identification_service_areas"
            in t["root"]["name"]
        ]
        check("slow_trace_captured", len(get_traces) == 1,
              [t["root"]["name"] for t in d["traces"]])
        check(
            "fast_trace_not_captured",
            all(t["trace_id"] != fast_tid for t in d["traces"]),
        )
        if get_traces:
            tr = get_traces[0]
            check("captured_as_slow", tr["kept"] == "slow", tr["kept"])
            check(
                "captured_id_matches_header",
                tr["trace_id"] == slow_tid,
                (tr["trace_id"], slow_tid),
            )
            spans = {}
            stack = [tr["root"]]
            while stack:
                n = stack.pop()
                spans.setdefault(n["name"], 0.0)
                spans[n["name"]] = max(
                    spans[n["name"]], n["duration_ms"]
                )
                stack.extend(n["children"])
            # the stitched cross-process tree: ring RTT + the owner's
            # span slots, the tentpole acceptance surface
            for needed in ("shm.ring", "owner.queue_wait", "plan",
                           "cache.lookup", "admission",
                           "device.dispatch", "collect"):
                check(f"span_{needed}", needed in spans,
                      sorted(spans))
            disp = spans.get("device.dispatch", 0.0)
            check(
                "injected_stage_dominates",
                disp >= delay_s * 1000 * 0.8
                and disp >= 0.5 * tr["duration_ms"],
                f"device.dispatch={disp:.0f}ms "
                f"root={tr['duration_ms']:.0f}ms",
            )
        for s in sessions.values():
            s.close()
    finally:
        srv.terminate()
        try:
            srv.wait(timeout=40)
        except Exception:  # noqa: BLE001
            srv.kill()

    result = {
        "metric": "trace_smoke",
        "ok": not failures,
        "failures": failures,
    }
    print(json.dumps(result))
    return 0 if not failures else 1


def scenario_leg(smoke: bool = False) -> int:
    """`bench.py --leg scenario`: run the named city-scale scenarios
    (dss_tpu/scenario) end-to-end through the real HTTP stack — one
    fresh server per scenario — and emit per-scenario, per-phase SLO
    JSON (p50/p99/shed/unexpected/route mix).  The mass-event scenario
    additionally reports the closure write's subscription-fanout count
    and the number of intersecting intents it invalidated.

    `--leg scenario-smoke` (CI): tiny seeded run asserting the replay
    contract — same seed => same request-stream digest — plus zero
    unexpected statuses and a complete per-phase SLO report; exits
    nonzero on any violation."""
    from benchmarks.bench_rid_search import _free_port, wait_for_healthy

    from dss_tpu.scenario import build_scenario, env_knobs, stream_digest

    k = env_knobs()
    if smoke:
        k["scale"] = min(k["scale"], 0.05)
        k["duration_s"] = min(k["duration_s"], 8.0)

    # the replay gate: building the same (name, seed, scale, duration)
    # twice must produce bit-identical streams
    digests = {}
    replay_ok = True
    for name in k["names"]:
        d1 = stream_digest(
            build_scenario(name, k["seed"], k["scale"], k["duration_s"])
        )
        d2 = stream_digest(
            build_scenario(name, k["seed"], k["scale"], k["duration_s"])
        )
        digests[name] = d1
        if d1 != d2:
            replay_ok = False

    scen_rows = []
    total_unexpected = 0
    for name in k["names"]:
        sc = build_scenario(name, k["seed"], k["scale"], k["duration_s"])
        port = _free_port()
        base = f"http://127.0.0.1:{port}"
        srv = _boot_scd_server(
            port, k["storage"], platform="cpu" if smoke else None
        )
        try:
            wait_for_healthy(base)
            t0_epoch = time.time()
            phase_rows = []
            captured_all = {}
            t_sc0 = time.perf_counter()
            for phase in sc.phases:
                m0 = _co_plan_totals(base)
                results, captured = _run_scenario_phase(
                    base, phase, t0_epoch, k["threads"]
                )
                m1 = _co_plan_totals(base)
                captured_all.update(captured)
                phase_rows.append(
                    _phase_slo_row(phase.name, results, _mix_delta(m0, m1))
                )
            wall = time.perf_counter() - t_sc0
        finally:
            srv.terminate()
            try:
                srv.wait(timeout=30)
            except Exception:  # noqa: BLE001
                srv.kill()
        row = {
            "scenario": name,
            "digest": digests[name],
            "seed": k["seed"],
            "scale": k["scale"],
            "requests": sc.n_requests,
            "wall_s": round(wall, 1),
            "meta": sc.meta,
            "phases": phase_rows,
        }
        if name == "mass_event":
            census = captured_all.get("intent_census", {})
            closure = captured_all.get("closure_put", {})
            subs = closure.get("subscribers", [])
            row["intersecting_intents"] = len(
                census.get("operation_references", [])
            )
            row["closure_fanout_subscriptions"] = sum(
                len(s.get("subscriptions", [])) for s in subs
            )
            row["closure_fanout_uss"] = len(subs)
        total_unexpected += sum(p["unexpected"] for p in phase_rows)
        scen_rows.append(row)

    # "complete SLO report" is part of the gate: a phase whose every
    # request was shed has no percentile samples — that is exactly the
    # degradation the report exists to surface, so it must FAIL the
    # leg, not silently render as nulls
    slo_complete = all(
        p["p50_ms"] is not None
        for s in scen_rows for p in s["phases"]
        if p["requests"] > 0
    )
    ok = replay_ok and total_unexpected == 0 and slo_complete
    result = {
        "metric": "scenario_slo",
        "value": len(scen_rows),
        "unit": "scenarios",
        "vs_baseline": None,
        "detail": {
            "smoke": smoke,
            "replay_deterministic": replay_ok,
            "unexpected_total": total_unexpected,
            "slo_complete": slo_complete,
            "storage": k["storage"],
            "host_cpus": os.cpu_count() or 1,
            "scenarios": scen_rows,
        },
    }
    out_path = os.environ.get("DSS_SCENARIO_OUT", "")
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump(result, fh, indent=1)
    print(json.dumps(result))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# BENCH_r06: the mixed poll+write+bulk qps/latency curve through the REAL
# HTTP stack with all six planner routes live (`--leg http-curve`)
# ---------------------------------------------------------------------------


def _http_curve_populate(base, n_isas, n_ops, pool):
    """Seed the store over HTTP: ISAs + lane-separated SCD ops spread
    over the quantized poll pool."""
    import requests as _rq

    import uuid as _uuid

    sess = _rq.Session()
    now = time.time()

    def iso(off):
        return time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime(now + off)
        )

    for i in range(n_isas):
        lat, lng = pool[i % len(pool)]
        r = sess.put(
            f"{base}/v1/dss/identification_service_areas/"
            f"{_uuid.UUID(int=(11 << 64) | i, version=4)}",
            json={
                "extents": {
                    "spatial_volume": {
                        "footprint": {"vertices": [
                            {"lat": lat - 0.01, "lng": lng - 0.012},
                            {"lat": lat - 0.01, "lng": lng + 0.012},
                            {"lat": lat + 0.01, "lng": lng + 0.012},
                            {"lat": lat + 0.01, "lng": lng - 0.012},
                        ]},
                        "altitude_lo": 0.0,
                        "altitude_hi": 120.0,
                    },
                    "time_start": iso(30),
                    "time_end": iso(7200),
                },
                "flights_url": "https://pop.uss.example/flights",
            },
            timeout=30,
        )
        r.raise_for_status()
    for i in range(n_ops):
        lat, lng = pool[i % len(pool)]
        alt0 = 40.0 + 6.0 * i
        r = sess.put(
            f"{base}/dss/v1/operation_references/"
            f"{_uuid.UUID(int=(12 << 64) | i, version=4)}",
            json={
                "extents": [{
                    "volume": {
                        "outline_polygon": {"vertices": [
                            {"lat": lat - 0.008, "lng": lng - 0.01},
                            {"lat": lat - 0.008, "lng": lng + 0.01},
                            {"lat": lat + 0.008, "lng": lng + 0.01},
                            {"lat": lat + 0.008, "lng": lng - 0.01},
                        ]},
                        "altitude_lower": {
                            "value": alt0, "reference": "W84",
                            "units": "M",
                        },
                        "altitude_upper": {
                            "value": alt0 + 4.0, "reference": "W84",
                            "units": "M",
                        },
                    },
                    "time_start": {"value": iso(60), "format": "RFC3339"},
                    "time_end": {"value": iso(7200), "format": "RFC3339"},
                }],
                "uss_base_url": "https://pop.uss.example",
                "new_subscription": {
                    "uss_base_url": "https://pop.uss.example",
                    "notify_for_constraints": False,
                },
                "state": "Accepted",
                "old_version": 0,
                "key": [],
            },
            timeout=30,
        )
        r.raise_for_status()


def _http_curve_client(base, offered, secs, warm_s, pool, seed, out_q,
                       threads=4):
    """One load-generator PROCESS: a single-threaded asyncio event
    loop driving `threads` persistent raw-socket connections, each an
    open-loop sender owning 1/threads of this proc's offered-rate
    share.  Mixed workload: 70% repeat polls (RID search / SCD op
    query over the quantized pool), 15% ISA writes, 15% bulk
    district-wide stale-ok searches.  Latency from the scheduled send
    time; non-200/429/504 statuses are returned as a histogram so a
    failing leg names its failure.

    The generator shares the host with the server, so its per-request
    CPU is part of the measurement budget: N blocking-socket sender
    THREADS convoy on the GIL (~7 CPU-ms/request at 16 threads on the
    2-core dev box, vs ~1 CPU-ms single-threaded — measured), which
    made the GENERATOR the ceiling once the shm front pushed serving
    past the r06 knee.  One event loop + a hand-rolled HTTP/1.1
    keep-alive reader keeps the client near its single-threaded cost,
    so the curve measures the server again.  The request bytes on the
    wire are unchanged (same mix, same RNG streams, same headers)."""
    import asyncio as _asyncio
    import uuid as _uuid

    import numpy as _np

    hostport = base.split("//", 1)[1]
    host, _, port_s = hostport.partition(":")
    port = int(port_s or 80)
    now = time.time()

    def iso(off):
        return time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime(now + off)
        )

    per_conn = max(offered, 1e-9) / threads
    interval = 1.0 / per_conn
    t_start = time.perf_counter()
    stop_at = t_start + warm_s + secs
    warm_until = t_start + warm_s
    lats_all = [[] for _ in range(threads)]
    sheds = [0] * threads
    dl_sheds = [0] * threads
    err_hist: list = [dict() for _ in range(threads)]

    def build(method, path, body=None):
        payload = b"" if body is None else json.dumps(body).encode()
        head = (
            f"{method} {path} HTTP/1.1\r\nHost: {hostport}\r\n"
            "Accept-Encoding: identity\r\n"
        )
        if payload:
            head += (
                "Content-Type: application/json\r\n"
                f"Content-Length: {len(payload)}\r\n"
            )
        return head.encode() + b"\r\n" + payload

    async def one_request(reader, writer, data):
        """-> (status, keep_alive).  Minimal HTTP/1.1 client side:
        status line, headers (Content-Length / chunked / close), body
        drained so the connection is clean for the next request."""
        writer.write(data)
        await writer.drain()
        status_line = await reader.readline()
        if not status_line:
            raise ConnectionResetError("server closed connection")
        status = int(status_line.split(None, 2)[1])
        length = 0
        chunked = False
        keep = True
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.decode("latin-1").partition(":")
            k = k.strip().lower()
            v = v.strip().lower()
            if k == "content-length":
                length = int(v)
            elif k == "transfer-encoding" and "chunked" in v:
                chunked = True
            elif k == "connection" and v == "close":
                keep = False
        if chunked:
            while True:
                szline = await reader.readline()
                sz = int(szline.strip() or b"0", 16)
                await reader.readexactly(sz + 2)  # chunk + CRLF
                if sz == 0:
                    break
        elif length:
            await reader.readexactly(length)
        return status, keep

    async def sender(ci):
        rng = _np.random.default_rng(seed * 131 + ci)
        conn = None
        next_t = time.perf_counter() + float(rng.uniform(0, interval))
        wi = 0
        while True:
            now_t = time.perf_counter()
            if now_t >= stop_at:
                break
            if now_t < next_t:
                await _asyncio.sleep(next_t - now_t)
                continue
            r = float(rng.uniform())
            lat, lng = pool[int(rng.integers(0, len(pool)))]
            if r < 0.45:  # RID poll
                area = ",".join(
                    f"{a:.5f},{b:.5f}" for a, b in [
                        (lat - 0.01, lng - 0.012),
                        (lat - 0.01, lng + 0.012),
                        (lat + 0.01, lng + 0.012),
                        (lat + 0.01, lng - 0.012),
                    ]
                )
                data = build(
                    "GET",
                    "/v1/dss/identification_service_areas"
                    f"?area={area}",
                )
            elif r < 0.70:  # SCD op poll
                data = build(
                    "POST",
                    "/dss/v1/operation_references/query",
                    body={"area_of_interest": {
                        "volume": {"outline_polygon": {"vertices": [
                            {"lat": lat - 0.01, "lng": lng - 0.012},
                            {"lat": lat - 0.01, "lng": lng + 0.012},
                            {"lat": lat + 0.01, "lng": lng + 0.012},
                            {"lat": lat + 0.01, "lng": lng - 0.012},
                        ]}},
                    }},
                )
            elif r < 0.85:  # write: fresh ISA in the pool area
                wi += 1
                uid = _uuid.UUID(
                    int=(13 << 80) | (seed << 40) | (ci << 32) | wi,
                    version=4,
                )
                data = build(
                    "PUT",
                    "/v1/dss/identification_service_areas/"
                    f"{uid}",
                    body={
                        "extents": {
                            "spatial_volume": {
                                "footprint": {"vertices": [
                                    {"lat": lat - 0.006,
                                     "lng": lng - 0.008},
                                    {"lat": lat - 0.006,
                                     "lng": lng + 0.008},
                                    {"lat": lat + 0.006,
                                     "lng": lng + 0.008},
                                    {"lat": lat + 0.006,
                                     "lng": lng - 0.008},
                                ]},
                                "altitude_lo": 0.0,
                                "altitude_hi": 120.0,
                            },
                            "time_start": iso(30),
                            "time_end": iso(3600),
                        },
                        "flights_url": "https://w.uss.example/flights",
                    },
                )
            else:  # bulk: district-wide search (stale-ok on the
                #       service; sized under the pi-inflated cap)
                area = ",".join(
                    f"{a:.5f},{b:.5f}" for a, b in [
                        (47.54, -122.38), (47.54, -122.22),
                        (47.66, -122.22), (47.66, -122.38),
                    ]
                )
                data = build(
                    "GET",
                    "/v1/dss/identification_service_areas"
                    f"?area={area}",
                )
            status = None
            try:
                for attempt in (0, 1):
                    try:
                        if conn is None:
                            conn = await _asyncio.wait_for(
                                _asyncio.open_connection(host, port),
                                30,
                            )
                        status, keep = await _asyncio.wait_for(
                            one_request(conn[0], conn[1], data), 30
                        )
                        if not keep:
                            conn[1].close()
                            conn = None
                        break
                    except (OSError, _asyncio.IncompleteReadError,
                            ConnectionError, ValueError) as e:
                        # one transparent reconnect for a dropped
                        # keep-alive (what urllib3 did for the old
                        # stack)
                        if conn is not None:
                            conn[1].close()
                        conn = None
                        if attempt:
                            raise e
            except Exception as e:  # noqa: BLE001 — counted, not fatal
                status = f"exc:{type(e).__name__}"
            done = time.perf_counter()
            if done >= warm_until:
                if status == 429:
                    sheds[ci] += 1
                elif status == 504:
                    dl_sheds[ci] += 1
                elif status != 200:
                    key = str(status)
                    err_hist[ci][key] = err_hist[ci].get(key, 0) + 1
                else:
                    lats_all[ci].append(done - next_t)
            next_t += interval
        if conn is not None:
            conn[1].close()

    async def _main():
        await _asyncio.gather(*(sender(i) for i in range(threads)))

    _asyncio.run(_main())
    merged_err: dict = {}
    for h in err_hist:
        for k, v in h.items():
            merged_err[k] = merged_err.get(k, 0) + v
    out_q.put((
        [x for l in lats_all for x in l],
        sum(sheds), sum(dl_sheds), merged_err,
    ))


def _proc_cpu_seconds(pids: dict) -> dict:
    """{name: cumulative user+sys CPU seconds} for each pid — the
    per-process saturation currency of the http-curve ladder (who hits
    the core wall first: the device owner or a request worker)."""
    tck = os.sysconf("SC_CLK_TCK")
    out = {}
    for name, pid in pids.items():
        try:
            with open(f"/proc/{pid}/stat") as fh:
                parts = fh.read().rsplit(")", 1)[1].split()
            out[name] = (int(parts[11]) + int(parts[12])) / tck
        except (OSError, IndexError, ValueError):
            out[name] = 0.0
    return out


def _stage_hist_scrape(sess) -> dict:
    """One /metrics scrape's dss_stage_duration_seconds data:
    {(route, stage): (cumulative bucket counts by le, sum_s, count)}.
    Works on both the per-process local family (workers=0) and the
    merged whole-front family (shm front)."""
    import re

    base = getattr(sess, "_dss_base", None)
    txt = sess.get(f"{base}/metrics", timeout=10).text
    buckets: dict = {}
    sums: dict = {}
    cnts: dict = {}
    pat = re.compile(
        r"^dss_stage_duration_seconds_(bucket|sum|count)"
        r"\{([^}]*)\}\s+([0-9.eE+-]+|\+Inf)$"
    )
    for line in txt.splitlines():
        m = pat.match(line)
        if not m:
            continue
        kind, labels, val = m.groups()
        lab = dict(
            p.split("=", 1) for p in labels.split(",") if "=" in p
        )
        route = lab.get("route", "").strip('"')
        stage = lab.get("stage", "").strip('"')
        key = (route, stage)
        if kind == "bucket":
            le = lab.get("le", "").strip('"')
            if le == "+Inf":
                continue
            buckets.setdefault(key, {})[float(le)] = float(val)
        elif kind == "sum":
            sums[key] = float(val)
        else:
            cnts[key] = float(val)
    out = {}
    for key, bs in buckets.items():
        out[key] = (
            tuple(v for _, v in sorted(bs.items())),
            sums.get(key, 0.0),
            cnts.get(key, 0.0),
        )
    return out


def _stage_attribution(h0: dict, h1: dict) -> dict:
    """Per-stage latency attribution over a measurement window, from
    two dss_stage_duration_seconds scrapes: {stage: {count, mean_ms,
    p99_ms}} with p99 linearly interpolated inside the breached
    bucket (routes merged — the table answers 'which STAGE owns the
    tail').  The BENCH_r07 hand-rolled per-process CPU breakdown,
    generalized: measured stage tails, from the serving stack itself.
    The interpolation itself lives in
    obs/metrics.stage_hist_quantile."""
    from dss_tpu.obs.metrics import stage_hist_quantile

    by_stage: dict = {}
    for key, (counts1, sum1, cnt1) in h1.items():
        counts0, sum0, cnt0 = h0.get(
            key, ((0.0,) * len(counts1), 0.0, 0.0)
        )
        stage = key[1]
        cur = by_stage.setdefault(
            stage, [np.zeros(len(counts1)), 0.0, 0.0]
        )
        cur[0] += np.asarray(counts1) - np.asarray(counts0)
        cur[1] += sum1 - sum0
        cur[2] += cnt1 - cnt0
    out = {}
    for stage, (cum, ssum, cnt) in sorted(by_stage.items()):
        if cnt <= 0:
            continue
        p99 = stage_hist_quantile(tuple(cum), cnt, 0.99)
        out[stage] = {
            "count": int(cnt),
            "mean_ms": round(1000.0 * ssum / cnt, 3),
            "p99_ms": round(1000.0 * p99, 3),
        }
    return out


def _shm_front_totals(sess) -> dict:
    """Whole-front shm counters from ONE leader scrape (the leader
    aggregates every worker's stats block)."""
    out = {}
    for fam in ("enqueued", "cache_hits", "cache_misses",
                "proxy_fallbacks", "ring_full"):
        out[fam] = int(sum(
            _shm_metric(sess, f"dss_shm_worker_{fam}").values()
        ))
    out["owner_served"] = int(
        _shm_metric(sess, "dss_shm_served_total").get("", 0)
    )
    return out


def _http_curve_rung(workers: int, *, rates, secs, warm_s, procs,
                     threads, n_isas, n_ops, storage, replica,
                     pool) -> dict:
    """One ladder rung: boot the server (single-process when
    workers=0 — the BENCH_r06 shape — else leader + N shm-front
    workers), run the SAME mixed workload sweep, and record per-point
    latency/shed/route-mix plus the per-process CPU and shm-front
    breakdowns."""
    import multiprocessing as mp
    import tempfile

    import requests as _rq

    from benchmarks.bench_rid_search import _free_port, wait_for_healthy

    port = _free_port()
    base = f"http://127.0.0.1:{port}"
    extra = []
    tmpdir = tempfile.TemporaryDirectory(prefix="dss-http-curve-")
    if replica:
        # the mesh replica tails a log; give the server a WAL (the
        # workers-mode leader also feeds its read workers from it)
        extra += ["--sharded_replica", replica]
    extra += ["--wal_path", os.path.join(tmpdir.name, "dss.wal")]
    if workers > 0:
        extra += ["--workers", str(workers)]
    srv = _boot_scd_server(
        port, storage, platform=None, extra=extra, no_warmup=False
    )
    rows = []
    drain_burst: dict = {}
    lsess = None
    backend: dict = {}
    try:
        wait_for_healthy(base, deadline_s=120.0)
        if workers > 0:
            sessions = _shm_sessions(
                base, want_workers=workers, deadline_s=180.0
            )
            lsess = sessions["leader"]
            for k, s in sessions.items():
                if k != "leader":
                    s.close()
        else:
            lsess = _rq.Session()
            lsess._dss_base = base
        # what the process that SERVES says it runs on (the device
        # owner's /status) — never this parent's own jax.devices()
        backend = lsess.get(f"{base}/status", timeout=10).json()["backend"]
        pids = {"leader": srv.pid}
        if workers > 0:
            pids.update({
                f"worker-{i}": p
                for i, p in _shm_worker_pids(port).items()
            })
        _http_curve_populate(base, n_isas, n_ops, pool)
        # let the background kernel warm + the replica's first full
        # refresh finish before measuring (their compiles otherwise
        # land inside the first points on a small host)
        time.sleep(float(os.environ.get("DSS_BENCH_HTTP_SETTLE", 20.0)))
        stage_h0 = _stage_hist_scrape(lsess)
        for pt, offered in enumerate(rates):
            m0 = _co_plan_totals(base, lsess)
            shm0 = _shm_front_totals(lsess) if workers > 0 else None
            if workers > 0:
                # re-resolve worker pids each point: the leader
                # respawns crashed workers, and a stale pid would
                # silently zero that worker's cpu_s for the rest of
                # the sweep — corrupting the per-process breakdown
                # the curve exists to measure
                pids = {"leader": srv.pid}
                pids.update({
                    f"worker-{i}": p
                    for i, p in _shm_worker_pids(port).items()
                })
            cpu0 = _proc_cpu_seconds(pids)
            q = mp.Queue()
            ps = [
                mp.Process(
                    target=_http_curve_client,
                    # seed is also the write-id namespace: it must be
                    # unique across rate POINTS, or a later point
                    # re-PUTs an earlier point's ISA ids and 409s
                    args=(base, offered / procs, secs, warm_s, pool,
                          100 + pt * procs + i, q, threads),
                )
                for i in range(procs)
            ]
            t0 = time.perf_counter()
            for p in ps:
                p.start()
            outs = [q.get(timeout=warm_s + secs + 120) for _ in ps]
            for p in ps:
                p.join(timeout=30)
            span = time.perf_counter() - t0 - warm_s
            m1 = _co_plan_totals(base, lsess)
            cpu1 = _proc_cpu_seconds(pids)
            cpu_s = {
                k: round(cpu1.get(k, 0.0) - cpu0.get(k, 0.0), 2)
                for k in cpu0
            }
            full_span = span + warm_s
            cpu_util = {
                k: round(v / max(full_span, 1e-9), 3)
                for k, v in cpu_s.items()
            }
            all_l = np.sort(np.concatenate(
                [np.asarray(o[0]) for o in outs]
            )) if any(len(o[0]) for o in outs) else np.array([])
            n_shed = sum(o[1] for o in outs)
            n_dl = sum(o[2] for o in outs)
            err_hist: dict = {}
            for o in outs:
                for k, v in o[3].items():
                    err_hist[k] = err_hist.get(k, 0) + v
            n_err = sum(err_hist.values())
            if len(all_l) == 0:
                rows.append({
                    "offered_qps": offered, "achieved_qps": 0.0,
                    "shed": n_shed, "deadline_shed": n_dl,
                    "errors": n_err, "error_statuses": err_hist,
                    "cpu_s": cpu_s, "cpu_util": cpu_util,
                })
                continue
            row = {
                "offered_qps": offered,
                "achieved_qps": round(len(all_l) / max(span, 1e-9), 1),
                "p50_ms": round(float(all_l[len(all_l) // 2]) * 1000, 2),
                "p99_ms": round(
                    float(all_l[int(len(all_l) * 0.99)]) * 1000, 2
                ),
                "samples": int(len(all_l)),
                "shed": n_shed,
                "deadline_shed": n_dl,
                "errors": n_err,
                **({"error_statuses": err_hist} if err_hist else {}),
                "shed_rate": round(
                    (n_shed + n_dl)
                    / max(1, n_shed + n_dl + len(all_l)), 4,
                ),
                "route_mix": _mix_delta(m0, m1),
                "cpu_s": cpu_s,
                "cpu_util": cpu_util,
            }
            if shm0 is not None:
                row["shm_mix"] = _mix_delta(
                    shm0, _shm_front_totals(lsess)
                )
            rows.append(row)
        # per-stage p99 attribution over the whole sweep, from the
        # dss_stage_duration_seconds histograms (whole-front merged
        # under the shm front; leader-local at workers=0)
        stage_attribution = _stage_attribution(
            stage_h0, _stage_hist_scrape(lsess)
        )
        # bulk drain burst: fire `conc` concurrent district-wide
        # stale-ok searches so oversized coalesced batches form — the
        # reachability probe for the hostchunk/device/mesh bulk routes
        # that steady per-request load at this host's capacity never
        # builds
        m0 = _co_plan_totals(base, lsess)
        burst_n = int(os.environ.get("DSS_BENCH_HTTP_BURST", 256))
        # >= the coalescer's mesh min_batch (64): smaller bursts can
        # never form a mesh-eligible batch
        conc = int(os.environ.get("DSS_BENCH_HTTP_BURST_CONC", 64))
        area = ",".join(
            f"{a:.5f},{b:.5f}" for a, b in [
                (47.54, -122.38), (47.54, -122.22),
                (47.66, -122.22), (47.66, -122.38),
            ]
        )
        b_lats: list = []
        b_lock = threading.Lock()

        def burst_worker(wi):
            sess = _rq.Session()
            for _ in range(burst_n // conc):
                t0 = time.perf_counter()
                try:
                    sess.get(
                        f"{base}/v1/dss/identification_service_areas"
                        f"?area={area}",
                        timeout=60,
                    )
                except _rq.RequestException:
                    continue
                with b_lock:
                    b_lats.append(time.perf_counter() - t0)

        bts = [
            threading.Thread(target=burst_worker, args=(i,))
            for i in range(conc)
        ]
        for t in bts:
            t.start()
        for t in bts:
            t.join()
        b_sorted = np.sort(np.asarray(b_lats))
        drain_burst = {
            "requests": int(len(b_sorted)),
            "concurrency": conc,
            "p50_ms": (
                round(float(b_sorted[len(b_sorted) // 2]) * 1000, 2)
                if len(b_sorted) else None
            ),
            "route_mix": _mix_delta(m0, _co_plan_totals(base, lsess)),
        }
    finally:
        if lsess is not None:
            lsess.close()
        srv.terminate()
        try:
            srv.wait(timeout=30)
        except Exception:  # noqa: BLE001
            srv.kill()
        tmpdir.cleanup()

    sustained = max(
        (r["achieved_qps"] for r in rows
         if r.get("errors", 1) == 0 and "achieved_qps" in r),
        default=0.0,
    )
    low_load_p50 = next(
        (r["p50_ms"] for r in rows if r.get("p50_ms") is not None),
        None,
    )
    return {
        "workers": workers,
        "backend": backend,
        "rows": rows,
        "drain_burst": drain_burst,
        "sustained_qps": sustained,
        "low_load_p50_ms": low_load_p50,
        # which STAGE owns the p99 at this rung: measured stage tails
        # from the serving stack's own histograms, not a hand-rolled
        # breakdown (stage names in obs/metrics.STAGE_NAMES)
        "stage_attribution": stage_attribution,
    }


def http_curve_leg() -> int:
    """`bench.py --leg http-curve` (BENCH_r06/r07, ROADMAP item 1):
    the qps/latency curve through the REAL HTTP stack — server binary
    in its own process(es), out-of-process load generators, mixed
    poll+write+bulk workload — now a WORKER LADDER: the same sweep at
    each DSS_BENCH_HTTP_WORKERS count (default 0,2,4; 0 = the single-
    process BENCH_r06 shape, N>0 = leader + N shm-front workers).  Each
    point carries the per-process CPU and shm-front breakdowns, so the
    curve names who saturates first (the device owner or a request
    worker).  The workload mix is byte-identical across rungs and to
    BENCH_r06 for comparability.  DSS_BENCH_HTTP_OUT writes the full
    result JSON (BENCH_r07.json)."""
    rates = [
        int(x)
        for x in os.environ.get(
            "DSS_BENCH_HTTP_QPS", "25,50,100,200,400,800"
        ).split(",")
        if x.strip()
    ]
    workers_set = [
        int(x)
        for x in os.environ.get(
            "DSS_BENCH_HTTP_WORKERS", "0,2,4"
        ).split(",")
        if x.strip() != ""
    ]
    secs = float(os.environ.get("DSS_BENCH_HTTP_SECS", 5.0))
    warm_s = float(os.environ.get("DSS_BENCH_HTTP_WARM_S", 2.0))
    procs = int(os.environ.get("DSS_BENCH_HTTP_PROCS", 3))
    # enough in-flight per proc that the open loop can track the
    # offered rate past the old ceiling (concurrency ~= rate x
    # latency); raw-http threads are cheap, requests threads were not
    threads = int(os.environ.get("DSS_BENCH_HTTP_THREADS", 16))
    n_isas = int(os.environ.get("DSS_BENCH_HTTP_ISAS", 200))
    n_ops = int(os.environ.get("DSS_BENCH_HTTP_OPS", 200))
    storage = os.environ.get("DSS_BENCH_HTTP_STORAGE", "tpu")
    replica = os.environ.get("DSS_BENCH_HTTP_REPLICA", "1,2")

    pool = [
        (47.5 + 0.05 * i, -122.5 + 0.06 * j)
        for i in range(5) for j in range(5)
    ]
    ladder = [
        _http_curve_rung(
            w, rates=rates, secs=secs, warm_s=warm_s, procs=procs,
            threads=threads, n_isas=n_isas, n_ops=n_ops,
            storage=storage, replica=replica, pool=pool,
        )
        for w in workers_set
    ]

    def rung_ok_rates(rung):
        return [
            r["offered_qps"] for r in rung["rows"]
            if r.get("p50_ms") is not None
            and r["p50_ms"] < 5.0
            and r["achieved_qps"] >= r["offered_qps"] * 0.9
            and (r["shed"] + r["deadline_shed"])
            <= 0.01 * max(1, r.get("samples", 0))
            and r["errors"] == 0
        ]

    max_ok = max(
        (max(rung_ok_rates(rg), default=0) for rg in ladder),
        default=0,
    )
    routes_seen = {r: 0 for r in _PLAN_ROUTES}
    for rung in ladder:
        for row in rung["rows"] + [rung["drain_burst"]]:
            for k, v in row.get("route_mix", {}).items():
                if k in routes_seen:
                    routes_seen[k] += v
    capacity_by_workers = {
        str(rg["workers"]): rg["sustained_qps"] for rg in ladder
    }
    base_cap = capacity_by_workers.get("0")
    best_front = max(
        (rg["sustained_qps"] for rg in ladder if rg["workers"] > 0),
        default=0.0,
    )
    result = {
        "metric": "http_mixed_curve_qps_p50_under_5ms",
        "value": max_ok,
        "unit": "offered qps",
        "vs_baseline": round(max_ok / 100_000.0, 4),
        "detail": {
            "host_cpus": os.cpu_count() or 1,
            "storage": storage,
            "sharded_replica": replica,
            "workers_ladder": workers_set,
            "populated": {"isas": n_isas, "ops": n_ops},
            "workload": "45% RID poll / 25% SCD op poll / 15% ISA write"
                        " / 15% bulk metro search, open-loop,"
                        " out-of-process clients",
            "secs_per_point": secs,
            "client_procs": procs,
            "capacity_by_workers": capacity_by_workers,
            "front_speedup": (
                round(best_front / base_cap, 2)
                if base_cap else None
            ),
            "low_load_p50_by_workers": {
                str(rg["workers"]): rg["low_load_p50_ms"]
                for rg in ladder
            },
            "ladder": ladder,
            "route_totals": routes_seen,
            # as each rung's serving process reported it (every rung
            # boots from the same environment, so they agree)
            "backend": ladder[0]["backend"] if ladder else None,
            "note": (
                "full HTTP stack (server binaries in their own"
                " processes); latency from scheduled send; shed = 429"
                " + 504; clients share the host, so points past"
                " saturation also carry client scheduling debt;"
                " cpu_util is per-process CPU seconds / wall over"
                " each point"
            ),
        },
    }
    print(json.dumps(result))
    out_path = os.environ.get("DSS_BENCH_HTTP_OUT", "")
    if out_path:
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump(result, fh, indent=1)
    errs = sum(
        r.get("errors", 0) for rg in ladder for r in rg["rows"]
    )
    return 0 if errs == 0 else 1


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--leg",
        choices=["north-star", "workers", "curve-smoke",
                 "resident-smoke", "poll", "cache-smoke", "skew",
                 "skew-smoke", "autotune", "autotune-smoke",
                 "chaos", "chaos-smoke", "scenario", "scenario-smoke",
                 "http-curve", "federation", "shm-smoke",
                 "trace-smoke", "fanout-push", "fanout-smoke"],
        default="north-star",
        help="'north-star': the headline SCD conflict-qps benchmark "
        "(default); 'workers': multi-worker HTTP serving scaling smoke "
        "(--workers 0 vs N through the real binary); 'curve-smoke': "
        "short CPU sweep asserting the deadline router exercises both "
        "the host-chunk and device routes; 'resident-smoke': boots "
        "the resident device-feeder loop, pushes a deterministic "
        "burst through it, and asserts clean shutdown with batches "
        "still queued in the ring; 'poll': the repeat-poll workload "
        "(DSS_BENCH_POLL_RATIO polls per write over Zipf areas) with "
        "the version-fenced read cache on vs off; 'cache-smoke': "
        "deterministic hit -> write-invalidate -> miss -> repopulate "
        "CI cycle asserting a hit is bit-identical and performs zero "
        "coalescer enqueues; 'skew': Zipf hot-spot sweep "
        "(DSS_BENCH_ZIPF_ALPHAS) with load-weighted shard rebalancing "
        "ON vs OFF on the same store, reporting p50/p99 + measured "
        "imbalance factor; 'skew-smoke': deterministic hot cell -> "
        "imbalance detected -> boundaries move -> imbalance recovers "
        "CI chain; 'autotune': measured mapping-space sweep -> "
        "deploy/autotune/<host-class>.json profile + cold-start "
        "comparison (profile-seeded boot vs default seeds); "
        "'autotune-smoke': tiny deterministic grid, route "
        "reachability + live co_plan_* counters + real-binary boot "
        "with the emitted profile (CI plan-smoke job); 'chaos': the "
        "four named seeded fault scenarios (device-lost-mid-stream, "
        "wal-fsync-stall, region-partition, mirror-link-flap) "
        "reporting error-budget burn, degraded-mode dwell, and "
        "recovery time; 'chaos-smoke': deterministic device-loss CI "
        "scenario — hostchunk serving under loss, zero unexpected "
        "5xx, bit-identical answers after recovery; 'scenario': the "
        "named city-scale scenarios (corridors, mass_event, emergency, "
        "diurnal — dss_tpu/scenario) driven through the real HTTP "
        "stack with per-scenario per-phase SLO JSON (p50/p99/shed/"
        "route mix); DSS_SCENARIO_* knobs in docs/OPERATIONS.md; "
        "'scenario-smoke': tiny seeded scenario run asserting "
        "deterministic replay (same seed -> same stream digest), zero "
        "unexpected statuses, and a complete SLO report; 'http-curve': "
        "the BENCH_r06 mixed poll+write+bulk qps/latency sweep through "
        "the full HTTP stack with all six planner routes live "
        "(DSS_BENCH_HTTP_QPS rates, out-of-process clients); "
        "'federation': the two-region partition drill (seeded "
        "FaultPlan leg + SIGKILL-a-region leg over real processes) "
        "emitting FED_r01.json with partition dwell, error-budget "
        "burn, and recovery time; 'shm-smoke': the shared-memory "
        "serving front drill (leader + 2 workers through the real "
        "binary: ring burst bit-identical to leader-served, fenced "
        "worker cache hits + exact write invalidation, read-your-"
        "writes on a worker session, SIGKILL-one-worker with zero "
        "5xx from survivors + slot reclaim + HEALTHY ladder, clean "
        "SIGTERM with searches in flight); 'trace-smoke': the "
        "end-to-end tracing drill (leader + 2 shm workers: tracing "
        "disabled performs zero recorder allocations in every "
        "process, then a fault-injected delay at device.dispatch is "
        "tail-captured as ONE stitched worker->owner trace with the "
        "injected stage dominating its span tree); 'fanout-push': the "
        "push-pipeline headline — one write matched against 10k+ "
        "subscriptions through the rqmatch device kernel then fanned "
        "out as durable webhook deliveries (match qps, matched "
        "pairs/s, delivery-lag p50/p99; emits FANOUT_r01.json; "
        "DSS_BENCH_PUSH_SUBS/_USS/_WRITES knobs); 'fanout-smoke': "
        "deterministic push CI drill — seeded faults at push.match "
        "(absorbed onto the bit-identical host oracle) and "
        "push.deliver (retry-recovered, nothing parked), the "
        "delivery-worker SIGKILL drill over a real child process "
        "proving zero acked-notification loss + at-least-once "
        "redelivery, and queue saturation flipping PUSH_DEGRADED "
        "then recovering HEALTHY",
    )
    args = ap.parse_args()
    if args.leg == "workers":
        return workers_leg()
    if args.leg == "skew":
        return 0 if skew_leg() else 1
    if args.leg == "skew-smoke":
        return skew_smoke_leg()
    if args.leg == "curve-smoke":
        return curve_smoke_leg()
    if args.leg == "resident-smoke":
        return resident_smoke_leg()
    if args.leg == "poll":
        return poll_leg()
    if args.leg == "cache-smoke":
        return cache_smoke_leg()
    if args.leg == "autotune":
        autotune_leg()
        return 0
    if args.leg == "autotune-smoke":
        return autotune_smoke_leg()
    if args.leg == "chaos":
        return chaos_leg()
    if args.leg == "chaos-smoke":
        return chaos_smoke_leg()
    if args.leg == "scenario":
        return scenario_leg()
    if args.leg == "scenario-smoke":
        return scenario_leg(smoke=True)
    if args.leg == "http-curve":
        return http_curve_leg()
    if args.leg == "federation":
        return federation_leg()
    if args.leg == "shm-smoke":
        return shm_smoke_leg()
    if args.leg == "trace-smoke":
        return trace_smoke_leg()
    if args.leg == "fanout-push":
        return fanout_push_leg()
    if args.leg == "fanout-smoke":
        return fanout_smoke_leg()

    n_entities = int(os.environ.get("DSS_BENCH_ENTITIES", 1_000_000))
    n_cells = int(os.environ.get("DSS_BENCH_CELLS", 200_000))
    kpe = 8
    batch = int(os.environ.get("DSS_BENCH_BATCH", 8192))
    width = int(os.environ.get("DSS_BENCH_WIDTH", 8))
    reps = int(os.environ.get("DSS_BENCH_REPS", 12))
    serving_threads = int(os.environ.get("DSS_BENCH_SERVING_THREADS", 32))
    serving_secs = float(os.environ.get("DSS_BENCH_SERVING_SECS", 10))
    do_serving = os.environ.get("DSS_BENCH_SERVING", "1") != "0"

    table = build_table(n_entities, n_cells, kpe)
    ft = table._state.tiers[0].snap.fast
    # what the server does after boot (cmds/server.py): park the
    # built table outside gen2 GC scans — the 1M-record heap otherwise
    # costs ~8 ms of stall per full collection
    from dss_tpu.runtime import freeze_boot_heap

    freeze_boot_heap()

    h = headline(ft, batch, reps, n_cells, width)

    floor_ms = dispatch_floor_ms()
    # the r6 split: cold (sync per batch) vs resident (amortized
    # through the pipelined resident path) dispatch floors, measured
    # on the REAL fused kernel with negligible compute
    floors = dispatch_floor_split(ft, n_cells)
    serving = None
    if do_serving:
        # light load: small coalesced batches ride the exact host path
        # (no device round trip) — the realistic single-request p50
        light = serving_leg(
            table, n_cells, width,
            threads=4, warm_s=2.0, run_s=max(serving_secs / 2, 3.0),
        )
        serving = serving_leg(
            table, n_cells, width,
            threads=serving_threads, warm_s=6.0, run_s=serving_secs,
        )
        serving["light_load"] = {
            k: (round(v, 2) if isinstance(v, float) else v)
            for k, v in light.items()
        }
        serving["dispatch_floor_ms"] = round(floor_ms, 2)
        serving["cold_dispatch_ms"] = floors["cold_dispatch_ms"]
        serving["resident_dispatch_ms"] = floors["resident_dispatch_ms"]
        serving["note"] = (
            "closed-loop through DarTable+QueryCoalescer; coalesced"
            " batches <=64 answer from the exact host postings copy"
            " (no device round trip), larger bursts ride the resident"
            " device stream (resident_dispatch_ms = amortized"
            " per-batch dispatch through the pipelined resident loop;"
            " cold_dispatch_ms = one synchronous fused round trip)"
        )
        serving = {
            k: (round(v, 2) if isinstance(v, float) else v)
            for k, v in serving.items()
        }

    curve = None
    max_ok = None
    if do_serving and os.environ.get("DSS_BENCH_CURVE", "1") != "0":
        # DSS_BENCH_CURVE_QPS is the configurable offered-qps sweep
        # (default extends through 16k so the post-router knee is
        # visible); DSS_BENCH_CURVE_RATES kept as the legacy alias
        rates = [
            int(x)
            for x in os.environ.get(
                "DSS_BENCH_CURVE_QPS",
                os.environ.get(
                    "DSS_BENCH_CURVE_RATES",
                    "500,1000,2000,4000,8000,12000,16000",
                ),
            ).split(",")
            if x.strip()
        ]
        curve, max_ok = curve_leg(
            table, n_cells, width, rates,
            secs=float(os.environ.get("DSS_BENCH_CURVE_SECS", 3.0)),
        )

    poll = None
    if do_serving and os.environ.get("DSS_BENCH_POLL", "1") != "0":
        # the repeat-poll leg (version-fenced read cache on vs off at
        # a DSS_BENCH_POLL_RATIO read:write mix) rides the default run
        # so the recorded BENCH JSON carries it
        poll = poll_leg(emit=False)

    skew = None
    if do_serving and os.environ.get("DSS_BENCH_SKEW", "1") != "0":
        # the Zipf hot-spot leg (load-weighted shard rebalancing on vs
        # off on the same mesh store) rides the default run too
        skew = skew_leg(emit=False)

    autotune = None
    if do_serving and os.environ.get("DSS_BENCH_AUTOTUNE", "1") != "0":
        # the offline mapping-space autotune + cold-start comparison
        # (profile-seeded boot vs default seeds) rides the default run
        # so the recorded BENCH JSON carries the early-window p99 cut
        autotune = autotune_leg(emit=False)["detail"]

    qps = h["qps"]
    result = {
        "metric": "scd_conflict_qps_1M_intents",
        "value": round(qps, 1),
        "unit": "queries/s",
        "vs_baseline": round(qps / 100_000.0, 3),
        "detail": {
            "entities": n_entities,
            "cells": n_cells,
            "batch": batch,
            "reps": reps,
            "pipelined_batch_ms": round(h["pipelined_batch_ms"], 2),
            "worst_pass_batch_ms": round(h["worst_pass_batch_ms"], 2),
            "single_batch_latency_ms": round(h["single_batch_latency_ms"], 2),
            "kernel_only_qps": round(h["kernel_only_qps"], 1),
            "warmup_hits_per_query": round(h["warmup_hits_per_query"], 1),
            "dispatch_floor_ms": round(floor_ms, 2),
            # the resident tentpole's headline pair: the same minimal
            # fused batch, synchronous vs streamed through the
            # resident path (AOT bucket + donated I/O + pipelined
            # submits) — resident_floor_cut is the measured reduction
            "cold_dispatch_ms": floors["cold_dispatch_ms"],
            "resident_dispatch_ms": floors["resident_dispatch_ms"],
            "resident_floor_cut": floors["resident_floor_cut"],
            "resident_dispatch_stream": floors["resident_stream"],
            "serving": serving,
            # the north-star claim, stated jointly and honestly:
            # batched pipeline sustains `value` qps; the serving path
            # holds p50 < 5 ms up to max_serving_qps_p50_under_5ms
            # offered load on this host (see dispatch_floor_ms)
            "qps_latency_curve": curve,
            "max_serving_qps_p50_under_5ms": max_ok,
            # repeat-poll workload: the version-fenced read cache's
            # served-qps/hit-rate/p99 claim at ~100:1 poll:write
            "poll": poll,
            # Zipf hot-spot workload: skew-aware shard placement's
            # p99-under-skew claim (rebalancing on vs off, measured
            # per-shard imbalance from the kernels' hit counts)
            "skew": skew,
            # offline autotune: the emitted host profile + the
            # cold-start case (profiled vs default boot seeds)
            "autotune": autotune,
            # this process built and served the table in-process, so
            # its own devices ARE the serving devices; the legs that
            # re-exec or boot a server carry their own backend field
            "backend": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
            "pipeline": "DarTable snapshot; fused: host-searchsorted +"
                        " device filter+compact+exact, pipelined submits",
        },
    }
    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
