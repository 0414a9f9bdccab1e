"""Resident serving kernel: kill the per-call device dispatch floor.

The fused query path (ops/fastpath.py) pays three per-call costs that
have nothing to do with the query itself: a trace/compile when a batch
lands in an unwarmed shape bucket, a fresh output allocation per call,
and the dispatch round trip itself (not measured on the chip).  PR 5's
deadline router *dodges* that floor by shedding floor-blowing batches
to chunked host scans; this subsystem *shrinks* it, with three parts:

  PRE-COMPILED SHAPE BUCKETS (AotCache / ResidentKernel) — at warm
  time (server boot, replica rebuild, major compaction) the fused
  kernel is AOT-lowered and compiled for the pow2 batch x window
  bucket grid the serving path actually hits
  (ops/fastpath.py pow2_bucket), so no serving request ever pays a
  trace or an XLA compile.  Executables are keyed by shape only — the
  postings arrays are *arguments*, not captures — so tables with equal
  block counts share entries (the L0 tier keeps its block count across
  minor folds: warm once, hit forever).  This is the mapping-search
  frame of the GOMA / Turbo-Charged-Mapper papers (PAPERS.md): the
  bucket grid is a searched mapping seeded from measured traffic, not
  a fixed layout — size it from the live miss counters.

  DONATED, PRE-PINNED I/O (the AOT twin's donate_argnums) — the
  query's one packed buffer (windows + per-query bounds) is donated to
  the executable, so in steady state XLA can re-use its device memory for
  the output instead of allocating per call; the table-side postings
  blocks stay resident in HBM exactly as the kernel consumes them (the
  pjit pitfall the SNIPPETS.md reference warns about: outputs of one
  call must already be laid out as the next call's inputs — here the
  DAR snapshot arrays are device_put once at fold time and never
  resharded at the call site).  Donation only ever recycles *input*
  buffers: a collected result is decoded into fresh host memory before
  the next batch is enqueued, so results are never aliased
  (tests/test_resident.py pins this).

  THE RESIDENT LOOP (ResidentLoop) — a dedicated device-feeder thread
  owning a bounded host ring buffer that the coalescer's pack stage
  enqueues drained batches into.  The feeder submits batch after batch
  into the device stream WITHOUT waiting for results (up to
  `max_inflight` outstanding), and a collector thread resolves them in
  order — so consecutive batches never serialize on a full round trip
  and the dispatch cost amortizes across every batch in flight.  The
  floor the router's cost model learns from this route is the
  *resident* floor (the steady-state inter-completion gap), not the
  cold-dispatch floor.

  Stretch (not implemented): a single on-device `lax.while_loop`
  megakernel polling the ring via pinned staging buffers would remove
  even the per-batch dispatch.  jax has no portable pinned-host-write
  primitive for that, so the feeder thread is the honest version;
  docs/SERVING.md records the gap.

The loop plugs into the deadline router (dar/coalesce.py) as a third
route candidate with its own cost-model key (`est_res_floor_ms`,
seeded by DSS_CO_EST_RES_FLOOR_MS): resident observations never feed
the cold-device floor estimate and vice versa — two routes sharing one
model would poison routing the moment either is preferred.
"""

from __future__ import annotations

import os
import queue as _queue
import threading
import time
import warnings
from collections import deque
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from dss_tpu.chaos import fault_point
from dss_tpu.obs import trace as _trace
from dss_tpu.ops import conflict as _conflict  # noqa: F401 — enables
#   x64 before the first jax array touch (the kernel's i64 columns)
from dss_tpu.ops import compile_site, current_compile_site, fastpath

# donation is advisory: backends that cannot re-use a buffer (CPU for
# some shapes) warn and fall back to a copy — correctness never depends
# on it, so the per-executable warning is noise here
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)


def _env_buckets(name: str, default: Tuple[int, ...]) -> Tuple[int, ...]:
    raw = os.environ.get(name)
    if raw is None:
        return default
    return tuple(
        sorted({int(x) for x in raw.split(",") if x.strip()})
    )


def batch_bucket_grid() -> Tuple[int, ...]:
    """Default batch-axis buckets to AOT-warm (DSS_RES_BATCH_BUCKETS):
    the coalescer's drain sizes land in pow2 buckets >= 16; the grid
    covers the post-host-cutoff sizes the device routes actually see.
    Unwarmed buckets are not wrong — they fall back to the shared jit
    (a compile on first hit, same as pre-resident serving) and show up
    in the miss counters, which is how operators size this grid."""
    return _env_buckets("DSS_RES_BATCH_BUCKETS", (128, 512, 2048, 4096))


def window_bucket_grid() -> Tuple[int, ...]:
    """Default window-axis buckets to AOT-warm (DSS_RES_WINDOW_BUCKETS).
    Window counts scale with batch x covering width x postings-run
    spread; the pow2 rule is pow2_bucket (ops/fastpath.py)."""
    return _env_buckets(
        "DSS_RES_WINDOW_BUCKETS", (256, 1024, 4096, 16384, 65536)
    )


# the grid's executables are keyed by what submit() auto-sizes to: ONE
# rule, fastpath's (tests/test_packed_query.py holds the two together)
max_words_for = fastpath.max_words_for


class AotCache:
    """Process-wide AOT executable cache for the fused kernel.

    Key: (table block count, window bucket, batch bucket, max_words).
    The executable closes over NO table state — the postings block
    columns are arguments — so any FastTable with the same block count
    hits the same entry.  compile() is idempotent and thread-safe;
    concurrent compiles of the same key race benignly (last one wins,
    both are valid)."""

    def __init__(self, max_entries: Optional[int] = None):
        self._lock = threading.Lock()
        self._exe: Dict[tuple, object] = {}
        # LRU bound: tier rebuilds change the block count, and the
        # executables keyed by a dead block count would otherwise
        # accumulate forever in a long-lived server.  Eviction is by
        # last use, so live tiers' buckets stay hot.
        self._max = (
            int(os.environ.get("DSS_RES_AOT_CAP", "128"))
            if max_entries is None
            else int(max_entries)
        )
        self._use: Dict[tuple, int] = {}
        self._tick = 0
        self.evictions = 0
        self._jit = None
        self.compiles = 0
        self.compile_ms_total = 0.0
        # background compiler: misses schedule their bucket here so
        # the NEXT batch in the same bucket hits — the warm grid sizes
        # itself from live traffic instead of a guessed static list
        # (the searched-mapping frame: traffic is the cost signal)
        self._pending: "deque[tuple]" = deque()
        self._pending_keys: set = set()
        self._compiler: Optional[threading.Thread] = None

    def _donating_jit(self):
        # one jit object for every bucket: lower() specializes per
        # shape.  The donated position is the query's one packed
        # buffer — donating the table's postings columns would free
        # the snapshot under every other reader.
        if self._jit is None:
            self._jit = jax.jit(
                fastpath.fused_window_filter,
                static_argnames=("windows", "max_words", "chunk"),
                donate_argnums=(4,),
            )
        return self._jit

    @staticmethod
    def key_for(ft, window_bucket: int, batch_bucket: int,
                max_words: int) -> tuple:
        return (
            int(ft.n_blocks), int(window_bucket), int(batch_bucket),
            int(max_words),
        )

    def get(self, key: tuple):
        with self._lock:
            exe = self._exe.get(key)
            if exe is not None:
                self._tick += 1
                self._use[key] = self._tick
            return exe

    def size(self) -> int:
        with self._lock:
            return len(self._exe)

    def compile(self, ft, window_bucket: int, batch_bucket: int,
                max_words: Optional[int] = None) -> bool:
        """AOT-lower + compile one bucket for `ft`'s block count.
        Returns True when a fresh executable was built (False: cache
        hit).  Runs OFF any serving path — warm calls come from boot /
        fold / rebuild hooks."""
        if max_words is None:
            max_words = max_words_for(window_bucket)
        key = self.key_for(ft, window_bucket, batch_bucket, max_words)
        with self._lock:
            if key in self._exe:
                return False
        nb = int(ft.n_blocks)
        sds = jax.ShapeDtypeStruct
        args = (
            sds((nb, fastpath.BLOCK), jnp.float32),  # b_alo
            sds((nb, fastpath.BLOCK), jnp.float32),  # b_ahi
            sds((nb, fastpath.BLOCK), jnp.int64),  # b_t0
            sds((nb, fastpath.BLOCK), jnp.int64),  # b_t1
            sds(  # packed: the windows, then the per-query bounds
                (fastpath.packed_words(window_bucket, batch_bucket),),
                jnp.int32,
            ),
        )
        t0 = time.perf_counter()
        # chaos seam: an injected failure models an XLA compile error
        # for one bucket — the async compiler logs and drops it, and
        # submits in that bucket keep riding the shared jit
        fault_point("aot.compile", detail=str(key))
        exe = (
            self._donating_jit()
            .lower(
                *args, windows=int(window_bucket),
                max_words=int(max_words),
            )
            .compile()
        )
        dt = (time.perf_counter() - t0) * 1000
        with self._lock:
            self._tick += 1
            self._exe[key] = exe
            self._use[key] = self._tick
            self.compiles += 1
            self.compile_ms_total += dt
            while len(self._exe) > self._max:
                victim = min(
                    (k for k in self._exe if k != key),
                    key=lambda k: self._use.get(k, 0),
                    default=None,
                )
                if victim is None:
                    break
                del self._exe[victim]
                self._use.pop(victim, None)
                self.evictions += 1
        return True

    def compile_async(self, ft, window_bucket: int, batch_bucket: int,
                      max_words: int) -> None:
        """Schedule a bucket compile on the background compiler thread
        (miss-driven warm: the serving path never blocks on it, and
        the next batch landing in this bucket hits).  Deduped per key;
        only the table's block count is captured, never the table."""
        key = self.key_for(ft, window_bucket, batch_bucket, max_words)
        nb = int(ft.n_blocks)
        with self._lock:
            if key in self._exe or key in self._pending_keys:
                return
            self._pending_keys.add(key)
            # the compile is counted where it was asked for: a fold's
            # warm hands its site on, a serving thread's miss is a
            # request's (dss_jax_compiles_<site>)
            self._pending.append((key, nb, current_compile_site()))
            if self._compiler is None or not self._compiler.is_alive():
                self._compiler = threading.Thread(
                    target=self._compile_loop,
                    name="dss-resident-aot",
                    daemon=True,
                )
                self._compiler.start()

    def _compile_loop(self):
        while True:
            with self._lock:
                if not self._pending:
                    return
                key, nb, site = self._pending.popleft()
            try:
                with compile_site(site):
                    self._compile_key(key, nb)
            except Exception:  # noqa: BLE001 — a bad bucket must not
                import logging  # kill the compiler

                logging.getLogger("dss.resident").exception(
                    "async AOT compile failed for %s", key
                )
            finally:
                with self._lock:
                    self._pending_keys.discard(key)

    def _compile_key(self, key: tuple, nb: int) -> None:
        _, window_bucket, batch_bucket, max_words = key
        with self._lock:
            if key in self._exe:
                return

        class _Shape:  # duck-typed ft: compile() reads n_blocks only
            n_blocks = nb

        self.compile(_Shape, window_bucket, batch_bucket, max_words)


# the process-wide cache every ResidentKernel shares (executables are
# pure shape specializations — there is nothing per-table to isolate)
_CACHE = AotCache()


class ResidentKernel:
    """The `kernel=` hook FastTable.submit consumes.

    lookup() maps a submit's shape bucket to a pre-compiled donated
    executable; a miss returns None (submit falls back to the shared
    jit — exactly pre-resident behavior) and is counted, so the warm
    grid is sized from live traffic, not guesses.  Hit/miss counters
    are per-instance (one per resident loop / entity class) while the
    executables live in the shared process cache."""

    __slots__ = ("cache", "autocompile", "hits", "misses")

    def __init__(self, cache: Optional[AotCache] = None,
                 autocompile: bool = True):
        self.cache = cache if cache is not None else _CACHE
        # miss-driven background warm: a missed bucket is scheduled on
        # the cache's compiler thread so the next batch in it hits
        self.autocompile = bool(autocompile)
        self.hits = 0
        self.misses = 0

    def lookup(self, ft, window_bucket: int, batch_bucket: int,
               max_words: int):
        exe = self.cache.get(
            self.cache.key_for(ft, window_bucket, batch_bucket, max_words)
        )
        if exe is None:
            self.misses += 1
            if self.autocompile:
                self.cache.compile_async(
                    ft, window_bucket, batch_bucket, max_words
                )
            return None
        self.hits += 1
        return exe

    def warm(self, ft, batch_buckets=None, window_buckets=None) -> int:
        """Compile the bucket grid for one FastTable, SYNCHRONOUSLY.
        Returns the number of fresh executables built (0 = everything
        already cached, e.g. a minor fold that kept the block count).
        For boot/test warm only — anything on a fold or serving path
        wants warm_async."""
        n = 0
        for bb in batch_buckets or batch_bucket_grid():
            for wb in window_buckets or window_bucket_grid():
                if self.cache.compile(ft, wb, bb):
                    n += 1
        return n

    def warm_async(self, ft, batch_buckets=None,
                   window_buckets=None) -> None:
        """Schedule the bucket grid on the background compiler — the
        fold-time warm hook uses this so a tier rebuild whose block
        count changed never stalls the fold behind multi-second XLA
        compiles; until a bucket lands, submits fall back to the
        shared jit (pre-resident behavior)."""
        for bb in batch_buckets or batch_bucket_grid():
            for wb in window_buckets or window_bucket_grid():
                self.cache.compile_async(
                    ft, wb, bb, max_words_for(wb)
                )

    def buckets(self) -> int:
        return self.cache.size()


# feeder/collector shutdown sentinel
_DONE = object()


class ResidentLoop:
    """Persistent device execution loop for the fused query path.

    A dedicated feeder thread owns a bounded host ring buffer; the
    coalescer's pack stage enqueues drained batches (enqueue() —
    non-blocking, False on a full ring so the router can fall back to
    the cold device path instead of stalling the pack stage).  The
    feeder pops jobs and submits them through the table's resident
    path (DarTable.query_many_submit(kernel=...): AOT shape buckets +
    donated query-side buffers) WITHOUT waiting for results, keeping
    up to `max_inflight` batches in the device stream; the collector
    thread resolves them in submission order and invokes each job's
    `done` callback with the results and the measured marginal cost.

    The cost a done callback receives is the *inter-completion gap*
    (time since the previous batch finished, floored at this batch's
    own submit time): in a full pipeline that is the marginal per-batch
    cost — the resident floor — while a lone batch honestly pays its
    full round trip.  Feeding that to the router's resident cost key
    is what makes the learned floor the amortized one.

    close() stops admission, DRAINS the ring (every enqueued batch is
    still submitted, collected, and delivered — the coalescer's
    every-admitted-caller-resolves contract), then joins both threads.
    """

    def __init__(self, table, *, ring_capacity: int = 32,
                 max_inflight: int = 4,
                 kernel: Optional[ResidentKernel] = None):
        self._table = table
        self.kernel = kernel if kernel is not None else ResidentKernel()
        self._ring: deque = deque()
        self._ring_cap = max(1, int(ring_capacity))
        self._cond = threading.Condition()
        self._closed = False
        self._inflight_q: _queue.Queue = _queue.Queue(
            maxsize=max(1, int(max_inflight))
        )
        self._max_inflight = max(1, int(max_inflight))
        self._feeder: Optional[threading.Thread] = None
        self._collector: Optional[threading.Thread] = None
        self._last_done = 0.0  # perf_counter of the last collection
        # counters (stats() -> co_res_* gauges)
        self.enqueued = 0
        self.rejected = 0
        self.submitted = 0
        self.collected = 0
        self.errors = 0

    # -- admission -----------------------------------------------------------

    def has_space(self) -> bool:
        return not self._closed and len(self._ring) < self._ring_cap

    def enqueue(self, payload, done) -> bool:
        """payload: the 7-tuple QueryCoalescer._pack_args produces
        (keys_list, alt_lo, alt_hi, t_start, t_end, now, owner_ids).
        done(results, error, gap_ms, lat_ms, used_device) runs on the
        collector thread — gap_ms is the marginal inter-completion
        cost (the resident floor signal), lat_ms the full
        submit->delivered wall time (the deadline signal).  Returns
        False (nothing happens) when the ring is full or the loop is
        closed — the caller keeps ownership of the batch and routes
        it elsewhere."""
        with self._cond:
            if self._closed or len(self._ring) >= self._ring_cap:
                self.rejected += 1
                return False
            self._ring.append((payload, done))
            self.enqueued += 1
            self._ensure_threads()
            self._cond.notify_all()
        return True

    def _ensure_threads(self):
        if self._feeder is None or not self._feeder.is_alive():
            self._feeder = threading.Thread(
                target=self._feed_loop, name="dss-resident-feeder",
                daemon=True,
            )
            self._feeder.start()
        if self._collector is None or not self._collector.is_alive():
            self._collector = threading.Thread(
                target=self._collect_loop, name="dss-resident-collect",
                daemon=True,
            )
            self._collector.start()

    # -- the loop ------------------------------------------------------------

    def _feed_loop(self):
        """Pop the ring, submit into the device stream, hand to the
        collector.  The bounded inflight queue is the stream-depth
        governor: when `max_inflight` batches are outstanding the
        put() blocks, the ring fills, and enqueue() starts returning
        False — backpressure the router converts into cold-device or
        host routing instead of unbounded device queueing."""
        while True:
            with self._cond:
                while not self._ring and not self._closed:
                    self._cond.wait()
                if not self._ring:
                    break  # closed and fully drained
                payload, done = self._ring.popleft()
                self._cond.notify_all()
            t_sub = time.perf_counter()
            try:
                # chaos seam: device loss mid-stream — the error rides
                # the normal delivery path to the coalescer's done
                # callback, which absorbs it (host re-run + ladder)
                fault_point("resident.submit")
                keys, lo, hi, t0s, t1s, now, owners = payload
                with _trace.annotate("resident.submit"):
                    pq = self._table.query_many_submit(
                        keys, lo, hi, t0s, t1s, now=now,
                        owner_ids=owners, kernel=self.kernel,
                    )
            except BaseException as e:  # noqa: BLE001 — deliver, don't die
                self._inflight_q.put((None, done, t_sub, e))
                continue
            with self._cond:
                self.submitted += 1
            self._inflight_q.put((pq, done, t_sub, None))
        self._inflight_q.put(_DONE)

    def _collect_loop(self):
        while True:
            item = self._inflight_q.get()
            if item is _DONE:
                return
            pq, done, t_sub, err = item
            results = None
            used_device = False
            if err is None:
                try:
                    with _trace.annotate("resident.collect"):
                        if pq is not None:
                            pq.wait_device()
                            # the shared predicate (dar/snapshot.py
                            # _PendingQuery.used_device) — cost
                            # attribution here must agree with the
                            # coalescer's pressure accounting
                            fn = getattr(pq, "used_device", None)
                            used_device = bool(fn()) if fn else False
                        results = self._table.query_many_collect(pq)
                except BaseException as e:  # noqa: BLE001
                    err = e
            t_done = time.perf_counter()
            # marginal cost: gap since the previous completion, never
            # earlier than this batch's own submit (an idle loop pays
            # its full latency; a saturated one pays the amortized
            # gap).  lat is the full wall time a caller experienced —
            # the two feed DIFFERENT cost-model keys (floor vs
            # latency), see dar/coalesce._CostModel.
            gap_ms = (t_done - max(t_sub, self._last_done)) * 1000
            lat_ms = (t_done - t_sub) * 1000
            self._last_done = t_done
            with self._cond:
                self.collected += 1
                if err is not None:
                    self.errors += 1
            try:
                done(results, err, gap_ms, lat_ms, used_device)
            except Exception:  # noqa: BLE001 — a bad callback must not
                import logging  # kill the loop

                logging.getLogger("dss.resident").exception(
                    "resident done-callback failed"
                )

    # -- lifecycle / introspection -------------------------------------------

    def close(self, join: bool = True, timeout: float = 30.0) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()
            feeder, collector = self._feeder, self._collector
        if not join:
            return
        me = threading.current_thread()
        for th in (feeder, collector):
            if th is not None and th is not me:
                th.join(timeout)

    def stats(self) -> dict:
        with self._cond:
            return {
                "ring_depth": len(self._ring),
                "ring_cap": self._ring_cap,
                "inflight": self._inflight_q.qsize(),
                "max_inflight": self._max_inflight,
                "enqueued": self.enqueued,
                "rejected": self.rejected,
                "submitted": self.submitted,
                "collected": self.collected,
                "errors": self.errors,
                "aot_hits": self.kernel.hits,
                "aot_misses": self.kernel.misses,
                "aot_buckets": self.kernel.buckets(),
                "aot_evictions": self.kernel.cache.evictions,
                "aot_compiles": self.kernel.cache.compiles,
                "aot_compile_ms_total": round(
                    self.kernel.cache.compile_ms_total, 1
                ),
            }
