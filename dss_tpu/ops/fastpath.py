"""The fast conflict-query path: host index lookup + dense TPU filter.

Division of labor (each side doing what its hardware is good at):

  host (CPU)   — cell-key -> postings-range lookup (numpy searchsorted
                 over the sorted key column; the CRDB range-lookup
                 analog), plus result assembly from the compacted hit
                 words the device returns.
  device (TPU) — the dense part: for every (query, cell) window of the
                 attribute-inlined postings blocks, a vectorized EXACT
                 4D overlap test (f32 altitudes, i64 ns times), hits
                 bit-packed to u32 words, and the non-empty words
                 compacted on device (hand-rolled cumsum+scatter — NOT
                 jnp.nonzero, whose searchsorted lowering is ~20x
                 slower on TPU) so the result's useful part is
                 proportional to hits, not windows scanned.

A query crosses the bus twice: one packed i32 buffer up (the windows,
then the per-query bounds as their f32 / i64 bit patterns; pack_bounds)
and one flat i32 result down, sized by the window bucket the query
scans (max_words_for).

This replaces the reference's per-query SQL conflict scan
(pkg/scd/store/cockroach/operations.go:374-435) and the RID
`cells && $x` search (pkg/rid/cockroach/identification_service_area.go
:166-197).

Submit/collect are asynchronous: submit() enqueues the upload + kernel
and starts the D2H copy without blocking, so many batches pipeline and
the dispatch round trip is paid once per *stream*, not once per batch.

One device implementation: XLA, a leading-dim block gather
(embedding-lookup shape) over the four exact block columns.  When the
compacted hit-word buffer overflows, collect() re-runs the same fused
kernel at the hard bound of four words a window.  Small batches never
reach the device: query_host_auto scans the host postings copy with
the same compares on the same values.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

INT32_MAX = np.int32(2**31 - 1)
BLOCK = 128  # postings per block == TPU lane width
WORDS = BLOCK // 32  # u32 hit words per window

_NATIVE: Optional[tuple] = None  # one-shot import cache (module|None,)


def _native_mod():
    """The dss_tpu.native module, or None when it can't import.  The
    import is cached; native.available() stays cheap per call (a lazy
    dlopen behind a lock-free fast path)."""
    global _NATIVE
    if _NATIVE is None:
        try:
            from dss_tpu import native
        except Exception:  # pragma: no cover
            native = None
        _NATIVE = (native,)
    return _NATIVE[0]


def segmented_arange(counts: np.ndarray) -> np.ndarray:
    """Ragged expansion: for counts [2, 3] -> [0, 1, 0, 1, 2].  The
    cumsum-minus-repeat idiom, factored once (off-by-one prone)."""
    counts = np.asarray(counts)
    total = int(counts.sum())
    return np.arange(total) - np.repeat(np.cumsum(counts) - counts, counts)


def pow2_bucket(n: int, lo: int = 256) -> int:
    """Smallest power-of-two >= max(n, lo) — the shared shape-bucketing
    rule that keeps XLA executable counts bounded.

    Measured dead end: 3*2^(k-1) intermediate buckets on the fused
    kernel's window axis (to cut the up-to-2x pad waste in H2D/grid/
    D2H) ran ~3x SLOWER end to end — XLA's TPU lowering of the gather/
    compaction tiles pow2 extents far better.  Keep buckets pow2."""
    v = lo
    while v < n:
        v *= 2
    return v


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------


# hit-word decode tables: popcount per uint16 half, and a de Bruijn
# count-trailing-zeros LUT (the multiply wraps mod 2^32 by design).
# uint16 halves the table footprint on the hot gather; vectorized
# construction keeps import cheap.
_POPCOUNT16 = (
    np.unpackbits(np.arange(1 << 16, dtype="<u2").view(np.uint8))
    .reshape(-1, 16)
    .sum(axis=1)
    .astype(np.uint16)
)
_DEBRUIJN_CTZ = np.zeros(32, np.int8)
for _i in range(32):
    _DEBRUIJN_CTZ[(((1 << _i) * 0x077CB531) & 0xFFFFFFFF) >> 27] = _i


def _expand_hit_words(bits_u32: np.ndarray):
    """(word_index, bit_position) pairs for every set bit, word-major
    with ascending bit positions within a word — the same order
    unpackbits+nonzero produces, at ~2x the speed.  Per-word popcount
    gives each word's output span; iteration k extracts the k-th
    lowest set bit of every still-active word via the de Bruijn ctz
    trick and scatters it to span start + k."""
    pc = _POPCOUNT16[bits_u32 & 0xFFFF] + _POPCOUNT16[bits_u32 >> 16]
    total = int(pc.sum())
    base = np.cumsum(pc) - pc
    wi = np.repeat(np.arange(len(bits_u32), dtype=np.int64), pc)
    bitpos = np.empty(total, np.int32)
    rem = bits_u32.copy()
    active = np.flatnonzero(rem)
    k = 0
    while active.size:
        v = rem[active]
        low = v & (~v + np.uint32(1))
        ctz = _DEBRUIJN_CTZ[
            ((low * np.uint32(0x077CB531)) >> np.uint32(27)).astype(
                np.int64
            )
        ]
        bitpos[base[active] + k] = ctz
        v &= v - np.uint32(1)
        rem[active] = v
        active = active[v != 0]
        k += 1
    return wi, bitpos


def max_words_for(window_bucket: int) -> int:
    """Hit-word slots of the result for one window bucket: THE sizing
    rule (submit's auto size; ops/resident.py compiles its grid by it).
    A window yields at most WORDS non-empty words, so up to 16,384
    windows the buffer is that hard bound and cannot overflow; above,
    one word a window is the typical ceiling and collect() retries at
    the hard bound."""
    wb = int(window_bucket)
    return min(WORDS * wb, max(1 << 16, wb))


# the query's wire format, host -> device: ONE i32 buffer
#   [0, W)          window block index        (W = window bucket)
#   [W, 2W)         window meta: start | end<<8 | qidx<<16
#   [2W, 2W+B)      alt_lo, f32 bit patterns  (B = batch bucket)
#   [2W+B, 2W+2B)   alt_hi, f32 bit patterns
#   [2W+2B, 2W+4B)  t0_eff, i64 as (low, high) i32 pairs
#   [2W+4B, 2W+6B)  t_end,  i64 as (low, high) i32 pairs
# little-endian, as every platform JAX runs on; pad queries stay zero
# (inert: no window's meta names an index >= the real batch).
QUERY_WORDS = 6  # i32 words per padded query after the windows


def packed_words(windows: int, batch_bucket: int) -> int:
    """Length of the packed query buffer for one shape bucket."""
    return 2 * int(windows) + QUERY_WORDS * int(batch_bucket)


def pack_bounds(packed, windows, alt_lo, alt_hi, t0_eff, t_end) -> None:
    """Write the per-query bounds behind the windows of `packed`
    (_pack_windows left QUERY_WORDS * batch bucket zero words there).
    Bit patterns only: nothing is rounded or quantised."""
    o = 2 * windows
    bb = (len(packed) - o) // QUERY_WORDS
    n = len(alt_lo)
    packed[o:o + n] = np.asarray(alt_lo, np.float32).view(np.int32)
    o += bb
    packed[o:o + n] = np.asarray(alt_hi, np.float32).view(np.int32)
    o += bb
    packed[o:o + 2 * n] = np.ascontiguousarray(t0_eff, np.int64).view(
        np.int32
    )
    o += 2 * bb
    packed[o:o + 2 * n] = np.ascontiguousarray(t_end, np.int64).view(
        np.int32
    )


def unpack_query(packed, windows):
    """-> (wins (2, W), q_alo f32[B], q_ahi f32[B], q_t0 i64[B], q_t1
    i64[B]) of one packed query buffer: a numpy buffer on the host
    (views, for tests and tools) or the traced one inside the kernel."""
    w2 = 2 * windows
    bb = (packed.shape[0] - w2) // QUERY_WORDS
    wins = packed[:w2].reshape(2, windows)
    parts = (
        packed[w2:w2 + bb],
        packed[w2 + bb:w2 + 2 * bb],
        packed[w2 + 2 * bb:w2 + 4 * bb],
        packed[w2 + 4 * bb:],
    )
    if isinstance(packed, np.ndarray):
        return (wins, parts[0].view(np.float32), parts[1].view(np.float32),
                parts[2].view(np.int64), parts[3].view(np.int64))

    def i64(x):  # plain arithmetic: the TPU's 64-bit lowering has it
        x = x.reshape(bb, 2)
        return (x[:, 1].astype(jnp.int64) << 32) | (
            x[:, 0].astype(jnp.int64) & 0xFFFFFFFF
        )

    return (
        wins,
        jax.lax.bitcast_convert_type(parts[0], jnp.float32),
        jax.lax.bitcast_convert_type(parts[1], jnp.float32),
        i64(parts[2]),
        i64(parts[3]),
    )


def fused_window_filter(
    b_alo, b_ahi, b_t0, b_t1,  # (NB, 128) exact block columns
    packed,  # i32[2W + 6B]: the query's one upload (layout above).
    #          wins (2, W): [block index, start | end<<8 | qidx<<16];
    #          exact per-query f32 altitudes and i64 instants, the
    #          lower one pre-folded with now host-side: t0_eff =
    #          max(t_start, now), so `t_end >= t0_eff` covers both the
    #          window test and the `ends at/after now` liveness rule
    *, windows, max_words, chunk=16384,
):
    """Exact window filter + hit bit-packing + word compaction, all
    on device — the fused kernel's pure function, at module level so
    the resident subsystem (ops/resident.py) can AOT-compile its own
    donated twin of the SAME tracing (bit-identical by construction).
    FastTable._fused_xla is the shared non-donating jit of this.

    Each window is one postings run's slice of one 128-block,
    described by [start, end) lanes — no per-lane key compare (and no
    key gather) needed.  Returns one flat i32 array:

      out[0]                     = count of non-empty hit words
      out[1 : 1+max_words]       = flat word positions (window*4+w)
      out[1+max_words : ]        = u32 hit bits per word (as i32)

    The result is sized by the window bucket (max_words_for), its
    useful part by the hits.  Compaction is a hand-rolled
    cumsum+scatter (~35x faster than jnp.nonzero's searchsorted
    lowering on TPU)."""
    # named scopes put the kernel and its three phases into the op
    # names of a profiler capture.  Metadata only: they change neither
    # the compiled program nor its persistent-cache key
    # (jax_compilation_cache_include_metadata_in_key is off).
    with jax.named_scope("dss.fused_window_filter"):
        wins, q_alo, q_ahi, q_t0, q_t1 = unpack_query(packed, windows)
        nw = windows
        win_blk, meta = wins[0], wins[1]
        win_q = meta >> 16
        lanes = jnp.arange(BLOCK, dtype=jnp.int32)

        def one_chunk(c):
            blk, meta_c, alo_c, ahi_c, t0_c, t1_c = c
            with jax.named_scope("compare_mask"):
                start = meta_c & 0xFF
                end = (meta_c >> 8) & 0xFF
                hit = (lanes[None, :] >= start[:, None]) & (
                    lanes[None, :] < end[:, None]
                )
            # each exact block column: gather the windows' rows, then
            # compare them with the windows' queries — (C, 128) bool
            for col, keep, bound in (
                (b_ahi, jnp.greater_equal, alo_c),
                (b_alo, jnp.less_equal, ahi_c),
                (b_t1, jnp.greater_equal, t0_c),
                (b_t0, jnp.less_equal, t1_c),
            ):
                with jax.named_scope("gather"):
                    rows = jnp.take(col, blk, axis=0)
                with jax.named_scope("compare_mask"):
                    hit = hit & keep(rows, bound[:, None])
            with jax.named_scope("compare_mask"):
                # bit-pack 128 lanes -> 4 u32 words (exact, incl. bit
                # 31: disjoint bits, so modular i32 addition ==
                # bitwise OR)
                h = hit.astype(jnp.int32).reshape(-1, WORDS, 32)
                return jnp.sum(
                    h << jnp.arange(32, dtype=jnp.int32)[None, None, :],
                    axis=2,
                    dtype=jnp.int32,
                )  # (C, 4) i32 bit patterns

        cargs = (
            win_blk,
            meta,
            jnp.take(q_alo, win_q),
            jnp.take(q_ahi, win_q),
            jnp.take(q_t0, win_q),
            jnp.take(q_t1, win_q),
        )
        if nw <= chunk:
            words = one_chunk(cargs)
        else:
            pad = (-nw) % chunk

            def padq(a):
                if pad:
                    a = jnp.concatenate([a, jnp.zeros(pad, a.dtype)])
                return a.reshape(-1, chunk)

            words = jax.lax.map(
                one_chunk, tuple(padq(a) for a in cargs)
            ).reshape(-1, WORDS)[:nw]

        with jax.named_scope("compact"):
            flat = words.ravel()  # (NW*4,) i32
            nz = flat != 0
            pos = jnp.cumsum(nz.astype(jnp.int32))
            n_words = pos[-1]
            # compact: scatter word index + bits into max_words slots
            dst = jnp.where(nz, pos - 1, max_words)
            wordpos = (
                jnp.zeros((max_words + 1,), jnp.int32)
                .at[dst]
                .set(
                    jnp.arange(flat.shape[0], dtype=jnp.int32),
                    mode="drop",
                )[:max_words]
            )
            bits = (
                jnp.zeros((max_words + 1,), jnp.int32)
                .at[dst]
                .set(flat, mode="drop")[:max_words]
            )
            return jnp.concatenate([n_words[None], wordpos, bits])


def warmup(device=None) -> None:
    """Compile the fused kernel's small-burst executable ahead of
    traffic.  Point lookups (batch <= HOST_MAX_BATCH) answer from the
    host postings copy and never touch the device, so this warms the
    FIRST device shapes a coalesced burst beyond that threshold hits
    (batch bucket 128; window buckets 256 and 1024, each with its
    max_words_for result)
    — the multi-second XLA compiles stay off request deadlines.
    Servers call this from a background thread at startup."""
    n = BLOCK
    keys = np.arange(n, dtype=np.int32)
    ft = FastTable(
        keys,
        np.arange(n, dtype=np.int32),
        np.zeros(n, np.float32),
        np.ones(n, np.float32),
        np.zeros(n, np.int64),
        np.full(n, 2, np.int64),
        np.ones(n, bool),
        slot_exact=dict(
            alt_lo=np.zeros(n, np.float32),
            alt_hi=np.ones(n, np.float32),
            t0=np.zeros(n, np.int64),
            t1=np.full(n, 2, np.int64),
            live=np.ones(n, bool),
        ),
        device=device,
    )
    b = FastTable.HOST_MAX_BATCH + 1  # first device-path batch bucket
    # warm the two window buckets such a burst lands in: b point-ish
    # queries (3 keys -> nw <= 195 -> bucket 256) and b full coverings
    # (8 keys -> nw ~ 520 -> bucket 1024)
    for width in (3, 8):
        qk = np.broadcast_to(
            np.arange(width, dtype=np.int32)[None, :], (b, width)
        ).copy()
        ft.query_fused(
            qk,
            np.zeros(b, np.float32),
            np.ones(b, np.float32),
            np.zeros(b, np.int64),
            np.ones(b, np.int64),
            now=1,
        )


class PendingBatch:
    """In-flight fused query batch: device future + host decode state.

    Created by FastTable.submit(); resolved by FastTable.collect().
    Nothing here blocks — jax dispatch is async and submit() starts the
    D2H copy (copy_to_host_async), so many batches can be in flight at
    once and the host sync per collect only waits for the stream."""

    __slots__ = (
        "out", "win_q", "win_blk", "host_inputs", "nw", "max_words",
        "kernel", "io",
    )

    def __init__(self, out, win_q, win_blk, host_inputs, nw, max_words,
                 kernel=None, up_bytes=0):
        self.out = out  # device flat i32: [n_words, wordpos..., bits...]
        self.win_q = win_q
        self.win_blk = win_blk
        self.host_inputs = host_inputs  # for the overflow fallback
        self.nw = nw
        self.max_words = max_words
        self.kernel = kernel  # resident AOT selector (overflow retry)
        # what this batch moved across the bus: (launches, uploads,
        # bytes up, bytes down); collect() adds an overflow re-run's.
        # DarTable sums them per entity class (co_dev_* on /metrics)
        self.io = (1, 1, int(up_bytes), int(out.nbytes))

    def ready(self) -> None:
        """Block until the device computation has completed (readiness
        only — no data fetch, no decode).  Lets a pipelined caller
        (the coalescer's collect stage) time the pure device wait
        separately from collect()'s D2H + decode."""
        self.out.block_until_ready()


class FastTable:
    """Device-resident packed postings + host decode state."""

    def __init__(
        self,
        post_key: np.ndarray,  # i32[P] sorted (live postings only)
        post_ent: np.ndarray,  # i32[P]
        alt_lo: np.ndarray,  # f32[P] per-posting (inlined)
        alt_hi: np.ndarray,
        t_start: np.ndarray,  # i64[P] ns
        t_end: np.ndarray,
        live: np.ndarray,  # bool[P]
        *,
        slot_exact: dict,  # per-SLOT exact columns {"alt_lo", "alt_hi",
        #                    "t0", "t1", "live"}: the host scan's values
        #                    and the post-build tombstones
        device=None,
    ):
        P = len(post_key)
        # a query row pads its keys with -1, which must stay
        # distinguishable from a real DAR key, so keys are required to
        # be non-negative (cell_to_dar_key yields 30-bit keys,
        # geo/s2cell.py).
        if P and int(post_key.min()) < 0:
            raise ValueError(
                f"FastTable requires non-negative DAR keys, got min "
                f"{int(post_key.min())}"
            )
        # the native run search computes key+1 (UB at INT32_MAX); real
        # DAR keys are 30-bit (geo/s2cell.py), so reject it outright
        if P and int(post_key.max()) >= INT32_MAX:
            raise ValueError(
                "FastTable requires DAR keys < INT32_MAX, "
                f"got max {int(post_key.max())}"
            )
        self.n_postings = P
        # 2 extra blocks of padding so lo_blk+1 never reads out of range
        ppad = ((P + 2 * BLOCK - 1) // (2 * BLOCK)) * 2 * BLOCK + 4 * BLOCK
        nb = ppad // BLOCK
        self.n_blocks = nb
        self.host_key = np.asarray(post_key)
        self.host_ent = np.asarray(post_ent)
        self.host_live = np.asarray(live, bool)

        # EXACT per-posting attribute columns in block layout, resident
        # in HBM, so the window test is exact (no quantization, no host
        # re-filter): 24 B a padded posting.  Tombstoned postings get
        # t_end = -2^62 so `t_end >= now` never passes; post-build
        # tombstones are dropped host-side in collect() via
        # slot_exact["live"].
        nblo = np.int64(-(2**62))
        b_alo = np.full(ppad, np.inf, np.float32)
        b_ahi = np.full(ppad, -np.inf, np.float32)
        b_t0 = np.full(ppad, 2**62, np.int64)
        b_t1 = np.full(ppad, nblo, np.int64)
        b_alo[:P] = np.asarray(alt_lo, np.float32)
        b_ahi[:P] = np.asarray(alt_hi, np.float32)
        b_t0[:P] = np.asarray(t_start, np.int64)
        b_t1[:P] = np.where(self.host_live, np.asarray(t_end, np.int64), nblo)
        self.b_alo = jax.device_put(b_alo.reshape(nb, BLOCK), device)
        self.b_ahi = jax.device_put(b_ahi.reshape(nb, BLOCK), device)
        self.b_t0 = jax.device_put(b_t0.reshape(nb, BLOCK), device)
        self.b_t1 = jax.device_put(b_t1.reshape(nb, BLOCK), device)
        self.slot_exact = {k: np.asarray(v) for k, v in slot_exact.items()}
        # normalize the live column to a contiguous buffer HERE, where
        # no concurrent mutator can exist yet: mark_dead() flips bits of
        # THIS array in place and the native host path caches a uint8
        # view of the same memory — adopting a contiguous copy lazily on
        # the query path could lose a tombstone that raced the adoption
        self.slot_exact["live"] = np.ascontiguousarray(
            self.slot_exact["live"]
        )

    def device_bytes(self) -> int:
        """Bytes of this table's device-resident arrays: the four exact
        block columns."""
        return int(sum(
            a.nbytes for a in (self.b_alo, self.b_ahi, self.b_t0, self.b_t1)
        ))

    def mark_dead(self, slot: int) -> None:
        """Tombstone one slot in place (no rebuild): flips the host
        live bit; collect() drops the slot during result assembly, so
        the fused path stops returning it immediately."""
        self.slot_exact["live"][slot] = False

    # -- fused on-device kernel ----------------------------------------------

    WORDS = WORDS  # u32 hit words per window (module constant, kept
    #                as a class attr for back-compat)

    # the shared (non-donating) jit of the module-level fused kernel;
    # the resident path compiles its own donated AOT twin of the same
    # function (ops/resident.py) so both trace identically
    _fused_xla = staticmethod(
        partial(
            jax.jit, static_argnames=("windows", "max_words", "chunk")
        )(
            fused_window_filter
        )
    )

    # -- host window expansion -----------------------------------------------

    def _range_lookup(self, k: np.ndarray):
        """Vectorized postings-range lookup: for each query key, the
        [lo, hi) slice of the sorted key column.  Queries are sorted
        first so consecutive binary searches walk the same bottom-level
        cache lines (~1.7x over two cold searchsorted passes at 8M
        postings); results are scattered back to query order."""
        P = len(self.host_key)
        if P <= 4096 or len(k) <= 512:
            # small table or batch: the plain path is already cached
            return (
                np.searchsorted(self.host_key, k, side="left"),
                np.searchsorted(self.host_key, k, side="right"),
            )
        order = np.argsort(k, kind="stable")
        ks = k[order]
        lo = np.empty(len(k), np.int64)
        hi = np.empty(len(k), np.int64)
        lo[order] = np.searchsorted(self.host_key, ks, side="left")
        hi[order] = np.searchsorted(self.host_key, ks, side="right")
        return lo, hi

    def _expand_windows(self, qkeys: np.ndarray):
        """(query, cell) pairs -> every 128-block their postings runs
        touch.  Returns (win_q, win_blk, win_start, win_end) host i32
        arrays; [start, end) is the run's lane slice within
        the window's block."""
        B, W = qkeys.shape
        qk = np.ascontiguousarray(qkeys, np.int32)
        lo, hi = self._range_lookup(qk.ravel())
        nonempty = hi > lo  # also drops pad cells (-1)
        lo, hi = lo[nonempty], hi[nonempty]
        flat_q = np.repeat(np.arange(B), W)[nonempty]
        first_blk = lo // BLOCK
        n_blocks = (hi - 1) // BLOCK - first_blk + 1  # >= 1
        win_q = np.repeat(flat_q, n_blocks).astype(np.int32)
        starts = np.repeat(first_blk, n_blocks)
        win_blk = (starts + segmented_arange(n_blocks)).astype(np.int32)
        blk0 = win_blk.astype(np.int64) * BLOCK
        win_start = np.maximum(np.repeat(lo, n_blocks) - blk0, 0).astype(np.int32)
        win_end = np.minimum(np.repeat(hi, n_blocks) - blk0, BLOCK).astype(np.int32)
        return win_q, win_blk, win_start, win_end

    def _sample_index(self):
        """(host_key i32, sample, sample0) for the native range
        lookups: 1/64- and 1/4096-sampled key columns (~500 KB and
        ~8 KB at 8M postings) that keep the search's top levels
        cache-resident.  The table is immutable, so built once and
        cached; None samples below 2^14 postings (flat search is
        already cache-resident)."""
        hk = np.ascontiguousarray(self.host_key, np.int32)
        sample = getattr(self, "_hk_sample", None)
        sample0 = getattr(self, "_hk_sample0", None)
        if sample is None and len(hk) > 1 << 14:
            sample = self._hk_sample = np.ascontiguousarray(hk[::64])
            sample0 = self._hk_sample0 = np.ascontiguousarray(
                sample[::64]
            )
        return hk, sample, sample0

    def _pack_windows(self, qkeys: np.ndarray, tail: int = 0):
        """Expand + pack windows for the fused kernel into the head of
        the query's one upload: a flat i32 buffer of the (2, bucket)
        windows [blk | start|end<<8|qidx<<16], row after row, then
        `tail` zero words for the caller (submit's per-query bounds,
        pack_bounds).  Returns (packed, win_q, win_blk, nw); nw == 0
        means no work (packed None); bucket == (len(packed) - tail) // 2.

        Prefers the native (C++) kernel — the binary searches + ragged
        expansion cost ~22 ms per 8k-query batch at 1M postings in
        numpy vs ~3 ms native, and this is the serial host stage that
        bounds pipelined fused throughput (bench.py headline).
        Bit-identical outputs, pinned by tests/test_native_fastwin.py."""
        if len(qkeys) > (1 << 15):
            raise ValueError("fused path supports batches up to 32768")
        nat = _native_mod()
        if nat is not None and nat.available():
            qk = np.ascontiguousarray(qkeys, np.int32)
            hk, sample, sample0 = self._sample_index()
            res = nat.pack_windows(
                hk, qk.ravel(), qk.shape[1], BLOCK, pow2_bucket,
                sample=sample, sample0=sample0, tail=tail,
            )
            if res is not None:
                return res
        win_q, win_blk, win_start, win_end = self._expand_windows(qkeys)
        nw = len(win_blk)
        if nw == 0:
            return None, win_q, win_blk, 0
        # qidx lives in bits 16-31 of a signed i32 meta word; the
        # <= 2^15 batch gate above keeps the sign bit clear so
        # meta >> 16 recovers it intact
        bucket = pow2_bucket(nw)
        packed = np.zeros(2 * bucket + tail, np.int32)
        packed[:nw] = win_blk
        # pad rows keep meta 0 -> start == end == 0 -> no lanes match
        packed[bucket:bucket + nw] = (
            win_start | (win_end << 8) | (win_q << 16)
        )
        return packed, win_q, win_blk, nw

    def _pack_query(self, qkeys, alt_lo, alt_hi, t_start, t_end, now):
        """The query batch's one upload, whole: windows, then bounds.
        -> (packed, window bucket, batch bucket, win_q, win_blk, nw);
        nw == 0 means no work (packed None)."""
        # pad the batch axis to a pow2 bucket too: the coalescer drains
        # arbitrary batch sizes, and an unpadded (B,) shape would force
        # a fresh XLA compile per distinct B.  Pad queries are inert —
        # no window's meta references an index >= B.
        b = len(qkeys)
        bb = pow2_bucket(b, lo=16)
        packed, win_q, win_blk, nw = self._pack_windows(
            qkeys, tail=QUERY_WORDS * bb
        )
        if nw == 0:
            return None, 0, bb, win_q, win_blk, 0
        bucket = (len(packed) - QUERY_WORDS * bb) // 2
        # fold the liveness rule into the lower time bound per query:
        # t_end >= max(t_start, now) == (t_end >= t_start) & (t_end >= now)
        t0_eff = np.maximum(
            np.asarray(t_start, np.int64), np.asarray(now, np.int64)
        )
        pack_bounds(
            packed, bucket, alt_lo, alt_hi,
            np.broadcast_to(t0_eff, (b,)), t_end,
        )
        return packed, bucket, bb, win_q, win_blk, nw

    def submit(
        self,
        qkeys: np.ndarray,  # i32[B, W] DAR keys, pad -1
        alt_lo: np.ndarray,  # f32[B] (-inf if unbounded)
        alt_hi: np.ndarray,
        t_start: np.ndarray,  # i64[B] ns (NO_TIME_LO if unbounded)
        t_end: np.ndarray,
        *,
        now,  # int scalar or i64[B] per-query request time
        max_words: Optional[int] = None,
        kernel=None,  # resident AOT selector (ops/resident.py): maps
        #               this submit's shape bucket to a pre-compiled
        #               donated executable; None (or a miss) runs the
        #               shared jit path
    ) -> Optional[PendingBatch]:
        """Enqueue one fused query batch (async; no device sync).
        Returns None when no query key has any postings (empty
        result).

        The query goes up as ONE numpy buffer, handed to the
        executable as it is (the dispatch path transfers it; no
        per-array jnp.asarray), and comes back as one buffer of
        max_words_for(window bucket) hit-word slots: the hard bound of
        four words a window up to 16,384 windows, so only larger
        buckets (or an explicit max_words) can overflow; collect()
        then retries at the hard bound."""
        packed, bucket, bb, win_q, win_blk, nw = self._pack_query(
            qkeys, alt_lo, alt_hi, t_start, t_end, now
        )
        if nw == 0:
            return None
        if max_words is None:
            max_words = max_words_for(bucket)
        # resident path: a pre-compiled (AOT, donated-I/O) executable
        # for exactly this (blocks, window bucket, batch bucket,
        # max_words) shape — no trace, no compile, no per-call output
        # allocation in steady state.  A miss (unwarmed bucket) falls
        # back to the shared jit, which is today's behavior.
        fn = None
        if kernel is not None:
            fn = kernel.lookup(self, bucket, bb, max_words)
        if fn is not None:
            out = fn(self.b_alo, self.b_ahi, self.b_t0, self.b_t1, packed)
        else:
            out = self._fused_xla(
                self.b_alo, self.b_ahi, self.b_t0, self.b_t1, packed,
                windows=bucket, max_words=max_words,
            )
        out.copy_to_host_async()
        return PendingBatch(
            out,
            win_q,
            win_blk,
            (qkeys, alt_lo, alt_hi, t_start, t_end, now),
            nw,
            max_words,
            kernel,
            packed.nbytes,
        )

    def collect(
        self, pending: Optional[PendingBatch]
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Resolve a submitted batch -> (qidx i64[H], slots i64[H]),
        exact (not deduped).  The one host sync per batch."""
        if pending is None:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        out = np.asarray(pending.out)
        mw = pending.max_words
        n_words = int(out[0])
        if n_words > mw:
            # overflow: the word buffer was too small — rerun the fused
            # kernel at the hard upper bound (4 words per window), which
            # cannot overflow.  Exact same semantics, one extra round
            # trip.
            qkeys, alt_lo, alt_hi, t_start, t_end, now = pending.host_inputs
            hard = WORDS * pow2_bucket(pending.nw)
            rerun = self.submit(
                qkeys, alt_lo, alt_hi, t_start, t_end,
                now=now, max_words=hard, kernel=pending.kernel,
            )
            res = self.collect(rerun)
            pending.io = tuple(a + b for a, b in zip(pending.io, rerun.io))
            return res
        wordpos = out[1 : 1 + n_words]
        bits = out[1 + mw : 1 + mw + n_words].astype(np.int32)
        if n_words == 0:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        nat = _native_mod()
        if nat is not None and nat.available():
            # native decode: popcount/ctz expansion + pad/tombstone
            # filter in one GIL-released call, same output order
            # (differentially pinned by tests/test_native_fastwin.py)
            wshift = FastTable.WORDS.bit_length() - 1
            res = nat.decode_hits(
                np.ascontiguousarray(wordpos, np.int32),
                np.ascontiguousarray(bits).view(np.uint32),
                np.ascontiguousarray(pending.win_q, np.int32),
                np.ascontiguousarray(pending.win_blk, np.int32),
                wshift, BLOCK,
                np.ascontiguousarray(self.host_ent, np.int32),
                self.n_postings,
                np.ascontiguousarray(self.slot_exact["live"]).view(
                    np.uint8
                ),
            )
            if res is not None:
                return res
        # expand hit words -> (word, bit) pairs (popcount + de Bruijn
        # ctz; ~2x unpackbits+flatnonzero)
        wi, bitpos = _expand_hit_words(bits.view(np.uint32))
        wp = wordpos[wi]
        wshift = FastTable.WORDS.bit_length() - 1  # WORDS is a pow2
        win = wp >> wshift
        lane = ((wp & (FastTable.WORDS - 1)) << 5) + bitpos
        offs = pending.win_blk[win].astype(np.int64) * BLOCK + lane
        ok = offs < self.n_postings
        offs = offs[ok]
        slots = self.host_ent[offs].astype(np.int64)
        qidx = pending.win_q[win[ok]].astype(np.int64)
        # post-build tombstones (mark_dead) are dropped here
        alive = self.slot_exact["live"][slots]
        return qidx[alive], slots[alive]

    def query_fused(
        self, qkeys, alt_lo, alt_hi, t_start, t_end, *, now,
        max_words: Optional[int] = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """submit + collect in one call -> exact (qidx, slots)."""
        return self.collect(
            self.submit(
                qkeys, alt_lo, alt_hi, t_start, t_end,
                now=now, max_words=max_words,
            )
        )

    # -- host small-batch path ----------------------------------------------

    # route small batches to the host when the candidate postings fit
    # comfortably in cache: a point lookup then costs ~100 us of numpy
    # instead of a device round trip — the <5 ms p50 leg of the north
    # star.  Large batches amortize the round trip and win on the
    # device.  (The cut-off was set against a dispatch floor that is
    # not measured on the chip: ROADMAP A3.)
    HOST_MAX_BATCH = 64
    HOST_MAX_CANDIDATES = 1 << 16
    # the deadline router's FORCED host route (query_host_chunked):
    # batches beyond HOST_MAX_BATCH are served as chunks of the warmed
    # HOST_MAX_BATCH bucket with a raised per-chunk candidate cap — a
    # deliberate latency-for-CPU trade when the device round trip would
    # blow a request deadline.  Beyond the raised cap the chunk really
    # is device-shaped work (a multi-ms host scan) and the route
    # declines (returns None) so the caller falls back to the kernel.
    HOST_ROUTE_MAX_CANDIDATES = 1 << 18

    def host_candidates(self, qkeys: np.ndarray, *,
                        max_batch: Optional[int] = None,
                        max_candidates: Optional[int] = None):
        """-> (lo, hi) postings ranges for the batch, or None when the
        batch should go to the device (too big).  Thread-safe: ranges
        are returned, not cached (readers are lock-free).  max_batch /
        max_candidates override the auto-route gates (the deadline
        router's forced host chunks raise them)."""
        mb = self.HOST_MAX_BATCH if max_batch is None else int(max_batch)
        if len(qkeys) > mb:
            return None
        mc = (
            self.HOST_MAX_CANDIDATES
            if max_candidates is None
            else int(max_candidates)
        )
        lo, hi = self._range_lookup(
            np.ascontiguousarray(qkeys, np.int32).ravel()
        )
        if int((hi - lo).sum()) > mc:
            return None
        return lo, hi

    def candidates(self, qkeys: np.ndarray) -> np.ndarray:
        """i64[B]: the candidate postings under each row of `qkeys`:
        what the host gate sums over the batch and a scan walks (pad
        keys find none).  For the serving accounts (dar/coalesce.py),
        never for routing."""
        qk = np.ascontiguousarray(qkeys, np.int32)
        lo, hi = self._range_lookup(qk.ravel())
        return (hi - lo).reshape(qk.shape).sum(axis=1)

    def query_host_chunked(
        self, qkeys, alt_lo, alt_hi, t_start, t_end, *, now,
        chunk: Optional[int] = None,
    ):
        """FORCED exact host answer for batches of any size: rows are
        served in chunks of the warmed HOST_MAX_BATCH bucket (the size
        every boot-warmed native/numpy scan already runs at), each with
        the raised HOST_ROUTE_MAX_CANDIDATES cap.  -> (qidx, slots)
        bit-identical to the fused device path, or None when any chunk
        exceeds the raised cap (then the batch is genuinely device
        work).  This is the deadline router's escape hatch from the
        device dispatch floor: N/64 sequential ~100 us scans beat one
        dispatch round trip whenever the floor is the larger."""
        b = len(qkeys)
        step = self.HOST_MAX_BATCH if chunk is None else max(1, int(chunk))
        now_b = np.broadcast_to(np.asarray(now, np.int64), (b,))
        parts_q: List[np.ndarray] = []
        parts_s: List[np.ndarray] = []
        for s in range(0, b, step):
            e = min(b, s + step)
            res = self.query_host_auto(
                qkeys[s:e], alt_lo[s:e], alt_hi[s:e],
                t_start[s:e], t_end[s:e], now=now_b[s:e],
                max_batch=step,
                max_candidates=self.HOST_ROUTE_MAX_CANDIDATES,
            )
            if res is None:
                return None
            qi, sl = res
            parts_q.append(qi + s)
            parts_s.append(sl)
        if not parts_q:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        return np.concatenate(parts_q), np.concatenate(parts_s)

    def query_host_auto(
        self, qkeys, alt_lo, alt_hi, t_start, t_end, *, now,
        max_batch: Optional[int] = None,
        max_candidates: Optional[int] = None,
    ):
        """Exact host-path answer for small batches: (qidx, slots), or
        None when the batch should go to the device.  Prefers the
        native (C++) kernel — one GIL-released call instead of ~15
        numpy dispatches (~0.2 ms -> ~15 us at 1k entities, ~3 ms ->
        ~60 us at 1M); identical verdicts (same compares on the same
        values), pinned by tests/test_native_hostquery.py.  Falls back
        to the numpy path when the lib is absent.  max_batch /
        max_candidates raise the route gates for the deadline router's
        forced host chunks (query_host_chunked)."""
        mb = self.HOST_MAX_BATCH if max_batch is None else int(max_batch)
        if len(qkeys) > mb:
            return None
        try:
            from dss_tpu import native as _native
        except Exception:  # pragma: no cover
            _native = None
        if _native is not None and _native.available():
            cols = getattr(self, "_hostq_cols", None)
            if cols is None:
                # table-side columns are immutable buffers (tombstones
                # mutate slot_exact["live"] IN PLACE, and the cached
                # uint8 view shares its memory) — prepare once.
                se = self.slot_exact
                hk, sample, sample0 = self._sample_index()
                # live was normalized to a contiguous buffer in
                # __init__, so this view shares memory with the array
                # mark_dead() mutates — no adoption race on this path
                live = se["live"]
                cols = self._hostq_cols = (
                    hk,
                    np.ascontiguousarray(self.host_ent, np.int32),
                    np.ascontiguousarray(self.host_live).view(np.uint8),
                    live.view(np.uint8),
                    np.ascontiguousarray(se["alt_lo"], np.float32),
                    np.ascontiguousarray(se["alt_hi"], np.float32),
                    np.ascontiguousarray(se["t0"], np.int64),
                    np.ascontiguousarray(se["t1"], np.int64),
                    sample, sample0,
                )
            res = _native.query_host(
                *cols[:8],
                np.ascontiguousarray(qkeys, np.int32),
                np.ascontiguousarray(alt_lo, np.float32),
                np.ascontiguousarray(alt_hi, np.float32),
                np.ascontiguousarray(t_start, np.int64),
                np.ascontiguousarray(t_end, np.int64),
                np.ascontiguousarray(
                    np.broadcast_to(
                        np.asarray(now, np.int64), (len(qkeys),)
                    )
                ),
                self.HOST_MAX_CANDIDATES
                if max_candidates is None
                else int(max_candidates),
                sample=cols[8], sample0=cols[9],
            )
            if res is None:
                return None  # candidate gate: device path
            return res[0], res[1].astype(np.int64)
        ranges = self.host_candidates(
            qkeys, max_batch=mb, max_candidates=max_candidates
        )
        if ranges is None:
            return None
        return self.query_host(
            qkeys, alt_lo, alt_hi, t_start, t_end, now=now, ranges=ranges
        )

    def query_host(
        self, qkeys, alt_lo, alt_hi, t_start, t_end, *, now, ranges,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Exact small-batch query on the host postings + exact
        columns: identical semantics (and results) to query_fused.
        `ranges` comes from host_candidates()."""
        B, W = qkeys.shape
        lo, hi = ranges
        n = hi - lo
        nonempty = n > 0
        lo_n, n_n = lo[nonempty], n[nonempty]
        flat_q = np.repeat(np.arange(B), W)[nonempty]
        total = int(n_n.sum())
        if total == 0:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        offs = np.repeat(lo_n, n_n) + segmented_arange(n_n)
        slots = self.host_ent[offs]
        qidx = np.repeat(flat_q, n_n)
        se = self.slot_exact
        now_q = np.asarray(now, np.int64)
        if now_q.ndim:
            now_q = now_q[qidx]
        alt_lo = np.asarray(alt_lo, np.float32)
        alt_hi = np.asarray(alt_hi, np.float32)
        t_start = np.asarray(t_start, np.int64)
        t_end = np.asarray(t_end, np.int64)
        keep = (
            self.host_live[offs]  # per-posting build-time tombstones
            & se["live"][slots]  # per-slot post-build tombstones
            & (se["alt_hi"][slots] >= alt_lo[qidx])
            & (se["alt_lo"][slots] <= alt_hi[qidx])
            & (se["t1"][slots] >= np.maximum(t_start[qidx], now_q))
            & (se["t0"][slots] <= t_end[qidx])
        )
        return qidx[keep].astype(np.int64), slots[keep].astype(np.int64)
