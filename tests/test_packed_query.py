"""The query's wire format between host and device (ops/fastpath.py):
one packed i32 upload (windows, then the per-query bounds as their f32
and i64 bit patterns), one launch, and a result sized by the window
bucket (max_words_for).

The spine is the differential: the packed kernel against
FastTable.query_host, the numpy reference over the host postings copy,
pair for pair, on the values a packing could lose (infinite altitudes,
the unbounded-time sentinels, negative and > 2^31 instants, per-query
`now`, pad keys, tombstones).  Beside it: the buffer's bit patterns
round trip on the host and on the device, the sizing rule is one rule
(submit and the AOT grid agree), an auto-sized result cannot overflow
up to 16,384 windows, a forced overflow still retries and stays exact
on the shared jit and the AOT twin, the jitted entry point keeps the
module name the benchmark reads, and the traffic counters reach
/metrics.  All on the CPU backend.
"""

from __future__ import annotations

from datetime import timedelta

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dss_tpu.ops import fastpath, resident
from dss_tpu.ops.conflict import NO_TIME_HI, NO_TIME_LO
from dss_tpu.ops.fastpath import BLOCK, WORDS, FastTable

NOW = 1_700_000_000_000_000_000
HOUR = 3_600_000_000_000
# instants a 32-bit or float path would lose: negative, just past 2^31
# and 2^32, near the sentinels
INSTANTS = np.asarray(
    [-(2**40) - 1, -1, 0, 2**31, 2**31 + 1, 2**32 - 1, 2**32, 2**32 + 1,
     NOW - 1, NOW, NOW + 1, 2**62 - 1],
    np.int64,
)


def _table(rng, n_ent=900, n_cells=60, kpe=5):
    """A FastTable whose slots hold the awkward values: unbounded
    altitudes and times, instants from INSTANTS, build-time and
    post-build tombstones."""
    pk = rng.integers(0, n_cells, n_ent * kpe).astype(np.int32)
    pe = np.repeat(np.arange(n_ent, dtype=np.int32), kpe)
    order = np.argsort(pk, kind="stable")
    pk, pe = pk[order], pe[order]
    alo = rng.uniform(0, 1000, n_ent).astype(np.float32)
    ahi = (alo + rng.uniform(1, 300, n_ent)).astype(np.float32)
    alo[::7], ahi[::11] = -np.inf, np.inf
    t0 = rng.choice(INSTANTS[:-1], n_ent)
    t1 = t0 + rng.integers(0, 3, n_ent) * HOUR + rng.integers(0, 3, n_ent)
    t0[::5], t1[::9] = NO_TIME_LO, NO_TIME_HI
    live_post = rng.random(len(pe)) > 0.05
    ft = FastTable(
        pk, pe, alo[pe], ahi[pe], t0[pe], t1[pe], live_post,
        slot_exact=dict(alt_lo=alo, alt_hi=ahi, t0=t0, t1=t1,
                        live=np.ones(n_ent, bool)),
    )
    for s in rng.integers(0, n_ent, 40):
        ft.mark_dead(int(s))
    return ft, n_cells


def _queries(rng, b, n_cells, w=6):
    qk = np.full((b, w), -1, np.int32)  # pad keys stay in every row
    for i in range(b):
        n = int(rng.integers(1, w))
        qk[i, :n] = rng.integers(0, n_cells, n)
    alo = rng.uniform(0, 1000, b).astype(np.float32)
    ahi = (alo + 200).astype(np.float32)
    alo[::3], ahi[1::3] = -np.inf, np.inf
    ts = rng.choice(INSTANTS, b)
    te = ts + rng.integers(0, 4, b) * HOUR
    ts[::4], te[2::4] = NO_TIME_LO, NO_TIME_HI
    now = rng.choice(INSTANTS[:-1], b)  # per-query request time
    return qk, alo, ahi, ts, te, now


def _pairs(res):
    return sorted(zip(res[0].tolist(), res[1].tolist()))


def _host(ft, qk, alo, ahi, ts, te, now):
    return ft.query_host(
        qk, alo, ahi, ts, te, now=now,
        ranges=ft._range_lookup(np.ascontiguousarray(qk).ravel()),
    )


@pytest.mark.parametrize("b", [1, 16, 17])
def test_packed_kernel_matches_the_host_reference(b):
    """Batches that fill no, exactly one and just over one batch
    bucket: identical (qidx, slots) to the numpy reference."""
    rng = np.random.default_rng(350 + b)
    ft, n_cells = _table(rng)
    seen = 0
    for _ in range(6):
        qk, alo, ahi, ts, te, now = _queries(rng, b, n_cells)
        got = ft.query_fused(qk, alo, ahi, ts, te, now=now)
        want = _host(ft, qk, alo, ahi, ts, te, now)
        assert _pairs(got) == _pairs(want)
        seen += len(want[0])
    assert seen > 0  # the draws hit something


def test_a_scalar_now_and_a_per_query_now_fold_alike():
    rng = np.random.default_rng(7)
    ft, n_cells = _table(rng)
    qk, alo, ahi, ts, te, _ = _queries(rng, 17, n_cells)
    for now in (NOW, -5, 2**32 + 1):
        one = ft.query_fused(qk, alo, ahi, ts, te, now=now)
        each = ft.query_fused(
            qk, alo, ahi, ts, te, now=np.full(17, now, np.int64))
        assert _pairs(one) == _pairs(each) == _pairs(
            _host(ft, qk, alo, ahi, ts, te, now))


# -- the buffer ---------------------------------------------------------------

F32_BITS = np.asarray(
    [np.inf, -np.inf, 0.0, -0.0, 1e-45, 3.4028235e38, 120.5, -7.25],
    np.float32,
)
I64_BITS = np.concatenate([
    INSTANTS,
    np.asarray([NO_TIME_LO, NO_TIME_HI, -(2**63), 2**63 - 1], np.int64),
])


def _packed_bounds(windows=256):
    b = len(I64_BITS)
    bb = fastpath.pow2_bucket(b, lo=16)
    packed = np.zeros(fastpath.packed_words(windows, bb), np.int32)
    packed[: 2 * windows] = np.arange(2 * windows, dtype=np.int32) - 9
    alo = np.resize(F32_BITS, b)
    ahi = np.resize(F32_BITS[::-1], b)
    t0, t1 = I64_BITS, I64_BITS[::-1].copy()
    fastpath.pack_bounds(packed, windows, alo, ahi, t0, t1)
    return packed, bb, (alo, ahi, t0, t1)


def test_the_buffer_round_trips_every_bit_on_the_host():
    packed, bb, (alo, ahi, t0, t1) = _packed_bounds()
    wins, q_alo, q_ahi, q_t0, q_t1 = fastpath.unpack_query(packed, 256)
    b = len(t0)
    assert wins.shape == (2, 256) and len(q_alo) == len(q_t1) == bb
    np.testing.assert_array_equal(
        wins.ravel(), np.arange(512, dtype=np.int32) - 9)
    # bit for bit: -0.0 and the infinities compare by their patterns
    for got, want in ((q_alo, alo), (q_ahi, ahi)):
        assert got.dtype == np.float32
        np.testing.assert_array_equal(
            got[:b].view(np.int32), want.view(np.int32))
        assert not got[b:].view(np.int32).any()  # pad queries: zero
    for got, want in ((q_t0, t0), (q_t1, t1)):
        assert got.dtype == np.int64
        np.testing.assert_array_equal(got[:b], want)
        assert not got[b:].any()


def test_the_device_unpacks_the_same_bits():
    """lax.bitcast_convert_type and the two-halves arithmetic give
    back exactly what the host wrote (x64 on, as the kernel runs)."""
    packed, bb, (alo, ahi, t0, t1) = _packed_bounds()
    dev = jax.jit(fastpath.unpack_query, static_argnums=1)(packed, 256)
    host = fastpath.unpack_query(packed, 256)
    for d, h in zip(dev, host):
        d = np.asarray(d)
        assert d.dtype == h.dtype and d.shape == h.shape
        np.testing.assert_array_equal(
            d.view(np.uint8), np.ascontiguousarray(h).view(np.uint8))


# -- the sizing rule ----------------------------------------------------------

BUCKETS = [256 << k for k in range(9)]  # 256 ... 65,536


@pytest.mark.parametrize("bucket", BUCKETS + [1 << 17, 1 << 18])
def test_max_words_is_the_hard_bound_up_to_16384_windows(bucket):
    mw = fastpath.max_words_for(bucket)
    if bucket <= 16384:
        assert mw == WORDS * bucket  # cannot overflow
    else:
        assert mw == max(1 << 16, bucket)  # as before, with the retry
    assert mw & (mw - 1) == 0  # a pow2: the executable set keeps its size
    assert resident.max_words_for(bucket) == mw
    assert resident.max_words_for is fastpath.max_words_for  # one rule


class _Launch(Exception):
    pass


@pytest.mark.parametrize("bucket", resident.window_bucket_grid())
def test_submit_and_the_aot_grid_size_alike(bucket, monkeypatch):
    """A submit whose windows land in a grid bucket asks the kernel
    selector for max_words_for(bucket): the key AotCache.compile
    built for that bucket."""
    ft = _dense(1, n_keys=64)  # one block, one window, a key
    asked = []

    class Selector:
        def lookup(self, table, window_bucket, batch_bucket, max_words):
            asked.append((window_bucket, batch_bucket, max_words))
            raise _Launch  # the shapes are what is under test

    # one window a (query, key): 3/4 of the bucket, in rows of 48 keys
    b = bucket * 3 // 4 // 48
    qk = np.tile(np.arange(48, dtype=np.int32), (b, 1))
    with pytest.raises(_Launch):
        ft.submit(
            qk, np.zeros(b, np.float32), np.ones(b, np.float32),
            np.zeros(b, np.int64), np.ones(b, np.int64), now=0,
            kernel=Selector(),
        )
    assert asked == [(
        bucket, fastpath.pow2_bucket(b, lo=16),
        resident.max_words_for(bucket),
    )]
    key = resident.AotCache.key_for(ft, *asked[0])
    assert key[3] == fastpath.max_words_for(bucket)


def _dense(n_blocks_a_key, n_keys=1):
    """Every lane of every window a hit: runs of whole blocks, all
    alive, unbounded query -> four non-empty words a window."""
    pk = np.repeat(np.arange(n_keys, dtype=np.int32), n_blocks_a_key * BLOCK)
    n = len(pk)
    return FastTable(
        pk, np.arange(n, dtype=np.int32), np.zeros(n, np.float32),
        np.ones(n, np.float32), np.zeros(n, np.int64),
        np.full(n, 2, np.int64), np.ones(n, bool),
        slot_exact=dict(alt_lo=np.zeros(n, np.float32),
                        alt_hi=np.ones(n, np.float32),
                        t0=np.zeros(n, np.int64),
                        t1=np.full(n, 2, np.int64), live=np.ones(n, bool)),
    )


def _unbounded(b, keys):
    return (
        np.tile(np.asarray(keys, np.int32), (b, 1)),
        np.full(b, -np.inf, np.float32), np.full(b, np.inf, np.float32),
        np.full(b, NO_TIME_LO, np.int64), np.full(b, NO_TIME_HI, np.int64),
    )


@pytest.mark.parametrize("b,blocks", [(1, 250), (16, 250), (4, 4000)])
def test_full_windows_cannot_overflow_an_auto_sized_result(b, blocks):
    """4 words from every window: the worst a bucket can hold.  One
    launch, no retry, every posting back."""
    ft = _dense(blocks)
    q = _unbounded(b, [0])
    pend = ft.submit(*q, now=1)
    bucket = fastpath.pow2_bucket(pend.nw)
    assert pend.nw == b * blocks and bucket <= 16384
    assert pend.max_words == WORDS * bucket
    assert int(np.asarray(pend.out)[0]) == WORDS * pend.nw  # all four
    qidx, slots = ft.collect(pend)
    assert pend.io[0] == 1  # one launch: it did not overflow
    assert len(slots) == b * blocks * BLOCK
    assert _pairs((qidx, slots)) == _pairs(_host(ft, *q, 1))


@pytest.mark.parametrize("path", ["jit", "aot"])
@pytest.mark.parametrize("max_words", [8, 16, 64])
def test_a_forced_overflow_retries_and_stays_exact(path, max_words):
    """An explicit max_words under the hit words: collect() launches
    again at the hard bound, through the same selector, and the answer
    is the reference's; the handle counts both launches and both
    results."""
    ft = _dense(3, n_keys=5)
    q = _unbounded(17, [0, 1, 2, 3, 4, -1])
    kern = None
    if path == "aot":
        kern = resident.ResidentKernel(
            resident.AotCache(), autocompile=False)
        for mw in (max_words, WORDS * 256):
            kern.cache.compile(ft, 256, 32, mw)
    pend = ft.submit(*q, now=1, max_words=max_words, kernel=kern)
    assert pend.nw == 17 * 15 and int(np.asarray(pend.out)[0]) > max_words
    got = ft.collect(pend)
    assert _pairs(got) == _pairs(_host(ft, *q, 1))
    launches, uploads, up, down = pend.io
    assert (launches, uploads) == (2, 2)
    assert up == 2 * 4 * fastpath.packed_words(256, 32)
    assert down == 4 * (1 + 2 * max_words) + 4 * (1 + 2 * WORDS * 256)
    if kern is not None:
        assert (kern.hits, kern.misses) == (2, 0)  # stayed resident


# -- what the benchmark reads -------------------------------------------------


def _lowered(jitted, ft):
    sds = jax.ShapeDtypeStruct
    nb = ft.n_blocks
    return jitted.lower(
        sds((nb, BLOCK), jnp.float32), sds((nb, BLOCK), jnp.float32),
        sds((nb, BLOCK), jnp.int64), sds((nb, BLOCK), jnp.int64),
        sds((fastpath.packed_words(256, 16),), jnp.int32),
        windows=256, max_words=1024,
    )


@pytest.mark.parametrize("which", ["shared_jit", "aot_twin"])
def test_the_kernels_module_keeps_its_name(which):
    """dssbench reads kernel_ms_per_launch, kernel_launches_per_request
    and fused_window_filter_roofline from the module
    `jit_fused_window_filter` on a capture's Modules line
    (dssbench/metrics/*.json, args.kernel): a rename or a wrapper
    under another jitted name silences all three."""
    ft = _dense(1)
    jitted = (FastTable._fused_xla if which == "shared_jit"
              else resident.AotCache()._donating_jit())
    text = _lowered(jitted, ft).as_text()
    assert "module @jit_fused_window_filter " in text.splitlines()[0]


def test_the_traffic_counters_reach_metrics(monkeypatch):
    """One device-routed search moves co_dev_launches / _uploads /
    _h2d_bytes / _d2h_bytes of its class (device_uploads_per_launch
    and device_d2h_kb_per_launch read them), a host scan moves none,
    and the families render on /metrics from the first scrape."""
    from dss_tpu.obs.metrics import MetricsRegistry
    from tests.test_shmring import T0, _depth_store, _ring_req, _served

    names = [f"dss_dar_op_co_dev_{k}" for k in
             ("launches", "uploads", "h2d_bytes", "d2h_bytes")]
    store = _depth_store("tpu")
    try:
        store.scd._op_index.table.fold()  # postings, not only overlay
        now = T0 + timedelta(minutes=5)
        st0 = store.stats()
        assert all(n in st0 for n in names)

        def moved():
            st = store.stats()
            return [st[n] - st0[n] for n in names]

        (ids, _), _ = _served(store, _ring_req("op", now))
        assert len(ids) == 7 and moved() == [0, 0, 0, 0]  # a host scan

        monkeypatch.setattr(FastTable, "HOST_MAX_CANDIDATES", 3)
        (ids, _), _ = _served(
            store, _ring_req("op", now + timedelta(seconds=1)))
        assert len(ids) == 7
        launches, uploads, up, down = moved()
        assert (launches, uploads) == (1, 1)
        # one bucket of 256 windows, one of 16 queries; the result at
        # the hard bound of that bucket
        assert up == 4 * fastpath.packed_words(256, 16)
        assert down == 4 * (1 + 2 * fastpath.max_words_for(256))

        reg = MetricsRegistry()
        for name, val in store.stats().items():
            if not isinstance(val, dict):
                reg.set_gauge(name, val)
        text = reg.render()
        for n in names:
            assert f"\n{n} " in text, n
    finally:
        store.close()
