"""The fast path's two exact implementations vs the oracle (CPU): the
fused device kernel (submit / collect, XLA) and the host scan
(query_host_auto: the native kernel, or numpy without the library).
"""

import numpy as np
import pytest

from dss_tpu.dar import oracle
from dss_tpu.dar.oracle import Record
from dss_tpu.ops.conflict import NO_TIME_HI, NO_TIME_LO
from dss_tpu.ops.fastpath import BLOCK, FastTable

NOW = 1_700_000_000_000_000_000
HOUR = 3_600_000_000_000
PATHS = ("fused", "host")


def _answer(ft, path, qkeys, alo, ahi, ts, te, now=NOW):
    """(qidx, slots) by the served path's two executions of a query."""
    if path == "fused":
        return ft.query_fused(qkeys, alo, ahi, ts, te, now=now)
    res = ft.query_host_auto(qkeys, alo, ahi, ts, te, now=now)
    assert res is not None, "the batch is under both host gates"
    return res


def _pack(recs, live_slots=None):
    """Records -> FastTable over their sorted postings (slot = index)."""
    pk, pe = [], []
    for slot, r in enumerate(recs):
        pk.extend(int(k) for k in r.keys)
        pe.extend([slot] * len(r.keys))
    pk = np.asarray(pk, np.int32)
    pe = np.asarray(pe, np.int32)
    order = np.argsort(pk, kind="stable")
    pk, pe = pk[order], pe[order]
    live = np.ones(len(recs), bool) if live_slots is None else live_slots
    return FastTable(
        pk,
        pe,
        np.asarray([recs[s].alt_lo for s in pe], np.float32),
        np.asarray([recs[s].alt_hi for s in pe], np.float32),
        np.asarray([recs[s].t_start for s in pe], np.int64),
        np.asarray([recs[s].t_end for s in pe], np.int64),
        live[pe],
        slot_exact=dict(
            alt_lo=np.asarray([r.alt_lo for r in recs], np.float32),
            alt_hi=np.asarray([r.alt_hi for r in recs], np.float32),
            t0=np.asarray([r.t_start for r in recs], np.int64),
            t1=np.asarray([r.t_end for r in recs], np.int64),
            live=live.copy(),
        ),
    )


def _mk_table(rng, n, key_space=400):
    recs = []
    for i in range(n):
        nk = int(rng.integers(1, 10))
        keys = np.unique(rng.integers(0, key_space, nk).astype(np.int32))
        alo, ahi = sorted(rng.uniform(0, 3000, 2))
        t0 = NOW + int(rng.integers(-5, 5)) * HOUR
        t1 = t0 + int(rng.integers(1, 8)) * HOUR
        recs.append(
            Record(
                entity_id=f"e{i}",
                keys=keys,
                alt_lo=float(alo),
                alt_hi=float(ahi),
                t_start=t0,
                t_end=t1,
                owner_id=int(rng.integers(0, 5)),
            )
        )
    return recs, _pack(recs)


@pytest.mark.parametrize("path", PATHS)
def test_fastpath_matches_oracle(path):
    rng = np.random.default_rng(42)
    recs, ft = _mk_table(rng, 250)
    B, W = 8, 16
    qkeys = np.full((B, W), -1, np.int32)
    alo = np.full(B, -np.inf, np.float32)
    ahi = np.full(B, np.inf, np.float32)
    ts = np.full(B, NO_TIME_LO, np.int64)
    te = np.full(B, NO_TIME_HI, np.int64)
    for i in range(B):
        nk = int(rng.integers(1, W))
        u = np.unique(rng.integers(0, 400, nk).astype(np.int32))
        qkeys[i, : len(u)] = u
        if i % 2:
            a, b = sorted(rng.uniform(0, 3000, 2))
            alo[i], ahi[i] = a, b
        if i % 3:
            ts[i] = NOW - 2 * HOUR
            te[i] = NOW + 2 * HOUR

    qidx, slots = _answer(ft, path, qkeys, alo, ahi, ts, te)
    recs_map = dict(enumerate(recs))
    for i in range(B):
        want = sorted(
            oracle.search(
                recs_map,
                qkeys[i][qkeys[i] >= 0],
                None if alo[i] == -np.inf else float(alo[i]),
                None if ahi[i] == np.inf else float(ahi[i]),
                None if ts[i] == NO_TIME_LO else int(ts[i]),
                None if te[i] == NO_TIME_HI else int(te[i]),
                NOW,
            )
        )
        got = sorted(set(slots[qidx == i].tolist()))
        assert got == want, f"query {i} ({path})"


@pytest.mark.parametrize("max_words", [1 << 14, 64, 8])
def test_fused_path_matches_oracle(max_words):
    """The fused on-device decode path (submit/collect) must produce
    exactly the oracle result sets, including when the compaction
    buffer overflows (max_words small -> the kernel again at the hard
    bound)."""
    rng = np.random.default_rng(43)
    recs, ft = _mk_table(rng, 250)
    B, W = 8, 16
    qkeys = np.full((B, W), -1, np.int32)
    alo = np.full(B, -np.inf, np.float32)
    ahi = np.full(B, np.inf, np.float32)
    ts = np.full(B, NO_TIME_LO, np.int64)
    te = np.full(B, NO_TIME_HI, np.int64)
    for i in range(B):
        nk = int(rng.integers(1, W))
        u = np.unique(rng.integers(0, 400, nk).astype(np.int32))
        qkeys[i, : len(u)] = u
        if i % 2:
            a, b = sorted(rng.uniform(0, 3000, 2))
            alo[i], ahi[i] = a, b
        if i % 3:
            ts[i] = NOW - 2 * HOUR
            te[i] = NOW + 2 * HOUR

    qidx, slots = ft.query_fused(
        qkeys, alo, ahi, ts, te, now=NOW, max_words=max_words
    )
    recs_map = dict(enumerate(recs))
    for i in range(B):
        want = sorted(
            oracle.search(
                recs_map,
                qkeys[i][qkeys[i] >= 0],
                None if alo[i] == -np.inf else float(alo[i]),
                None if ahi[i] == np.inf else float(ahi[i]),
                None if ts[i] == NO_TIME_LO else int(ts[i]),
                None if te[i] == NO_TIME_HI else int(te[i]),
                NOW,
            )
        )
        got = sorted(set(slots[qidx == i].tolist()))
        assert got == want, f"query {i} (max_words={max_words})"


def test_fused_pipelined_submit_collect():
    """Many batches in flight at once resolve to the same results as
    one-at-a-time execution."""
    rng = np.random.default_rng(44)
    recs, ft = _mk_table(rng, 300)
    batches = []
    for b in range(6):
        B, W = 4, 16
        qkeys = np.full((B, W), -1, np.int32)
        for i in range(B):
            u = np.unique(rng.integers(0, 400, 8).astype(np.int32))
            qkeys[i, : len(u)] = u
        alo = np.full(B, -np.inf, np.float32)
        ahi = np.full(B, np.inf, np.float32)
        ts = np.full(B, NOW - HOUR, np.int64)
        te = np.full(B, NOW + HOUR, np.int64)
        batches.append((qkeys, alo, ahi, ts, te))

    serial = [
        ft.query_fused(*b, now=NOW) for b in batches
    ]
    pendings = [ft.submit(*b, now=NOW) for b in batches]
    for (sq, ss), p in zip(serial, pendings):
        pq, ps = ft.collect(p)
        assert sorted(zip(sq.tolist(), ss.tolist())) == sorted(
            zip(pq.tolist(), ps.tolist())
        )


@pytest.mark.parametrize("path", PATHS)
def test_fastpath_hot_cell_long_run(path):
    """A cell with a postings run spanning many 128-blocks must return
    every entity (regression: the old fixed 2-block window dropped the
    tail of runs longer than ~256)."""
    n = 500  # run of 500 postings on one cell -> 5 blocks
    pk = np.full(n + 10, 7, np.int32)
    pk[n:] = 9  # a few postings on another cell after the run
    pe = np.arange(n + 10, dtype=np.int32)
    pe[n:] = np.arange(10)
    ft = FastTable(
        pk, pe,
        np.zeros(n + 10, np.float32),
        np.full(n + 10, 100.0, np.float32),
        np.full(n + 10, NOW - HOUR, np.int64),
        np.full(n + 10, NOW + HOUR, np.int64),
        np.ones(n + 10, bool),
        slot_exact=dict(
            alt_lo=np.zeros(n, np.float32),
            alt_hi=np.full(n, 100.0, np.float32),
            t0=np.full(n, NOW - HOUR, np.int64),
            t1=np.full(n, NOW + HOUR, np.int64),
            live=np.ones(n, bool),
        ),
    )
    qkeys = np.full((1, 16), -1, np.int32)
    qkeys[0, 0] = 7
    _, slots = _answer(
        ft, path, qkeys,
        np.full(1, -np.inf, np.float32),
        np.full(1, np.inf, np.float32),
        np.full(1, NO_TIME_LO, np.int64),
        np.full(1, NO_TIME_HI, np.int64),
    )
    slots = np.unique(slots)
    assert len(slots) == n, f"lost {n - len(slots)} of {n} entities"


@pytest.mark.parametrize("path", PATHS)
def test_fastpath_tombstones_and_subsecond_edges(path):
    """Both paths compare exact nanoseconds: an entity that ends 1 ns
    before the window or starts 1 ns after it is out, one that touches
    either end is in (a path that rounded to seconds would have to
    keep all four and re-check), and a tombstone never appears."""
    rng = np.random.default_rng(1)
    recs, _ = _mk_table(rng, 20)
    t_q = NOW + HOUR

    def edge(name, t0, t1):
        return Record(
            entity_id=name, keys=np.asarray([7], np.int32),
            alt_lo=0.0, alt_hi=100.0, t_start=t0, t_end=t1, owner_id=0,
        )

    recs[0] = edge("ends-1ns-early", NOW - HOUR, t_q - 1)
    recs[1] = edge("ends-at-the-start", NOW - HOUR, t_q)
    recs[2] = edge("starts-at-the-end", t_q + HOUR, t_q + 2 * HOUR)
    recs[4] = edge("starts-1ns-late", t_q + HOUR + 1, t_q + 2 * HOUR)
    recs[3] = edge("tombstoned", NOW - HOUR, t_q + HOUR)
    live = np.ones(len(recs), bool)
    live[3] = False
    ft = _pack(recs, live)
    qkeys = np.full((1, 16), -1, np.int32)
    qkeys[0, 0] = 7
    alo = np.full(1, -np.inf, np.float32)
    ahi = np.full(1, np.inf, np.float32)
    ts = np.asarray([t_q], np.int64)
    te = np.asarray([t_q + HOUR], np.int64)
    _, slots = _answer(ft, path, qkeys, alo, ahi, ts, te)
    got = sorted(set(slots.tolist()))
    assert {1, 2} <= set(got) and not {0, 3, 4} & set(got)
    del recs[3]  # the oracle knows no tombstones; slots above 3 shift
    want = oracle.search(
        dict(enumerate(recs)), np.asarray([7], np.int32), None, None,
        int(ts[0]), int(te[0]), NOW,
    )
    assert [g - (g > 3) for g in got] == sorted(want)


def test_device_holds_the_exact_columns_only():
    """24 B a padded posting on the device (two f32 altitudes, two i64
    instants), and no table without the per-slot exact columns."""
    recs, ft = _mk_table(np.random.default_rng(5), 40)
    assert ft.device_bytes() == ft.n_blocks * BLOCK * 24
    assert ft.n_blocks * BLOCK >= ft.n_postings
    z = np.zeros(1, np.float32)
    with pytest.raises(TypeError):
        FastTable(
            np.zeros(1, np.int32), np.zeros(1, np.int32), z, z,
            np.zeros(1, np.int64), np.ones(1, np.int64), np.ones(1, bool),
        )


def test_query_host_matches_fused():
    """The host small-batch path must return exactly the fused device
    path's (qidx, slot) set — same data, same semantics — including
    per-posting build tombstones and per-slot mark_dead."""
    rng = np.random.default_rng(3)
    n_ent, n_cells, kpe = 3000, 400, 6
    pk = rng.integers(0, n_cells, n_ent * kpe).astype(np.int32)
    pe = np.repeat(np.arange(n_ent, dtype=np.int32), kpe)
    order = np.argsort(pk, kind="stable")
    pk, pe = pk[order], pe[order]
    alt_lo = rng.uniform(0, 1000, n_ent).astype(np.float32)
    alt_hi = alt_lo + rng.uniform(5, 200, n_ent).astype(np.float32)
    t0 = rng.integers(0, 10**6, n_ent).astype(np.int64)
    t1 = t0 + rng.integers(1, 10**6, n_ent).astype(np.int64)
    live_post = rng.random(len(pe)) > 0.05  # some build tombstones
    ft = FastTable(
        pk, pe, alt_lo[pe], alt_hi[pe], t0[pe], t1[pe], live_post,
        slot_exact=dict(
            alt_lo=alt_lo, alt_hi=alt_hi, t0=t0, t1=t1,
            live=np.ones(n_ent, bool),
        ),
    )
    for s in rng.integers(0, n_ent, 50):
        ft.mark_dead(int(s))  # some post-build tombstones

    for trial in range(8):
        b = int(rng.integers(1, 8))
        qkeys = np.full((b, 8), -1, np.int32)
        for i in range(b):
            w = int(rng.integers(1, 8))
            qkeys[i, :w] = rng.integers(0, n_cells, w)
        alo = rng.uniform(0, 1000, b).astype(np.float32)
        ahi = (alo + 150).astype(np.float32)
        ts = rng.integers(0, 10**6, b).astype(np.int64)
        te = ts + rng.integers(1, 10**6, b).astype(np.int64)
        now = int(rng.integers(0, 10**6))

        ranges = ft.host_candidates(qkeys)
        assert ranges is not None
        hq, hs = ft.query_host(
            qkeys, alo, ahi, ts, te, now=now, ranges=ranges
        )
        fq, fs = ft.query_fused(qkeys, alo, ahi, ts, te, now=now)
        host_set = set(zip(hq.tolist(), hs.tolist()))
        fused_set = set(zip(fq.tolist(), fs.tolist()))
        assert host_set == fused_set, (
            trial, len(host_set ^ fused_set)
        )
