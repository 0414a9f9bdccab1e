"""The planning mix on the coalescer: small, district and wide conflict
checks in one stream (dssbench cell scd-dense-urban-125k.query-mixed,
rehearsed at a small size with the host scan's cap lowered).

Every answer equals a brute-force numpy reference over the entities
whichever path served it, and the accounts by path (co_host_*,
co_device_members*, co_drains_mixed) equal a recount made from what
the table was asked and the reference's own candidate counts.

Deterministic on the CPU backend.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from dss_tpu import native
from dss_tpu.dar.coalesce import QueryCoalescer
from dss_tpu.dar.oracle import Record
from dss_tpu.dar.snapshot import DarTable
from dss_tpu.obs import trace
from dss_tpu.ops.fastpath import FastTable

NOW = 1_700_000_000_000_000_000
HOUR = 3_600_000_000_000
G = 24  # the metro: G x G cells, DAR key = i * G + j
CAP = 4096  # the host scan's cap for these tests (65,536 as shipped)
# (share, side range, candidate bounds): the cell's three populations.
# ~42 postings a cell: small <= 16 cells stays far under CAP, a
# district of 36-49 cells under it, a wide one of 144-196 far over
POPULATIONS = (
    ("small", 0.70, (1, 4)),
    ("district", 0.18, (6, 7)),
    ("wide", 0.12, (12, 14)),
)


class World:
    """Seeded entities over the metro, and the plain reference: one
    boolean membership row per entity, nothing of the program's."""

    def __init__(self, seed: int, n: int = 6000):
        rng = np.random.default_rng(seed)
        self.member = np.zeros((n, G * G), bool)
        self.alt_lo = rng.uniform(0, 2800, n).astype(np.float32)
        self.alt_hi = (self.alt_lo + rng.uniform(20, 120, n)).astype(
            np.float32)
        self.t0 = NOW + rng.integers(-4, 4, n) * HOUR
        self.t1 = self.t0 + rng.integers(1, 6, n) * HOUR
        self.records = []
        for e in range(n):
            i, j = rng.integers(0, G - 2, 2)
            w, h = rng.integers(1, 4, 2)
            keys = np.asarray(
                [(i + a) * G + (j + b) for a in range(w) for b in range(h)],
                np.int32)
            self.member[e, keys] = True
            self.records.append(Record(
                entity_id=f"e{e:05d}", keys=np.sort(keys),
                alt_lo=float(self.alt_lo[e]), alt_hi=float(self.alt_hi[e]),
                t_start=int(self.t0[e]), t_end=int(self.t1[e]),
                owner_id=e % 7))
        self.per_key = self.member.sum(axis=0)

    def candidates(self, keys) -> int:
        return int(self.per_key[np.unique(keys)].sum())

    def hits(self, q) -> np.ndarray:
        """Entity numbers the query's volume meets, live at `now`."""
        keys, alo, ahi, ts, te = q
        ok = self.member[:, np.unique(keys)].any(axis=1)
        ok &= (self.alt_hi >= alo) & (self.alt_lo <= ahi)
        ok &= (self.t1 >= max(ts, NOW)) & (self.t0 <= te)
        return np.flatnonzero(ok)

    def search(self, q) -> list:
        return [f"e{e:05d}" for e in self.hits(q)]

    def stream(self, seed: int, n: int) -> list:
        """n queries dealt in the populations' shares: (name, (keys,
        alt_lo, alt_hi, t_start, t_end))."""
        rng = np.random.default_rng([seed, 1])
        names = np.repeat(
            [p[0] for p in POPULATIONS],
            [int(round(p[1] * n)) for p in POPULATIONS])[:n]
        out = []
        for name in rng.permutation(names):
            lo, hi = next(p[2] for p in POPULATIONS if p[0] == name)
            w, h = rng.integers(lo, hi + 1, 2)
            i, j = rng.integers(0, G - w + 1), rng.integers(0, G - h + 1)
            keys = np.asarray(
                [(i + a) * G + (j + b) for a in range(w) for b in range(h)],
                np.int32)
            alo = float(rng.uniform(0, 2800))
            ts = NOW + int(rng.integers(1, 3)) * HOUR
            out.append((str(name), (
                keys, alo, alo + (10.0 if name == "wide" else 60.0),
                ts, ts + HOUR)))
        return out


@pytest.fixture(scope="module")
def world():
    w = World(29)
    for name, _, _ in POPULATIONS:  # the populations sit where they should
        cands = [w.candidates(q[0]) for n, q in w.stream(3, 200) if n == name]
        if name == "wide":
            assert min(cands) > 1.25 * CAP
        else:
            assert max(cands) <= CAP
    return w


@pytest.fixture
def table(world, monkeypatch):
    monkeypatch.setattr(FastTable, "HOST_MAX_CANDIDATES", CAP)
    t = DarTable(delta_capacity=256)
    t.bulk_load(world.records)
    yield t
    t.close()


class _Spy:
    """Records what the table was asked, batch by batch, and whether
    the batch touched the device: the recount's input."""

    def __init__(self, table):
        self.batches = []
        self._lock = threading.Lock()
        real = table.query_many_submit

        def submit(keys, *a, **kw):
            pq = real(keys, *a, **kw)
            with self._lock:
                self.batches.append(
                    ([np.asarray(k) for k in keys], pq.used_device()))
            return pq

        table.query_many_submit = submit

    def recount(self, world) -> dict:
        out = dict.fromkeys((
            "co_host_scans", "co_host_scan_candidates_total",
            "co_host_members", "co_device_members",
            "co_device_members_under_cap", "co_drains_mixed"), 0)
        for keys, on_device in self.batches:
            cands = [world.candidates(k) for k in keys]
            if on_device:
                under = sum(c <= CAP for c in cands)
                out["co_device_members"] += len(keys)
                out["co_device_members_under_cap"] += under
                out["co_drains_mixed"] += 0 < under < len(keys)
            else:
                out["co_host_scans"] += 1
                out["co_host_scan_candidates_total"] += sum(cands)
                out["co_host_members"] += len(keys)
        return out


def _ask(co, q):
    keys, alo, ahi, ts, te = q
    return co.query(keys, alo, ahi, ts, te, now=NOW)


def _hold(co):
    """As an inline caller does while it executes: arrivals queue."""
    with co._cond:
        co._busy = True


def _release(co):
    with co._cond:
        co._busy = False
        co._ensure_threads()
        co._cond.notify_all()


def _settled(co) -> dict:
    """The stats once no drain is in flight: a drain's accounts are
    written after its answers are out, so the callers return first."""
    t_end = time.monotonic() + 30
    while True:
        st = co.stats()
        if st["co_inflight"] == 0 and st["co_queue_depth"] == 0:
            return st
        assert time.monotonic() < t_end, "a drain never finished"
        time.sleep(0.001)


def _one_drain(co, queries) -> list:
    """Offer `queries` from a thread each while the coalescer is held,
    release it once all are queued: they are planned as ONE drain."""
    _hold(co)
    with ThreadPoolExecutor(max_workers=len(queries)) as pool:
        futs = [pool.submit(_ask, co, q) for q in queries]
        t_end = time.monotonic() + 30
        while co.stats()["co_queue_depth"] < len(queries):
            assert time.monotonic() < t_end, "callers never queued"
            time.sleep(0.001)
        _release(co)
        return [f.result(timeout=120) for f in futs]


def _pick(world, name, n, seed=5):
    return [q for p, q in world.stream(seed, 400) if p == name][:n]


def test_a_mixed_drain_rides_the_device_and_is_counted_by_member(
        world, table):
    """Five small checks drained with one wide one: all six ride the
    fused kernel, the five are the under-cap members the device
    served, the drain is a mixed one; the same five drained alone are
    one host scan of their summed candidates."""
    spy = _Spy(table)
    co = QueryCoalescer(table, inline=True)
    try:
        small = _pick(world, "small", 5)
        wide = _pick(world, "wide", 1)
        got = _one_drain(co, small + wide)
        assert got == [world.search(q) for q in small + wide]
        st = _settled(co)
        assert (st["co_batches"], st["co_items"], st["co_inline"]) == (
            1, 6, 0)
        assert st["co_device_members"] == 6
        assert st["co_device_members_under_cap"] == 5
        assert st["co_drains_mixed"] == 1
        assert st["co_host_members"] == 0 and st["co_host_scans"] == 0

        got = _one_drain(co, small)
        assert got == [world.search(q) for q in small]
        st = _settled(co)
        assert st["co_host_scans"] == 1 and st["co_host_members"] == 5
        assert st["co_host_scan_candidates_total"] == sum(
            world.candidates(q[0]) for q in small)
        assert st["co_host_scan_ms_total"] > 0
        assert st["co_drains_mixed"] == 1  # under the cap together

        # alone, inline: a district check is a host scan of its own
        # candidates, a wide one a device member over the cap
        district = _pick(world, "district", 1)[0]
        assert _ask(co, district) == world.search(district)
        assert _ask(co, wide[0]) == world.search(wide[0])
        st = co.stats()
        assert st["co_inline"] == 2 and st["co_inline_device"] == 1
        assert st["co_host_scans"] == 2 and st["co_host_members"] == 6
        assert st["co_device_members"] == 7
        assert st["co_device_members_under_cap"] == 5
        for k, v in spy.recount(world).items():
            assert st[k] == v, k
    finally:
        co.close()


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_a_mixed_stream_from_several_threads_answers_and_accounts(
        world, table, seed):
    """The stream offered from several threads, so that drains form as
    they will: every answer is the reference's whichever path served
    it, and the accounts by path equal the recount."""
    spy = _Spy(table)
    co = QueryCoalescer(table, inline=True)
    stream = [q for _, q in world.stream(seed, 240)]
    try:
        with ThreadPoolExecutor(max_workers=8) as pool:
            got = list(pool.map(lambda q: _ask(co, q), stream))
        for q, g in zip(stream, got):
            assert g == world.search(q)
        st = _settled(co)
    finally:
        co.close()
    assert st["co_inline"] + st["co_items"] == len(stream)
    assert st["co_host_members"] + st["co_device_members"] == len(stream)
    assert st["co_batches"] >= 1, "no drain formed"
    want = spy.recount(world)
    for k, v in want.items():
        assert st[k] == v, (k, st[k], v)
    assert st["co_device_members"] >= sum(
        world.candidates(q[0]) > CAP for q in stream)
    assert st["co_host_scan_ms_total"] > 0


# -- one seeded mixed batch, three ways to answer it -------------------------


def _fast_table(world):
    from dss_tpu.dar.pack import pack_records

    packed = pack_records(world.records, pad_postings=False)
    pe = packed.post_ent
    return FastTable(
        packed.post_key, pe, packed.alt_lo[pe], packed.alt_hi[pe],
        packed.t_start[pe], packed.t_end[pe], packed.active[pe],
        slot_exact={
            "alt_lo": packed.alt_lo, "alt_hi": packed.alt_hi,
            "t0": packed.t_start, "t1": packed.t_end,
            "live": packed.active.copy(),
        },
    )


def _scan_native(ft, args, now_arr):
    if not native.ensure_built():
        pytest.skip("native lib unavailable")
    return ft.query_host_auto(*args, now=now_arr, max_candidates=1 << 24)


def _scan_numpy(ft, args, now_arr):
    ranges = ft.host_candidates(args[0], max_candidates=1 << 24)
    return ft.query_host(*args, now=now_arr, ranges=ranges)


def _kernel(ft, args, now_arr):
    return ft.query_fused(*args, now=now_arr)


@pytest.mark.parametrize("path", [_scan_native, _scan_numpy, _kernel])
def test_native_scan_numpy_scan_and_fused_kernel_agree_on_a_mixed_batch(
        world, path):
    """tests/test_native_hostquery.py's differential, on the mix: the
    same seeded batch of small, district and wide rows through the
    native scan, the numpy scan and the fused kernel, each against
    the brute-force reference."""
    ft = _fast_table(world)
    batch = [q for _, q in world.stream(11, 24)]
    assert {n for n, _ in world.stream(11, 24)} == {
        "small", "district", "wide"}
    qkeys = np.full((len(batch), 256), -1, np.int32)
    for r, q in enumerate(batch):
        qkeys[r, : len(q[0])] = np.sort(q[0])
    args = (
        qkeys,
        np.asarray([q[1] for q in batch], np.float32),
        np.asarray([q[2] for q in batch], np.float32),
        np.asarray([q[3] for q in batch], np.int64),
        np.asarray([q[4] for q in batch], np.int64),
    )
    assert ft.candidates(qkeys).tolist() == [
        world.candidates(q[0]) for q in batch]
    qidx, slots = path(ft, args, np.full(len(batch), NOW, np.int64))
    got = sorted(set(zip(qidx.tolist(), slots.tolist())))
    want = sorted(
        (r, int(e)) for r, q in enumerate(batch) for e in world.hits(q))
    assert got == want


# -- the span ----------------------------------------------------------------


def test_the_host_scan_is_annotated_on_the_thread_that_runs_it(
        world, table, monkeypatch):
    """Under a capture the table's host attempt is `dss.host.scan`,
    inside the inline caller's `dss.device.dispatch` and on its
    thread; without one it is the shared no-op."""
    seen = []

    class _Ann:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("in", self.name, threading.get_ident()))

        def __exit__(self, *exc):
            seen.append(("out", self.name, threading.get_ident()))

    co = QueryCoalescer(table, inline=True)
    small = _pick(world, "small", 1)[0]
    try:
        before = trace.stats()["dss_trace_annotations_total"]
        assert _ask(co, small) == world.search(small)
        assert trace.stats()["dss_trace_annotations_total"] == before
        monkeypatch.setattr(trace, "_ANNOTATION", _Ann)
        trace.set_capture(True)
        try:
            assert _ask(co, small) == world.search(small)
        finally:
            trace.set_capture(False)
    finally:
        co.close()
    me = threading.get_ident()
    order = [(io, name) for io, name, tid in seen if tid == me]
    at = order.index(("in", "dss.host.scan"))
    assert order[at - 1] == ("in", "dss.device.dispatch")
    assert order[at + 1: at + 3] == [
        ("out", "dss.host.scan"), ("out", "dss.device.dispatch")]
    assert ("in", "dss.collect") in order[at:]
    assert co.stats()["co_host_scans"] == 2
