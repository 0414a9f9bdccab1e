"""Pallas fused-path parity: the hand-tiled kernel must produce
EXACTLY the fused XLA path's pre-compaction hit words and, decoded,
exactly the serving results (SURVEY §2 "[TPU kernel target]"; lowering
delta documented in docs/DESIGN.md).  Tier-1 runs the kernels in
interpret mode on the CPU; the `*_on_tpu` tests compile them with
Mosaic (interpret=False) and run only under DSS_TEST_TPU=1 on a chip.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from dss_tpu.dar import oracle
from dss_tpu.dar.oracle import Record
from dss_tpu.ops.conflict import NO_TIME_HI, NO_TIME_LO
from dss_tpu.dar.pack import pack_records
from dss_tpu.ops import fastpath
from dss_tpu.ops.fastpath import FastTable
from dss_tpu.ops.fastpath_pallas import fused_filter_pack_pallas

HOUR = 3_600_000_000_000
NOW = 1_700_000_000_000_000_000


def _mk_table(rng, n, n_cells=400, hot_cell=None):
    recs = []
    for i in range(n):
        k = np.unique(rng.integers(0, n_cells, rng.integers(1, 6)))
        if hot_cell is not None and i % 3 == 0:
            k = np.unique(np.append(k, hot_cell))
        alo = float(rng.uniform(0, 3000))
        t0 = NOW + int(rng.integers(-4, 4)) * HOUR
        recs.append(
            Record(
                entity_id=f"e{i}",
                keys=k.astype(np.int32),
                alt_lo=alo if i % 4 else -np.inf,
                alt_hi=alo + 400.0 if i % 4 else np.inf,
                t_start=t0 if i % 5 else NO_TIME_LO,
                t_end=t0 + 2 * HOUR if i % 5 else NO_TIME_HI,
                owner_id=i % 7,
            )
        )
    packed = pack_records(recs, pad_postings=False)
    pe = packed.post_ent
    ft = FastTable(
        packed.post_key, pe,
        packed.alt_lo[pe], packed.alt_hi[pe],
        packed.t_start[pe], packed.t_end[pe],
        packed.active[pe],
        slot_exact={
            "alt_lo": packed.alt_lo, "alt_hi": packed.alt_hi,
            "t0": packed.t_start, "t1": packed.t_end,
            "live": packed.active.copy(),
        },
    )
    return recs, ft


def _mk_queries(rng, b, w, n_cells=400):
    qkeys = np.full((b, w), -1, np.int32)
    alo = np.full(b, -np.inf, np.float32)
    ahi = np.full(b, np.inf, np.float32)
    ts = np.full(b, NO_TIME_LO, np.int64)
    te = np.full(b, NO_TIME_HI, np.int64)
    for i in range(b):
        u = np.unique(
            rng.integers(0, n_cells, rng.integers(1, w)).astype(np.int32)
        )
        qkeys[i, : len(u)] = u
        if i % 2:
            a, bb = sorted(rng.uniform(0, 3400, 2))
            alo[i], ahi[i] = a, bb
        if i % 3:
            ts[i] = NOW - 2 * HOUR
            te[i] = NOW + 2 * HOUR
    return qkeys, alo, ahi, ts, te


def _needs_tpu():
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("needs a TPU backend (DSS_TEST_TPU=1 on a chip)")


def _pallas_words(ft, qkeys, alo, ahi, ts, te, interpret=True):
    """Run the pallas fused twin on the same windows _fused_xla sees."""
    wins, _, _, nw = ft._pack_windows(qkeys)
    if nw == 0:
        # no candidate windows at all: both paths produce zero words
        return np.zeros((0, FastTable.WORDS), np.int32), np.zeros(
            (2, 0), np.int32
        )
    wins = np.asarray(wins)
    b = qkeys.shape[0]
    t0_eff = np.maximum(ts, np.int64(NOW))
    win_blk = wins[0]
    meta = wins[1]
    win_q = meta >> 16
    # pad NW to GROUP; padded windows use block 0 with empty lane range
    from dss_tpu.ops.fastpath_pallas import GROUP

    pad = (-len(win_blk)) % GROUP
    if pad:
        win_blk = np.concatenate([win_blk, np.zeros(pad, np.int32)])
        meta = np.concatenate([meta, np.zeros(pad, np.int32)])
        win_q = np.concatenate([win_q, np.zeros(pad, np.int32)])
    words = fused_filter_pack_pallas(
        ft.b_alo, ft.b_ahi, ft.b_t0, ft.b_t1,
        jnp.asarray(win_blk, jnp.int32),
        jnp.asarray(meta & 0xFFFF, jnp.int32),
        jnp.asarray(alo[win_q], jnp.float32),
        jnp.asarray(ahi[win_q], jnp.float32),
        jnp.asarray(t0_eff[win_q], jnp.int64),
        jnp.asarray(te[win_q], jnp.int64),
        interpret=interpret,
    )
    return np.asarray(words)[: nw if pad == 0 else len(win_blk) - pad], wins


def _xla_words(ft, qkeys, alo, ahi, ts, te):
    """Reconstruct the fused XLA path's full word array from its
    compacted output."""
    wins, _, _, nw = ft._pack_windows(qkeys)
    if nw == 0:
        return np.zeros((0, FastTable.WORDS), np.int32)
    t0_eff = np.maximum(ts, np.int64(NOW))
    mw = fastpath.pow2_bucket(nw * FastTable.WORDS, lo=1 << 10)
    out = np.asarray(
        ft._fused_xla(
            ft.b_alo, ft.b_ahi, ft.b_t0, ft.b_t1,
            jnp.asarray(np.asarray(wins)),
            jnp.asarray(alo, jnp.float32),
            jnp.asarray(ahi, jnp.float32),
            jnp.asarray(t0_eff, jnp.int64),
            jnp.asarray(te, jnp.int64),
            max_words=mw,
        )
    )
    count = int(out[0])
    assert count <= mw, "test must size max_words above overflow"
    pos = out[1 : 1 + count]
    bits = out[1 + mw : 1 + mw + count]
    words = np.zeros((nw, FastTable.WORDS), np.int32)
    words[pos // FastTable.WORDS, pos % FastTable.WORDS] = bits
    return words


@pytest.mark.parametrize("seed,n", [(1, 120), (2, 300), (3, 60)])
def test_pallas_words_match_fused_xla(seed, n):
    rng = np.random.default_rng(seed)
    recs, ft = _mk_table(rng, n, hot_cell=7 if seed == 2 else None)
    qkeys, alo, ahi, ts, te = _mk_queries(rng, b=8, w=16)
    pw, _ = _pallas_words(ft, qkeys, alo, ahi, ts, te)
    xw = _xla_words(ft, qkeys, alo, ahi, ts, te)
    np.testing.assert_array_equal(pw[: len(xw)], xw)


def test_pallas_decode_matches_serving_results():
    """End to end: pallas words -> the serving decode -> exactly the
    query_fused result sets (and the oracle's)."""
    rng = np.random.default_rng(11)
    recs, ft = _mk_table(rng, 200)
    qkeys, alo, ahi, ts, te = _mk_queries(rng, b=6, w=16)
    qidx_f, slots_f = ft.query_fused(qkeys, alo, ahi, ts, te, now=NOW)
    want = [
        sorted(set(slots_f[qidx_f == i].tolist()))
        for i in range(len(qkeys))
    ]

    pw, wins = _pallas_words(ft, qkeys, alo, ahi, ts, te)
    win_q = np.asarray(wins)[1] >> 16
    win_blk = np.asarray(wins)[0]
    got = [set() for _ in range(len(qkeys))]
    for w in range(len(pw)):
        for word in range(FastTable.WORDS):
            bits = int(np.uint32(pw[w, word]))
            lane0 = word * 32
            while bits:
                b = bits & -bits
                lane = lane0 + b.bit_length() - 1
                slot = int(ft.host_ent[win_blk[w] * 128 + lane])
                got[win_q[w]].add(slot)
                bits ^= b
    got = [sorted(s) for s in got]
    assert got == want

    # and both equal the oracle
    recs_map = dict(enumerate(recs))
    for i in range(len(qkeys)):
        w = sorted(
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
        assert got[i] == w, i


def test_pallas_empty_and_padded_windows():
    rng = np.random.default_rng(5)
    recs, ft = _mk_table(rng, 40)
    # one query with no candidate postings at all (cells the table
    # never uses), one that may match
    qkeys = np.full((2, 16), -1, np.int32)
    qkeys[0, 0] = 9999  # no candidate postings at all
    qkeys[1, 0] = int(recs[0].keys[0])  # definitely has postings
    alo = np.full(2, -np.inf, np.float32)
    ahi = np.full(2, np.inf, np.float32)
    ts = np.full(2, NO_TIME_LO, np.int64)
    te = np.full(2, NO_TIME_HI, np.int64)
    pw, _ = _pallas_words(ft, qkeys, alo, ahi, ts, te)
    xw = _xla_words(ft, qkeys, alo, ahi, ts, te)
    np.testing.assert_array_equal(pw[: len(xw)], xw)


def test_pallas_no_windows_at_all():
    rng = np.random.default_rng(6)
    recs, ft = _mk_table(rng, 20)
    qkeys = np.full((1, 16), -1, np.int32)
    qkeys[0, 0] = 9999  # outside every posting run
    pw, _ = _pallas_words(
        ft, qkeys,
        np.full(1, -np.inf, np.float32), np.full(1, np.inf, np.float32),
        np.full(1, NO_TIME_LO, np.int64), np.full(1, NO_TIME_HI, np.int64),
    )
    assert pw.shape[0] == 0


def test_gridless_twin_interpret_parity():
    """filter_windows_gridless (the compiled-mode twin) matches the
    legacy DMA kernel's mask bit-for-bit in interpret mode — the
    everywhere-runnable leg of the compiled-path canary."""
    from dss_tpu.ops.fastpath import mm_floor, mm_ceil, sec_floor, sec_ceil
    from dss_tpu.ops.fastpath_pallas import (
        GRIDLESS_MAX_WINDOWS,
        GROUP,
        filter_windows_gridless,
        filter_windows_pallas,
    )

    rng = np.random.default_rng(5)
    _, ft = _mk_table(rng, 1500, 300)
    qkeys, alo, ahi, ts, te = _mk_queries(rng, 24, 5, 300)
    win_q, win_key, win_blk, _, _ = ft._expand_windows(qkeys)
    nw = len(win_blk)
    assert 0 < nw <= GRIDLESS_MAX_WINDOWS
    alo_mm = mm_floor(np.where(np.isneginf(alo), -2e6, alo))
    ahi_mm = mm_ceil(np.where(np.isposinf(ahi), 2e6, ahi))
    t0s = sec_floor(np.maximum(ts, np.int64(NOW)))
    t1s = sec_ceil(te)
    got = np.asarray(
        filter_windows_gridless(
            ft.p3,
            jnp.asarray(win_blk, jnp.int32),
            jnp.asarray(win_key, jnp.int32),
            jnp.asarray(alo_mm[win_q], jnp.int32),
            jnp.asarray(ahi_mm[win_q], jnp.int32),
            jnp.asarray(t0s[win_q], jnp.int32),
            jnp.asarray(t1s[win_q], jnp.int32),
            interpret=True,
        )
    )
    pad = (-nw) % GROUP
    zpad = np.zeros(pad, np.int32)
    legacy = np.asarray(
        filter_windows_pallas(
            ft.p3,
            jnp.asarray(np.concatenate([win_blk, zpad]), jnp.int32),
            jnp.asarray(
                np.concatenate([win_key, np.full(pad, -2, np.int32)]),
                jnp.int32,
            ),
            jnp.asarray(np.concatenate([alo_mm[win_q], zpad]), jnp.int32),
            jnp.asarray(np.concatenate([ahi_mm[win_q], zpad]), jnp.int32),
            jnp.asarray(np.concatenate([t0s[win_q], zpad]), jnp.int32),
            jnp.asarray(np.concatenate([t1s[win_q], zpad]), jnp.int32),
            interpret=True,
        )
    )
    np.testing.assert_array_equal(got, legacy[:nw].astype(np.int8))


def test_gridless_twin_compiles_on_tpu():
    """On a real TPU backend (not the CI CPU mesh) the gridless twin
    must COMPILE (interpret=False) and match interpret mode exactly.
    Skips off-TPU."""
    _needs_tpu()
    from dss_tpu.ops.fastpath import mm_floor, mm_ceil, sec_floor, sec_ceil
    from dss_tpu.ops.fastpath_pallas import (
        GRIDLESS_MAX_WINDOWS,
        filter_windows_gridless,
    )

    rng = np.random.default_rng(9)
    _, ft = _mk_table(rng, 1200, 250)
    qkeys, alo, ahi, ts, te = _mk_queries(rng, 16, 4, 250)
    win_q, win_key, win_blk, _, _ = ft._expand_windows(qkeys)
    if len(win_blk) == 0 or len(win_blk) > GRIDLESS_MAX_WINDOWS:
        pytest.skip("window draw out of gridless bounds")
    alo_mm = mm_floor(np.where(np.isneginf(alo), -2e6, alo))
    ahi_mm = mm_ceil(np.where(np.isposinf(ahi), 2e6, ahi))
    t0s = sec_floor(np.maximum(ts, np.int64(NOW)))
    t1s = sec_ceil(te)
    args = (
        ft.p3,
        jnp.asarray(win_blk, jnp.int32),
        jnp.asarray(win_key, jnp.int32),
        jnp.asarray(alo_mm[win_q], jnp.int32),
        jnp.asarray(ahi_mm[win_q], jnp.int32),
        jnp.asarray(t0s[win_q], jnp.int32),
        jnp.asarray(t1s[win_q], jnp.int32),
    )
    compiled = np.asarray(filter_windows_gridless(*args, interpret=False))
    interp = np.asarray(filter_windows_gridless(*args, interpret=True))
    np.testing.assert_array_equal(compiled, interp)


def _exact_gridless_args_and_oracle(seed):
    """Window args for fused_filter_gridless + the straight-from-
    columns numpy oracle of the production fused filter semantics."""
    from dss_tpu.ops.fastpath_pallas import BLOCK, GRIDLESS_MAX_WINDOWS

    rng = np.random.default_rng(seed)
    recs, ft = _mk_table(rng, 900, 250)
    qkeys, alo, ahi, ts, te = _mk_queries(rng, 16, 4, 250)
    wins, _, _, nw = ft._pack_windows(qkeys)
    if nw == 0 or nw > GRIDLESS_MAX_WINDOWS:
        pytest.skip("window draw out of gridless bounds")
    wins = np.asarray(wins)
    t0_eff = np.maximum(ts, np.int64(NOW))
    win_blk, meta = wins[0][:nw], wins[1][:nw]
    win_q = meta >> 16
    args = (
        ft.b_alo, ft.b_ahi, ft.b_t0, ft.b_t1,
        jnp.asarray(win_blk, jnp.int32),
        jnp.asarray(meta & 0xFFFF, jnp.int32),
        jnp.asarray(alo[win_q], jnp.float32),
        jnp.asarray(ahi[win_q], jnp.float32),
        jnp.asarray(t0_eff[win_q], jnp.int64),
        jnp.asarray(te[win_q], jnp.int64),
    )
    lanes = np.arange(BLOCK)[None, :]
    start = (meta & 0xFF)[:, None]
    end = ((meta >> 8) & 0xFF)[:, None]
    oracle = (
        (lanes >= start)
        & (lanes < end)
        & (np.asarray(ft.b_ahi)[win_blk] >= alo[win_q][:, None])
        & (np.asarray(ft.b_alo)[win_blk] <= ahi[win_q][:, None])
        & (np.asarray(ft.b_t1)[win_blk] >= t0_eff[win_q][:, None])
        & (np.asarray(ft.b_t0)[win_blk] <= te[win_q][:, None])
    ).astype(np.int8)
    return args, oracle


@pytest.mark.parametrize("seed", [4, 8])
def test_exact_gridless_interpret_matches_oracle(seed):
    """fused_filter_gridless (EXACT fused semantics, i64 times carried
    as split-i32 planes) matches the straight numpy oracle in
    interpret mode — validates the hi/lo' comparison identity on real
    ns-scale timestamps."""
    from dss_tpu.ops.fastpath_pallas import fused_filter_gridless

    args, oracle = _exact_gridless_args_and_oracle(seed)
    got = np.asarray(fused_filter_gridless(*args, interpret=True))
    np.testing.assert_array_equal(got, oracle)


def test_exact_gridless_compiles_on_tpu():
    """The production fused filter's EXACT math (f32 altitudes + i64
    time bounds via the split-plane identity) compiled on the real
    chip.  Skips off-TPU."""
    _needs_tpu()
    from dss_tpu.ops.fastpath_pallas import fused_filter_gridless

    args, oracle = _exact_gridless_args_and_oracle(4)
    compiled = np.asarray(fused_filter_gridless(*args, interpret=False))
    np.testing.assert_array_equal(compiled, oracle)


def test_dma_filter_compiles_on_tpu():
    """The gridded quantized-mask DMA kernel (grid + scalar prefetch +
    make_async_copy) compiled by Mosaic must match its own interpret
    mode bit for bit.  Skips off-TPU."""
    _needs_tpu()
    from dss_tpu.ops.fastpath import mm_floor, mm_ceil, sec_floor, sec_ceil
    from dss_tpu.ops.fastpath_pallas import GROUP, filter_windows_pallas

    rng = np.random.default_rng(5)
    _, ft = _mk_table(rng, 1500, 300)
    qkeys, alo, ahi, ts, te = _mk_queries(rng, 24, 5, 300)
    win_q, win_key, win_blk, _, _ = ft._expand_windows(qkeys)
    nw = len(win_blk)
    assert nw > 0
    alo_mm = mm_floor(np.where(np.isneginf(alo), -2e6, alo))
    ahi_mm = mm_ceil(np.where(np.isposinf(ahi), 2e6, ahi))
    t0s = sec_floor(np.maximum(ts, np.int64(NOW)))
    t1s = sec_ceil(te)
    pad = (-nw) % GROUP
    zpad = np.zeros(pad, np.int32)
    args = (
        ft.p3,
        jnp.asarray(np.concatenate([win_blk, zpad]), jnp.int32),
        jnp.asarray(
            np.concatenate([win_key, np.full(pad, -2, np.int32)]), jnp.int32
        ),
        jnp.asarray(np.concatenate([alo_mm[win_q], zpad]), jnp.int32),
        jnp.asarray(np.concatenate([ahi_mm[win_q], zpad]), jnp.int32),
        jnp.asarray(np.concatenate([t0s[win_q], zpad]), jnp.int32),
        jnp.asarray(np.concatenate([t1s[win_q], zpad]), jnp.int32),
    )
    compiled = np.asarray(filter_windows_pallas(*args, interpret=False))
    interp = np.asarray(filter_windows_pallas(*args, interpret=True))
    np.testing.assert_array_equal(compiled, interp)


@pytest.mark.xfail(
    strict=True,
    reason="TPU v5e, jax 0.9.0 / libtpu 0.0.34: the kernel takes i64 "
    "operands and XLA refuses the call before Mosaic sees it — "
    "'UNIMPLEMENTED: While rewriting computation to not contain X64 "
    "element types, XLA encountered an HLO for which this rewriting is "
    "not implemented: %pallas_call.1 = s32[256,128]{1,0} custom-call(...)"
    ", custom_call_target=\"tpu_custom_call\"'.  Needs the split-i32 "
    "time planes of fused_filter_gridless (ROADMAP A5/C5).",
)
def test_dma_fused_compiles_on_tpu():
    """The gridded fused DMA kernel (exact f32/i64 columns, int64 VMEM
    scratch) compiled for the chip must produce the fused XLA path's
    hit words.  Skips off-TPU; on the chip it is an expected failure
    until the kernel stops taking i64 operands."""
    _needs_tpu()
    rng = np.random.default_rng(2)
    _, ft = _mk_table(rng, 300, hot_cell=7)
    qkeys, alo, ahi, ts, te = _mk_queries(rng, b=8, w=16)
    pw, _ = _pallas_words(ft, qkeys, alo, ahi, ts, te, interpret=False)
    xw = _xla_words(ft, qkeys, alo, ahi, ts, te)
    np.testing.assert_array_equal(pw[: len(xw)], xw)
