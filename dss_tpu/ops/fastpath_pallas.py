"""Pallas TPU kernel for the fast-path window filter.

Flat window list: each window is ONE 128-posting block of the
attribute-inlined postings array plus per-window query scalars.  Each
grid program handles GROUP=32 consecutive windows (int8 tiling needs
32x128 output blocks), DMA-ing each window's block HBM->VMEM double-
buffered and running the 4D compare on the VPU.  Equivalent to
FastTable._filter_xla but with explicit DMA scheduling.

What the real compiler says (TPU v5e, jax 0.9.0 / libtpu 0.0.34, PR 21;
`DSS_TEST_TPU=1 pytest tests/test_pallas_fused_parity.py -k on_tpu`):

  - filter_windows_pallas (grid + scalar prefetch + manual DMA over the
    quantized i32 pack): compiles with Mosaic and matches interpret
    mode bit for bit — once its index map returns an explicit i32 (the
    repo enables jax x64; a bare 0 traced as i64 and Mosaic failed with
    "failed to legalize operation 'func.func'" on transform_1).
  - fused_filter_pack_pallas (the exact twin: i64 time columns, an
    int64 VMEM scratch): refused before Mosaic sees it — "UNIMPLEMENTED:
    While rewriting computation to not contain X64 element types, XLA
    encountered an HLO for which this rewriting is not implemented:
    %pallas_call.1 = s32[256,128]{1,0} custom-call(...),
    custom_call_target="tpu_custom_call"".  XLA emulates i64 on the
    TPU by rewriting it into i32 pairs and cannot rewrite through a
    Pallas call, so this kernel cannot take i64 operands at all.  The
    way through is the split-i32 time planes fused_filter_gridless
    already uses (ROADMAP A5/C5); it stays interpret-tested until then.
  - filter_windows_gridless, fused_filter_gridless: compile and match.

None of these has a serving caller; the XLA kernel in ops/fastpath.py
is the one device implementation in use.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128
GROUP = 32  # windows per grid program (int8 min tile sublanes)


def _out_index(g, *_):
    # block index of a grid program's output rows.  The column index is
    # an explicit i32: the repo enables jax x64, a bare 0 would trace as
    # i64, and Mosaic cannot legalize an index map returning (i32, i64)
    return g, jnp.int32(0)


def _kernel(blk_ref, qkey_ref, qalo_ref, qahi_ref, qt0_ref, qt1_ref,
            packed_hbm, mask_ref, scratch, sems):
    g = pl.program_id(0)
    base = g * GROUP

    def dma(i, slot):
        slot = jnp.int32(slot)
        return pltpu.make_async_copy(
            packed_hbm.at[pl.ds(blk_ref[base + i], 1)],
            scratch.at[slot],
            sems.at[slot],
        )

    dma(jnp.int32(0), 0).start()
    for i in range(GROUP):
        slot = i % 2
        if i + 1 < GROUP:
            dma(jnp.int32(i + 1), (i + 1) % 2).start()
        dma(jnp.int32(i), slot).wait()
        win = scratch[slot]  # (1, 5, 128) i32
        w = base + i
        hit = (
            (win[:, 0, :] == qkey_ref[w])
            & (win[:, 2, :] >= qalo_ref[w])
            & (win[:, 1, :] <= qahi_ref[w])
            & (win[:, 4, :] >= qt0_ref[w])
            & (win[:, 3, :] <= qt1_ref[w])
        )
        mask_ref[i : i + 1, :] = hit.astype(jnp.int8)


@partial(jax.jit, static_argnames=("interpret",))
def filter_windows_pallas(
    p3,  # (NB, 5, 128) i32
    win_blk,  # (NW,) i32 block index per window, NW % GROUP == 0
    qk,  # (NW,) i32 key to match (negative = never matches)
    qalo_mm,  # (NW,) i32
    qahi_mm,
    qt0s,
    qt1s,
    *,
    interpret: bool = False,
):
    """-> per-lane hit mask (NW, 128) int8."""
    nw = win_blk.shape[0]
    assert nw % GROUP == 0, f"NW must be padded to a multiple of {GROUP}"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(nw // GROUP,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((GROUP, BLOCK), _out_index)],
        scratch_shapes=[
            pltpu.VMEM((2, 1, 5, BLOCK), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((nw, BLOCK), jnp.int8)],
        interpret=interpret,
    )(win_blk, qk, qalo_mm, qahi_mm, qt0s, qt1s, p3)[0]


# ---------------------------------------------------------------------------
# Fused-path twin: exact columns + lane-range windows + on-device bit-pack
# ---------------------------------------------------------------------------
#
# Mirrors FastTable._fused_xla's filter+pack stages (fastpath.py:368-415)
# with explicit DMA scheduling: per window, the EXACT f32 altitude and
# i64 time block columns stream HBM->VMEM double-buffered, the 4D
# compare runs on lanes [start, end), and the 128 hit lanes bit-pack to
# 4 u32 words on device.  The compaction stage (cumsum+scatter of
# non-empty words) remains XLA — that is the documented lowering delta
# (docs/DESIGN.md): compaction is a data-dependent scatter that XLA
# already schedules well, while filter+pack dominate the FLOPs/bytes.
#
# Output lane layout: (NW, 128) i32 with words in lanes 0..3 and zeros
# elsewhere — full-width blocks so the kernel stays tile-aligned.
# Interpret-only: on the chip XLA refuses its i64 operands (module
# docstring); differential parity is pinned by
# tests/test_pallas_fused_parity.py.


def _fused_kernel(blk_ref, meta_ref, alo_ref, ahi_ref, t0_ref, t1_ref,
                  alt_hbm, time_hbm, words_ref, alt_scr, time_scr, sems):
    g = pl.program_id(0)
    base = g * GROUP

    def dma_alt(i, slot):
        # indices must trace as i32: the repo enables jax x64, and
        # Mosaic's memref_slice rejects i64 operands
        s = jnp.int32(slot)
        return pltpu.make_async_copy(
            alt_hbm.at[pl.ds(blk_ref[base + i], 1)],
            alt_scr.at[s],
            sems.at[s, jnp.int32(0)],
        )

    def dma_time(i, slot):
        s = jnp.int32(slot)
        return pltpu.make_async_copy(
            time_hbm.at[pl.ds(blk_ref[base + i], 1)],
            time_scr.at[s],
            sems.at[s, jnp.int32(1)],
        )

    dma_alt(jnp.int32(0), 0).start()
    dma_time(jnp.int32(0), 0).start()
    for i in range(GROUP):
        slot = i % 2
        if i + 1 < GROUP:
            dma_alt(jnp.int32(i + 1), (i + 1) % 2).start()
            dma_time(jnp.int32(i + 1), (i + 1) % 2).start()
        dma_alt(jnp.int32(i), slot).wait()
        dma_time(jnp.int32(i), slot).wait()
        alt = alt_scr[slot]    # (1, 2, 128) f32: [alo, ahi]
        tim = time_scr[slot]   # (1, 2, 128) i64: [t0, t1]
        w = base + i
        meta = meta_ref[w]
        start = meta & 0xFF
        end = (meta >> 8) & 0xFF
        lanes = jax.lax.broadcasted_iota(jnp.int32, (1, BLOCK), 1)
        hit = (
            (lanes >= start)
            & (lanes < end)
            & (alt[:, 1, :] >= qf32_ref_get(alo_ref, w))
            & (alt[:, 0, :] <= qf32_ref_get(ahi_ref, w))
            & (tim[:, 1, :] >= t0_ref[w])
            & (tim[:, 0, :] <= t1_ref[w])
        )  # (1, 128) bool, exact
        # bit-pack 128 lanes -> 4 i32 words in lanes 0..3 (disjoint
        # bits: modular add == bitwise OR, matching _fused_xla)
        h = hit.astype(jnp.int32).reshape(1, 4, 32)
        words = jnp.sum(
            h << jax.lax.broadcasted_iota(jnp.int32, (1, 4, 32), 2),
            axis=2,
            dtype=jnp.int32,
        )  # (1, 4)
        # place the 4 words in lanes 0..3 via concat (Mosaic lowers
        # concatenate; .at[].set scatter has no TPU lowering)
        row = jnp.concatenate(
            [words, jnp.zeros((1, BLOCK - 4), jnp.int32)], axis=1
        )
        words_ref[i : i + 1, :] = row


def qf32_ref_get(ref, i):
    """Scalar-prefetch refs hold f32 per-window query bounds; indexing
    helper kept explicit for Mosaic-compat experiments."""
    return ref[i]


@partial(jax.jit, static_argnames=("interpret",))
def fused_filter_pack_pallas(
    b_alo,  # (NB, 128) f32 exact block columns
    b_ahi,
    b_t0,  # (NB, 128) i64
    b_t1,
    win_blk,  # (NW,) i32 block index per window, NW % GROUP == 0
    meta,  # (NW,) i32: start | end<<8 (lane range within the block)
    alo_w,  # (NW,) f32 per-window query bounds (pre-gathered by qidx)
    ahi_w,
    t0_w,  # (NW,) i64 (t_start pre-folded with now, as _fused_xla)
    t1_w,
    *,
    interpret: bool = False,
):
    """-> (NW, 4) i32 hit-bit words, identical to _fused_xla's
    pre-compaction words."""
    nw = win_blk.shape[0]
    assert nw % GROUP == 0, f"NW must be padded to a multiple of {GROUP}"
    alt = jnp.stack([b_alo, b_ahi], axis=1)  # (NB, 2, 128) f32
    tim = jnp.stack([b_t0, b_t1], axis=1)  # (NB, 2, 128) i64
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(nw // GROUP,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[pl.BlockSpec((GROUP, BLOCK), _out_index)],
        scratch_shapes=[
            pltpu.VMEM((2, 1, 2, BLOCK), jnp.float32),
            pltpu.VMEM((2, 1, 2, BLOCK), jnp.int64),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out = pl.pallas_call(
        _fused_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((nw, BLOCK), jnp.int32)],
        interpret=interpret,
    )(win_blk, meta, alo_w, ahi_w, t0_w, t1_w, alt, tim)[0]
    return out[:, :4]


# ---------------------------------------------------------------------------
# Gridless twins: whole-array kernels over VMEM-resident operands
# ---------------------------------------------------------------------------
#
# The window gather and (for the exact twin) the i64 -> split-i32
# conversion run in XLA; the 4D compare is the Pallas kernel.  Both
# compile on the chip and are parity-pinned there against interpret
# mode / the numpy oracle for a VMEM-sized window slice.


def _gridless_kernel(win_ref, qk_ref, qalo_ref, qahi_ref, qt0_ref,
                     qt1_ref, out_ref):
    win = win_ref[...]  # (NW, 5, BLOCK) i32, pre-gathered by win_blk
    hit = (
        (win[:, 0, :] == qk_ref[...])
        & (win[:, 2, :] >= qalo_ref[...])
        & (win[:, 1, :] <= qahi_ref[...])
        & (win[:, 4, :] >= qt0_ref[...])
        & (win[:, 3, :] <= qt1_ref[...])
    )
    out_ref[...] = hit.astype(jnp.int8)


# ~2 MB of VMEM operands per call at this bound (NW*5*128 i32 + cols)
GRIDLESS_MAX_WINDOWS = 512


@partial(jax.jit, static_argnames=("interpret",))
def filter_windows_gridless(
    p3,  # (NB, 5, 128) i32 block-packed quantized columns
    win_blk,  # (NW,) i32, NW <= GRIDLESS_MAX_WINDOWS
    qk,  # (NW,) i32 (negative = never matches)
    qalo_mm,  # (NW,) i32
    qahi_mm,
    qt0s,
    qt1s,
    *,
    interpret: bool = False,
):
    """-> per-lane hit mask (NW, 128) int8, same semantics as
    filter_windows_pallas.  The window gather runs in XLA; the filter
    itself is the compiled Pallas kernel over whole VMEM-resident
    arrays."""
    nw = win_blk.shape[0]
    assert nw <= GRIDLESS_MAX_WINDOWS, "gridless twin is VMEM-bounded"
    gathered = jnp.take(p3, win_blk, axis=0)  # (NW, 5, 128)

    def col(a):
        return a.reshape(nw, 1)

    return pl.pallas_call(
        _gridless_kernel,
        out_shape=jax.ShapeDtypeStruct((nw, BLOCK), jnp.int8),
        interpret=interpret,
    )(gathered, col(qk), col(qalo_mm), col(qahi_mm), col(qt0s),
      col(qt1s))


def _gridless_exact_kernel(
    alo_ref, ahi_ref, t0h_ref, t0l_ref, t1h_ref, t1l_ref,
    start_ref, end_ref, qalo_ref, qahi_ref,
    q0h_ref, q0l_ref, q1h_ref, q1l_ref, out_ref,
):
    """EXACT fused-path 4D compare, gridless.  Times arrive as split
    i32 planes (hi = x >> 32 signed; lo' = low 32 bits with the sign
    bit flipped) because a Pallas call cannot take i64 operands on the
    TPU (module docstring): for int64 a, b
        a >= b  ==  (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo' >= b_lo'))
    with the lo' bias turning the unsigned low-word compare into a
    signed one."""
    lanes = jax.lax.broadcasted_iota(
        jnp.int32, out_ref.shape, 1
    )
    t1h, q0h = t1h_ref[...], q0h_ref[...]
    t0h, q1h = t0h_ref[...], q1h_ref[...]
    t1_ge_q0 = (t1h > q0h) | ((t1h == q0h) & (t1l_ref[...] >= q0l_ref[...]))
    t0_le_q1 = (t0h < q1h) | ((t0h == q1h) & (t0l_ref[...] <= q1l_ref[...]))
    hit = (
        (lanes >= start_ref[...])
        & (lanes < end_ref[...])
        & (ahi_ref[...] >= qalo_ref[...])
        & (alo_ref[...] <= qahi_ref[...])
        & t1_ge_q0
        & t0_le_q1
    )
    out_ref[...] = hit.astype(jnp.int8)


def _split_i64(x):
    """int64 -> (hi i32 signed, lo' i32 = low word with sign bit
    flipped) such that lexicographic (hi, lo') signed compare equals
    the i64 compare."""
    hi = (x >> 32).astype(jnp.int32)
    lo = jax.lax.bitcast_convert_type(
        (x & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32), jnp.int32
    )
    return hi, lo ^ jnp.int32(-(2**31))


@partial(jax.jit, static_argnames=("interpret",))
def fused_filter_gridless(
    b_alo,  # (NB, 128) f32 exact block columns
    b_ahi,
    b_t0,  # (NB, 128) i64
    b_t1,
    win_blk,  # (NW,) i32, NW <= GRIDLESS_MAX_WINDOWS
    meta,  # (NW,) i32: start | end<<8
    alo_w,  # (NW,) f32 per-window query bounds
    ahi_w,
    t0_w,  # (NW,) i64 (t_start pre-folded with now)
    t1_w,
    *,
    interpret: bool = False,
):
    """-> (NW, 128) i8 EXACT hit mask — the production fused path's
    filter semantics (fused_filter_pack_pallas without the bit-pack),
    compiled: gathers + i64 splitting run in XLA, the 4D compare is
    the gridless Pallas kernel."""
    nw = win_blk.shape[0]
    assert nw <= GRIDLESS_MAX_WINDOWS, "gridless twin is VMEM-bounded"
    alo = jnp.take(b_alo, win_blk, axis=0)  # (NW, 128) f32
    ahi = jnp.take(b_ahi, win_blk, axis=0)
    t0h, t0l = _split_i64(jnp.take(b_t0, win_blk, axis=0))
    t1h, t1l = _split_i64(jnp.take(b_t1, win_blk, axis=0))
    q0h, q0l = _split_i64(t0_w)
    q1h, q1l = _split_i64(t1_w)

    def col(a):
        return a.reshape(nw, 1)

    return pl.pallas_call(
        _gridless_exact_kernel,
        out_shape=jax.ShapeDtypeStruct((nw, BLOCK), jnp.int8),
        interpret=interpret,
    )(
        alo, ahi, t0h, t0l, t1h, t1l,
        col(meta & 0xFF), col((meta >> 8) & 0xFF),
        col(alo_w), col(ahi_w),
        col(q0h), col(q0l), col(q1h), col(q1l),
    )
