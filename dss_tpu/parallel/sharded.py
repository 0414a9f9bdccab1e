"""Sharded DAR conflict queries: shard_map over a ("dp", "sp") mesh.

Replaces the reference's CRDB range layer for the read path
(implementation_details.md:11-42 — ranges shard the cell keyspace, any
node proxies to the right range).  Here:

  - the globally-sorted postings array is split into `sp` contiguous
    cell-key ranges (equal postings counts, so load is balanced even
    when cell occupancy is skewed);
  - each device runs the single-chip candidate gather + 4D attribute
    test (dss_tpu.ops.conflict) against its local range and compacts
    its hits to a fixed width;
  - per-shard results are merged with an all_gather over the "sp" axis
    (ICI) and dedup-compacted — the SQL DISTINCT across ranges;
  - the query batch itself is sharded over "dp": independent query
    streams never communicate.

The EntityTable is replicated: attribute columns are ~29 B/entity
(vs ~8 B/posting x ~dozens of postings/entity), and every shard needs
random access to attributes of slots its postings name.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dss_tpu.dar import oracle
from dss_tpu.dar.oracle import Record
from dss_tpu.dar.pack import pack_records
from dss_tpu.parallel.mesh import mesh_spans_processes
from dss_tpu.ops.conflict import (
    INT32_MAX,
    NO_TIME_HI,
    NO_TIME_LO,
    EntityTable,
    Postings,
    QuerySpec,
    _attr_test,
    _candidates,
    _compact_unique,
)


def shard_postings(
    post_key: np.ndarray,
    post_ent: np.ndarray,
    n_sp: int,
    sentinel_slot: int,
    boundaries: Optional[np.ndarray] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Split sorted postings into n_sp contiguous ranges.

    Returns ([n_sp, Ps] keys, [n_sp, Ps] slots), each row sorted, padded
    with INT32_MAX / sentinel.  Without `boundaries` the split is by
    equal postings *count* — balanced by storage, the cold-start
    fallback.  With `boundaries` (n_sp-1 sorted int32 DAR-key split
    points, usually from `weighted_boundaries`) shard i takes the key
    range [boundaries[i-1], boundaries[i]) — the load-weighted
    placement the rebalancer broadcasts, applicable to ANY postings
    array over the same key space (base and delta tiers share one
    boundary map).  Contiguity keeps each row sorted so per-shard
    searchsorted still works.
    """
    live = post_key != INT32_MAX
    pk = np.asarray(post_key)[live]
    pe = np.asarray(post_ent)[live]
    n = len(pk)
    if boundaries is None:
        ps = max((n + n_sp - 1) // n_sp, 8)
        lohi = [
            (i * ps, min((i + 1) * ps, n)) if i * ps < n else (n, n)
            for i in range(n_sp)
        ]
    else:
        b = np.asarray(boundaries, np.int32)
        if len(b) != n_sp - 1:
            raise ValueError(
                f"boundaries has {len(b)} split points for {n_sp} shards"
            )
        cuts = [0] + [int(c) for c in np.searchsorted(pk, b)] + [n]
        lohi = [(cuts[i], cuts[i + 1]) for i in range(n_sp)]
        ps = max(max((hi - lo) for lo, hi in lohi), 8)
    keys = np.full((n_sp, ps), INT32_MAX, np.int32)
    ents = np.full((n_sp, ps), sentinel_slot, np.int32)
    for i, (lo, hi) in enumerate(lohi):
        if hi > lo:
            keys[i, : hi - lo] = pk[lo:hi]
            ents[i, : hi - lo] = pe[lo:hi]
    return keys, ents


def weighted_boundaries(
    post_key: np.ndarray,
    weights: Optional[np.ndarray],
    n_sp: int,
    member_capacity: Optional[np.ndarray] = None,
) -> Optional[np.ndarray]:
    """Key-space split points equalizing predicted *query work* per
    shard (the searched-mapping step: placement driven by measured
    cost, not storage count).

    `weights` is per-posting measured load (RangeLoad.weights_for);
    every posting additionally carries one unit of count baseline, so
    zero measured load (cold start) reproduces the equal-count split
    and cold ranges still spread by storage.  Returns n_sp-1 sorted
    int32 DAR keys, or None when there is nothing to split.  Split
    points snap to key values (a single key's postings never straddle
    shards), so a single cell hotter than a whole shard ends up alone
    in its shard — the best key-range placement can do.  Per-shard
    posting counts are capped at 4x the equal-count mean (the device
    postings array is rectangular, padded to the LARGEST shard — the
    cap bounds that memory/refresh-traffic blowup at 4x; indivisible
    single-key runs excepted).

    `member_capacity` (optional, length n_sp) weighs each shard's
    TARGET work by its host's measured serving capacity (the
    `capacity_weight` scalar from per-host autotune profiles —
    dss_tpu/plan/autotune.py): a slow host gets a proportionally
    lighter key run.  None or a uniform vector reproduces the
    equal-target split bit-identically.
    """
    pk = np.asarray(post_key, np.int32).ravel()
    pk = pk[pk != INT32_MAX]
    n = len(pk)
    if n == 0 or n_sp <= 1:
        return None
    if member_capacity is None:
        cap = np.ones(n_sp, np.float64)
    else:
        cap = np.asarray(member_capacity, np.float64).ravel()
        if len(cap) != n_sp:
            raise ValueError(
                f"member_capacity has {len(cap)} entries for "
                f"{n_sp} shards"
            )
        if not np.all(cap > 0):
            raise ValueError("member_capacity entries must be > 0")
    w = np.ones(n, np.float64)
    if weights is not None:
        lw = np.asarray(weights, np.float64).ravel()
        tot = lw.sum()
        if tot > 0:
            # normalize measured load to the same mass as the count
            # baseline, then let it dominate: a shard's predicted work
            # is mostly its query load, tempered by storage so empty-
            # load ranges still split by count
            w += lw * (n / tot) * 8.0
    # greedy fill at KEY-RUN granularity (a key's postings never
    # straddle shards), re-targeting the remaining weight over the
    # remaining shards after each cut — a single run heavier than a
    # whole shard then gets (nearly) its own shard instead of
    # collapsing every later boundary onto the same key, and the mass
    # on either side of it still splits evenly
    uk, starts = np.unique(pk, return_index=True)
    run_w = np.add.reduceat(w, starts)
    run_n = np.diff(np.append(starts, n))
    # the device postings array is rectangular ([n_sp, max shard
    # postings]): cap any one shard's posting COUNT at 4x the mean so
    # a load-weighted split that packs cold mass densely can cost at
    # most 4x the equal-count layout's device bytes, never unbounded
    # (a single key run larger than the cap is indivisible and allowed
    # through)
    count_cap = max(4 * ((n + n_sp - 1) // n_sp), 8)
    bounds: list = []
    rem_w = float(run_w.sum())
    rem_sh = n_sp
    acc = 0.0
    acc_n = 0
    consumed = 0  # postings in already-closed shards

    def fits_after_cut(extra: int) -> bool:
        # a cut is only legal when the postings left over still fit in
        # the remaining shards under the cap — otherwise an early cut
        # would force some LATER shard (often the last) over it
        return (n - (consumed + extra)) <= (rem_sh - 1) * count_cap

    def next_target() -> float:
        # the shard being filled is bounds-index len(bounds); its
        # target is its capacity's share of the remaining weight
        # (uniform capacity: exactly rem_w / rem_sh, the historical
        # equal-target split)
        s = len(bounds)
        return rem_w * float(cap[s]) / float(cap[s:].sum())

    for i in range(len(uk)):
        if len(bounds) == n_sp - 1:
            break
        target = next_target()
        if (
            acc > 0
            and (
                (run_w[i] >= target and acc + run_w[i] > 1.5 * target)
                or acc_n + int(run_n[i]) > count_cap
            )
            and fits_after_cut(acc_n)
        ):
            # the next run would overfill the shard (by weight, or by
            # the rectangular-padding count cap): cut BEFORE it so the
            # accumulated cold mass isn't welded to the hot run
            bounds.append(int(uk[i]))
            consumed += acc_n
            rem_w -= acc
            rem_sh -= 1
            acc = 0.0
            acc_n = 0
            if len(bounds) == n_sp - 1:
                break
            target = next_target()
        acc += float(run_w[i])
        acc_n += int(run_n[i])
        if acc >= target and i + 1 < len(uk) and fits_after_cut(acc_n):
            bounds.append(int(uk[i + 1]))
            consumed += acc_n
            rem_w -= acc
            rem_sh -= 1
            acc = 0.0
            acc_n = 0
    while len(bounds) < n_sp - 1:
        # out of keys: remaining shards are empty (legal — duplicate
        # boundaries yield zero-width ranges)
        bounds.append(bounds[-1] if bounds else int(uk[-1]))
    return np.asarray(bounds, np.int32)


def shard_of_keys(
    keys: np.ndarray, boundaries: Optional[np.ndarray], n_sp: int
) -> np.ndarray:
    """Shard index for each key under a boundary map (None = cannot be
    answered without the postings array; used for move accounting and
    predicted-load-per-shard summaries)."""
    k = np.asarray(keys, np.int32).ravel()
    if boundaries is None or not len(k):
        return np.zeros(len(k), np.int32)
    return np.searchsorted(
        np.asarray(boundaries, np.int32), k, side="right"
    ).astype(np.int32)


def imbalance_factor(loads) -> float:
    """max/mean over per-shard loads — 1.0 is perfectly balanced; the
    rebalance trigger compares this against DSS_SHARD_REBALANCE_RATIO."""
    arr = np.asarray(loads, np.float64).ravel()
    if not len(arr) or arr.sum() <= 0:
        return 1.0
    return float(arr.max() / arr.mean())


def put_global(mesh: Mesh, spec: P, arr: np.ndarray):
    """Materialize a host array onto the mesh under `spec`.

    Single-process meshes keep the plain device_put fast path.  A
    process-spanning mesh cannot device_put host data onto devices it
    does not address; make_array_from_callback instead asks each
    process for ONLY its addressable shards — every host materializes
    (and for sharded specs, folds device-side state for) just the
    shard rows it owns, which is the multi-host memory story.
    """
    sharding = NamedSharding(mesh, spec)
    if not mesh_spans_processes(mesh):
        return jax.device_put(arr, sharding)
    arr = np.asarray(arr)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


def _local_query(
    post: Postings,
    ents: EntityTable,
    q: QuerySpec,
    now,  # [Q] int64 per-query visibility time
    owner,
    *,
    cap: int,
    shard_results: int,
    with_owner: bool,
):
    """Per-device: candidates from the local postings range, 4D test,
    compact to shard_results.  Returns (slots [Q, sr], n_unique [Q])."""

    def one(qq, nw, ow):
        ent, valid = _candidates(post, ents, qq.keys, cap)
        hit = valid & _attr_test(
            ents, ent, qq, nw, ow if with_owner else None
        )
        return _compact_unique(ent, hit, shard_results)

    if with_owner:
        return jax.vmap(one)(q, now, owner)
    return jax.vmap(one, in_axes=(0, 0, None))(q, now, jnp.int32(0))


@partial(
    jax.jit,
    static_argnames=(
        "mesh",
        "cap",
        "shard_results",
        "max_results",
        "with_owner",
        "replicate_out",
    ),
)
def sharded_conflict_query_batch(
    post_key,  # [n_sp, Ps] int32, rows sorted, pad INT32_MAX
    post_ent,  # [n_sp, Ps] int32
    ents: EntityTable,  # replicated
    q: QuerySpec,  # leading batch axis Q, Q % dp == 0
    now,  # [Q] int64 per-query visibility time
    owner=None,  # [Q] int32 when with_owner
    *,
    mesh: Mesh,
    cap: int,
    shard_results: int,
    max_results: int,
    with_owner: bool = False,
    replicate_out: bool = False,
):
    """Batched sharded query.  Returns (slots [Q, max_results] padded
    with INT32_MAX, overflowed [Q] bool, shard_hits [n_sp] int32 —
    per-shard unique candidate hits summed over the batch, the
    measured per-shard work the skew-aware rebalancer consumes).

    replicate_out=True all_gathers the merged results over "dp" as
    well, so EVERY device (and therefore every process of a multi-host
    mesh) ends up holding the full [Q, max_results] answer — required
    when the caller cannot address all of the mesh's devices.  The
    merged values are bit-identical to the sharded-output path: the
    extra gather only changes placement, never the merge."""
    owner_arr = owner if with_owner else jnp.zeros(q.keys.shape[0], jnp.int32)

    def step(pk, pe, ents, keys, alo, ahi, ts, te, now, ow):
        post = Postings(post_key=pk[0], post_ent=pe[0])
        qq = QuerySpec(keys=keys, alt_lo=alo, alt_hi=ahi, t_start=ts, t_end=te)
        slots_s, n_uni = _local_query(
            post,
            ents,
            qq,
            now,
            ow,
            cap=cap,
            shard_results=shard_results,
            with_owner=with_owner,
        )
        shard_ovf = n_uni > shard_results  # [Qloc]
        # per-shard measured work: unique hits this shard contributed
        # across its local query slice, summed over "dp" so every
        # device (and host) holds the identical [n_sp] load vector
        hits = jax.lax.psum(
            jax.lax.all_gather(jnp.sum(n_uni).astype(jnp.int32), "sp"),
            "dp",
        )
        gathered = jax.lax.all_gather(slots_s, "sp")  # [n_sp, Qloc, sr]
        merged = jnp.moveaxis(gathered, 0, 1).reshape(slots_s.shape[0], -1)

        def compact(m):
            return _compact_unique(m, m != INT32_MAX, max_results)

        out, n_unique = jax.vmap(compact)(merged)
        ovf = (
            jax.lax.psum(shard_ovf.astype(jnp.int32), "sp") > 0
        ) | (n_unique > max_results)
        if replicate_out:
            # [dp, Qloc, mr] -> [Q, mr] (dp-major, matching the P("dp")
            # input split) on every device
            out = jax.lax.all_gather(out, "dp").reshape(
                -1, out.shape[-1]
            )
            ovf = jax.lax.all_gather(ovf, "dp").reshape(-1)
        return out, ovf, hits

    qspec = P("dp")
    out_specs = (
        (P(), P(), P())
        if replicate_out
        else (P("dp", None), P("dp"), P())
    )
    return shard_map(
        step,
        mesh=mesh,
        in_specs=(
            P("sp", None),  # post_key
            P("sp", None),  # post_ent
            P(),  # ents (replicated)
            P("dp", None),  # q.keys
            qspec,
            qspec,
            qspec,
            qspec,  # q scalars-per-query
            qspec,  # now (per-query)
            qspec,  # owner
        ),
        out_specs=out_specs,
        check_vma=False,
    )(
        post_key,
        post_ent,
        ents,
        q.keys,
        q.alt_lo,
        q.alt_hi,
        q.t_start,
        q.t_end,
        now,
        owner_arr,
    )


class ShardedDar:
    """A read-only sharded snapshot of a DAR entity class.

    Built from host Records (e.g. a DarTable's authoritative state or a
    WAL replay); holds device arrays laid out for the mesh.  This is
    the multi-chip read replica — writes go through the single-chip
    DarTable / WAL and periodically refresh this snapshot, mirroring
    the reference's CRDB-as-source-of-truth split (SURVEY.md §7).
    """

    def __init__(
        self,
        records: List[Record],
        mesh: Mesh,
        *,
        max_results: int = 512,
        shard_results: Optional[int] = None,
        boundaries: Optional[np.ndarray] = None,
    ):
        self.mesh = mesh
        self.n_sp = mesh.shape["sp"]
        self.dp = mesh.shape["dp"]
        # process-spanning mesh: arrays materialize addressable-shard-
        # by-shard and query outputs must replicate to every process
        self.multihost = mesh_spans_processes(mesh)
        self.max_results = max_results
        self.shard_results = shard_results or max_results
        self.records = {slot: r for slot, r in enumerate(records)}
        self.overflow_fallbacks = 0  # host-scan fallbacks (observability)
        # key-space split map this dar was built under (None = legacy
        # equal-count); kept for move accounting across rebuilds
        self.boundaries = (
            None if boundaries is None
            else np.asarray(boundaries, np.int32)
        )
        # measured per-shard unique-hit work, accumulated across
        # query batches (the rebalancer's measured-imbalance input);
        # locked — concurrent snapshot readers must not lose updates
        self.shard_hits = np.zeros(self.n_sp, np.int64)
        self._hits_mu = threading.Lock()

        packed = pack_records(records, pad_postings=False)
        self.cap = packed.base_cap
        skey, sent = shard_postings(
            packed.post_key,
            packed.post_ent,
            self.n_sp,
            packed.capacity,
            boundaries=self.boundaries,
        )

        # host->device bytes this snapshot materializes (refresh
        # traffic accounting; on a multi-host mesh each process ships
        # only its addressable slice of the sharded arrays)
        self.nbytes = int(
            skey.nbytes
            + sent.nbytes
            + sum(
                np.asarray(a).nbytes
                for a in (
                    packed.alt_lo, packed.alt_hi, packed.t_start,
                    packed.t_end, packed.active, packed.owner,
                )
            )
        )
        self.post_key = put_global(mesh, P("sp", None), skey)
        self.post_ent = put_global(mesh, P("sp", None), sent)
        self.ents = EntityTable(
            alt_lo=put_global(mesh, P(), packed.alt_lo),
            alt_hi=put_global(mesh, P(), packed.alt_hi),
            t_start=put_global(mesh, P(), packed.t_start),
            t_end=put_global(mesh, P(), packed.t_end),
            active=put_global(mesh, P(), packed.active),
            owner=put_global(mesh, P(), packed.owner),
        )

    def query_batch(
        self,
        keys_batch: np.ndarray,  # [Q, K] int32 DAR keys, pad -1
        alt_lo: np.ndarray,  # [Q] f32
        alt_hi: np.ndarray,
        t_start: np.ndarray,  # [Q] i64
        t_end: np.ndarray,
        *,
        now,  # int scalar or [Q] i64 per-query visibility time
    ):
        """Run a batch of queries; returns list-of-lists of entity slots."""
        qn = keys_batch.shape[0]
        now_arr = np.broadcast_to(
            np.asarray(now, np.int64), (qn,)
        ).copy()
        # pad the key width to a pow2 bucket: K is data-dependent (area
        # covering size) and an unpadded shape would compile a fresh
        # executable per distinct K
        kw = 16
        while kw < keys_batch.shape[1]:
            kw *= 2
        if kw != keys_batch.shape[1]:
            keys_batch = np.concatenate(
                [
                    keys_batch,
                    np.full(
                        (qn, kw - keys_batch.shape[1]), -1, np.int32
                    ),
                ],
                axis=1,
            )
        # bucket the batch axis (pow2, dp-aligned): Q is traffic-
        # dependent and an unbucketed shape would compile a fresh
        # multi-chip executable per distinct batch size — stalling
        # every coalesced caller behind a ~30s jit for each new size
        bucket = 16
        while bucket < qn:
            bucket *= 2
        if bucket % self.dp:
            bucket = ((bucket + self.dp - 1) // self.dp) * self.dp
        pad = bucket - qn
        if pad:
            keys_batch = np.concatenate(
                [keys_batch, np.full((pad, keys_batch.shape[1]), -1, np.int32)]
            )
            alt_lo = np.concatenate([alt_lo, np.full(pad, -np.inf, np.float32)])
            alt_hi = np.concatenate([alt_hi, np.full(pad, np.inf, np.float32)])
            t_start = np.concatenate([t_start, np.full(pad, NO_TIME_LO)])
            t_end = np.concatenate([t_end, np.full(pad, NO_TIME_HI)])
            now_arr = np.concatenate(
                [now_arr, np.zeros(pad, np.int64)]
            )
        if self.multihost:
            # every process runs this same call in lockstep (SPMD);
            # inputs shard onto the global mesh addressable-first and
            # the replicated output lands whole on every process
            mk = partial(put_global, self.mesh)
            spec = QuerySpec(
                keys=mk(P("dp", None), np.asarray(keys_batch, np.int32)),
                alt_lo=mk(P("dp"), np.asarray(alt_lo, np.float32)),
                alt_hi=mk(P("dp"), np.asarray(alt_hi, np.float32)),
                t_start=mk(P("dp"), np.asarray(t_start, np.int64)),
                t_end=mk(P("dp"), np.asarray(t_end, np.int64)),
            )
            now_dev = mk(P("dp"), np.asarray(now_arr, np.int64))
        else:
            # pre-partition the query inputs to the EXACT layout the
            # compiled kernel consumes (the shard_map in_specs) — the
            # pjit pitfall: an uncommitted jnp.asarray lands on the
            # default device and XLA inserts a call-site resharding
            # into every query, exactly what the resident-kernel work
            # removes from the single-chip path (ops/resident.py).
            # The postings/entity arrays were already put_global'd to
            # their specs at build time; this closes the gap for the
            # per-call side.
            mk = partial(put_global, self.mesh)
            spec = QuerySpec(
                keys=mk(P("dp", None), np.asarray(keys_batch, np.int32)),
                alt_lo=mk(P("dp"), np.asarray(alt_lo, np.float32)),
                alt_hi=mk(P("dp"), np.asarray(alt_hi, np.float32)),
                t_start=mk(P("dp"), np.asarray(t_start, np.int64)),
                t_end=mk(P("dp"), np.asarray(t_end, np.int64)),
            )
            now_dev = mk(P("dp"), np.asarray(now_arr, np.int64))
        slots, ovf, shard_hits = sharded_conflict_query_batch(
            self.post_key,
            self.post_ent,
            self.ents,
            spec,
            now_dev,
            mesh=self.mesh,
            cap=self.cap,
            shard_results=self.shard_results,
            max_results=self.max_results,
            replicate_out=self.multihost,
        )
        slots = np.asarray(slots)[:qn]
        ovf = np.asarray(ovf)[:qn]
        with self._hits_mu:
            self.shard_hits += np.asarray(shard_hits, np.int64)
        out = []
        for i in range(qn):
            if ovf[i]:
                # result wider than max_results: exact host fallback
                # for this query (counted — a hot cell silently
                # degrading to the slow path must be observable)
                self.overflow_fallbacks += 1
                out.append(
                    oracle.search(
                        self.records,
                        keys_batch[i][keys_batch[i] >= 0],
                        None
                        if alt_lo[i] == -np.inf
                        else float(alt_lo[i]),
                        None if alt_hi[i] == np.inf else float(alt_hi[i]),
                        None if t_start[i] == NO_TIME_LO else int(t_start[i]),
                        None if t_end[i] == NO_TIME_HI else int(t_end[i]),
                        int(now_arr[i]),
                    )
                )
            else:
                row = slots[i]
                out.append([int(s) for s in row[row != INT32_MAX]])
        return out
