"""Spatial index strategies behind the store implementations.

MemorySpatialIndex — pure-python linear scan (the reference's in-memory
test-fake analog, pkg/rid/application/isa_test.go:29-77).

TpuSpatialIndex — the DarTable HBM index (dss_tpu.dar.snapshot); cell
ids are compressed to int32 DAR keys on the way in.

Both expose identical query semantics (the SQL COALESCE rules); the
store contract tests run every scenario against both.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from dss_tpu.dar import oracle
from dss_tpu.dar import tiers as tiersmod
from dss_tpu.dar.coalesce import QueryCoalescer
from dss_tpu.dar.coalesce import env_knobs as coalesce_env_knobs
from dss_tpu.dar.oracle import Record
from dss_tpu.dar.snapshot import DarTable
from dss_tpu.geo import s2cell


def _to_keys(cells_u64: np.ndarray) -> np.ndarray:
    return s2cell.cell_to_dar_key(np.asarray(cells_u64, dtype=np.uint64))


class MemorySpatialIndex:
    def __init__(self):
        self._recs: Dict[str, Record] = {}
        # same per-cell write clock as the DarTable backend, so the
        # version-fenced read cache (dar/readcache.py) is exact on
        # both storage strategies
        self.cell_clock = tiersmod.CellClock()

    def put(self, id, cells_u64, alt_lo, alt_hi, t_start, t_end, owner_id):
        keys = np.unique(_to_keys(cells_u64))
        old = self._recs.get(id)
        self._recs[id] = Record(
            entity_id=id,
            keys=keys,
            alt_lo=-np.inf if alt_lo is None else float(alt_lo),
            alt_hi=np.inf if alt_hi is None else float(alt_hi),
            t_start=int(t_start),
            t_end=int(t_end),
            owner_id=int(owner_id),
        )
        # bump after the mutation (fail-closed for lock-free readers);
        # old + new coverings both change their cells' answers
        self.cell_clock.bump(None if old is None else old.keys, keys)

    def remove(self, id):
        old = self._recs.pop(id, None)
        if old is not None:
            self.cell_clock.bump(old.keys)

    def bulk_load(self, records: List[Record]) -> None:
        """Replace the contents with `records` at once (the bulk boot,
        dar/boot.py); the clock's floor is raised instead of a stamp
        per covering, as DarTable.bulk_load does."""
        self._recs = {r.entity_id: r for r in records}
        self.cell_clock.bump_all()

    def clock_fence(self, cells_u64) -> "tuple[int, int, int, int]":
        """(incarnation, max stamp, generation, floor) over the
        covering — the read cache's O(|cells|) validity check."""
        return self.cell_clock.fence(_to_keys(cells_u64))

    def adopt_cell_clock(self, clock: tiersmod.CellClock) -> None:
        """Carry a predecessor index's clock across a state reset
        (region resync): the caller bump_all()s it, which floors every
        older fence — O(1), no stamp-array reallocation inside the
        resync swap window lock-free readers can observe."""
        self.cell_clock = clock

    def query_ids(
        self,
        cells_u64,
        alt_lo=None,
        alt_hi=None,
        t_start=None,
        t_end=None,
        *,
        now,
        owner_id=None,
        allow_stale=False,  # no replica tier here; same-freshness reads
    ) -> List[str]:
        keys = _to_keys(cells_u64)
        recs = {i: r for i, r in enumerate(self._recs.values())}
        slots = oracle.search(
            recs, keys, alt_lo, alt_hi, t_start, t_end, now, owner_id
        )
        return [recs[s].entity_id for s in slots]

    def max_owner_count(self, cells_u64, owner_id, *, now) -> int:
        keys = _to_keys(cells_u64)
        recs = {i: r for i, r in enumerate(self._recs.values())}
        return oracle.max_count_per_cell(recs, keys, owner_id, now)

    def max_count_of(self, ids, cells_u64) -> int:
        """DarTable.max_count_of's twin: the most of `ids` (live
        answers of a query over the cells) that hold one cell."""
        held = [set(self._recs[i].keys.tolist()) for i in ids
                if i in self._recs]
        return max((sum(k in s for s in held)
                    for k in np.unique(_to_keys(cells_u64)).tolist()),
                   default=0)

    def stats(self) -> dict:
        return {
            "live_records": len(self._recs),
            "write_generation": self.cell_clock.generation,
            "cell_clock_high_water": self.cell_clock.high_water,
        }


class TpuSpatialIndex:
    def __init__(self, **table_kwargs):
        self._table = DarTable(**table_kwargs)
        # concurrent readers (one thread per in-flight request) are
        # micro-batched into single fused kernel launches; serving
        # knobs come from DSS_CO_* env vars (docs/SERVING.md) and can
        # be adjusted at runtime via DSSStore.configure_serving
        self._coalescer = QueryCoalescer(
            self._table, **coalesce_env_knobs()
        )

    def put(self, id, cells_u64, alt_lo, alt_hi, t_start, t_end, owner_id):
        self._table.upsert(
            id, _to_keys(cells_u64), alt_lo, alt_hi, int(t_start), int(t_end), owner_id
        )

    def remove(self, id):
        self._table.remove(id)

    def bulk_load(self, records: List[Record]) -> None:
        """Replace the contents with `records` in one table build and
        one upload (the bulk boot, dar/boot.py)."""
        self._table.bulk_load(records)

    def query_ids(
        self,
        cells_u64,
        alt_lo=None,
        alt_hi=None,
        t_start=None,
        t_end=None,
        *,
        now,
        owner_id=None,
        allow_stale=False,
    ) -> List[str]:
        return self._coalescer.query(
            _to_keys(cells_u64),
            alt_lo,
            alt_hi,
            None if t_start is None else int(t_start),
            None if t_end is None else int(t_end),
            now=int(now),
            owner_id=owner_id,
            allow_stale=allow_stale,
        )

    def max_owner_count(self, cells_u64, owner_id, *, now) -> int:
        return self._table.max_owner_count(
            _to_keys(cells_u64), owner_id, now=int(now)
        )

    def max_count_of(self, ids, cells_u64) -> int:
        return self._table.max_count_of(ids, _to_keys(cells_u64))

    @property
    def cell_clock(self) -> tiersmod.CellClock:
        return self._table.cell_clock

    def clock_fence(self, cells_u64) -> "tuple[int, int, int, int]":
        """(incarnation, max stamp, generation, floor) over the
        covering — the read cache's O(|cells|) validity check."""
        return self._table.cell_clock.fence(_to_keys(cells_u64))

    def adopt_cell_clock(self, clock: tiersmod.CellClock) -> None:
        """See MemorySpatialIndex.adopt_cell_clock."""
        self._table.cell_clock = clock

    def stats(self) -> dict:
        out = self._table.stats()
        # serving-pipeline gauges (queue depth, adaptive batch size,
        # pack/device/collect stage totals, shed count) ride along and
        # land in /metrics as dss_dar_<class>_co_* via DSSStore.stats()
        out.update(self._coalescer.stats())
        return out

    @property
    def table(self) -> DarTable:
        return self._table

    @property
    def coalescer(self) -> QueryCoalescer:
        return self._coalescer

    def close(self):
        self._coalescer.close()
        self._table.close()
