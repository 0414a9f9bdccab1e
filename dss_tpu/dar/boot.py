"""Bulk boot: a whole log applied to an empty store as ONE batch.

What a boot needs of its log is the end state.  The per-record path
(DSSStore.apply_log_record) is the serving write path: every record
splices the overlay, stamps its covering and may trigger a fold, which
is right for a tail of a few records and ~8 x too slow for a log of a
million.  Here a Resolver takes the log's records in order, as the
reader decodes them (wal.LogScan: the one read of the file), and keeps
the end state alone: a put is decoded to its model at once and replaces
an earlier one of the same id, a delete removes it, a bump replaces the
live subscription by one with the next notification index.  Nothing of
a record is kept beside that, so memory holds the surviving models and
never the log.  commit() then builds each class's index Records from
flat arrays and each table once (index.bulk_load: one build, one
upload).

The end state is the loop's: the same record maps in the same order,
the same owner interning (every put interns, in log order, also one
that is replaced later), the same notification indices.  What differs
is what a boot cannot observe: the overlay is empty and each touched
class's cell clock has been raised once (bump_all: generation 1, floor
1) where the loop stamps every covering (generation = writes, floor 0).
A fence taken after boot reads the floor; no cache entry is older.

A log that holds a record type this module does not know, or a
document the codec refuses, makes the Resolver give up (`refused` says
why) before anything of the store but its owner interner is touched,
and that only as the loop would; the caller then applies the whole log
by the loop and says so.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Iterable, List, NamedTuple

import numpy as np

from dss_tpu.clock import to_nanos
from dss_tpu.dar import codec
from dss_tpu.dar.oracle import Record
from dss_tpu.dar.wal import FORMAT_RECORD_TYPE
from dss_tpu.geo import s2cell
from dss_tpu.runtime import gc_paused
from dss_tpu.services.serialization import _WIRE

_PUT, _DEL, _BUMP = range(3)


def bump_sub(subs: Dict[str, object], sub_id: str):
    """The next notification index of subscription `sub_id`: a new
    record, equal to the stored one but for the index, stored under
    the id and returned; None if the id is absent.  The one way a
    bump is made, on the leader's notify paths, in a replica's
    `apply_wal` and in a bulk boot.

    Copy-on-write: a lock-free reader that holds the old record keeps
    the old index, since no field of a stored record is ever assigned.
    The copy is shallow, its fields taken as they are with no
    `__init__` run, and it leaves out the old record's remembered wire
    bytes (services/serialization.py `_WIRE`), which its own first
    answer makes anew."""
    sub = subs.get(sub_id)
    if sub is None:
        return None
    fields = dict(sub.__dict__)
    fields.pop(_WIRE, None)
    fields["notification_index"] = sub.notification_index + 1
    bumped = object.__new__(type(sub))
    bumped.__dict__ = fields
    subs[sub_id] = bumped
    return bumped


class _Class(NamedTuple):
    """Where one entity class lives in a DSSStore."""

    sub: str  # the sub-store's attribute of DSSStore
    recmap: str  # its record map {id: model}
    index: str  # its spatial index
    decode: Callable[[dict], object]  # WAL document -> model
    alts: "tuple[str, str]"  # the model's (low, high) altitude fields


_CLASSES = {
    "isa": _Class("rid", "_isas", "_isa_index", codec.doc_to_isa,
                  ("altitude_lo", "altitude_hi")),
    "rid_sub": _Class("rid", "_subs", "_sub_index", codec.doc_to_rid_sub,
                      ("altitude_lo", "altitude_hi")),
    "op": _Class("scd", "_ops", "_op_index", codec.doc_to_op,
                 ("altitude_lower", "altitude_upper")),
    "scd_sub": _Class("scd", "_subs", "_sub_index", codec.doc_to_scd_sub,
                      ("altitude_lo", "altitude_hi")),
    "constraint": _Class("scd", "_csts", "_cst_index",
                         codec.doc_to_constraint,
                         ("altitude_lower", "altitude_upper")),
}

# record type -> (entity class, what it does): the twelve types
# RIDStoreImpl.apply_wal and SCDStoreImpl.apply_wal know
_TYPES = {
    "isa_put": ("isa", _PUT), "isa_del": ("isa", _DEL),
    "rid_sub_put": ("rid_sub", _PUT), "rid_sub_del": ("rid_sub", _DEL),
    "rid_sub_bump": ("rid_sub", _BUMP),
    "scd_op_put": ("op", _PUT), "scd_op_del": ("op", _DEL),
    "scd_sub_put": ("scd_sub", _PUT), "scd_sub_del": ("scd_sub", _DEL),
    "scd_sub_bump": ("scd_sub", _BUMP),
    "scd_cst_put": ("constraint", _PUT), "scd_cst_del": ("constraint", _DEL),
}


class Unbulkable(Exception):
    """The log holds a record type the bulk path cannot take."""


def is_empty(store) -> bool:
    """No record in any class: what a bulk apply may start from."""
    return not any(
        getattr(getattr(store, c.sub), c.recmap) for c in _CLASSES.values()
    )


class Resolver:
    """The end state of a log, resolved record by record."""

    def __init__(self, owners):
        self._owners = owners
        # class -> {id: model}, in the order the loop's maps would hold
        self.live: Dict[str, Dict[str, object]] = {c: {} for c in _CLASSES}
        self.touched = set()  # classes with at least one put
        self.records = 0
        self.seconds = 0.0  # spent in consume(): read, decode, resolve
        self.refused = ""  # why the log cannot be taken as a batch

    def consume(self, recs: Iterable[dict]) -> None:
        """Take the records of `recs` in order.  Interns every put's
        owner, as the loop's _index_* calls do.  Never raises for what
        a record holds: it gives up instead (`refused`), and the caller
        falls back to the loop, which decides what such a log means."""
        t0 = time.perf_counter()
        live, touched = self.live, self.touched
        intern = self._owners.intern
        decoders = {c: spec.decode for c, spec in _CLASSES.items()}
        n = 0
        # a million fresh models: the collector's passes over them find
        # nothing to free and cost more than the decoding
        with gc_paused():
            # what the READER raises (an unsupported format) is not a
            # record's fault and goes up; what a record raises ends
            # the batch
            for rec in recs:
                n += 1
                try:
                    t = rec["t"]
                    kind = _TYPES.get(t)
                    if kind is None:
                        if t == FORMAT_RECORD_TYPE:
                            continue  # gate metadata past the head
                        raise Unbulkable(f"record type {t!r}")
                    cls, act = kind
                    if act == _PUT:
                        model = decoders[cls](rec["doc"])
                        intern(model.owner)
                        live[cls][model.id] = model
                        touched.add(cls)
                    elif act == _DEL:
                        live[cls].pop(rec["id"], None)
                    else:
                        models = live[cls]
                        for i in rec["ids"]:
                            bump_sub(models, i)
                except Exception as e:  # noqa: BLE001 — the loop decides
                    self.refused = f"{type(e).__name__}: {e}"
                    self.live = {c: {} for c in _CLASSES}  # free it now
                    n += sum(1 for _ in recs)  # the log's size, for the loop
                    break
        self.records += n
        self.seconds += time.perf_counter() - t0

    def commit(self, store) -> dict:
        """Fill the EMPTY `store` (caller holds its lock) with the
        resolved state: record maps, one table build per touched class,
        one upload.  -> the boot's account: records, parse_s, build_s,
        postings, device_bytes."""
        if self.refused:
            raise Unbulkable(self.refused)
        if not is_empty(store):
            raise ValueError("bulk apply needs an empty store")
        t0 = time.perf_counter()
        postings = device_bytes = 0
        with gc_paused():
            # every class's Records first: what a document can still
            # refuse (an instant that is not there) it refuses here,
            # with the store untouched
            records = {
                cls: index_records(
                    list(self.live[cls].values()), _CLASSES[cls].alts,
                    self._owners,
                )
                for cls in self.touched
            }
            for cls, spec in _CLASSES.items():
                sub = getattr(store, spec.sub)
                getattr(sub, spec.recmap).update(self.live[cls])
                if cls in records:
                    index = getattr(sub, spec.index)
                    index.bulk_load(records.pop(cls))
                    st = index.stats()
                    postings += st.get("tier_postings", 0)
                    device_bytes += st.get("tier_device_bytes", 0)
        self.live = {}
        return {
            "records": self.records, "parse_s": self.seconds,
            "build_s": time.perf_counter() - t0,
            "postings": int(postings), "device_bytes": int(device_bytes),
        }


def index_records(models: list, alt_attrs, owners) -> List[Record]:
    """Models of one class -> their index Records, as TpuSpatialIndex
    .put / MemorySpatialIndex.put make them one by one: int32 DAR keys
    sorted and unique within a record, unbounded altitudes as +-inf,
    instants in ns.  The keys of all records are computed, sorted and
    deduplicated in flat arrays; each Record holds a slice."""
    n = len(models)
    lens = np.fromiter((len(m.cells) for m in models), np.int64, n)
    total = int(lens.sum())
    if total:
        flat = np.concatenate([m.cells for m in models])
        keys = s2cell.cell_to_dar_key(flat)
        owner = np.repeat(np.arange(n, dtype=np.int64), lens)
        # by record, then by key: `owner` already ascends, so one
        # stable sort of record * 2^32 + (key + 2^31) does both
        order = np.argsort(
            (owner << np.int64(32))
            + (keys.astype(np.int64) + np.int64(1 << 31)),
            kind="stable",
        )
        # `owner` stays as it is: only the inside of its runs moved
        keys = keys[order]
        keep = np.ones(total, bool)
        keep[1:] = (keys[1:] != keys[:-1]) | (owner[1:] != owner[:-1])
        keys = keys[keep]
        lens = np.bincount(owner[keep], minlength=n)
    else:
        keys = np.zeros(0, np.int32)
    ends = np.cumsum(lens).tolist()
    lo_attr, hi_attr = alt_attrs
    intern = owners.intern
    inf = float("inf")
    out = []
    a = 0
    for m, b in zip(models, ends):
        lo, hi = getattr(m, lo_attr), getattr(m, hi_attr)
        out.append(Record(
            m.id,
            keys[a:b],
            -inf if lo is None else float(lo),
            inf if hi is None else float(hi),
            int(to_nanos(m.start_time)),
            int(to_nanos(m.end_time)),
            int(intern(m.owner)),
        ))
        a = b
    return out
