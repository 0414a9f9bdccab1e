"""Append-only write-ahead log: the durable source of truth.

Plays the role CockroachDB plays in the reference (the DAR snapshot is
a cache rebuilt from it; see SURVEY.md §5 checkpoint/resume).  Records
are JSON lines {"seq": n, "t": type, ...}; replay applies them in order
to rebuild store state.  fsync per append is configurable (off by
default: group-commit style durability is the deployment's call, like
the reference's reliance on CRDB commit semantics).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Iterable, Iterator, Optional

from dss_tpu.chaos import fault_point

# Log format version.  A head record {"t": "__format__", "version": N}
# gates boot: replaying a log written by an incompatible future format
# must refuse loudly instead of rebuilding garbage state — the
# reference's schema gate (MustSupportSchema,
# /root/reference/cmds/grpc-backend/main.go:75-86,
# pkg/rid/cockroach/store.go:165-187).  Logs predating versioning
# (no head record) read as version 0, which is compatible.
FORMAT_VERSION = 1
FORMAT_RECORD_TYPE = "__format__"


class LogFormatError(RuntimeError):
    """The log was written by an unsupported (newer) format."""


class LogCorruptError(RuntimeError):
    """The log has an undecodable region FOLLOWED by valid records —
    mid-log corruption (bit rot, partial page write), not a crash-torn
    tail.  Truncating here would silently delete fsync-acked records,
    so boot refuses instead; the file is left byte-for-byte intact for
    repair/forensics (the quarantine).  Operators repair or move the
    file aside explicitly to proceed."""


def format_record() -> dict:
    return {"t": FORMAT_RECORD_TYPE, "version": FORMAT_VERSION}


def check_format_record(rec: Optional[dict], path: str) -> None:
    """Raise LogFormatError if the head record declares an unsupported
    version.  rec=None (legacy headerless log) is accepted."""
    if rec is None or rec.get("t") != FORMAT_RECORD_TYPE:
        return
    v = rec.get("version", 0)
    if not isinstance(v, int) or v > FORMAT_VERSION:
        raise LogFormatError(
            f"log {path} has format version {v}, but this binary "
            f"supports <= {FORMAT_VERSION}; refusing to start "
            "(upgrade the binary or restore a compatible log)"
        )


class LogScan:
    """ONE pass over a log file from byte `offset` (a record boundary):
    iterate it for the records in order; once exhausted, `valid` is the
    end of the valid prefix in bytes and `seq` the highest sequence
    number.  Blank lines pass; the first undecodable, non-object or
    newline-less line ends the prefix (a torn tail, or rot: the caller
    tells them apart).  A scan from offset 0 applies the head format
    gate and raises LogFormatError on an unsupported version.  Format
    records are validated metadata: they count into the prefix and are
    not yielded.

    A record is yielded as soon as it is decoded and not kept: a
    consumer that keeps what it needs of each (dar/boot.py) holds the
    log's end state in memory, never the log."""

    def __init__(self, path: str, offset: int = 0):
        self.path = path
        self.valid = offset
        self.seq = 0
        self._first = offset == 0
        self._records = self._read()  # opens the file at the first next()

    def __iter__(self) -> Iterator[dict]:
        """The pass itself: a second iteration goes on where the
        first stopped."""
        return self._records

    def _read(self) -> Iterator[dict]:
        loads = json.loads
        # line by line off the buffered reader: each line is freed
        # before the next is read
        with open(self.path, "rb", buffering=1 << 20) as fh:
            fh.seek(self.valid)
            for line in fh:
                if not line.endswith(b"\n"):
                    return  # torn tail (no newline): not complete
                try:
                    rec = loads(line)
                except ValueError:
                    # JSONDecodeError, or UnicodeDecodeError from
                    # json's encoding sniff on rotted bytes (e.g. NUL
                    # runs look like UTF-32) — both ValueError
                    if not line.isspace():
                        return
                    rec = None  # a blank line
                if rec is not None:
                    if not isinstance(rec, dict):
                        return  # rot that decodes as a JSON scalar
                    if self._first:
                        self._first = False
                        check_format_record(rec, self.path)
                    if rec.get("t") != FORMAT_RECORD_TYPE:
                        s = rec.get("seq", 0)
                        if s > self.seq:
                            self.seq = s
                        yield rec
                self.valid += len(line)

    def drain(self) -> None:
        """Run the pass to its end for `valid` and `seq` alone."""
        for _ in self._records:
            pass


class WriteAheadLog:
    def __init__(self, path: Optional[str], fsync: bool = False,
                 sink: Optional[Callable[[Iterable[dict]], None]] = None):
        """path=None -> disabled (in-memory deployments / tests).
        `sink` is handed the recovery pass itself, an iterable of the
        log's records in order, so that a boot reads and decodes its
        log ONCE (DSSStore resolves the log's end state through it);
        without it the pass finds the valid prefix and the sequence
        and keeps nothing.  A sink that stops early is drained."""
        self.path = path
        self.fsync = fsync
        self._lock = threading.Lock()
        self._seq = 0
        self._fh = None
        # True when boot recovery truncated a torn tail: with fsync
        # off, acked records may have been lost with the tear, so the
        # log's history is no longer guaranteed to be a superset of
        # what readers saw.  The region log rotates its persisted
        # epoch on this signal (and ONLY this signal or promotion) so
        # clean restarts no longer fence every writer.
        self.recovered_truncation = False
        # what the journal cost since the process started, counted
        # where no request owns the work (under _lock; stats()):
        # appends and the records they wrote, their bytes, fsyncs, and
        # the seconds of the whole append and of os.fsync alone.
        # Recovery and replay move none.
        self.appends = 0
        self.records = 0
        self.bytes = 0
        self.fsyncs = 0
        self.append_s = 0.0
        self.fsync_s = 0.0
        if path is not None:
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            if os.path.exists(path) and os.path.getsize(path) > 0:
                # one recovery pass: format gate + seq recovery + the
                # valid-prefix length.  A crash can leave a torn final
                # line; appending after it would MERGE the next record
                # into one garbage line that a later replay drops
                # (silent loss of that write and everything after it),
                # so truncate to the last complete record first.
                # Truncation is ONLY legal when the invalid region
                # extends to EOF (a true crash tear): valid records
                # after an undecodable line mean mid-log corruption,
                # and deleting them would be silent loss of
                # fsync-acked writes — refuse to start instead.
                scan = LogScan(path)
                if sink is not None:
                    sink(scan)
                scan.drain()
                valid, self._seq = scan.valid, scan.seq
                if valid < os.path.getsize(path):
                    if self._valid_records_after(path, valid):
                        raise LogCorruptError(
                            f"log {path} is corrupt at byte {valid}: "
                            "valid records exist after an undecodable "
                            "region (mid-log corruption, not a crash "
                            "tear).  Refusing to truncate acked "
                            "records; repair the file or move it "
                            "aside to proceed."
                        )
                    with open(path, "r+b") as fh:
                        fh.truncate(valid)
                    self.recovered_truncation = True
            # re-stat AFTER truncation: a fully-torn header line must
            # count as a fresh log and get a fresh format header
            fresh = os.path.getsize(path) == 0 if os.path.exists(
                path
            ) else True
            self._fh = open(path, "a", encoding="utf-8")
            if fresh:
                # header carries no seq: user records stay 1-based
                self._fh.write(
                    json.dumps(format_record(), separators=(",", ":"))
                    + "\n"
                )
                self._fh.flush()

    @staticmethod
    def _valid_records_after(path: str, offset: int) -> bool:
        """True when any complete, decodable JSON record line exists
        AFTER the undecodable line at `offset` — the mid-log-corruption
        discriminator.  A torn tail (the common crash shape) has
        nothing decodable after it; bit rot in the middle does."""
        with open(path, "rb") as fh:
            fh.seek(offset)
            bad = fh.readline()
            if not bad.endswith(b"\n"):
                return False  # the bad region runs to EOF: a tear
            while True:
                line = fh.readline()
                if not line:
                    return False
                if not line.endswith(b"\n"):
                    return False  # only a torn tail remains
                stripped = line.strip()
                if not stripped:
                    continue
                try:
                    rec = json.loads(stripped)
                except ValueError:  # undecodable bytes or bad JSON
                    continue  # more damage; keep scanning
                if isinstance(rec, dict):
                    return True

    @property
    def seq(self) -> int:
        """Last assigned sequence number (leader-side freshness stamp)."""
        return self._seq

    def append(self, *records: dict) -> int:
        """Write `records` as consecutive lines with consecutive seqs:
        one write, one flush and (fsync on) one fsync for all of them,
        so a transaction's records are durable together.  -> the last
        record's seq."""
        with self._lock:
            t0 = time.perf_counter()
            # chaos seam BEFORE the seq assignment/write: an injected
            # append error leaves no half-recorded state, and a delay
            # models a slow disk stalling the writer
            fault_point("wal.append")
            first = self._seq + 1
            self._seq += len(records)
            if self._fh is not None:
                data = "".join(
                    json.dumps(dict(rec, seq=seq), separators=(",", ":"))
                    + "\n"
                    for seq, rec in enumerate(records, first)
                )
                self._fh.write(data)
                self._fh.flush()
                self.bytes += len(data)  # ensure_ascii: chars are bytes
                if self.fsync:
                    self._fsync_locked()
            self.appends += 1
            self.records += len(records)
            self.append_s += time.perf_counter() - t0
            return self._seq

    def _fsync_locked(self) -> None:
        # the chaos seam inside the timer, as append's is: an injected
        # delay models a slow disk and reads as one
        t0 = time.perf_counter()
        fault_point("wal.fsync")
        os.fsync(self._fh.fileno())
        self.fsyncs += 1
        self.fsync_s += time.perf_counter() - t0

    def sync(self) -> None:
        """fsync the log regardless of the per-append fsync setting —
        for rare, must-survive records (epoch rotations) on deployments
        that run with fsync off for throughput."""
        with self._lock:
            if self._fh is not None:
                self._fh.flush()
                self._fsync_locked()

    def stats(self) -> dict:
        """The dss_wal_* counters (DSSStore.stats, the leader's
        /metrics): a rising fsync mean is the disk."""
        with self._lock:
            return {
                "dss_wal_appends_total": self.appends,
                "dss_wal_records_total": self.records,
                "dss_wal_bytes_total": self.bytes,
                "dss_wal_fsyncs_total": self.fsyncs,
                "dss_wal_append_seconds_total": round(self.append_s, 6),
                "dss_wal_fsync_seconds_total": round(self.fsync_s, 6),
            }

    def replay(self) -> Iterator[dict]:
        """Yield records in order; tolerates a torn final line.  Raises
        LogFormatError if the head record declares an unsupported
        format (the boot gate)."""
        if self.path is None or not os.path.exists(self.path):
            return
        yield from LogScan(self.path)

    def adopt(self, tmp_path: str, seq: int) -> None:
        """Swap a fully-written, fsynced replacement log into place:
        rename over the old log and reopen for append.  The caller
        guarantees no append races the swap (e.g. by staging the swap
        on the thread that owns all appends)."""
        if self.path is None:
            return
        with self._lock:
            if self._fh is not None:
                self._fh.close()
            os.replace(tmp_path, self.path)
            self._seq = seq
            self._fh = open(self.path, "a", encoding="utf-8")

    def close(self):
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None
