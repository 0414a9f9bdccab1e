#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path still starts
on the chip.

One command, no arguments: builds the native host library from source,
generates a dense-urban WAL from --seed (BASELINE config 4: 1,000,000
altitude-stratified op intents of 2-12 level-13 cells over one metro
area, plus ISAs and RID/SCD subscriptions), boots the normal server
binary behind the shm front

    python -m dss_tpu.cmds.server --addr :<port> --enable_scd
        --storage tpu --insecure_no_auth --wal_path <wal> --workers 2

with JAX_PLATFORMS=tpu in the child's environment (JAX itself raises
when no chip is found), drives RID and SCD requests over HTTP, compares
every answer ID set for ID set with a plain numpy reference built from
the records this process generated, proves from the device owner's
counters that the device carried work, and requires a clean SIGTERM
exit.  The last line of stdout is the result, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

with the device as the serving process reported it.  The line before it
is one JSON object {"facts": {...}} with the facts of the run (sizes,
set-up and compile seconds, plan counts per route, learned dispatch
floors).  Any failed step exits non-zero and prints neither line.

This process never imports JAX: one process holds the chip — the server
leader.  The only other modes are explicit:

  --rehearse-cpu   tiny sizes on JAX_PLATFORMS=cpu for the sandbox and
                   tier-1; the output says "platform": "cpu",
                   "rehearsal": true.  The default never falls back.
  --mesh dp,sp     the same steps with --sharded_replica dp,sp (four
                   chips: --mesh 1,4), asserting mesh plans and shard
                   placement on distinct devices.
"""

from __future__ import annotations

import argparse
import http.client
import importlib.metadata
import importlib.util
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
HOUR = 3600
NS = 1_000_000_000
OWNERS = 64  # USSs that wrote the preloaded entities
DEADLINE_S = 1170


class SmokeFailure(Exception):
    """A step of the smoke failed; the message says which and why."""


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.monotonic()


# ---------------------------------------------------------------------------
# set-up: native library, accelerator probe
# ---------------------------------------------------------------------------


def build_native() -> float:
    """Rebuild libdsscover.so from the committed sources, always: the
    .so on disk may come from another machine (its digest sidecar
    covers the sources only), and the numpy fallback must not be what
    is smoked.  _buildlib is loaded by path so importing the package
    cannot start its own background build first."""
    native_dir = os.path.join(REPO, "dss_tpu", "native")
    if not os.path.isdir(native_dir):
        raise SmokeFailure(
            f"no checkout around {__file__}: dss_tpu/native is missing"
        )
    spec = importlib.util.spec_from_file_location(
        "_dss_buildlib", os.path.join(native_dir, "_buildlib.py")
    )
    buildlib = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(buildlib)
    t0 = time.monotonic()
    if not buildlib.build(native_dir):
        raise SmokeFailure("g++ build of libdsscover.so failed")
    from dss_tpu import native

    if not native.ensure_built():
        raise SmokeFailure("freshly built libdsscover.so does not load")
    return time.monotonic() - t0


def probe_accelerator(platform: str, env: dict) -> dict:
    """Ask JAX, in a child with the server's environment that exits
    before the server starts, what it finds on `platform`.  Fails fast
    on a machine without the chip — before a million records are
    generated for nothing."""
    code = (
        "import json, jax; d = jax.devices(); "
        "print(json.dumps({'platform': d[0].platform, "
        "'kind': d[0].device_kind, 'count': len(d)}))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300,
    )
    if proc.returncode != 0:
        tail = proc.stderr.strip().splitlines()[-1:] or ["(no output)"]
        raise SmokeFailure(
            f"JAX found no {platform.upper()} on this machine "
            f"(JAX_PLATFORMS={platform}): {tail[0]}"
        )
    found = json.loads(proc.stdout.strip().splitlines()[-1])
    if found["platform"] != platform:
        raise SmokeFailure(
            f"asked JAX for {platform}, it resolved {found['platform']}"
        )
    return found


# ---------------------------------------------------------------------------
# the deployment: one metro area of level-13 cells, seeded entities
# ---------------------------------------------------------------------------


class Metro:
    """A G x G block of level-13 S2 cells on one cube face around a
    city centre (~1.27 km^2 per cell).  Cells are addressed by their
    (i, j) position in the block; on a cube face straight lines in
    (u, v) are geodesics, so a polygon with vertices inside the corner
    cells of a rectangular sub-block covers exactly that sub-block."""

    LAT, LNG = 34.05, -118.25

    def __init__(self, g: int):
        from dss_tpu.geo import s2cell

        self._s2 = s2cell
        self.g = g
        leaf = s2cell.cell_id_from_latlng(
            np.array([self.LAT]), np.array([self.LNG])
        )
        face, i, j, _ = s2cell.to_face_ij(leaf)
        self.face = int(face[0])
        self._shift = s2cell.MAX_LEVEL - s2cell.DAR_LEVEL
        self.i0 = (int(i[0]) >> self._shift) - g // 2
        self.j0 = (int(j[0]) >> self._shift) - g // 2
        ii, jj = np.meshgrid(np.arange(g), np.arange(g), indexing="ij")
        leaf_ij = lambda a, base: (a + base).astype(np.int64) << self._shift
        cells = s2cell.cell_parent(
            s2cell.from_face_ij(
                np.full(g * g, self.face),
                leaf_ij(ii.ravel(), self.i0),
                leaf_ij(jj.ravel(), self.j0),
            ),
            s2cell.DAR_LEVEL,
        )
        self.cells = cells.astype(np.uint64)  # flat index i * g + j
        order = np.argsort(self.cells)
        self._sorted_cells = self.cells[order]
        self._sorted_flat = order

    def flat_of(self, cell_ids: np.ndarray) -> np.ndarray:
        """Level-13 cell ids -> flat block indices (-1 outside)."""
        cell_ids = np.asarray(cell_ids, np.uint64)
        pos = np.searchsorted(self._sorted_cells, cell_ids)
        pos = np.minimum(pos, len(self._sorted_cells) - 1)
        hit = self._sorted_cells[pos] == cell_ids
        return np.where(hit, self._sorted_flat[pos], -1)

    def latlng(self, fi: float, fj: float):
        """Fractional block position -> (lat, lng) degrees."""
        s2 = self._s2
        size = float(1 << s2.MAX_LEVEL)
        s = (self.i0 + fi) * (1 << self._shift) / size
        t = (self.j0 + fj) * (1 << self._shift) / size
        xyz = s2.face_uv_to_xyz(
            np.array([self.face]),
            s2.st_to_uv(np.array([s])), s2.st_to_uv(np.array([t])),
        )
        lat, lng = s2.xyz_to_latlng(xyz)
        return float(np.ravel(lat)[0]), float(np.ravel(lng)[0])

    def rect(self, i: int, j: int, w: int, h: int) -> list:
        """Polygon vertices (lat/lng dicts) whose covering is the
        w x h sub-block at (i, j): corners a quarter cell inside."""
        pts = [
            (i + 0.25, j + 0.25), (i + w - 0.25, j + 0.25),
            (i + w - 0.25, j + h - 0.25), (i + 0.25, j + h - 0.25),
        ]
        return [
            dict(zip(("lat", "lng"), self.latlng(a, b))) for a, b in pts
        ]


def _uuids(rng, n: int) -> list:
    h = rng.bytes(16 * n).hex()
    return [
        f"{h[k:k + 8]}-{h[k + 8:k + 12]}-4{h[k + 13:k + 16]}-"
        f"8{h[k + 17:k + 20]}-{h[k + 20:k + 32]}"
        for k in range(0, 32 * n, 32)
    ]


class EntitySet:
    """One entity class as the reference holds it: columns plus a
    cell -> entity postings index over the metro's flat cell space."""

    def __init__(self, ids, flat_cells, counts, alt_lo, alt_hi, t0, t1):
        self.ids = np.asarray(ids, dtype=object)
        self.alt_lo = np.asarray(alt_lo, np.float64)
        self.alt_hi = np.asarray(alt_hi, np.float64)
        self.t0 = np.asarray(t0, np.int64)  # ns
        self.t1 = np.asarray(t1, np.int64)
        ent = np.repeat(np.arange(len(ids)), counts)
        order = np.argsort(flat_cells, kind="stable")
        self._post_cell = np.asarray(flat_cells)[order]
        self._post_ent = ent[order]

    def search(self, flat, alt_lo=None, alt_hi=None, t0=None, t1=None,
               *, now: int) -> set:
        """The plain semantics of every DSS search: shares a cell AND
        altitude ranges overlap AND time ranges overlap AND not ended
        (t_end >= now).  Unbounded sides are None."""
        flat = np.asarray(flat)
        flat = flat[flat >= 0]
        lo = np.searchsorted(self._post_cell, flat, side="left")
        hi = np.searchsorted(self._post_cell, flat, side="right")
        if not len(flat) or not (hi > lo).any():
            return set()
        cand = np.unique(np.concatenate(
            [self._post_ent[a:b] for a, b in zip(lo, hi) if b > a]
        ))
        keep = self.t1[cand] >= now
        if alt_lo is not None:
            keep &= self.alt_hi[cand] >= alt_lo
        if alt_hi is not None:
            keep &= self.alt_lo[cand] <= alt_hi
        if t0 is not None:
            keep &= self.t1[cand] >= t0
        if t1 is not None:
            keep &= self.t0[cand] <= t1
        return set(self.ids[cand[keep]].tolist())


def _footprints(rng, n: int, g: int, kmin: int, kmax: int):
    """L-shaped (concave) footprints of kmin..kmax cells: a row arm and
    a column arm from a shared corner.  -> (flat cells, counts)."""
    half = (kmax + 1) // 2
    a = rng.integers(1, half + 2, n)  # row arm, 1..half+1
    b = rng.integers(1, kmax - half + 1, n)  # column arm
    a = np.where(a + b - 1 < kmin, kmin, a)
    ci = rng.integers(0, g - a.max(), n)
    cj = rng.integers(0, g - b.max(), n)
    counts = a + b - 1
    owner = np.repeat(np.arange(n), counts)
    k = np.arange(counts.sum()) - np.repeat(np.cumsum(counts) - counts, counts)
    on_row = k < a[owner]
    di = np.where(on_row, k, 0)
    dj = np.where(on_row, 0, k - a[owner] + 1)
    return (ci[owner] + di) * g + (cj[owner] + dj), counts


def _times(rng, n: int, t_gen: int):
    """Whole-second windows around the real now: starts within +-6 h,
    30 min .. 4 h long; about a third have already ended.  No window
    ends within an hour of t_gen, so 'not ended' has one answer for
    the whole run."""
    t0 = t_gen + rng.integers(-6 * HOUR, 6 * HOUR, n)
    t1 = t0 + rng.integers(HOUR // 2, 4 * HOUR, n)
    near = np.abs(t1 - t_gen) < HOUR
    t1 = np.where(near, t1 + 2 * HOUR, t1)
    return t0 * NS, t1 * NS


def generate(seed: int, sizes: dict, t_gen: int, wal_path: str):
    """Write the WAL and return (metro, reference sets).  Everything
    derives from `seed` and the generation second `t_gen`."""
    rng = np.random.default_rng(seed)
    metro = Metro(sizes["grid"])
    g = metro.g
    cell_str = np.array([str(int(c)) for c in metro.cells], dtype=object)
    ref = {}
    seq = 0
    with open(wal_path, "w", encoding="utf-8") as wal:
        wal.write('{"t":"__format__","version":1}\n')

        def emit(kind: str, n: int, kmin: int, kmax: int, doc_fn,
                 stratified: bool):
            nonlocal seq
            flat, counts = _footprints(rng, n, g, kmin, kmax)
            ids = _uuids(rng, n)
            if stratified:
                # 60 strata of 50 m; quarter-metre values are exact in
                # float32, the width the DAR stores altitudes in
                lo = (rng.integers(0, 60, n) * 50
                      + rng.integers(0, 80, n) * 0.25)
                hi = lo + 20 + rng.integers(0, 100, n) * 0.25
            else:
                lo, hi = np.zeros(n), np.full(n, 3000.0)
            t0, t1 = _times(rng, n, t_gen)
            owners = rng.integers(0, OWNERS, n)
            joined = cell_str[flat]
            pos = 0
            lines = []
            for k in range(n):
                seq += 1
                c = counts[k]
                lines.append(doc_fn(
                    ids[k], owners[k], ",".join(joined[pos:pos + c]),
                    lo[k], hi[k], t0[k], t1[k], seq,
                ))
                pos += c
                if len(lines) == 20000:
                    wal.write("".join(lines))
                    lines = []
            wal.write("".join(lines))
            ref[kind] = EntitySet(ids, flat, counts, lo, hi, t0, t1)

        emit("op", sizes["ops"], 2, 12, (
            lambda i, o, cells, lo, hi, t0, t1, s:
            f'{{"t":"scd_op_put","doc":{{"id":"{i}","owner":"uss{o}",'
            f'"version":1,"ovn":"ovn-{i[:13]}","start_time":{t0},'
            f'"end_time":{t1},"altitude_lower":{lo},"altitude_upper":{hi},'
            f'"uss_base_url":"https://uss{o}.example/scd",'
            f'"state":"Accepted","cells":[{cells}],"subscription_id":"",'
            f'"constraint_aware":false}},"seq":{s}}}\n'
        ), True)
        emit("isa", sizes["isas"], 4, 24, (
            lambda i, o, cells, lo, hi, t0, t1, s:
            f'{{"t":"isa_put","doc":{{"id":"{i}","owner":"uss{o}",'
            f'"url":"https://uss{o}.example/flights","cells":[{cells}],'
            f'"start_time":{t0},"end_time":{t1},"version":"1smoke",'
            f'"altitude_hi":{hi},"altitude_lo":{lo}}},"seq":{s}}}\n'
        ), False)
        emit("rid_sub", sizes["rid_subs"], 4, 24, (
            lambda i, o, cells, lo, hi, t0, t1, s:
            f'{{"t":"rid_sub_put","doc":{{"id":"{i}","owner":"uss{o}",'
            f'"url":"https://uss{o}.example/isa","notification_index":0,'
            f'"cells":[{cells}],"start_time":{t0},"end_time":{t1},'
            f'"version":"1smoke","altitude_hi":{hi},"altitude_lo":{lo}}},'
            f'"seq":{s}}}\n'
        ), False)
        emit("scd_sub", sizes["scd_subs"], 4, 24, (
            lambda i, o, cells, lo, hi, t0, t1, s:
            f'{{"t":"scd_sub_put","doc":{{"id":"{i}","owner":"uss{o}",'
            f'"version":1,"notification_index":0,"start_time":{t0},'
            f'"end_time":{t1},"altitude_hi":{hi},"altitude_lo":{lo},'
            f'"base_url":"https://uss{o}.example/scd",'
            f'"notify_for_operations":true,"notify_for_constraints":false,'
            f'"implicit_subscription":false,"dependent_operations":[],'
            f'"cells":[{cells}]}},"seq":{s}}}\n'
        ), False)
    return metro, ref


# ---------------------------------------------------------------------------
# the server under test
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """The leader process (device owner), its stderr, and the URLs of
    the front: workers own the public port, the leader only its
    internal loopback."""

    def __init__(self, argv, env, stderr_path):
        self.stderr_path = stderr_path
        self._stderr = open(stderr_path, "wb")
        self.t_spawn = time.monotonic()
        self.proc = subprocess.Popen(
            argv, env=env, cwd=REPO, stdout=subprocess.DEVNULL,
            stderr=self._stderr,
        )
        self.port = int(argv[argv.index("--addr") + 1].lstrip(":"))
        self.leader_url = ""

    def log_records(self):
        """(json records, other lines) of the combined stderr of the
        leader and its workers (they inherit the descriptor)."""
        recs, other = [], []
        with open(self.stderr_path, "r", encoding="utf-8",
                  errors="replace") as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    rec = None
                if isinstance(rec, dict) and "level" in rec:
                    recs.append(rec)
                else:
                    other.append(line)
        return recs, other

    def worker_pids(self) -> dict:
        """{worker index: pid} from /proc cmdlines of this front."""
        out = {}
        for pid in os.listdir("/proc"):
            if not pid.isdigit():
                continue
            try:
                with open(f"/proc/{pid}/cmdline", "rb") as fh:
                    cmd = fh.read().decode(errors="replace").split("\0")
            except OSError:
                continue
            if "--shm_worker_index" in cmd and f":{self.port}" in cmd:
                out[int(cmd[cmd.index("--shm_worker_index") + 1])] = int(pid)
                self.leader_url = cmd[cmd.index("--leader_url") + 1]
        return out

    def check_alive(self, what: str) -> None:
        rc = self.proc.poll()
        if rc is not None:
            _, other = self.log_records()
            raise SmokeFailure(
                f"server exited with code {rc} while {what}: "
                + " | ".join(other[-6:])
            )

    def kill(self) -> None:
        for pid in self.worker_pids().values():
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self._stderr.close()


def http_json(base: str, method: str, path: str, body=None,
              timeout: float = 120.0):
    """One request on a fresh connection -> (status, parsed body;
    the text itself when it is not JSON)."""
    host, port = base.replace("http://", "").split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        data = None if body is None else json.dumps(body)
        conn.request(
            method, path, body=data,
            headers={"Content-Type": "application/json"} if data else {},
        )
        resp = conn.getresponse()
        raw = resp.read()
        try:
            return resp.status, json.loads(raw) if raw else None
        except ValueError:
            return resp.status, raw.decode(errors="replace")
    finally:
        conn.close()


def scrape(base: str) -> dict:
    """/metrics -> {name: value} for unlabeled series, plus
    name{labels} keys verbatim for labeled ones."""
    out = {}
    for line in http_json(base, "GET", "/metrics")[1].splitlines():
        m = re.match(r"^(\w+)(\{[^}]*\})?\s+([0-9.eE+-]+)$", line)
        if m:
            labels = m.group(2) or ""
            # the per-process label is the only one on scalar gauges
            key = m.group(1) if re.fullmatch(
                r'\{process="[^"]*"\}', labels
            ) else m.group(1) + labels
            out[key] = float(m.group(3))
    return out


# ---------------------------------------------------------------------------
# the drive
# ---------------------------------------------------------------------------


def iso(t_s: int) -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_s))


class Smoke:
    def __init__(self, seed, sizes, metro, ref, t_gen, server):
        self.seed = seed
        self.sizes = sizes
        self.metro = metro
        self.ref = ref
        self.t_gen = t_gen
        self.srv = server
        self.base = f"http://127.0.0.1:{server.port}"
        self.rng = np.random.default_rng(seed + 1)
        self.sent = 0
        self.matched = 0
        self.retries = 0
        # op intents this run PUT (the reference's overlay): id ->
        # (flat cells, alt_lo, alt_hi, t0 ns, t1 ns)
        self.written = {}

    # -- plumbing ------------------------------------------------------------

    def call(self, method, path, body=None, *, ok=(200,)):
        """A request that must be answered: overload and deadline
        verdicts (429/503/504 — the server's honest 'not now', e.g. a
        first-shape XLA compile on the request path) are retried a
        bounded number of times and counted."""
        for attempt in range(8):
            self.sent += 1
            status, doc = http_json(self.base, method, path, body)
            if status in ok:
                return status, doc
            if status not in (429, 503, 504):
                break
            self.retries += 1
            time.sleep(min(0.5 * (attempt + 1), 3.0))
        raise SmokeFailure(
            f"{method} {path} -> {status}: {str(doc)[:300]}"
        )

    def random_rect(self, w: int, h: int) -> list:
        """Polygon over a w x h cell rectangle somewhere in the metro."""
        i = int(self.rng.integers(0, self.metro.g - w))
        j = int(self.rng.integers(0, self.metro.g - h))
        return self.metro.rect(i, j, w, h)

    def covering_flat(self, vertices) -> np.ndarray:
        """Flat metro indices of the polygon's covering (-1 outside)."""
        from dss_tpu import geo

        cells = geo.covering_polygon(
            [(v["lat"], v["lng"]) for v in vertices]
        )
        return self.metro.flat_of(cells)

    def expect_ops(self, flat, alt_lo, alt_hi, t0_s, t1_s) -> set:
        """Reference answer to an op-intent search issued now."""
        now = time.time_ns()
        t0 = None if t0_s is None else t0_s * NS
        t1 = None if t1_s is None else t1_s * NS
        want = self.ref["op"].search(
            flat, alt_lo, alt_hi, t0, t1, now=now
        )
        fset = set(flat[flat >= 0].tolist())
        for oid, (cells, lo, hi, a, b) in self.written.items():
            if (
                fset & cells and hi >= alt_lo and lo <= alt_hi
                and b >= max(now, t0 or 0) and (t1 is None or a <= t1)
            ):
                want.add(oid)
        return want

    def same(self, what: str, got, want) -> None:
        got, want = set(got), set(want)
        if got != want:
            raise SmokeFailure(
                f"{what}: answer differs from the reference — "
                f"{len(got - want)} unexpected {sorted(got - want)[:3]}, "
                f"{len(want - got)} missing {sorted(want - got)[:3]} "
                f"(want {len(want)})"
            )
        self.matched += 1

    def aoi(self, vertices, alt_lo, alt_hi, t0_s, t1_s) -> dict:
        vol = {
            "volume": {
                "outline_polygon": {"vertices": vertices},
                "altitude_lower": {"value": alt_lo, "reference": "W84",
                                   "units": "M"},
                "altitude_upper": {"value": alt_hi, "reference": "W84",
                                   "units": "M"},
            },
        }
        if t0_s is not None:
            vol["time_start"] = {"value": iso(t0_s), "format": "RFC3339"}
        if t1_s is not None:
            vol["time_end"] = {"value": iso(t1_s), "format": "RFC3339"}
        return vol

    def search_case(self, w: int, h: int, band: float, timed: bool):
        """A distinct-area op-intent search and its expected answer
        inputs: a w x h cell rectangle somewhere in the metro, an
        altitude band, and (timed) a window that opens two hours out —
        past every 'ended' ambiguity — or (untimed) none, which makes
        the server's own clock the lower bound."""
        verts = self.random_rect(w, h)
        alt_lo = float(self.rng.integers(0, 2900 * 4)) * 0.25
        alt_hi = alt_lo + band
        if timed:
            t0 = self.t_gen + 2 * HOUR + int(self.rng.integers(0, 2 * HOUR))
            t1 = t0 + int(self.rng.integers(HOUR // 4, HOUR))
        else:
            t0 = t1 = None
        return verts, alt_lo, alt_hi, t0, t1

    def run_search(self, case, *, what="op search"):
        verts, alt_lo, alt_hi, t0, t1 = case
        flat = self.covering_flat(verts)
        _, doc = self.call(
            "POST", "/dss/v1/operation_references/query",
            {"area_of_interest": self.aoi(verts, alt_lo, alt_hi, t0, t1)},
        )
        # the reference is taken AFTER the answer: both clocks only
        # move forward and no record ends within an hour of the run
        want = self.expect_ops(flat, alt_lo, alt_hi, t0, t1)
        self.same(
            what, (o["id"] for o in doc["operation_references"]), want
        )
        return len(want)

    # -- phases --------------------------------------------------------------

    def phase_scd_writes(self, n: int) -> dict:
        """PUT op intents the way a USS does: an empty key is refused
        with the conflicting intents and their OVNs (the precheck
        search), the retry with that key lands (WAL + overlay), and the
        intent reads back through a worker by id and by search."""
        conflicts = 0
        for k in range(n):
            w, h = int(self.rng.integers(1, 4)), int(self.rng.integers(1, 4))
            verts, alt_lo, alt_hi, t0, t1 = self.search_case(w, h, 40.0, True)
            flat = self.covering_flat(verts)
            oid = str(uuid.UUID(int=(0x5C0D << 100) | (self.seed << 32) | k,
                                version=4))
            body = {
                "extents": [self.aoi(verts, alt_lo, alt_hi, t0, t1)],
                "old_version": 0,
                "state": "Accepted",
                "uss_base_url": "https://smoke.example/scd",
                "new_subscription": {
                    "uss_base_url": "https://smoke.example/scd"},
                "key": [],
            }
            path = f"/dss/v1/operation_references/{oid}"
            want = self.expect_ops(flat, alt_lo, alt_hi, t0, t1)
            status, doc = self.call("PUT", path, body, ok=(200, 409))
            if want:
                if status != 409:
                    raise SmokeFailure(
                        f"PUT {oid} with an empty key over {len(want)} "
                        f"conflicting intents returned {status}, not 409"
                    )
                got = doc["entity_conflicts"]
                self.same(
                    "conflict set of a refused PUT",
                    (c["operation_reference"]["id"] for c in got), want,
                )
                conflicts += len(got)
                body["key"] = [
                    c["operation_reference"]["ovn"] for c in got
                ]
                status, doc = self.call("PUT", path, body)
            elif status != 200:
                raise SmokeFailure(
                    f"PUT {oid} over empty airspace returned {status}"
                )
            subs = {
                s["subscription_id"]
                for grp in doc["subscribers"] for s in grp["subscriptions"]
            }
            want_subs = self.ref["scd_sub"].search(flat, now=time.time_ns())
            want_subs.add(doc["operation_reference"]["subscription_id"])
            self.same("subscribers of an op PUT", subs, want_subs)
            self.written[oid] = (
                set(flat[flat >= 0].tolist()), alt_lo, alt_hi,
                t0 * NS, t1 * NS,
            )
            _, got = self.call("GET", path)
            if got["operation_reference"]["id"] != oid:
                raise SmokeFailure(f"GET {oid} read back another intent")
            self.matched += 1
            n_hit = self.run_search(
                (verts, alt_lo, alt_hi, t0, t1),
                what="search after an op PUT",
            )
            if n_hit < 1:
                raise SmokeFailure("an acknowledged PUT is not searchable")
        return {"puts": n, "conflicts_listed": conflicts}

    def phase_rid(self) -> dict:
        """ISA put + search + subscription on the RID side."""
        verts = self.random_rect(3, 2)
        flat = self.covering_flat(verts)
        t0, t1 = self.t_gen + 2 * HOUR, self.t_gen + 3 * HOUR
        extents = {
            "spatial_volume": {
                "footprint": {"vertices": verts},
                "altitude_lo": 0.0, "altitude_hi": 120.0,
            },
            "time_start": iso(t0), "time_end": iso(t1),
        }
        isa_id = str(uuid.UUID(int=(0x15A << 100) | self.seed,
                               version=4))
        _, doc = self.call(
            "PUT", f"/v1/dss/identification_service_areas/{isa_id}",
            {"extents": extents,
             "flights_url": "https://smoke.example/flights"},
        )
        self.same(
            "subscribers of an ISA PUT",
            (s["subscriptions"][0]["subscription_id"]
             for s in doc["subscribers"]),
            self.ref["rid_sub"].search(flat, now=time.time_ns()),
        )
        area = ",".join(f"{v['lat']!r},{v['lng']!r}" for v in verts)
        _, doc = self.call(
            "GET",
            f"/v1/dss/identification_service_areas?area={area}"
            f"&earliest_time={iso(t0)}&latest_time={iso(t1)}",
        )
        want = self.ref["isa"].search(
            flat, t0=t0 * NS, t1=t1 * NS, now=time.time_ns()
        ) | {isa_id}
        self.same("ISA search", (a["id"] for a in doc["service_areas"]), want)
        sub_id = str(uuid.UUID(int=(0x5AB << 100) | self.seed,
                               version=4))
        _, doc = self.call(
            "PUT", f"/v1/dss/subscriptions/{sub_id}",
            {"extents": extents, "callbacks": {
                "identification_service_area_url":
                    "https://smoke.example/isa"}},
        )
        # a new subscription is told of every ISA in its area that has
        # not ended — not only those inside its own time window
        self.same(
            "ISAs returned to a new RID subscription",
            (a["id"] for a in doc["service_areas"]),
            self.ref["isa"].search(flat, now=time.time_ns()) | {isa_id},
        )
        return {"isa": isa_id, "subscription": sub_id,
                "isas_in_window": len(want)}

    def phase_lone(self, n: int) -> dict:
        """Lone small searches, one at a time: the planner's inline /
        host route by design (recorded, not required)."""
        hits = 0
        for _ in range(n):
            hits += self.run_search(
                self.search_case(2, 2, 60.0, bool(self.rng.integers(0, 2))),
                what="lone op search",
            )
        return {"searches": n, "hits": hits}

    def burst_cases(self, n: int, big_every: int = 4) -> list:
        """n distinct-area searches.  Three in four are small polls; one
        in four covers 144-196 cells (the reference's area limit allows
        ~210) — more candidate postings than the host path may scan
        (FastTable.HOST_MAX_CANDIDATES), which is what sends a drained
        batch to the device."""
        big = self.sizes["big_side"]
        out = []
        for k in range(n):
            if big_every and k % big_every == 0:
                w = int(self.rng.integers(big - 2, big + 1))
                h = int(self.rng.integers(big - 2, big + 1))
                band = 10.0
            else:
                w = int(self.rng.integers(1, 5))
                h = int(self.rng.integers(1, 5))
                band = 60.0
            out.append(self.search_case(w, h, band, k % 5 != 0))
        return out

    def phase_burst(self, n: int, rounds: int, big_every: int = 4) -> dict:
        """`rounds` bursts of n distinct-area searches, all in flight
        at once from n client threads."""
        hits = 0
        walls = []
        with ThreadPoolExecutor(max_workers=n) as pool:
            for r in range(rounds):
                cases = self.burst_cases(n, big_every)
                gate = threading.Barrier(n)

                def one(case):
                    gate.wait()
                    return self.run_search(case, what=f"burst {r} search")

                t0 = time.monotonic()
                hits += sum(pool.map(one, cases))
                walls.append(round(time.monotonic() - t0, 3))
                self.srv.check_alive(f"serving burst {r}")
        return {"in_flight": n, "rounds": rounds, "hits": hits,
                "round_wall_s": walls}

    def phase_respawn(self) -> dict:
        """SIGKILL one worker: the leader must reap and respawn it
        while it keeps the chip, and the survivor must keep answering
        correctly.  The respawned worker replays the whole WAL before
        it serves; that wait is bounded and its outcome recorded."""
        pids = self.srv.worker_pids()
        victim = min(pids)
        os.kill(pids[victim], signal.SIGKILL)
        t0 = time.monotonic()
        new_pid = None
        while time.monotonic() - t0 < 60:
            now = self.srv.worker_pids()
            if now.get(victim) not in (None, pids[victim]):
                new_pid = now[victim]
                break
            time.sleep(0.25)
        if new_pid is None:
            raise SmokeFailure("the leader did not respawn a killed worker")
        for _ in range(6):  # the survivor owns the public port meanwhile
            self.run_search(
                self.search_case(2, 3, 60.0, True),
                what="search while a worker respawns",
            )
        self.srv.check_alive("respawning a worker")
        ready = False
        deadline = time.monotonic() + self.sizes["respawn_wait_s"]
        while time.monotonic() < deadline and not ready:
            m = scrape(self.srv.leader_url)
            ready = m.get("dss_shm_dead_workers", 1.0) == 0.0
            time.sleep(0.5)
        return {"killed_pid": pids[victim], "respawned_pid": new_pid,
                "revived_within_wait": ready}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

FULL = {
    "ops": 1_000_000, "isas": 4000, "rid_subs": 2000, "scd_subs": 2000,
    "grid": 96, "big_side": 14, "burst": 512, "rounds": 3, "puts": 6,
    # /healthy came 868-887 s after spawn in four cold runs on the chip;
    # the requests and the shutdown after it take under a minute, and the
    # whole command stops itself at DEADLINE_S
    "lone": 8, "boot_timeout_s": 1030, "respawn_wait_s": 5,
}
# the rehearsal keeps the full run's density (~700 op postings per
# cell) on a 16 x 16 metro, so its large searches also overflow the
# host path's candidate cap and the device route runs on the CPU backend
REHEARSAL = {
    "ops": 28_000, "isas": 60, "rid_subs": 40, "scd_subs": 40,
    "grid": 16, "big_side": 10, "burst": 48, "rounds": 2, "puts": 3,
    "lone": 4, "boot_timeout_s": 450, "respawn_wait_s": 90,
}


def wait_ready(srv: Server, sizes: dict) -> dict:
    """Block until the front answers /healthy, both workers hold ring
    rows, and the leader's boot warm (fused-kernel warm-up + resident
    AOT grid) has logged its end — compile time is set-up, so it must
    be over before the first request is sent."""
    base = f"http://127.0.0.1:{srv.port}"
    deadline = srv.t_spawn + sizes["boot_timeout_s"]
    t_healthy = None
    while time.monotonic() < deadline:
        srv.check_alive("booting")
        try:
            if http_json(base, "GET", "/healthy", timeout=5)[0] == 200:
                t_healthy = time.monotonic() - srv.t_spawn
                break
        except OSError:
            pass
        time.sleep(1.0)
    if t_healthy is None:
        raise SmokeFailure(
            f"/healthy did not answer within {sizes['boot_timeout_s']} s"
        )
    log(f"/healthy after {t_healthy:.1f}s; waiting for workers + boot warm")
    warm = None
    while time.monotonic() < deadline:
        srv.check_alive("warming")
        recs, _ = srv.log_records()
        warm = next((r for r in recs if r.get("msg", "").startswith(
            "resident AOT warm:")), None)
        have_workers = (
            len(srv.worker_pids()) >= sizes["workers"] and srv.leader_url
        )
        if warm is not None and have_workers:
            break
        time.sleep(1.0)
    else:
        raise SmokeFailure("boot warm / workers not ready in time")
    t_warm = time.monotonic() - srv.t_spawn
    # the leader's WAL replay: from its backend line (the last thing
    # logged before the store is built) to "store ready"
    leader = [r for r in recs if r.get("logger") == "dss.server"]
    ts = {k: next(r["ts"] for r in leader if r["msg"].startswith(k))
          for k in ("backend:", "store ready:")}
    m = scrape(srv.leader_url)
    return {
        "healthy_s": round(t_healthy, 1),
        "warm_done_s": round(t_warm, 1),
        "replay_s": round(ts["store ready:"] - ts["backend:"], 1),
        # every XLA compile of the leader up to here is set-up
        "compiles": int(m.get("dss_jax_compiles", 0)),
        "compile_s": round(m.get("dss_jax_compile_seconds", 0.0), 1),
        "compile_cache_hits": int(m.get("dss_jax_compile_cache_hits", 0)),
        "aot_executables": int(m.get("dss_dar_op_co_res_aot_buckets", 0)),
        "aot_compile_s": round(
            m.get("dss_dar_op_co_res_aot_compile_ms_total", 0) / 1000, 1
        ),
    }


def verify_and_summarize(smoke: Smoke, srv: Server, boot: dict, m0: dict,
                         m1: dict, want_platform: str) -> dict:
    """The acceptance checks over the device owner's own counters."""
    def plan(m, route):
        return int(m.get(f"dss_dar_op_co_plan_{route}", 0))

    routes = ("cache", "inline", "hostchunk", "device", "resident",
              "mesh", "rqmatch")
    plans = {r: plan(m1, r) for r in routes}
    burst_plans = {r: plan(m1, r) - plan(m0, r) for r in routes}
    status = http_json(srv.leader_url, "GET", "/status")[1]
    backend = status.get("backend", {})
    info = [k for k in m1 if k.startswith("dss_build_info{")]
    if backend.get("platform") != want_platform or not any(
        f'platform="{want_platform}"' in k for k in info
    ):
        raise SmokeFailure(
            f"the device owner reports backend {backend} / {info}, "
            f"not platform {want_platform}"
        )
    # a plan names the route a batch was SENT down; whether a kernel ran
    # is what the submit-side counters say: cold device batches that
    # touched the device, and resident submits (AOT hit or miss)
    kernels = int(
        m1.get("dss_dar_op_co_route_device_batches", 0)
        + m1.get("dss_dar_op_co_res_aot_hits", 0)
        + m1.get("dss_dar_op_co_res_aot_misses", 0)
    )
    if plans["device"] + plans["resident"] <= 0 or kernels <= 0:
        raise SmokeFailure(
            f"no op-intent batch reached the device: plans {plans}, "
            f"device-touching batches {kernels}"
        )
    absorbed = sum(
        v for k, v in m1.items() if k.endswith("_co_device_loss_absorbed")
    )
    if absorbed:
        raise SmokeFailure(f"{absorbed} device losses were absorbed")
    if status.get("degraded_mode", "healthy") != "healthy":
        raise SmokeFailure(f"degraded: {status.get('degraded')}")
    mesh = None
    want_devices = smoke.sizes.get("mesh_devices")
    if want_devices:
        mesh = {
            "plan_mesh": sum(
                int(v) for k, v in m1.items() if k.endswith("_co_plan_mesh")
            ),
            "mesh_offloads": sum(
                int(v) for k, v in m1.items() if k.endswith("_mesh_offloads")
            ),
            "shard_devices": int(m1.get("dss_shard_devices", 0)),
            "shard_members": int(m1.get("dss_shard_members", 0)),
            "replica_op_records": int(m1.get("replica_ops_records", 0)),
            "replica_overflow_fallbacks": int(
                m1.get("replica_ops_overflow_fallbacks", 0)),
        }
        if mesh["plan_mesh"] <= 0 or mesh["mesh_offloads"] <= 0:
            raise SmokeFailure(f"no batch rode the mesh: {mesh} {plans}")
        if mesh["shard_devices"] != want_devices:
            raise SmokeFailure(
                f"postings shards sit on {mesh['shard_devices']} devices, "
                f"the mesh names {want_devices}"
            )
        if mesh["shard_members"] < 1:
            raise SmokeFailure("dss_shard_members is not populated")
    ops = int(m1.get("dss_dar_op_live_records", 0))
    want_ops = smoke.sizes["ops"] + len(smoke.written)
    if ops != want_ops:
        raise SmokeFailure(f"{ops} op intents resident, generated {want_ops}")
    return {
        "backend": backend,
        "intents_loaded": ops,
        "resident_postings": int(m1.get("dss_dar_op_tier_postings", 0)),
        "resident_bytes": int(m1.get("dss_dar_op_tier_device_bytes", 0)),
        "folds": int(m1.get("dss_dar_op_folds", 0)),
        # compiles AFTER boot warm: each ran on a request or fold path
        "compiles_while_serving": int(
            m1.get("dss_jax_compiles", 0) - boot["compiles"]),
        "compile_s_while_serving": round(
            m1.get("dss_jax_compile_seconds", 0.0) - boot["compile_s"], 1),
        "aot_executables_at_end": int(
            m1.get("dss_dar_op_co_res_aot_buckets", 0)),
        "aot_hits": int(m1.get("dss_dar_op_co_res_aot_hits", 0)),
        "aot_misses": int(m1.get("dss_dar_op_co_res_aot_misses", 0)),
        "device_batches": int(
            m1.get("dss_dar_op_co_route_device_batches", 0)),
        "resident_batches": int(
            m1.get("dss_dar_op_co_route_resident_batches", 0)),
        "plans": plans,
        "burst_plans": burst_plans,
        "est_device_floor_ms": m1.get("dss_dar_op_co_est_device_floor_ms"),
        "est_resident_floor_ms": m1.get(
            "dss_dar_op_co_est_resident_floor_ms"),
        "mesh_facts": mesh,
    }


def check_logs(srv: Server) -> dict:
    """No error-level line from the leader or the workers — this is
    what catches the phases the program deliberately survives (warm
    failed, fold failed, async AOT compile failed) — and no traceback
    outside the JSON logger."""
    recs, other = srv.log_records()
    errors = [r for r in recs if r["level"] in ("error", "critical")]
    if errors:
        raise SmokeFailure(
            f"{len(errors)} error-level log lines, first: "
            + json.dumps(errors[0])[:600]
        )
    tracebacks = [ln for ln in other if ln.startswith("Traceback")]
    if tracebacks:
        raise SmokeFailure(
            f"{len(tracebacks)} tracebacks on stderr: " + " | ".join(other[-6:])
        )
    access = [r for r in recs if r.get("logger") == "dss.access"]
    s5xx = [r for r in access if r.get("status", 0) >= 500
            and r.get("status") not in (503, 504)]
    if s5xx:
        raise SmokeFailure(f"{len(s5xx)} 5xx responses, first: {s5xx[0]}")
    return {
        "log_lines": len(recs),
        "warnings": sum(1 for r in recs if r["level"] == "warning"),
        "served_504": sum(1 for r in access if r.get("status") == 504),
        "served_429": sum(1 for r in access if r.get("status") == 429),
    }


def shutdown(srv: Server) -> dict:
    """SIGTERM the leader; it must exit 0 and take its workers along."""
    pids = srv.worker_pids()
    t0 = time.monotonic()
    srv.proc.send_signal(signal.SIGTERM)
    try:
        rc = srv.proc.wait(timeout=90)
    except subprocess.TimeoutExpired:
        raise SmokeFailure("the leader ignored SIGTERM for 90 s")
    if rc != 0:
        raise SmokeFailure(f"the leader exited {rc} on SIGTERM")
    left = {}
    for _ in range(40):
        left = srv.worker_pids()
        if not left:
            break
        time.sleep(0.25)
    if left:
        raise SmokeFailure(f"workers outlived the leader: {left}")
    return {"leader_rc": rc, "workers_stopped": len(pids),
            "exit_s": round(time.monotonic() - t0, 1)}


def check_native_mapped(srv: Server) -> None:
    for name, pid in [("leader", srv.proc.pid)] + [
        (f"worker {i}", p) for i, p in srv.worker_pids().items()
    ]:
        with open(f"/proc/{pid}/maps", "r", encoding="utf-8") as fh:
            if "libdsscover.so" not in fh.read():
                raise SmokeFailure(
                    f"the {name} serves without libdsscover.so "
                    "(numpy fallback)"
                )


def pkg_version(name: str) -> str:
    try:
        return importlib.metadata.version(name)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


def run(args) -> tuple:
    """One whole smoke.  Returns (result, facts): the result line's
    object, and what the run measured."""
    rehearsal = args.rehearse_cpu
    platform = "cpu" if rehearsal else "tpu"
    sizes = dict(REHEARSAL if rehearsal else FULL)
    reduced = {}
    if args.intents is not None:
        if not rehearsal and args.intents < 250_000:
            raise SmokeFailure("--intents may not cut below 250,000")
        if args.intents != sizes["ops"]:
            # the metro shrinks with the count so the density (and with
            # it every per-request shape) stays the full run's
            grid = max(16, round(sizes["grid"] * (args.intents / sizes["ops"]) ** 0.5))
            reduced["op_intents"] = [sizes["ops"], args.intents]
            reduced["metro_cells"] = [sizes["grid"] ** 2, grid ** 2]
            sizes["grid"] = grid
        sizes["ops"] = args.intents
    env = dict(os.environ, JAX_PLATFORMS=platform)
    env.pop("DSS_LOG_LEVEL", None)  # the smoke reads the info lines
    sizes["workers"] = 2
    if args.mesh:
        if not re.fullmatch(r"\d+,\d+", args.mesh):
            raise SmokeFailure("--mesh takes 'dp,sp'")
        dp, sp = (int(x) for x in args.mesh.split(","))
        sizes["mesh_devices"] = dp * sp
        # the mesh route takes drains of 64-256 searches, and a drain is
        # bounded by the front's concurrency: the owner's ring-drain
        # pool (4 threads by default) and the workers' request threads
        # (aiohttp's default executor: min(32, cores + 4) each).  Both
        # are widened, for this drill only, to hold ~200 searches in the
        # coalescer at once
        env["DSS_SHM_OWNER_THREADS"] = "192"
        sizes["workers"] = -(-192 // min(32, (os.cpu_count() or 1) + 4))
        # the mixed burst stays under the mesh route's 64-query floor
        # (its large areas would compile a fresh multi-chip executable
        # per key width on the request path); the mesh burst of small
        # areas goes well over it
        sizes["mesh_burst"] = max(sizes["burst"], 256)
        sizes["burst"] = 48
        if rehearsal:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={dp * sp}"
            ).strip()

    native_s = build_native()
    log(f"libdsscover.so rebuilt in {native_s:.1f}s")
    device = probe_accelerator(platform, env)
    log(f"JAX finds {device}")
    if device["count"] < sizes.get("mesh_devices", 1):
        raise SmokeFailure(
            f"--mesh {args.mesh} needs {sizes['mesh_devices']} devices, "
            f"JAX finds {device['count']}"
        )

    work = tempfile.mkdtemp(prefix="dss-chip-smoke-")
    srv = None
    try:
        t_gen = int(time.time())
        t0 = time.monotonic()
        wal = os.path.join(work, "dss.wal")
        metro, ref = generate(args.seed, sizes, t_gen, wal)
        gen_s = time.monotonic() - t0
        log(f"WAL of {sizes['ops']} op intents + {sizes['isas']} ISAs + "
            f"{sizes['rid_subs']}/{sizes['scd_subs']} subscriptions "
            f"({os.path.getsize(wal) >> 20} MiB) in {gen_s:.1f}s")

        port = free_port()
        argv = [
            sys.executable, "-m", "dss_tpu.cmds.server",
            "--addr", f":{port}", "--enable_scd", "--storage", "tpu",
            "--insecure_no_auth", "--wal_path", wal,
            "--workers", str(sizes["workers"]),
        ]
        if args.mesh:
            argv += ["--sharded_replica", args.mesh]
        srv = Server(argv, env, os.path.join(work, "server.stderr"))
        boot = wait_ready(srv, sizes)
        log(f"serving: {boot}")

        smoke = Smoke(args.seed, sizes, metro, ref, t_gen, srv)
        phases = {}
        phases["scd_writes"] = smoke.phase_scd_writes(sizes["puts"])
        phases["rid"] = smoke.phase_rid()
        phases["lone"] = smoke.phase_lone(sizes["lone"])
        m_lone = scrape(srv.leader_url)
        log(f"writes + RID + lone searches matched ({smoke.matched})")
        phases["burst"] = smoke.phase_burst(sizes["burst"], sizes["rounds"])
        log(f"bursts matched: {phases['burst']}")
        if args.mesh:
            # bursts of small searches only: drains of 64-256 of them
            # are the mesh route's shape.  The replica builds its
            # snapshots on DEMAND (its 10 s boot grace is over long
            # before a real WAL is ingested): the first mesh-shaped
            # drain finds it stale, is served locally and wakes it.
            # So: burst, give the woken replica time to finish a sync,
            # and repeat until a batch has ridden the mesh — bounded
            rounds = []
            for _ in range(12):
                rounds.append(smoke.phase_burst(sizes["mesh_burst"], 1, 0))
                m = scrape(srv.leader_url)
                if sum(v for k, v in m.items()
                       if k.endswith("_mesh_offloads")) > 0:
                    break
                deadline = time.monotonic() + 60
                while (m.get("replica_staleness_s", -1.0) < 0
                       and time.monotonic() < deadline):
                    time.sleep(2.0)
                    m = scrape(srv.leader_url)
            phases["mesh_burst"] = {
                "in_flight": sizes["mesh_burst"],
                "rounds": len(rounds),
                "hits": sum(r["hits"] for r in rounds),
            }
            log(f"mesh bursts matched: {phases['mesh_burst']}")
        # the library loads lazily, on the first covering or host scan:
        # by now every process has served some
        check_native_mapped(srv)
        phases["respawn"] = smoke.phase_respawn()
        log(f"worker respawn: {phases['respawn']}")

        facts = verify_and_summarize(
            smoke, srv, boot, m_lone, scrape(srv.leader_url), platform
        )
        exit_facts = shutdown(srv)
        logs = check_logs(srv)
        backend = facts.pop("backend")
        if (backend["device_kind"], int(backend["device_count"])) != (
            device["kind"], device["count"]
        ):
            raise SmokeFailure(
                f"the server ran on {backend}, the probe found {device}"
            )
        result = {
            "ok": True,
            "device": {
                "platform": backend["platform"],
                "kind": backend["device_kind"],
                "count": int(backend["device_count"]),
            },
        }
        out = {"platform": backend["platform"]}
        if rehearsal:
            out["rehearsal"] = True
        out.update({
            "versions": {
                "jax": pkg_version("jax"), "jaxlib": pkg_version("jaxlib"),
                "libtpu": pkg_version("libtpu"),
            },
            "seed": args.seed,
            "mesh": args.mesh or None,
            "reduced": reduced,
            "setup": {
                "native_build_s": round(native_s, 1),
                "generate_s": round(gen_s, 1),
                **boot,
                "compile_cache_dir": os.environ.get(
                    "JAX_COMPILATION_CACHE_DIR",
                    os.path.join(REPO, ".jax_cache"),
                ),
            },
            **facts,
            "phases": phases,
            "requests_sent": smoke.sent,
            "answers_matched": smoke.matched,
            "retried_429_503_504": smoke.retries,
            "logs": logs,
            "shutdown": exit_facts,
            "wall_s": round(time.monotonic() - _T0, 1),
        })
        return result, out
    finally:
        if srv is not None:
            srv.kill()
            if args.keep_logs:
                shutil.copy(srv.stderr_path, args.keep_logs)
        shutil.rmtree(work, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--intents", type=int, default=None,
        help="cut the op-intent count (not below 250,000 on the chip); "
        "printed under 'reduced'",
    )
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--mesh", default="", metavar="DP,SP")
    ap.add_argument(
        "--keep-logs", default="", metavar="FILE",
        help="copy the servers' stderr here (e.g. chiprun_out/...)",
    )
    args = ap.parse_args()

    def out_of_time(*_):
        raise SmokeFailure(f"not done after {DEADLINE_S} s")

    # the whole run, compilation included, has 1200 s; stopping short
    # of that leaves time to stop the servers
    signal.signal(signal.SIGALRM, out_of_time)
    signal.alarm(DEADLINE_S)
    try:
        result, facts = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    signal.alarm(0)
    print(json.dumps({"facts": facts}))
    # the last line: these keys and no others
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
