"""The deployment under test: seeded data, its plain reference, and the
server process.

Copied from chip_smoke.py (PR 21) so that later PRs may change the
smoke but not the yardstick: Metro, EntitySet, _footprints, _times,
generate, Server, wait_ready, scrape.  What differs: the S2 cell
arithmetic is this file's own (the reference takes nothing from the
program), sizes come from a configuration file's `generator` block, and
failures raise BenchFailure.

This module never imports JAX: one process holds the chip, the server's
leader.
"""

from __future__ import annotations

import base64
import http.client
import importlib.util
import json
import math
import os
import re
import signal
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOUR = 3600
NS = 1_000_000_000


class BenchFailure(Exception):
    """A step of a run failed; the message says which and why."""


_T0 = time.monotonic()


def log(msg: str) -> None:
    print(f"[dssbench +{time.monotonic() - _T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# S2 cell arithmetic (quadratic projection, Hilbert curve) — written out
# here so that the reference shares no code with dss_tpu.geo
# ---------------------------------------------------------------------------

MAX_LEVEL = 30
DAR_LEVEL = 13
_IJ_TO_POS = ((0, 1, 3, 2), (0, 3, 1, 2), (2, 3, 1, 0), (2, 1, 3, 0))
_POS_TO_ORIENT = (1, 0, 0, 3)


def _st_to_uv(s: float) -> float:
    if s >= 0.5:
        return (4.0 * s * s - 1.0) / 3.0
    return (1.0 - 4.0 * (1.0 - s) * (1.0 - s)) / 3.0


def _uv_to_st(u: float) -> float:
    if u >= 0:
        return 0.5 * math.sqrt(1.0 + 3.0 * u)
    return 1.0 - 0.5 * math.sqrt(1.0 - 3.0 * u)


def _face_uv_to_xyz(face: int, u: float, v: float):
    return (
        (1.0, u, v), (-u, 1.0, v), (-u, -v, 1.0),
        (-1.0, -v, -u), (v, -1.0, -u), (v, u, -1.0),
    )[face]


def _xyz_to_face_uv(x: float, y: float, z: float):
    axis = max(range(3), key=lambda k: abs((x, y, z)[k]))
    face = axis + (3 if (x, y, z)[axis] < 0 else 0)
    u, v = (
        (y / x, z / x), (-x / y, z / y), (-x / z, -y / z),
        (z / x, y / x), (z / y, -x / y), (-y / z, -x / z),
    )[face]
    return face, u, v


def latlng_to_face_ij(lat: float, lng: float):
    """Degrees -> (face, leaf i, leaf j)."""
    la, lo = math.radians(lat), math.radians(lng)
    face, u, v = _xyz_to_face_uv(
        math.cos(la) * math.cos(lo), math.cos(la) * math.sin(lo),
        math.sin(la),
    )
    size = 1 << MAX_LEVEL
    clip = lambda a: max(0, min(size - 1, int(math.floor(a * size))))
    return face, clip(_uv_to_st(u)), clip(_uv_to_st(v))


def face_st_to_latlng(face: int, s: float, t: float):
    x, y, z = _face_uv_to_xyz(face, _st_to_uv(s), _st_to_uv(t))
    return (math.degrees(math.atan2(z, math.hypot(x, y))),
            math.degrees(math.atan2(y, x)))


def cell_id(face: int, i: int, j: int, level: int = DAR_LEVEL) -> int:
    """Leaf coordinates -> the id of the level-`level` cell holding them."""
    orient = face & 1
    pos = 0
    for k in range(MAX_LEVEL - 1, -1, -1):
        p = _IJ_TO_POS[orient][(((i >> k) & 1) << 1) | ((j >> k) & 1)]
        pos = (pos << 2) | p
        orient ^= _POS_TO_ORIENT[p]
    leaf = (face << 61) | (pos << 1) | 1
    lsb = 1 << (2 * (MAX_LEVEL - level))
    return (leaf & -lsb) | lsb


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
        self.g = g
        self._shift = MAX_LEVEL - DAR_LEVEL
        self.face, i, j = latlng_to_face_ij(self.LAT, self.LNG)
        self.i0 = (i >> self._shift) - g // 2
        self.j0 = (j >> self._shift) - g // 2
        self.cells = np.array([
            cell_id(self.face, (self.i0 + a) << self._shift,
                    (self.j0 + b) << self._shift)
            for a in range(g) for b in range(g)
        ], dtype=np.uint64)  # flat index i * g + j

    def latlng(self, fi: float, fj: float):
        """Fractional block position -> (lat, lng) degrees."""
        size = float(1 << MAX_LEVEL)
        return face_st_to_latlng(
            self.face,
            (self.i0 + fi) * (1 << self._shift) / size,
            (self.j0 + fj) * (1 << self._shift) / size,
        )

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

    def rect_flat(self, i: int, j: int, w: int, h: int) -> np.ndarray:
        """Flat indices of the w x h sub-block at (i, j)."""
        ii, jj = np.meshgrid(np.arange(i, i + w), np.arange(j, j + h),
                             indexing="ij")
        return (ii * self.g + jj).ravel()


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
        self.live = np.ones(len(self.ids), bool)
        ent = np.repeat(np.arange(len(ids)), counts)
        order = np.argsort(flat_cells, kind="stable")
        self._post_cell = np.asarray(flat_cells)[order]
        self._post_ent = ent[order]

    def candidates(self, flat) -> int:
        """Postings under the cells `flat` (what a scan has to read)."""
        flat = np.asarray(flat)
        return int((np.searchsorted(self._post_cell, flat, side="right")
                    - np.searchsorted(self._post_cell, flat, side="left")
                    ).sum())

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
        keep = (self.t1[cand] >= now) & self.live[cand]
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


_DOCS = {
    # class -> (WAL record type, altitude-stratified, document template)
    "op": ("scd_op_put", True, (
        lambda i, o, cells, lo, hi, t0, t1, s:
        f'{{"t":"scd_op_put","doc":{{"id":"{i}","owner":"uss{o}",'
        f'"version":1,"ovn":"ovn-{i[:13]}","start_time":{t0},'
        f'"end_time":{t1},"altitude_lower":{lo},"altitude_upper":{hi},'
        f'"uss_base_url":"https://uss{o}.example/scd",'
        f'"state":"Accepted","cells":[{cells}],"subscription_id":"",'
        f'"constraint_aware":false}},"seq":{s}}}\n'
    )),
    "isa": ("isa_put", False, (
        lambda i, o, cells, lo, hi, t0, t1, s:
        f'{{"t":"isa_put","doc":{{"id":"{i}","owner":"uss{o}",'
        f'"url":"https://uss{o}.example/flights","cells":[{cells}],'
        f'"start_time":{t0},"end_time":{t1},"version":"1bench",'
        f'"altitude_hi":{hi},"altitude_lo":{lo}}},"seq":{s}}}\n'
    )),
    "rid_sub": ("rid_sub_put", False, (
        lambda i, o, cells, lo, hi, t0, t1, s:
        f'{{"t":"rid_sub_put","doc":{{"id":"{i}","owner":"uss{o}",'
        f'"url":"https://uss{o}.example/isa","notification_index":0,'
        f'"cells":[{cells}],"start_time":{t0},"end_time":{t1},'
        f'"version":"1bench","altitude_hi":{hi},"altitude_lo":{lo}}},'
        f'"seq":{s}}}\n'
    )),
    "scd_sub": ("scd_sub_put", False, (
        lambda i, o, cells, lo, hi, t0, t1, s:
        f'{{"t":"scd_sub_put","doc":{{"id":"{i}","owner":"uss{o}",'
        f'"version":1,"notification_index":0,"start_time":{t0},'
        f'"end_time":{t1},"altitude_hi":{hi},"altitude_lo":{lo},'
        f'"base_url":"https://uss{o}.example/scd",'
        f'"notify_for_operations":true,"notify_for_constraints":false,'
        f'"implicit_subscription":false,"dependent_operations":[],'
        f'"cells":[{cells}]}},"seq":{s}}}\n'
    )),
}


def generate(seed: int, gen: dict, t_gen: int, wal_path: str):
    """Write the WAL and return (metro, reference sets).  Everything
    derives from `seed`, the generation second `t_gen` and the
    configuration's `generator` block: `grid` (metro side), `owners`,
    `strata` x `stratum_m` (altitude), and per class in `classes` a
    count `n` and a footprint range `cells`."""
    rng = np.random.default_rng(seed)
    metro = Metro(gen["grid"])
    g = metro.g
    cell_str = np.array([str(int(c)) for c in metro.cells], dtype=object)
    ref = {}
    seq = 0
    with open(wal_path, "w", encoding="utf-8") as wal:
        wal.write('{"t":"__format__","version":1}\n')
        for kind, spec in gen["classes"].items():
            n = spec["n"]
            _, stratified, doc_fn = _DOCS[kind]
            if not n:
                continue
            flat, counts = _footprints(rng, n, g, *spec["cells"])
            ids = _uuids(rng, n)
            if stratified:
                # strata of stratum_m; quarter-metre values are exact in
                # float32, the width the DAR stores altitudes in
                lo = (rng.integers(0, gen["strata"], n) * gen["stratum_m"]
                      + rng.integers(0, 80, n) * 0.25)
                hi = lo + 20 + rng.integers(0, 100, n) * 0.25
            else:
                lo, hi = np.zeros(n), np.full(n, 3000.0)
            t0, t1 = _times(rng, n, t_gen)
            owners = rng.integers(0, gen["owners"], n)
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
    return metro, ref


class Written:
    """What a run itself wrote, beside the WAL's EntitySets (which stay
    as generated): per planned flight its rectangle of metro cells, its
    altitudes and times, the id of its implicit subscription once a 200
    has named it, and on the generator's one monotonic clock the
    instant its first PUT was sent and the instant its 200's last byte
    was read (`acked`; inf = unknown: no final answer, so the flight
    may or may not stand).  One object is kept from the warm-up
    through the window to the traced stretch."""

    COLUMNS = ("i0", "i1", "j0", "j1", "lo", "hi", "t0", "t1",
               "first_sent", "acked")

    def __init__(self):
        self.ids = []
        self.subs = []  # implicit subscription ids; None = unknown
        self._rows = []
        self._cols = None

    def __len__(self):
        return len(self.ids)

    def add(self, req, first_sent: float, acked: float, sub) -> None:
        i, j, w, h = req.rect
        self.ids.append(req.id)
        self.subs.append(sub)
        self._rows.append((i, i + w, j, j + h, req.alt[0], req.alt[1],
                           req.when[0], req.when[1], first_sent, acked))
        self._cols = None

    def absorb(self, requests: list, out) -> None:
        """Take in the chains of one offered stretch."""
        for k, req in enumerate(requests):
            if req.kind != "write" or np.isnan(out.sent[k]):
                continue
            chain = out.chain[k]
            acked, sub = math.inf, None
            if chain and chain[-1].status == 200:
                acked = out.t_open + chain[-1].done
                try:
                    sub = json.loads(chain[-1].body)[
                        "operation_reference"]["subscription_id"]
                except (ValueError, KeyError, TypeError):
                    pass  # the comparison will call the answer unreadable
            self.add(req, out.t_open + float(out.sent[k]), acked, sub)

    def columns(self) -> dict:
        """{column: array over the flights}, with `ids` and `subs`."""
        if self._cols is None:
            rows = np.array(self._rows, np.float64).reshape(
                len(self._rows), len(self.COLUMNS))
            self._cols = dict(zip(self.COLUMNS, rows.T))
            self._cols["ids"] = np.array(self.ids, dtype=object)
            self._cols["subs"] = np.array(self.subs, dtype=object)
        return self._cols


# ---------------------------------------------------------------------------
# set-up: native library
# ---------------------------------------------------------------------------


def build_native() -> float:
    """Rebuild libdsscover.so from the committed sources, always: the
    .so is git-ignored, and the numpy fallback must not be what is
    measured.  _buildlib is loaded by path so importing the package
    cannot start its own background build first."""
    native_dir = os.path.join(REPO, "dss_tpu", "native")
    if not os.path.isdir(native_dir):
        raise BenchFailure(
            f"no checkout around {REPO}: dss_tpu/native is missing"
        )
    spec = importlib.util.spec_from_file_location(
        "_dss_buildlib", os.path.join(native_dir, "_buildlib.py")
    )
    buildlib = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(buildlib)
    t0 = time.monotonic()
    if not buildlib.build(native_dir):
        raise BenchFailure("g++ build of libdsscover.so failed")
    return time.monotonic() - t0


# ---------------------------------------------------------------------------
# set-up: the callers' identities, where the deployment authenticates
# ---------------------------------------------------------------------------


def make_keys(work: str) -> tuple:
    """(private key, path of its public half as PEM): the pair an OAuth
    provider would hold, made anew for every run."""
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric import rsa

    key = rsa.generate_private_key(public_exponent=65537, key_size=2048)
    path = os.path.join(work, "oauth.pem")
    with open(path, "wb") as fh:
        fh.write(key.public_key().public_bytes(
            serialization.Encoding.PEM,
            serialization.PublicFormat.SubjectPublicKeyInfo))
    return key, path


def mint(key, sub: str, audience: str, scope: str, ttl_s: int) -> bytes:
    """An RS256 access token as the reference's dummy OAuth mints them
    (cmds/dummy-oauth/main.go): `sub` is the USS, and so the owner of
    what it writes."""
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import padding

    def b64(raw: bytes) -> bytes:
        return base64.urlsafe_b64encode(raw).rstrip(b"=")

    signed = b64(b'{"alg":"RS256","typ":"JWT"}') + b"." + b64(json.dumps({
        "aud": audience, "scope": scope, "iss": "dssbench", "sub": sub,
        "exp": int(time.time()) + ttl_s}).encode())
    return signed + b"." + b64(
        key.sign(signed, padding.PKCS1v15(), hashes.SHA256()))


# ---------------------------------------------------------------------------
# the server under test
# ---------------------------------------------------------------------------


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
        self.tokens = []  # bearer tokens, where callers authenticate

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
            raise BenchFailure(
                f"server exited with code {rc} while {what}: "
                + " | ".join(other[-6:])
            )

    def stop(self) -> None:
        """SIGTERM the leader and wait; whatever is left is killed.
        Returns only when the leader and every worker have ended."""
        pids = self.worker_pids()
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and self.worker_pids():
            time.sleep(0.1)
        for pid in (self.worker_pids() or {}).values():
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        for pid in pids.values():  # wait until each has really gone
            while os.path.exists(f"/proc/{pid}") and time.monotonic() < deadline + 10:
                time.sleep(0.05)
        if not self._stderr.closed:
            self._stderr.close()


def http_json(base: str, method: str, path: str, body=None,
              timeout: float = 120.0, token: bytes = b""):
    """One request on a fresh connection -> (status, parsed body;
    the text itself when it is not JSON)."""
    host, port = base.replace("http://", "").split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        data = None if body is None else json.dumps(body)
        headers = {"Content-Type": "application/json"} if data else {}
        if token:
            headers["Authorization"] = "Bearer " + token.decode()
        conn.request(method, path, body=data, headers=headers)
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


def wait_ready(srv: Server, workers: int, boot_timeout_s: float) -> dict:
    """Block until the front answers /healthy, every worker holds ring
    rows, and the leader's boot warm (fused-kernel warm-up + resident
    AOT grid) has logged its end — compile time is set-up, so it must
    be over before the first request is sent."""
    base = f"http://127.0.0.1:{srv.port}"
    deadline = srv.t_spawn + boot_timeout_s
    t_healthy = None
    while time.monotonic() < deadline:
        srv.check_alive("booting")
        try:
            if http_json(base, "GET", "/healthy", timeout=5)[0] == 200:
                t_healthy = time.monotonic() - srv.t_spawn
                break
        except OSError:
            pass
        time.sleep(0.25)
    if t_healthy is None:
        raise BenchFailure(
            f"/healthy did not answer within {boot_timeout_s} s"
        )
    while time.monotonic() < deadline:
        srv.check_alive("warming")
        recs, _ = srv.log_records()
        warm = next((r for r in recs if r.get("msg", "").startswith(
            "resident AOT warm:")), None)
        if (warm is not None and len(srv.worker_pids()) >= workers
                and srv.leader_url):
            break
        time.sleep(0.25)
    else:
        raise BenchFailure("boot warm / workers not ready in time")
    return {"healthy_s": t_healthy,
            "warm_done_s": time.monotonic() - srv.t_spawn}
