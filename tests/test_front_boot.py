"""A `--workers` front boots side by side: the leader spawns its
workers before its own `build()`, and a worker that is ready first
keeps the public port closed until the leader serves.

Real processes over real sockets on the CPU backend, a WAL of a few
hundred records.  Where a case needs the leader held inside `build()`,
it hands it `--region_token_file` as a named pipe: `build()` opens that
file after `backend:` and before the store (and never forwards it to a
worker), so the leader blocks there until the test writes to the pipe.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from dssbench import deploy, traffic as tr
from dssbench.run import free_port
from dss_tpu.parallel import shmring

GENERATOR = {
    "grid": 8, "owners": 4, "strata": 60, "stratum_m": 50,
    "classes": {
        "op": {"n": 300, "cells": [2, 8]},
        "isa": {"n": 20, "cells": [2, 8]},
        "rid_sub": {"n": 5, "cells": [2, 8]},
        "scd_sub": {"n": 5, "cells": [2, 8]},
    },
}
# one small AOT bucket: the leader's boot warm is seconds, not a grid
ENV = {"JAX_PLATFORMS": "cpu", "DSS_RES_BATCH_BUCKETS": "16",
       "DSS_RES_WINDOW_BUCKETS": "1024"}
DEADLINE_S = 90.0


@pytest.fixture(scope="module")
def city(tmp_path_factory):
    """(metro, path of a generated WAL); each server takes a copy."""
    wal = str(tmp_path_factory.mktemp("city") / "dss.wal")
    metro, _ = deploy.generate(38, GENERATOR, int(time.time()), wal)
    return metro, wal


class Proc:
    """One `python -m dss_tpu.cmds.server`, its stderr in a file."""

    def __init__(self, argv, work, env=None):
        self.log_path = os.path.join(work, f"stderr.{len(os.listdir(work))}")
        self._fh = open(self.log_path, "wb")
        full = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
        full.update(ENV, **(env or {}))
        self.p = subprocess.Popen(
            [sys.executable, "-m", "dss_tpu.cmds.server", *argv],
            cwd=deploy.REPO, env=full, stdout=subprocess.DEVNULL,
            stderr=self._fh,
        )

    def records(self) -> list:
        out = []
        with open(self.log_path, "r", errors="replace") as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and "msg" in rec:
                    out.append(rec)
        return out

    def logged(self, prefix: str, logger: str = "") -> list:
        return [r for r in self.records() if r["msg"].startswith(prefix)
                and (not logger or r.get("logger") == logger)]

    def wait_logged(self, prefix: str, n: int = 1, logger: str = "") -> list:
        deadline = time.monotonic() + DEADLINE_S
        while time.monotonic() < deadline:
            got = self.logged(prefix, logger)
            if len(got) >= n:
                return got
            assert self.p.poll() is None, self.tail()
            time.sleep(0.1)
        raise AssertionError(f"{prefix!r} not logged {n} times: {self.tail()}")

    def tail(self) -> str:
        with open(self.log_path, "r", errors="replace") as fh:
            return fh.read()[-3000:]

    def stop(self) -> None:
        if self.p.poll() is None:
            self.p.send_signal(signal.SIGTERM)
            try:
                self.p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                self.p.kill()
                self.p.wait(timeout=5)
        self._fh.close()


def worker_pids(port: int) -> dict:
    """{worker index: pid} of the live read workers on `port` (keyed
    by -pid where DSS_SHM_ENABLE=0 hands out no index)."""
    out = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as fh:
                cmd = fh.read().decode(errors="replace").split("\0")
            with open(f"/proc/{pid}/stat") as fh:
                zombie = fh.read().rsplit(")", 1)[1].split()[0] == "Z"
        except OSError:
            continue
        if "--worker_reader" in cmd and f":{port}" in cmd and not zombie:
            if "--shm_worker_index" in cmd:
                out[int(cmd[cmd.index("--shm_worker_index") + 1])] = int(pid)
            else:
                out[-int(pid)] = int(pid)
    return out


def both_workers(port: int) -> dict:
    """worker_pids once it holds two, else nothing (for wait_for)."""
    pids = worker_pids(port)
    return pids if len(pids) == 2 else None


def wait_for(cond, what: str):
    deadline = time.monotonic() + DEADLINE_S
    while time.monotonic() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


def refused(port: int) -> bool:
    try:
        socket.create_connection(("127.0.0.1", port), timeout=1).close()
    except ConnectionRefusedError:
        return True
    return False


def healthy(port: int) -> bool:
    try:
        return deploy.http_json(
            f"http://127.0.0.1:{port}", "GET", "/healthy", timeout=2
        )[0] == 200
    except OSError:
        return False


def stays_closed(port: int, seconds: float) -> None:
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        assert refused(port), "the public port opened before the leader served"
        time.sleep(0.1)


def front_argv(port: int, wal: str) -> list:
    return ["--addr", f":{port}", "--storage", "tpu", "--enable_scd",
            "--insecure_no_auth", "--wal_path", wal, "--workers", "2",
            "--follower_poll_interval", "0.02"]


# -- (a) a worker that is ready first keeps the public port closed ----------


class _Healthy(BaseHTTPRequestHandler):
    def do_GET(self):  # noqa: N802 — the handler's protocol
        self.send_response(200)
        self.send_header("Content-Length", "2")
        self.end_headers()
        self.wfile.write(b"ok")

    def log_message(self, *args):
        pass


def _serve_on(sock) -> HTTPServer:
    """A stand-in leader answering on the listening socket `sock`."""
    srv = HTTPServer(sock.getsockname(), _Healthy, bind_and_activate=False)
    srv.socket.close()
    srv.socket = sock
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


@pytest.mark.parametrize("last", ["loopback", "heartbeat"])
def test_a_ready_worker_keeps_the_public_port_closed_until_the_leader_serves(
        last, city, tmp_path):
    """The worker's replica is caught up and its ring opened while the
    leader stands still: nothing listens on the public port.  It opens
    only when BOTH the loopback answers /healthy and the owner's
    heartbeat is fresh, whichever comes last."""
    wal = shutil.copy(city[1], tmp_path / "dss.wal")
    region = shmring.ShmRegion.create(
        str(tmp_path / "ring.shm"), nworkers=1, **shmring.env_knobs())
    struct.pack_into("<q", region._mm, 40, 0)  # the heartbeat, long stale
    loopback = socket.socket()
    loopback.bind(("127.0.0.1", 0))
    loopback.listen(16)  # as the leader's: bound and silent while it boots
    port = free_port()
    w = Proc(["--worker_reader",
              "--leader_url", f"http://127.0.0.1:{loopback.getsockname()[1]}",
              "--addr", f":{port}", "--storage", "tpu", "--enable_scd",
              "--insecure_no_auth", "--wal_path", wal,
              "--shm_region", region.path, "--shm_worker_index", "0"],
             str(tmp_path))
    stop = threading.Event()
    srv = None
    try:
        ready = w.wait_logged("worker replica ready:", logger="dss.worker")
        assert ready[0]["msg"].startswith("worker replica ready: 330 records")
        w.wait_logged("shm front: worker 0 of 1")
        stays_closed(port, 1.0)

        def beat():
            while not stop.wait(0.2):
                region.set_owner_heartbeat()

        if last == "loopback":
            threading.Thread(target=beat, daemon=True).start()
        else:
            srv = _serve_on(loopback)
        stays_closed(port, 1.5)  # one of the two is not enough
        assert not w.logged("worker waited for the leader:")
        if last == "loopback":
            srv = _serve_on(loopback)
        else:
            threading.Thread(target=beat, daemon=True).start()
        wait_for(lambda: healthy(port), "the worker's public /healthy")
        waited = w.wait_logged("worker waited for the leader:",
                               logger="dss.worker")
        seconds = float(waited[0]["msg"].split(": ")[1].split()[0])
        assert seconds >= 2.0, waited
        gauges = deploy.scrape(f"http://127.0.0.1:{port}")
        boot = {k.split('stage="')[1].split('"')[0]: v
                for k, v in gauges.items() if k.startswith("dss_boot_seconds{")}
        assert sorted(boot) == ["build", "leader_wait", "parse"]
        assert boot["leader_wait"] == pytest.approx(seconds, abs=0.01)
    finally:
        stop.set()
        w.stop()
        if srv is not None:
            srv.shutdown()
        loopback.close()
        region.close()


# -- (b) the same answers as a single process, the tail included ------------


def _volume(metro, rect, lo: float, hi: float) -> dict:
    return {"volume": {
        "outline_polygon": {"vertices": metro.rect(*rect)},
        "altitude_lower": {"value": lo, "reference": "W84", "units": "M"},
        "altitude_upper": {"value": hi, "reference": "W84", "units": "M"},
    }}


def _search(port: int, metro, rect) -> list:
    """Sorted op-intent ids of one SCD query, every altitude and hour,
    on a fresh connection."""
    body = {"area_of_interest": _volume(metro, rect, 0, 6000)}
    status, doc = deploy.http_json(
        f"http://127.0.0.1:{port}", "POST",
        "/dss/v1/operation_references/query", body, timeout=30)
    assert status == 200, doc
    return sorted(o["id"] for o in doc["operation_references"])


def _rid_search(port: int, metro, rect) -> list:
    area = ",".join(f"{v['lat']!r},{v['lng']!r}" for v in metro.rect(*rect))
    status, doc = deploy.http_json(
        f"http://127.0.0.1:{port}", "GET",
        f"/v1/dss/identification_service_areas?area={area}", timeout=30)
    assert status == 200, doc
    return sorted(a["id"] for a in doc["service_areas"])


FLIGHT = "4c5b7a10-38aa-4c00-9d00-0000000003e8"
RECTS = [(0, 0, 8, 8), (0, 0, 2, 2), (3, 3, 2, 3), (5, 1, 3, 3),
         (2, 5, 4, 2), (6, 6, 2, 2), (1, 1, 6, 6), (4, 0, 1, 8)]


def _file_flight(port: int, metro) -> None:
    """A planned flight above every stratum of the city (no conflict,
    so no key), over cells (3..4, 3..4)."""
    now = int(time.time())
    status, doc = deploy.http_json(
        f"http://127.0.0.1:{port}", "PUT",
        f"/dss/v1/operation_references/{FLIGHT}",
        {"extents": [{
            **_volume(metro, (3, 3, 2, 2), 5000, 5050),
            "time_start": {"value": tr.iso(now + 3600), "format": "RFC3339"},
            "time_end": {"value": tr.iso(now + 7200), "format": "RFC3339"}}],
         "old_version": 0, "state": "Accepted", "key": [],
         "uss_base_url": "https://uss.example",
         "new_subscription": {"uss_base_url": "https://uss.example"}},
        timeout=30)
    assert status == 200, doc


@pytest.mark.parametrize("shm", ["1", "0"], ids=["shm-front", "wal-tail"])
def test_a_front_booted_side_by_side_answers_as_a_single_process_does(
        shm, city, tmp_path):
    """Id for id, on the same WAL: the log's records, and a flight the
    leader journals after the workers' bulk catch-up has ended (it
    reaches them through the tail).  With DSS_SHM_ENABLE=0 a worker
    answers from its own replica alone."""
    metro = city[0]
    ports = free_port(), free_port()
    front = Proc(front_argv(ports[0], shutil.copy(city[1], tmp_path / "a.wal")),
                 str(tmp_path), {"DSS_SHM_ENABLE": shm})
    single = Proc(["--addr", f":{ports[1]}", "--storage", "tpu",
                   "--enable_scd", "--insecure_no_auth",
                   "--wal_path", shutil.copy(city[1], tmp_path / "b.wal")],
                  str(tmp_path))
    try:
        for port in ports:
            wait_for(lambda: healthy(port), f"/healthy on {port}")
        # born before the leader's replay, and behind it at the port
        spawned = front.logged("workers spawned: 2 at")[0]
        assert spawned["ts"] < front.logged("store ready:")[0]["ts"]
        wait_for(lambda: both_workers(ports[0]), "both workers")
        front.wait_logged("worker waited for the leader:", 2, "dss.worker")
        ready = front.logged("worker replica ready: 330 records")
        assert len(ready) == 2, front.tail()

        for rect in RECTS:
            want = _search(ports[1], metro, rect)
            assert want or rect != RECTS[0]
            for _ in range(4):  # fresh connections: both workers answer
                assert _search(ports[0], metro, rect) == want
            assert _rid_search(ports[0], metro, rect) == _rid_search(
                ports[1], metro, rect)

        for port in ports:
            _file_flight(port, metro)
        time.sleep(0.5)  # the tail's bounded staleness, 20 ms a poll
        for rect in RECTS:
            want = _search(ports[1], metro, rect)
            # the flight lies over cells 3..4 x 3..4
            assert (FLIGHT in want) == (
                rect[0] < 5 and rect[0] + rect[2] > 3
                and rect[1] < 5 and rect[1] + rect[3] > 3)
            for _ in range(4):
                assert _search(ports[0], metro, rect) == want
    finally:
        front.stop()
        single.stop()
    assert not worker_pids(ports[0])


# -- (c), (d) a death on either side while the leader is in build() ---------


def _held_front(tmp_path, city, extra=()):
    """A front whose leader blocks inside build() on a named pipe.
    -> (process, port, release())."""
    pipe = str(tmp_path / "token.pipe")
    os.mkfifo(pipe)
    port = free_port()
    p = Proc(front_argv(port, shutil.copy(city[1], tmp_path / "dss.wal"))
             + ["--region_token_file", pipe, *extra], str(tmp_path))

    def release():
        with open(pipe, "w") as fh:
            fh.write("unused\n")

    return p, port, release


def test_a_worker_killed_while_the_leader_boots_is_respawned(city, tmp_path):
    front, port, release = _held_front(tmp_path, city)
    try:
        front.wait_logged("backend:", logger="dss.server")  # inside build()
        pids = wait_for(lambda: both_workers(port),
                        "both workers born before the leader's store")
        os.kill(pids[0], signal.SIGKILL)
        front.wait_logged("worker 0 exited (rc=-9)")
        front.wait_logged("worker 0 respawned")
        assert not front.logged("store ready:")  # the leader is still held
        stays_closed(port, 0.5)
        release()
        wait_for(lambda: healthy(port), "the front's /healthy")
        now = wait_for(lambda: both_workers(port), "two workers again")
        assert now[1] == pids[1] and now[0] != pids[0]
        front.wait_logged("worker waited for the leader:", 2, "dss.worker")
        # every worker answers: ring rows for both at the leader
        for _ in range(6):
            assert _search(port, city[0], RECTS[0])
    finally:
        front.stop()
    assert not worker_pids(port)


@pytest.mark.parametrize("how", ["refused", "killed"])
def test_a_leader_that_exits_in_build_leaves_no_worker_behind(
        how, city, tmp_path):
    """`refused`: build() itself refuses the flags after the workers
    were born (SystemExit: atexit reaps them).  `killed`: SIGKILL while
    it is held in build(), before a worker could have looked for its
    parent (nothing runs at exit: the workers find it gone)."""
    extra = ["--federation_map", "none.json"] if how == "refused" else []
    front, port, release = _held_front(tmp_path, city, extra)
    try:
        wait_for(lambda: both_workers(port), "both workers born")
        if how == "killed":
            front.p.kill()
        else:
            release()
        assert front.p.wait(timeout=DEADLINE_S) != 0
        if how == "refused":
            assert "--federation_map with --workers" in front.tail()
        wait_for(lambda: not worker_pids(port), "the workers to be gone")
        assert refused(port)
    finally:
        front.stop()
