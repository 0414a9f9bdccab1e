"""python3 -m dssbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json: generate the deployment's WAL
from the seed, start the server binary on the chip on cores of its own,
warm the cell's own traffic, offer it open loop for --seconds, stop the
server, compare every answer with the plain reference, and print the
result object as the last line of stdout.  Facts of the run go on
earlier lines ({"facts": ...}); the numbers compared go, each beside
its limit, on the last lines of stderr and under `checks`, the last
key of the result.

The cell, its configuration, its traffic and (with --trace 1) its
per-layer metrics are data: the cell is looked up by name in
BENCHMARK.json, then dssbench/configs/<config>.json,
dssbench/traffic/<traffic>.json and every dssbench/metrics/*.json whose
`workloads` is absent or holds the cell; a metric names a reader module
in dssbench/readers/.  No file lists the others.

This process never initialises a JAX backend while the server runs: one
process holds the chip.  Without a TPU the server refuses to boot and
the run exits non-zero with no result line.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import glob
import importlib
import json
import os
import shutil
import signal
import socket
import sys
import tempfile
import threading
import time

import numpy as np

from . import check, deploy, traffic as tr
from .deploy import REPO, BenchFailure, log

HERE = os.path.dirname(os.path.abspath(__file__))
# the leader's capture hooks every Python call of its threads: it is kept
# short, and outside the window (traced_stretch), so that the trace stays
# small enough to read and the window is the one an untraced run measures
TRACE_MAX_S = 5.0
# the capture is started this long before the stretch's traffic and asked
# to outlast it by as much, so that every kernel run of the stretch's
# requests, and no other's, is in the trace (readers/xplane.py)
TRACE_PAD_S = 3.0


def process_age_s() -> float:
    """Seconds since this process was started (interpreter start-up
    and imports included): set-up is counted from there."""
    with open("/proc/self/stat", "rb") as fh:
        ticks = int(fh.read().rsplit(b")", 1)[1].split()[19])
    born = ticks / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - born


def _json(path: str) -> dict:
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def load_cell(name: str):
    """(BENCHMARK.json, the cell's configuration, its traffic), each
    found by name."""
    bench = _json(os.path.join(REPO, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise BenchFailure(f"BENCHMARK.json has no workload {name!r}")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = _json(os.path.join(REPO, conf["file"]))
    traffic = _json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    config["chips"] = cell["chips"]
    return bench, config, traffic


def load_metrics(cell: str, directory: str = "") -> list:
    """The per-layer metric files that apply to `cell`."""
    out = []
    for path in sorted(glob.glob(
            os.path.join(directory or os.path.join(HERE, "metrics"),
                         "*.json"))):
        m = _json(path)
        if "workloads" not in m or cell in m["workloads"]:
            out.append(m)
    return out


@contextlib.contextmanager
def workdir():
    """A scratch directory under TMPDIR, removed at the end."""
    work = tempfile.mkdtemp(prefix="dssbench-")
    try:
        yield work
    finally:
        shutil.rmtree(work, ignore_errors=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def split_cores(config: dict) -> tuple:
    """(generator's cores, server's cores) of this machine, by the
    configuration's rule: the generator takes the highest-numbered
    `cores.generator`, the server the rest."""
    have = sorted(os.sched_getaffinity(0))
    rule = config["cores"]
    if len(have) < rule["min_total"]:
        raise BenchFailure(
            f"{len(have)} cores; the cell needs {rule['min_total']} so "
            "that generator and server do not share any"
        )
    return have[-rule["generator"]:], have[:-rule["generator"]]


def start_server(config: dict, wal: str, work: str, platform: str,
                 trace: bool, server_cores: list, gen_cores: list):
    srv_conf = config["server"]
    env = dict(os.environ, JAX_PLATFORMS=platform, **srv_conf.get("env", {}))
    env.pop("DSS_LOG_LEVEL", None)  # the boot readers want the info lines
    argv = [
        sys.executable, "-m", "dss_tpu.cmds.server",
        "--addr", f":{free_port()}", "--storage", "tpu",
        "--wal_path", wal, "--workers", str(srv_conf["workers"]),
        *srv_conf["flags"],
    ]
    tokens = []
    auth = srv_conf.get("auth")
    if auth:
        # the deployment authenticates its callers: the key pair of its
        # OAuth provider, and one token per USS (owners uss0, uss1, ...)
        key, pem = deploy.make_keys(work)
        argv += ["--public_key_files", pem,
                 "--accepted_jwt_audiences", auth["audience"]]
        tokens = [deploy.mint(key, f"uss{k}", auth["audience"],
                              auth["scope"], auth["ttl_s"])
                  for k in range(auth["owners"])]
    if trace:
        argv += ["--profile_dir", os.path.join(work, "profile")]
    # children inherit the affinity of the thread that starts them
    os.sched_setaffinity(0, server_cores)
    try:
        srv = deploy.Server(argv, env, os.path.join(work, "server.stderr"))
    finally:
        os.sched_setaffinity(0, gen_cores)
    srv.tokens = tokens
    return srv


def scrape_all(srv) -> dict:
    """{'leader': gauges, 'front': gauges}: the device owner's own
    /metrics, and the public port's (a worker's own gauges plus the
    whole-front families it merges from shared memory)."""
    return {
        "leader": deploy.scrape(srv.leader_url),
        "front": deploy.scrape(f"http://127.0.0.1:{srv.port}"),
    }


async def warm_and_measure(srv, workers, traffic, metro, ref, seed, t_gen,
                           rate, seconds, trace, mutate):
    """Set-up's last part and the window.  -> dict of what happened."""
    area_pools = tr.pools(traffic, metro, ref, seed, t_gen)
    comps = traffic["components"]
    # caches as a long-lived deployment holds them: every pooled area
    # asked over fresh connections until each worker has seen it
    fill = []
    for c, pool in area_pools.items():
        for rect in pool:
            req = tr.Request(0.0, c, rect, None, None, token=tr.a_token())
            req.wire = tr.wire(comps[c], req, metro)
            fill.append(req)
    for p in range(traffic.get("prefill_passes", 0) if fill else 0):
        order = np.random.default_rng([seed, 3, p]).permutation(len(fill))
        lane = tr.Client(srv.port)
        await tr.prefill(lane, [fill[k] for k in order], 8)
        await lane.close()

    client = tr.Client(srv.port)
    await client.balance(workers, traffic.get("connections_per_worker", 16))
    warm = traffic["warmup"]
    # what this run writes, from its first planned flight on: the part
    # of the reference that changes (check.compare)
    written = deploy.Written()
    # every shape the window can meet, before anything is timed: the
    # un-pooled components' requests, closed loop, a few in flight, so
    # that the kernel's shape buckets of one to a few queries compile now
    loose = [c for c in comps if not c.get("pool")]
    if warm.get("burst_requests") and loose:
        burst = tr.build({"components": loose}, metro, ref, {},
                         np.random.default_rng([seed, 4]), t_gen,
                         warm["burst_requests"], 1.0)
        written.absorb(burst, await tr.prefill(
            client, burst, warm["burst_in_flight"]))
        srv.check_alive("warming up")
    compiles = [deploy.scrape(srv.leader_url).get("dss_jax_compiles", 0.0)]
    chunk = 0
    t_warm = time.monotonic()
    while True:
        reqs = tr.build(traffic, metro, ref, area_pools,
                        np.random.default_rng([seed, 2, chunk]), t_gen,
                        rate, warm["chunk_s"])
        written.absorb(reqs, await tr.offer(client, reqs, grace_s=0.0))
        chunk += 1
        srv.check_alive("warming up")
        compiles.append(
            deploy.scrape(srv.leader_url).get("dss_jax_compiles", 0.0))
        still = compiles[-1] == compiles[-2]
        if chunk * warm["chunk_s"] >= warm["at_least_s"] and still:
            break
        if chunk * warm["chunk_s"] >= warm["at_most_s"]:
            log(f"warm-up: compiles still moving after {warm['at_most_s']} s")
            break
    warm_s = time.monotonic() - t_warm

    requests = tr.build(traffic, metro, ref, area_pools,
                        np.random.default_rng([seed, 1]), t_gen,
                        rate, seconds)
    client.drop_spares()  # what a stall of the warm-up had opened
    opened_before = client.opened
    s0 = scrape_all(srv)
    opened = {}

    def on_open():
        opened["setup_s"] = process_age_s()

    out = await tr.offer(client, requests, on_open=on_open)
    written.absorb(requests, out)
    if mutate is not None:
        mutate(requests, out, written)
    s1 = scrape_all(srv)
    got = {
        "requests": requests, "out": out, "written": written,
        "scrape0": s0, "scrape1": s1,
        "setup_s": opened["setup_s"], "warm_s": warm_s,
        "warm_chunks": chunk,
        "opened_in_window": client.opened - opened_before,
    }
    if trace:
        got.update(await traced_stretch(
            srv, client, traffic, metro, ref, area_pools, seed, t_gen, rate,
            min(seconds, TRACE_MAX_S)))
        written.absorb(got["traced_requests"], got["traced_out"])
    got["connections"] = client.opened
    await client.close()
    return got


async def traced_stretch(srv, client, traffic, metro, ref, area_pools, seed,
                         t_gen, rate, traced_s) -> dict:
    """The capture, over a stretch of the cell's own traffic at its own
    rate that follows the window.  Not inside it: the leader's capture
    hooks every Python call, and under it the wide cell's latencies
    went from 14 ms to the deadline and the planner's routes with them
    (PERF.md section 6), so the counters and the generator's numbers of
    a traced run are read over an untouched window and only the trace
    over this stretch."""
    requests = tr.build(traffic, metro, ref, area_pools,
                        np.random.default_rng([seed, 6]), t_gen, rate,
                        traced_s)
    profile = {}

    def capture():
        profile["answer"] = deploy.http_json(
            srv.leader_url, "POST",
            f"/debug/profile?seconds={traced_s + 2 * TRACE_PAD_S}",
            timeout=traced_s + 2 * TRACE_PAD_S + 120, token=tr.a_token())

    thread = threading.Thread(target=capture)
    thread.start()
    await asyncio.sleep(TRACE_PAD_S)
    out = await tr.offer(client, requests, grace_s=0.0)
    await asyncio.get_running_loop().run_in_executor(None, thread.join)
    return {"traced_requests": requests, "traced_out": out,
            "profile": profile.get("answer"), "traced_s": traced_s}


def memory_bytes(leader: dict) -> int:
    """What the program itself counts as resident on the device, summed
    over the entity classes (dss_dar_<class>_tier_device_bytes).  The
    program exports no allocator peak, and only the process that holds
    the chip could ask for one (PERF.md, open questions)."""
    return int(sum(v for k, v in leader.items()
                   if k.endswith("_tier_device_bytes")))


@contextlib.contextmanager
def booted(config: dict, seed: int, platform: str, trace: bool,
           fault: str = ""):
    """The deployment, generated from the seed and serving: yields a
    dict with the server, the metro, the reference sets, the generation
    second, the leader's backend report and set-up facts.  The server
    and the scratch directory are gone when the block ends.  `fault`
    is for the tests (see FAULTS)."""
    all_cores = os.sched_getaffinity(0)
    gen_cores, server_cores = split_cores(config)
    with workdir() as work:
        srv = None
        try:
            os.sched_setaffinity(0, gen_cores)
            native_s = deploy.build_native()
            t_gen = int(time.time())
            t0 = time.monotonic()
            wal = os.path.join(work, "dss.wal")
            metro, ref = deploy.generate(seed, config["generator"], t_gen,
                                         wal)
            gen_s = time.monotonic() - t0
            if fault == "lose_tail":
                lose_tail(wal)
            log(f"native {native_s:.1f}s, WAL {os.path.getsize(wal) >> 20} "
                f"MiB in {gen_s:.1f}s")
            srv = start_server(config, wal, work, platform, trace,
                               server_cores, gen_cores)
            tr.TOKENS[:] = srv.tokens
            boot = deploy.wait_ready(srv, config["server"]["workers"],
                                     config["server"]["boot_timeout_s"])
            log(f"serving: {boot}")
            backend = deploy.http_json(
                srv.leader_url, "GET", "/status")[1]["backend"]
            if backend["platform"] != platform or int(
                    backend["device_count"]) < config["chips"]:
                raise BenchFailure(
                    f"the leader serves from {backend}; the cell needs "
                    f"{config['chips']} x {platform}")
            yield {
                "srv": srv, "work": work, "metro": metro, "ref": ref,
                "t_gen": t_gen, "backend": backend,
                "cores": {"generator": gen_cores, "server": server_cores},
                "setup": {"native_build_s": native_s, "generate_s": gen_s,
                          **boot},
            }
        finally:
            tr.TOKENS.clear()
            if srv is not None:
                srv.stop()
            os.sched_setaffinity(0, all_cores)


# faults that dssbench/tests plant under a run to see `correct` come out
# false: the server boots from a WAL that lost its newest records, one
# answer loses an id on its way out of the served path, or (where the
# traffic writes) a search answers as if a flight that this run was
# acknowledged before it had never been written
FAULTS = ("lose_tail", "alter_answer", "forget_write")


def lose_tail(wal: str, share: float = 0.02) -> None:
    with open(wal, "r", encoding="utf-8") as fh:
        lines = fh.readlines()
    with open(wal, "w", encoding="utf-8") as fh:
        fh.writelines(lines[: len(lines) - max(1, int(len(lines) * share))])


def alter_answer(requests: list, out, written=None) -> None:
    """Drop the first id of the first answer that holds one."""
    for k, body in enumerate(out.body):
        doc = json.loads(body) if body and out.status[k] == 200 else {}
        for key, val in doc.items():
            if isinstance(val, list) and val and "id" in val[0]:
                doc[key] = val[1:]
                out.body[k] = json.dumps(doc).encode()
                return


def forget_write(requests: list, out, written) -> None:
    """Drop the flights of this run from every search answer of the
    window: acknowledged writes that are not read back."""
    flights = set(written.ids)
    dropped = 0
    for k, req in enumerate(requests):
        if req.kind != "search" or out.status[k] != 200:
            continue
        doc = json.loads(out.body[k])
        for key, val in doc.items():
            kept = [e for e in val if e.get("id") not in flights]
            if len(kept) < len(val):
                dropped += len(val) - len(kept)
                doc[key] = kept
                out.body[k] = json.dumps(doc).encode()
    if not dropped:
        raise BenchFailure("no search of the window read a flight back")


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             config: dict, traffic: dict, metrics: list,
             end_to_end: list, platform: str = "tpu", fault: str = "",
             keep: str = "") -> tuple:
    """One whole run.  -> (result object, facts).  `platform` and
    `fault` are for the tests: the command always runs on the TPU,
    unaltered."""
    rate = traffic["rate_rps"]
    mutate = {"alter_answer": alter_answer,
              "forget_write": forget_write}.get(fault)
    with booted(config, seed, platform, trace, fault) as dep:
        srv, metro, ref = dep["srv"], dep["metro"], dep["ref"]
        backend = dep["backend"]
        got = asyncio.run(warm_and_measure(
            srv, config["server"]["workers"], traffic, metro, ref, seed,
            dep["t_gen"], rate, seconds, trace, mutate))
        srv.check_alive("serving the window")
        bootlog, _ = srv.log_records()
        trace_file = None
        if trace:
            found = glob.glob(os.path.join(
                dep["work"], "profile", "**", "*.xplane.pb"), recursive=True)
            if not found:
                raise BenchFailure(f"no trace captured: {got['profile']}")
            trace_file = max(found, key=os.path.getmtime)
        mem = memory_bytes(got["scrape1"]["leader"])
        srv.stop()  # the program's state is freed before the reference
        if keep:
            os.makedirs(keep, exist_ok=True)
            shutil.copy(srv.stderr_path, keep)
            if trace_file:
                shutil.copy(trace_file, keep)
            with open(os.path.join(keep, "scrapes.json"), "w") as fh:
                json.dump([got["scrape0"], got["scrape1"]], fh)
        t_cmp = time.monotonic()
        cmp = check.compare(traffic, got["requests"], got["out"], metro, ref,
                            got["written"])
        cmp_s = time.monotonic() - t_cmp
        ctx = {
            "cell": cell, "seconds": seconds, "rate": rate,
            "traffic": traffic, "requests": got["requests"],
            "out": got["out"], "good": cmp["good"],
            "read_back": cmp["read_back"], "metro": metro,
            "ref": ref, "scrape0": got["scrape0"],
            "scrape1": got["scrape1"], "bootlog": bootlog,
            "setup_s": got["setup_s"], "trace_file": trace_file,
            "device_kind": backend["device_kind"],
        }
        values = {}
        breakdown = capture = None
        device = {
            "platform": backend["platform"],
            "kind": backend["device_kind"],
            "count": int(backend["device_count"]),
            "memory_peak_bytes": mem,
        }
        if trace:
            from .readers import xplane as xreader

            # the stretch under the capture: its answers are judged only
            # to count the bytes that right ones needed, not for `correct`
            ctx["traced"] = {
                "requests": got["traced_requests"], "out": got["traced_out"],
                "seconds": got["traced_s"],
                "good": check.compare(
                    traffic, got["traced_requests"], got["traced_out"],
                    metro, ref, got["written"])["good"],
            }
            t_red = time.monotonic()
            red = xreader.reduction(ctx)
            log(f"trace of {os.path.getsize(trace_file) >> 20} MiB reduced "
                f"in {time.monotonic() - t_red:.1f}s")
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            log("capture without a device op" if red["busy_s"] <= 0 else
                f"capture {red['capture_s']:.2f}s: device work over "
                f"{red['window_s']:.2f}s of it, {red['lead_s']:.2f}s after "
                f"its start, {red['tail_s']:.2f}s before its end")
            breakdown = red["breakdown"]
            capture = {k: red[k] for k in ("capture_s", "window_s", "lead_s",
                                           "tail_s", "modules")}
        for m in (metrics if trace else end_to_end):
            reader = importlib.import_module(
                f"dssbench.readers.{m['reader']}")
            v = reader.read(ctx, **m.get("args", {}))
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
    lat = tr.latencies_ms(got["requests"], got["out"], cmp["good"])
    due = tr.due_times(got["requests"])
    which = np.array([r.comp for r in got["requests"]])
    chains = [c for c in got["out"].chain if c]
    correct, checks = check.verdict(cmp["numbers"], cmp["facts"]["compared"])
    result = {
        "correct": bool(correct),
        "attempted": len(got["requests"]),
        "failed": int((lat >= tr.DEADLINE_S * 1000.0).sum()),
        "metrics": values,
        "device": device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks
    facts = {
        "cell": cell, "seed": seed, "seconds": seconds, "rate_rps": rate,
        "knee_rps": traffic.get("knee_rps"), "trace": bool(trace),
        "cores": dep["cores"],
        "setup": {**dep["setup"], "warm_s": got["warm_s"],
                  "warm_chunks": got["warm_chunks"]},
        "connections": got["connections"],
        "opened_in_window": got["opened_in_window"], "compare_s": cmp_s,
        **cmp["facts"], "first_wrong": cmp["first_wrong"],
        "first_refusal": next(
            (f"{int(st)}: {(got['out'].body[k] or b'')[:300]!r}"
             for k, st in enumerate(got["out"].status)
             if st in check.REFUSALS), ""),
        "latency_ms": {f"p{q}": tr.percentile(lat, q)
                       for q in (50, 90, 95, 99)},
        "latency_ms_by_component": [
            {f"p{q}": tr.percentile(lat[which == c], q) for q in (50, 95)}
            for c in range(len(traffic["components"]))],
        # the planned flights' chains: how many exchanges each took, and
        # the status each ended in
        "chains": {
            "rounds": {str(n): sum(len(c) == n for c in chains)
                       for n in sorted({len(c) for c in chains})},
            "ended": {str(st): sum(c[-1].status == st for c in chains)
                      for st in sorted({c[-1].status for c in chains})},
        },
        "p95_ms_by_5s": [
            tr.percentile(lat[(due >= a) & (due < a + 5)], 95)
            for a in range(0, int(seconds), 5)],
        "gen_late_p95_ms": tr.percentile(
            tr.lateness_ms(got["requests"], got["out"]), 95),
        # what a stall leaves behind, in an untraced run too: whether the
        # generator itself stood still (and when), a compile, a ring that
        # timed out (PERF.md section 7: one poll run in about 40 stalls)
        "stall": stall_facts(got, due),
        "capture": capture,
        "resident_bytes": mem,
        "leader": {k: v - got["scrape0"]["leader"].get(k, 0.0)
                   if "_co_plan_" in k else v
                   for k, v in got["scrape1"]["leader"].items()
                   if k.startswith("dss_dar_op_co_plan_")
                   or k.startswith("dss_dar_op_co_est_")
                   or k.startswith("dss_dar_op_co_res_aot_")
                   or k.startswith("dss_dar_op_tier_l")},
    }
    return result, facts


def stall_facts(got: dict, due: np.ndarray) -> dict:
    late = (got["out"].sent - due) * 1000.0
    worst = int(np.nanargmax(late)) if not np.isnan(late).all() else 0
    s0, s1 = got["scrape0"], got["scrape1"]

    def moved(proc: str, name: str) -> float:
        return s1[proc].get(name, 0.0) - s0[proc].get(name, 0.0)

    return {
        "gen_late_max_ms": float(late[worst]),
        "gen_late_max_due_s": float(due[worst]),
        "compiles_in_window": moved("leader", "dss_jax_compiles"),
        "compile_s_in_window": moved("leader", "dss_jax_compile_seconds"),
        "ring_timeouts": moved("front", "dss_shm_worker_timeouts"),
        "ring_fallbacks": moved("front", "dss_shm_worker_proxy_fallbacks"),
    }


def end_to_end_readers(bench: dict, cell: str) -> list:
    """The end-to-end metrics of `cell`, each with the reader that
    computes it (dssbench/end_to_end/<name>.json)."""
    return [
        {**m, **_json(os.path.join(HERE, "end_to_end", m["name"] + ".json"))}
        for m in bench["end_to_end"]
        if "workloads" not in m or cell in m["workloads"]
    ]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default="",
                    help="copy the server's log and the trace here")
    args = ap.parse_args()

    def stopped(signum, _frame):
        raise BenchFailure(f"stopped by signal {signum}")

    signal.signal(signal.SIGTERM, stopped)  # the server goes down with us
    try:
        bench, config, traffic = load_cell(args.workload)
        result, facts = run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            config=config, traffic=traffic,
            metrics=load_metrics(args.workload),
            end_to_end=end_to_end_readers(bench, args.workload),
            keep=args.keep,
        )
    except BenchFailure as e:
        print(f"dssbench FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"facts": facts}))
    for name, c in result["checks"].items():
        print(f"check {name}: {json.dumps(c)}", file=sys.stderr)
    print(f"correct: {result['correct']}  {facts['first_wrong']}",
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
