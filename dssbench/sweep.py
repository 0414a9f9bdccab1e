"""Find a cell's knee, once, on the chip: not part of the cell command.

  chiprun -- python3 -m dssbench.sweep --workload <cell> [--seed n] [--start r]
  python3 -m dssbench.sweep --apply chiprun_out/sweep.<cell>.json

One boot; rungs of 15 s on a x1.25 ladder from --start.  The knee is the
highest rung at which at least 99% of the offered requests were
answered correctly within the 10 s deadline AND the last third of the
rung was no later than the first third (median latency within 1.5x and
2 ms of it: no growing backlog).  The sweep stops at the first rung
that fails, or where the generator itself ran late (its p95 lateness
over 5 ms: the rung then says nothing about the server).  The result
goes to chiprun_out/sweep.<cell>.json; --apply writes ladder, knee and
rate = floor(--fraction x knee), by default half, into the cell's
traffic file.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import sys

import numpy as np

from . import check, run, traffic as tr
from .deploy import REPO, BenchFailure, log

RUNG_S = 15.0
STEP = 1.25


def judge(requests, out, good) -> dict:
    lat = tr.latencies_ms(requests, out, good)
    due = tr.due_times(requests)
    third = RUNG_S / 3
    first = np.median(lat[due < third])
    last = np.median(lat[due >= 2 * third])
    ok_share = float((lat < tr.DEADLINE_S * 1000.0).mean())
    return {
        "ok_share": ok_share, "p50_ms": tr.percentile(lat, 50),
        "p95_ms": tr.percentile(lat, 95), "first_third_p50_ms": float(first),
        "last_third_p50_ms": float(last),
        "gen_late_p95_ms": tr.percentile(tr.lateness_ms(requests, out), 95),
        "sustained": bool(ok_share >= 0.99
                          and last <= 1.5 * first + 2.0),
    }


async def climb(dep, workers, traffic, seed, start, max_rungs):
    srv, metro, ref = dep["srv"], dep["metro"], dep["ref"]
    # set-up as a run makes it: pools, prefill, warm-up at the first rung
    written = (await run.warm_and_measure(
        srv, workers, traffic, metro, ref, seed, dep["t_gen"], start, RUNG_S,
        False, None))["written"]
    area_pools = tr.pools(traffic, metro, ref, seed, dep["t_gen"])
    client = tr.Client(srv.port)
    await client.balance(workers,
                         traffic.get("connections_per_worker", 16))
    rungs = []
    rate = start
    for k in range(max_rungs):
        requests = tr.build(
            traffic, metro, ref, area_pools,
            np.random.default_rng([seed, 5, k]), dep["t_gen"], rate, RUNG_S)
        out = await tr.offer(client, requests)
        written.absorb(requests, out)  # a chain is answered correctly
        cmp = check.compare(traffic, requests, out, metro, ref, written)
        row = {"rate_rps": rate, "requests": len(requests),
               **cmp["numbers"], **judge(requests, out, cmp["good"])}
        rungs.append(row)
        log(f"rung {json.dumps(row)}")
        srv.check_alive("sweeping")
        if not row["sustained"] or row["gen_late_p95_ms"] > 5.0:
            break
        rate = round(rate * STEP, 2)
    await client.close()
    return rungs


def apply(path: str, fraction: float) -> None:
    with open(path, encoding="utf-8") as fh:
        res = json.load(fh)
    name = next(w["traffic"] for w in run._json(
        os.path.join(REPO, "BENCHMARK.json"))["workloads"]
        if w["name"] == res["workload"])
    tpath = os.path.join(run.HERE, "traffic", name + ".json")
    doc = run._json(tpath)
    doc["knee_rps"] = res["knee_rps"]
    doc["ladder_rps"] = [r["rate_rps"] for r in res["rungs"]]
    doc["rate_rps"] = int(math.floor(fraction * res["knee_rps"]))
    doc["rate_rule"] = f"floor({fraction} x knee_rps)"
    doc["sweep"] = {"seed": res["seed"], "device": res["device"],
                    "stopped_by": res["stopped_by"]}
    with open(tpath, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=20260930)
    ap.add_argument("--start", type=float, default=10.0)
    ap.add_argument("--max-rungs", type=int, default=20)
    ap.add_argument("--platform", default="tpu", help=argparse.SUPPRESS)
    ap.add_argument("--apply", default="")
    ap.add_argument("--fraction", type=float, default=0.5,
                    help="with --apply: the share of the knee to offer")
    args = ap.parse_args()
    if args.apply:
        apply(args.apply, args.fraction)
        return 0
    try:
        _, config, traffic = run.load_cell(args.workload)
        with run.booted(config, args.seed, args.platform, False) as dep:
            rungs = asyncio.run(climb(
                dep, config["server"]["workers"], traffic, args.seed,
                args.start, args.max_rungs))
            device = dep["backend"]
    except BenchFailure as e:
        print(f"sweep FAILED: {e}", file=sys.stderr)
        return 1
    held = [r for r in rungs if r["sustained"] and r["gen_late_p95_ms"] <= 5]
    last = rungs[-1]
    res = {
        "workload": args.workload, "seed": args.seed, "device": device,
        "rung_s": RUNG_S, "step": STEP, "rungs": rungs,
        "knee_rps": held[-1]["rate_rps"] if held else None,
        "stopped_by": ("generator late" if last["gen_late_p95_ms"] > 5
                       else "server" if not last["sustained"]
                       else "ladder's end"),
    }
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out",
                           f"sweep.{args.workload}.json"), "w") as fh:
        json.dump(res, fh, indent=1)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
