"""Several runs of one cell in one call, and their spreads: not part of
the cell command.

  chiprun --timeout 3000 -- python3 -m dssbench.series --workload <cell> \
      --seeds 11,12,13,14,15,16 [--trace 0] [--seconds n] [--tag set1]

Each run is the cell command in a process of its own, one after another
(one process holds the chip).  Its stdout and the end of its stderr go
to chiprun_out/series.<cell>.<tag>/<seed>.{out,err}; what is printed is
one line per run (correct, every metric of its result line, and from
the facts line how late the generator ran and what compiled) and then,
per metric, the median and the spread PERF.md section 2 sets bounds by:
the distance between the quartiles (statistics.quantiles, n=4) as a
share of the median, over all runs and without the first, which
compiles.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

from .deploy import REPO


def spread(values: list):
    """IQR / median, or None under 3 values."""
    if len(values) < 3:
        return None
    q = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else None


def one(cell: str, seed: int, seconds: float, trace: int, out_dir: str):
    argv = [sys.executable, "-m", "dssbench.run", "--workload", cell,
            "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace)]
    proc = subprocess.run(argv, cwd=REPO, capture_output=True, text=True)
    with open(os.path.join(out_dir, f"{seed}.out"), "w") as fh:
        fh.write(proc.stdout)
    with open(os.path.join(out_dir, f"{seed}.err"), "w") as fh:
        fh.write(proc.stderr[-20000:])
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if proc.returncode or len(lines) < 2:
        return {"seed": seed, "rc": proc.returncode,
                "error": proc.stderr[-600:]}
    facts, result = json.loads(lines[-2])["facts"], json.loads(lines[-1])
    row = {"seed": seed, "correct": result["correct"],
           "attempted": result["attempted"], "failed": result["failed"],
           **{k: v["value"] for k, v in result["metrics"].items()},
           "gen_late_max_ms": facts["stall"]["gen_late_max_ms"],
           "compiles": facts["stall"]["compiles_in_window"],
           "first_wrong": facts["first_wrong"],
           "checks": {k: v["value"] for k, v in result["checks"].items()}}
    for key in ("busy_s", "window_s", "memory_peak_bytes"):
        if key in result["device"]:
            row[key] = result["device"][key]
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tag", default="runs")
    args = ap.parse_args()
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        seconds = args.seconds or json.load(fh)["run_seconds"]
    out_dir = os.path.join(REPO, "chiprun_out",
                           f"series.{args.workload}.{args.tag}")
    os.makedirs(out_dir, exist_ok=True)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(one(args.workload, seed, seconds, args.trace, out_dir))
        print(json.dumps(rows[-1]), flush=True)
    ok = [r for r in rows if "error" not in r]
    summary = {}
    for name in sorted({k for r in ok for k, v in r.items()
                        if isinstance(v, float)}):
        vals = [r[name] for r in ok if name in r]
        summary[name] = {"median": statistics.median(vals),
                         "spread": spread(vals),
                         "spread_without_first": spread(vals[1:]),
                         "values": vals}
    doc = {"workload": args.workload, "trace": args.trace,
           "seconds": seconds, "runs": rows, "summary": summary}
    with open(os.path.join(out_dir, "summary.json"), "w") as fh:
        json.dump(doc, fh, indent=1)
    print(json.dumps({"summary": {k: {a: b for a, b in v.items()
                                      if a != "values"}
                                  for k, v in summary.items()}}))
    return 0 if len(ok) == len(rows) and all(r["correct"] for r in ok) else 1


if __name__ == "__main__":
    sys.exit(main())
