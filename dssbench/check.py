"""The comparison that decides `correct`, and its controls.

Every answer of the window's own requests is compared, id set for id
set, with the plain reference (deploy.EntitySet.search) over the data
this process generated from the seed.  An exact comparison: the limit
on wrong answers is 0.  A refusal (429/503/504) or a late answer is
late, not wrong: it costs latency and goodput; only an answer that
never comes, or says the wrong thing, is for `correct`.

The controls are the reference put in the program's place with one
stated guarantee broken:

  stale    strong reads — the answer leaves out the newest 1% of the
           WAL's records (a replica that lags, an acknowledged write
           lost);
  lowprec  the DAR's stated widths — altitudes in float16 for float32,
           times in float32 seconds for int64 nanoseconds.

`python -m dssbench.check --workload <cell> --seeds a,b,c` puts each,
on the cell's own data and the window's own requests, through the same
`compare` and `verdict` that judge a run: the control answers every
request as a server would (answers_of), and what is printed per seed is
its `correct` and each number beside its limit.  No server and no chip
are needed for it; the benchmark's own runs do not run it.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time

import numpy as np

from . import deploy, traffic as tr
from .deploy import NS

# number compared -> its limit (upper bound, inclusive)
LIMITS = {"wrong_answers": 0, "never_answered": 0}
REFUSALS = (429, 503, 504)  # the server's honest "not now"


def expected(req, comp: dict, metro, ref, now_ns: int) -> set:
    """The reference's answer to one request."""
    cls = tr.ENDPOINTS[comp["endpoint"]]["class"]
    alt = req.alt or (None, None)
    when = req.when or (None, None)
    return ref[cls].search(
        metro.rect_flat(*req.rect), alt[0], alt[1],
        None if when[0] is None else when[0] * NS,
        None if when[1] is None else when[1] * NS,
        now=now_ns,
    )


def answered_ids(comp: dict, body: bytes) -> set:
    doc = json.loads(body)
    return {e["id"] for e in doc[tr.ENDPOINTS[comp["endpoint"]]["answer"]]}


def compare(traffic: dict, requests: list, out, metro, ref) -> dict:
    """-> {"good": per-request bool (a right answer, whenever it came),
    "numbers": {name: value}, "first_wrong": str}.  The reference is
    taken after the answers: both clocks only move forward and no
    record ends within an hour of the run."""
    now_ns = time.time_ns()
    comps = traffic["components"]
    good = np.zeros(len(requests), bool)
    memo = {}
    wrong = never = refused = compared = 0
    first = ""
    for k, req in enumerate(requests):
        status = int(out.status[k])
        if status in REFUSALS:
            refused += 1
            continue
        if status != 200 or out.body[k] is None:
            never += 1
            first = first or f"request {k}: status {status}, no answer"
            continue
        comp = comps[req.comp]
        key = (req.comp, req.rect, req.alt, req.when)
        if key not in memo:
            memo[key] = expected(req, comp, metro, ref, now_ns)
        want = memo[key]
        compared += 1
        try:
            got = answered_ids(comp, out.body[k])
        except (ValueError, KeyError, TypeError):
            got = None
        if got == want:
            good[k] = True
        else:
            wrong += 1
            if not first:
                first = (f"request {k} {req.rect}: " + (
                    "unreadable answer" if got is None else
                    f"{len(got - want)} unexpected, {len(want - got)} "
                    f"missing of {len(want)}"))
    return {
        "good": good,
        "numbers": {"wrong_answers": wrong, "never_answered": never},
        "facts": {"compared": compared, "refused": refused,
                  "distinct_answers": len(memo),
                  "ids_expected": sum(len(v) for v in memo.values())},
        "first_wrong": first,
    }


def verdict(numbers: dict, compared: int) -> tuple:
    """(correct, the `checks` object: each number beside its limit)."""
    checks = {k: {"value": numbers[k], "limit": LIMITS[k]} for k in LIMITS}
    checks["answers_compared"] = {"value": compared, "at_least": 1}
    ok = compared >= 1 and all(
        numbers[k] <= LIMITS[k] for k in LIMITS)
    return ok, checks


# ---------------------------------------------------------------------------
# controls: the reference with one guarantee broken
# ---------------------------------------------------------------------------


def stale(ref: dict, share: float = 0.01) -> dict:
    """The reference without the newest `share` of each class's records."""
    out = {}
    for cls, es in ref.items():
        es = copy.copy(es)
        es.live = np.ones(len(es.ids), bool)
        es.live[len(es.ids) - max(1, int(len(es.ids) * share)):] = False
        out[cls] = es
    return out


def lowprec(ref: dict) -> dict:
    """The reference with its columns one width down: altitudes through
    float16, instants through float32 seconds."""
    out = {}
    for cls, es in ref.items():
        es = copy.copy(es)
        es.alt_lo = es.alt_lo.astype(np.float16).astype(np.float64)
        es.alt_hi = es.alt_hi.astype(np.float16).astype(np.float64)
        sec = lambda t: (t / NS).astype(np.float32).astype(np.int64) * NS
        es.t0, es.t1 = sec(es.t0), sec(es.t1)
        out[cls] = es
    return out


CONTROLS = {"stale": stale, "lowprec": lowprec}


def answers_of(traffic: dict, requests: list, metro, served) -> tr.Outcome:
    """What the window would have brought back had `served` (a
    reference, sound or broken) stood in the program's place: every
    request answered at once with status 200 and a body in the
    endpoint's own form."""
    now_ns = time.time_ns()
    comps = traffic["components"]
    body = []
    for r in requests:
        comp = comps[r.comp]
        ids = expected(r, comp, metro, served, now_ns)
        body.append(json.dumps(
            {tr.ENDPOINTS[comp["endpoint"]]["answer"]:
             [{"id": i} for i in sorted(ids)]}).encode())
    n = len(requests)
    return tr.Outcome(np.zeros(n), np.full(n, 0.01),
                      np.full(n, 200, np.int32), body)


def judge_control(traffic: dict, requests: list, metro, ref, served) -> tuple:
    """(correct, checks) of a run in which `served` answered: through
    the comparison and the verdict that decide a run's `correct`."""
    cmp = compare(traffic, requests,
                  answers_of(traffic, requests, metro, served), metro, ref)
    return verdict(cmp["numbers"], cmp["facts"]["compared"])


# ---------------------------------------------------------------------------
# what a device-route request has to move, whatever implements the kernel
# ---------------------------------------------------------------------------

POSTING_BYTES = 24  # two f32 altitudes, two i64 instants
RESULT_BYTES = 8


def needed_bytes(candidates: int, results: int) -> int:
    """The least a search can read and write: the exact columns of
    every candidate posting under its covering, and one id per result."""
    return candidates * POSTING_BYTES + results * RESULT_BYTES


def main() -> int:
    from .run import load_cell, workdir

    ap = argparse.ArgumentParser(description="read a cell's controls")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args()
    bench, config, traffic = load_cell(args.workload)
    seconds = args.seconds or bench["run_seconds"]
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        with workdir() as work:
            t_gen = int(time.time())
            metro, ref = deploy.generate(
                seed, config["generator"], t_gen, f"{work}/dss.wal")
        reqs = tr.build(
            traffic, metro, ref, tr.pools(traffic, metro, ref, seed, t_gen),
            np.random.default_rng([seed, 1]), t_gen,
            traffic["rate_rps"], seconds)
        for name, fn in {"sound": lambda r: r, **CONTROLS}.items():
            correct, checks = judge_control(traffic, reqs, metro, ref,
                                            fn(ref))
            print(json.dumps({
                "workload": args.workload, "seed": seed, "served_by": name,
                "requests": len(reqs), "correct": correct, "checks": checks,
            }), flush=True)
            bad += correct is (name != "sound")
    return 1 if bad else 0  # a control that passes, or a sound one that fails


if __name__ == "__main__":
    sys.exit(main())
