"""The comparison that decides `correct`, and its controls.

Every answer of the window's own requests is compared, id set for id
set, with the plain reference (deploy.EntitySet.search) over the data
this process generated from the seed.  An exact comparison: the limit
on wrong answers is 0.  A refusal (429/503/504) or a late answer is
late, not wrong: it costs latency and goodput; only an answer that
never comes, or says the wrong thing, is for `correct`.

Where the traffic writes (planned flights, traffic.py `scd_put`), the
reference changes under the window: beside the WAL's static sets stands
deploy.Written, the flights of this run with the instants they were
first sent and acknowledged.  Every exchange, a search or one PUT of a
chain, sent at s and done at d, is then judged by must / may:

  must  the static answer and every flight acknowledged before s that
        matches the volume;
  may   every matching flight whose [first sent, acknowledged or never)
        overlaps [s, d];

a search or a 409's listing is right iff must <= got <= must | may; a
200 is wrong where its key misses a must conflict, a 409 where its key
held must | may entirely; a 200's subscribers are held to the same
rule over the subscriptions that share a cell with the flight.  That is
"reads: strong" as far as a run can show: an acknowledged write is read
back by every later search, through whichever worker answers it, and
nothing unwritten appears.

The controls are the reference put in the program's place with one
stated guarantee broken:

  stale    strong reads — the answer leaves out the newest 1% of the
           WAL's records (a replica that lags, an acknowledged write
           lost);
  lowprec  the DAR's stated widths — altitudes in float16 for float32,
           times in float32 seconds for int64 nanoseconds;
  lost_write  strong reads of the window's own writes — every 100th
           flight is acknowledged and then forgotten (only where the
           traffic writes: a read-only cell has nothing to lose).

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


def _is_op(comp: dict) -> bool:
    return tr.ENDPOINTS[comp["endpoint"]]["class"] == "op"


def answered_ids(comp: dict, body: bytes) -> set:
    doc = json.loads(body)
    return {e["id"] for e in doc[tr.ENDPOINTS[comp["endpoint"]]["answer"]]}


def notified(body: bytes) -> tuple:
    """(the subscription ids a 200 says to notify, the flight's own)."""
    doc = json.loads(body)
    return ({s["subscription_id"] for grp in doc["subscribers"]
             for s in grp["subscriptions"]},
            doc["operation_reference"]["subscription_id"])


def matching(cols: dict, req, cells_only: bool = False) -> np.ndarray:
    """Which of the written flights a request's volume meets, by the
    plain semantics of EntitySet.search: shares a cell (both are
    rectangles of metro cells) AND altitudes overlap AND times overlap.
    An untimed search starts at the server's now, hours before any
    flight opens."""
    i, j, w, h = req.rect
    hit = ((cols["i0"] < i + w) & (cols["i1"] > i)
           & (cols["j0"] < j + h) & (cols["j1"] > j))
    if cells_only:
        return hit
    if req.alt is not None:
        hit &= (cols["hi"] >= req.alt[0]) & (cols["lo"] <= req.alt[1])
    if req.when is not None:
        hit &= (cols["t1"] >= req.when[0]) & (cols["t0"] <= req.when[1])
    return hit


def must_may(cols: dict, hit: np.ndarray, s: float, d: float) -> tuple:
    """Of the flights `hit`, (those acknowledged before s, those that
    may or may not stand for an exchange sent at s and done at d)."""
    must = hit & (cols["acked"] < s)
    return must, hit & ~must & (cols["first_sent"] <= d)


def compare(traffic: dict, requests: list, out, metro, ref,
            written=None) -> dict:
    """-> {"good": per-request bool (a right answer, whenever it came),
    "numbers": {name: value}, "first_wrong": str, "read_back": per
    request, whether a flight of this run was due in its answer}.  The
    static reference is taken after the answers: both clocks only move
    forward and no record ends within an hour of the run; `written`
    (deploy.Written) is what the run itself wrote, by must / may."""
    now_ns = time.time_ns()
    comps = traffic["components"]
    cols = (written or deploy.Written()).columns()
    row_of = {i: n for n, i in enumerate(cols["ids"])}
    known = np.array([x is not None for x in cols["subs"]], bool)
    good = np.zeros(len(requests), bool)
    read_back = np.zeros(len(requests), bool)
    memo = {}
    wrong = never = refused = compared = 0
    first = ""

    def static(req):
        key = (req.comp, req.rect, req.alt, req.when)
        if key not in memo:
            memo[key] = expected(req, comps[req.comp], metro, ref, now_ns)
        return memo[key]

    def others(req, s, d, cells_only=False):
        """must_may over the flights that req meets, itself left out."""
        hit = matching(cols, req, cells_only)
        if req.id in row_of:
            hit[row_of[req.id]] = False
        return must_may(cols, hit, s, d)

    def sets(req, s, d):
        """(must, must | may, whether a flight is in must) of an
        exchange over req's own volume."""
        want = static(req)
        if not len(row_of) or not _is_op(comps[req.comp]):
            return want, want, False
        must, may = others(req, s, d)
        want = want | set(cols["ids"][must])
        return want, want | set(cols["ids"][may]), bool(must.any())

    def chain_fault(req, chain) -> str:
        """What is wrong with a chain's exchanges, '' if nothing."""
        for n, ex in enumerate(chain):
            s, d = out.t_open + ex.sent, out.t_open + ex.done
            must, allowed, _ = sets(req, s, d)
            key = set(ex.key)
            if ex.status == 409 and ex.listed is not None:
                got = set(ex.listed)
                if not must <= got <= allowed:
                    return (f"PUT {n} listed {len(got - allowed)} "
                            f"unexpected, {len(must - got)} missing of "
                            f"{len(must)} conflicts")
                if allowed <= key:
                    return f"PUT {n} refused though its key held all"
            elif ex.status == 200:
                if not must <= key:
                    return (f"PUT {n} accepted past {len(must - key)} "
                            "conflicts its key did not hold")
                try:
                    got, own = notified(ex.body)
                except (ValueError, KeyError, TypeError):
                    return f"PUT {n}: unreadable answer"
                before, during = others(req, s, d, cells_only=True)
                must_s = ref["scd_sub"].search(
                    metro.rect_flat(*req.rect), now=now_ns) | {own} | set(
                    cols["subs"][before & known])
                extra = got - must_s - set(cols["subs"][during & known])
                if not must_s <= got or len(extra) > int(
                        (during & ~known).sum()):
                    return (f"PUT {n} subscribers: {len(extra)} unexpected, "
                            f"{len(must_s - got)} missing of {len(must_s)}")
        return ""

    for k, req in enumerate(requests):
        status = int(out.status[k])
        chain = out.chain[k] if out.chain else None
        fault = chain_fault(req, chain) if chain else ""
        if fault:
            compared += 1
            wrong += 1
            first = first or f"request {k} {req.rect} flight {req.id}: {fault}"
            continue
        if status in REFUSALS:
            refused += 1
            continue
        if status != 200 or out.body[k] is None:
            never += 1
            first = first or f"request {k}: status {status}, no answer"
            continue
        compared += 1
        if req.kind == "write":
            good[k] = True
            continue
        want, allowed, read_back[k] = sets(
            req, out.t_open + out.sent[k], out.t_open + out.done[k])
        try:
            got = answered_ids(comps[req.comp], out.body[k])
        except (ValueError, KeyError, TypeError):
            got = None
        if got is not None and want <= got <= allowed:
            good[k] = True
        else:
            wrong += 1
            if not first:
                first = (f"request {k} {req.rect}: " + (
                    "unreadable answer" if got is None else
                    f"{len(got - allowed)} unexpected, {len(want - got)} "
                    f"missing of {len(want)}"))
    return {
        "good": good, "read_back": read_back,
        "numbers": {"wrong_answers": wrong, "never_answered": never},
        "facts": {"compared": compared, "refused": refused,
                  "distinct_answers": len(memo),
                  "ids_expected": sum(len(v) for v in memo.values()),
                  "flights_written": len(row_of),
                  "flights_unknown": int(np.isinf(cols["acked"]).sum()),
                  "searches_read_back": int(read_back.sum())},
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


class LostWrites(dict):
    """The sound reference in the place of a server that acknowledges
    every flight and forgets each `lose_every`-th, the first among
    them."""
    lose_every = 100


def lost_write(ref: dict) -> dict:
    return LostWrites(ref)


CONTROLS = {"stale": stale, "lowprec": lowprec, "lost_write": lost_write}


def writes(traffic: dict) -> bool:
    return any(tr.is_write(c) for c in traffic["components"])


def controls_for(traffic: dict) -> dict:
    """The controls that have to read not correct under `traffic`."""
    return {k: v for k, v in CONTROLS.items()
            if k != "lost_write" or writes(traffic)}


TICK = 1e-6  # what the stand-in takes over one exchange, seconds


def answers_of(traffic: dict, requests: list, metro, served) -> tr.Outcome:
    """What the window would have brought back had `served` (a
    reference, sound or broken) stood in the program's place: every
    request answered at the instant it was due, one after another, with
    status 200 and a body in the endpoint's own form; a planned flight
    by its chain (409 with the conflicts `served` and the flights that
    landed before it give, then 200 with its subscribers)."""
    now_ns = time.time_ns()
    comps = traffic["components"]
    lose_every = getattr(served, "lose_every", 0)
    landed = deploy.Written()  # what the stand-in still knows it wrote
    acks = 0
    out = tr.blank_outcome(len(requests), 0.0)

    def holds(r) -> set:
        ids = expected(r, comps[r.comp], metro, served, now_ns)
        if len(landed) and _is_op(comps[r.comp]):
            cols = landed.columns()
            ids = ids | set(cols["ids"][matching(cols, r)])
        return ids

    for k, r in enumerate(requests):
        t = out.sent[k] = r.due
        if r.kind != "write":
            body = json.dumps(
                {tr.ENDPOINTS[comps[r.comp]["endpoint"]]["answer"]:
                 [{"id": i} for i in sorted(holds(r))]}).encode()
            out.done[k] = t + TICK
        else:
            chain = out.chain[k] = []
            key = sorted(holds(r))
            if key:
                chain.append(tr.Exchange(t, t + TICK, 409, json.dumps(
                    {"entity_conflicts": [
                        {"operation_reference": {"id": i, "ovn": "ovn-" + i}}
                        for i in key]}).encode(), [], list(key)))
                t += TICK
            sub = "sub-" + r.id
            subs = served["scd_sub"].search(
                metro.rect_flat(*r.rect), now=now_ns) | {sub}
            if len(landed):
                cols = landed.columns()
                subs |= set(cols["subs"][matching(cols, r, cells_only=True)])
            body = json.dumps({
                "operation_reference": {"id": r.id, "subscription_id": sub},
                "subscribers": [{"subscriptions": [
                    {"subscription_id": i} for i in sorted(subs)]}],
            }).encode()
            chain.append(tr.Exchange(t, t + TICK, 200, body, key))
            out.done[k] = t + TICK
            acks += 1
            if not (lose_every and acks % lose_every == 1):
                landed.add(r, r.due, t + TICK, sub)
        out.status[k] = 200
        out.body[k] = body
    return out


def judge_control(traffic: dict, requests: list, metro, ref, served) -> tuple:
    """(correct, checks) of a run in which `served` answered: through
    the overlay, the comparison and the verdict that decide a run's
    `correct`."""
    out = answers_of(traffic, requests, metro, served)
    written = deploy.Written()
    written.absorb(requests, out)
    cmp = compare(traffic, requests, out, metro, ref, written)
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
        for name, fn in {"sound": lambda r: r,
                         **controls_for(traffic)}.items():
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
