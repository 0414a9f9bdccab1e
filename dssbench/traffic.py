"""One general traffic generator, driven by a traffic file, and the
open-loop HTTP client that offers it.

A traffic file (dssbench/traffic/<name>.json) fixes: the arrival
process and rate, and one or more `components`, each a population of
searches: an endpoint, rectangle sides in level-13 cells, an optional
pool of fixed areas with a Zipf skew (and, by `answer_ids`, the number
of ids each pooled area's answer holds), an optional floor or ceiling
on the candidate postings under a rectangle, an altitude band and the
share of requests that carry a time window.  A component whose endpoint
is `scd_put` is a population of planned flights: one request is a
chain of PUTs on one connection (put_wire, next_put), and its draws
come from a stream of their own, so a file without one builds what it
always built.

Every seed offers the same amount and the same set of work in another
order: the number of requests is rate x seconds exactly (a Poisson
process conditioned on its count, which is uniform order statistics),
rectangle sides and pool ranks are dealt from balanced decks and
shuffled, and only positions, altitudes and instants are free draws.
"""

from __future__ import annotations

import asyncio
import collections
import gc
import json
import math
import re
import time
from dataclasses import dataclass, field

import numpy as np

from .deploy import NS, BenchFailure, _uuids

DEADLINE_S = 10.0  # the reference's RPC deadline (BASELINE.md)

# what a component's `endpoint` may name: how the request is written and
# where the answer's ids are
ENDPOINTS = {
    "scd_query": {
        "method": "POST", "path": "/dss/v1/operation_references/query",
        "class": "op", "answer": "operation_references",
    },
    "rid_search": {
        "method": "GET", "path": "/v1/dss/identification_service_areas",
        "class": "isa", "answer": "service_areas",
    },
    # one planned flight: PUT with an empty key -> 409 with the
    # conflicting op intents and their OVNs -> PUT with those as the key
    "scd_put": {
        "method": "PUT", "path": "/dss/v1/operation_references/",
        "class": "op", "answer": "entity_conflicts", "kind": "write",
    },
}
USS_URL = "https://bench.example/scd"  # where a writer is called back
# exchanges a chain may take: the empty key, the key, and 3 more rounds
# where another writer landed in between
MAX_ROUNDS = 5
# bearer tokens, one per USS, where the deployment authenticates its
# callers (run.booted sets and clears them; deploy.mint): request k of
# a build is sent by USS k mod their number.  Empty: no header is sent.
TOKENS: list = []


def iso(t_s: int) -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_s))


@dataclass
class Request:
    due: float  # seconds after the window opens
    comp: int  # index into the traffic's components
    rect: tuple  # (i, j, w, h) in metro cells
    alt: tuple | None  # (lo, hi) metres
    when: tuple | None  # (t0, t1) whole seconds
    wire: bytes = b""  # the HTTP request as sent (a chain's first)
    kind: str = "search"  # or "write": a chain of PUTs
    id: str = ""  # a planned flight's id
    stem: bytes = b""  # a PUT's body up to its key
    token: bytes = b""  # the bearer token of the USS that sends it


def _deck(values: list, n: int, rng) -> list:
    """n draws that hold every value equally often (to within one; the
    same ones for every seed), in a seeded order."""
    whole, rest = divmod(n, len(values))
    idx = np.concatenate([
        np.tile(np.arange(len(values)), whole), np.arange(rest),
    ]).astype(int)
    return [values[k] for k in rng.permutation(idx)]


def _quota(shares: list, n: int) -> list:
    """Whole counts summing to n in proportion to `shares` (largest
    remainder), so every seed gets the same count of each kind."""
    total = float(sum(shares))
    exact = [s * n / total for s in shares]
    counts = [int(math.floor(x)) for x in exact]
    by_rem = sorted(range(len(shares)), key=lambda k: exact[k] - counts[k],
                    reverse=True)
    for k in by_rem[: n - sum(counts)]:
        counts[k] += 1
    return counts


def _sides(comp: dict) -> list:
    (w0, w1), (h0, h1) = comp["w_cells"], comp["h_cells"]
    return [(w, h) for w in range(w0, w1 + 1) for h in range(h0, h1 + 1)]


def _place(comp: dict, w: int, h: int, metro, ref, rng) -> tuple:
    """A w x h rectangle somewhere in the metro, drawn again while the
    reference counts fewer candidate postings under it than the
    component's `min_candidates` (or more than `max_candidates`)."""
    cls = ENDPOINTS[comp["endpoint"]]["class"]
    lo = comp.get("min_candidates", 0)
    hi = comp.get("max_candidates")
    for _ in range(1000):
        i = int(rng.integers(0, metro.g - w + 1))
        j = int(rng.integers(0, metro.g - h + 1))
        if not lo and hi is None:
            return (i, j, w, h)
        n = ref[cls].candidates(metro.rect_flat(i, j, w, h))
        if n >= lo and (hi is None or n <= hi):
            return (i, j, w, h)
    raise BenchFailure(
        f"no {w}x{h} rectangle with candidates in [{lo}, {hi}] found"
    )


def _matched(comp: dict, ranks: list, metro, ref, rng, t_gen: int) -> list:
    """Per rank a rectangle of its sides whose answer, by the reference
    at generation time, holds as nearly as the data allow the number of
    ids that the component's `answer_ids` gives for those sides ("2x3":
    9), the best matches to the best ranks.  So every seed's pool asks
    for the same work, rank for rank: left to chance, the few areas at
    the head of a Zipf ranking hold 0 to 30 ids, and the seed sets the
    cell's median (PERF.md section 6)."""
    es = ref[ENDPOINTS[comp["endpoint"]]["class"]]
    lo, hi = comp.get("min_candidates", 0), comp.get("max_candidates")
    nearest = {}
    out = []
    for w, h in ranks:
        if (w, h) not in nearest:
            want = comp["answer_ids"][f"{w}x{h}"]
            spots = [(i, j) for i in range(metro.g - w + 1)
                     for j in range(metro.g - h + 1)]
            miss = []
            for k in rng.permutation(len(spots)):  # ties in seeded order
                flat = metro.rect_flat(*spots[k], w, h)
                if lo or hi is not None:
                    n = es.candidates(flat)
                    if n < lo or (hi is not None and n > hi):
                        continue
                miss.append((abs(len(es.search(flat, now=t_gen * NS))
                                 - want), len(miss), spots[k]))
            nearest[(w, h)] = iter(sorted(miss))
        spot = next(nearest[(w, h)], None)
        if spot is None:
            raise BenchFailure(f"the metro has too few {w}x{h} rectangles")
        out.append((*spot[2], w, h))
    return out


def pools(traffic: dict, metro, ref, seed: int, t_gen: int) -> dict:
    """{component index: its fixed areas, best rank first}.  Drawn from
    a stream of their own so warm-up and window share them."""
    out = {}
    for c, comp in enumerate(traffic["components"]):
        if comp.get("pool"):
            rng = np.random.default_rng([seed, 7, c])
            sides = _sides(comp)  # by rank, the same for every seed
            ranks = [sides[r % len(sides)] for r in range(comp["pool"])]
            out[c] = (
                _matched(comp, ranks, metro, ref, rng, t_gen)
                if "answer_ids" in comp else
                [_place(comp, w, h, metro, ref, rng) for w, h in ranks])
    return out


def a_token() -> bytes:
    """Any USS's token, for what the harness itself asks (b"" where
    nobody authenticates)."""
    return TOKENS[0] if TOKENS else b""


def is_write(comp: dict) -> bool:
    return ENDPOINTS[comp["endpoint"]].get("kind") == "write"


def write_stream(rng):
    """The stream that a build's planned flights are drawn from: seeded
    by what `rng` itself was seeded with ([seed, phase, ...]) and one
    word more, so that it takes nothing from `rng`, and that warm-up,
    window and traced stretch each get ids of their own."""
    entropy = rng.bit_generator.seed_seq.entropy
    return np.random.default_rng([*np.atleast_1d(entropy).tolist(), 8])


def build(traffic: dict, metro, ref, area_pools: dict, rng, t_gen: int,
          rate: float, seconds: float) -> list:
    """The requests due in a window of `seconds` at `rate`, with their
    wire form, in due order."""
    n = int(round(rate * seconds))
    comps = traffic["components"]
    per_comp = _quota([c["share"] for c in comps], n)
    which = rng.permutation(np.repeat(np.arange(len(comps)), per_comp))
    due = np.sort(rng.uniform(0.0, seconds, n))
    writes = {c for c, comp in enumerate(comps) if is_write(comp)}
    wrng = write_stream(rng) if writes else None
    rects, ids = {}, {}
    for c, comp in enumerate(comps):
        m = per_comp[c]
        if c in area_pools:
            pool = area_pools[c]
            alpha = comp.get("zipf_alpha", 0.0)
            ranks = np.repeat(
                np.arange(len(pool)),
                _quota([(r + 1) ** -alpha for r in range(len(pool))], m),
            )
            rects[c] = [pool[r] for r in rng.permutation(ranks)]
        else:
            own = wrng if c in writes else rng
            rects[c] = [
                _place(comp, w, h, metro, ref, own)
                for w, h in _deck(_sides(comp), m, own)
            ]
        if c in writes:
            ids[c] = _uuids(wrng, m)
    out = []
    taken = [0] * len(comps)
    for k in range(n):
        c = int(which[k])
        comp = comps[c]
        seq = taken[c]
        taken[c] += 1
        own = wrng if c in writes else rng
        alt = when = None
        if "alt_band_m" in comp:
            lo = float(own.integers(0, comp["alt_ceiling_m"] * 4)) * 0.25
            alt = (lo, lo + comp["alt_band_m"])
        period = comp.get("timed_every", 0)  # 5: four in five are timed
        if c in writes or (period and seq % period != 0):
            a, b = comp["opens_in_s"]
            la, lb = comp["lasts_s"]
            t0 = t_gen + a + int(own.integers(0, b - a))
            when = (t0, t0 + int(own.integers(la, lb)))
        req = Request(float(due[k]), c, rects[c][seq], alt, when)
        if TOKENS:
            req.token = TOKENS[k % len(TOKENS)]
        if c in writes:
            req.kind, req.id = "write", ids[c][seq]
            req.stem = put_stem(req, metro)
            req.wire = put_wire(req, [])
        else:
            req.wire = wire(comp, req, metro)
        out.append(req)
    return out


def _volume(req: Request, metro) -> dict:
    """A request's 4D volume as the SCD API spells it."""
    vol = {"volume": {
        "outline_polygon": {"vertices": metro.rect(*req.rect)},
        "altitude_lower": {"value": req.alt[0], "reference": "W84",
                           "units": "M"},
        "altitude_upper": {"value": req.alt[1], "reference": "W84",
                           "units": "M"},
    }}
    if req.when:
        vol["time_start"] = {"value": iso(req.when[0]), "format": "RFC3339"}
        vol["time_end"] = {"value": iso(req.when[1]), "format": "RFC3339"}
    return vol


def _http(method: str, path: str, body: bytes, token: bytes = b"") -> bytes:
    head = (f"{method} {path} HTTP/1.1\r\nHost: dss\r\n"
            f"Content-Length: {len(body)}\r\n")
    if body:
        head += "Content-Type: application/json\r\n"
    if token:
        head += f"Authorization: Bearer {token.decode()}\r\n"
    return head.encode() + b"\r\n" + body


def put_stem(req: Request, metro) -> bytes:
    """A planned flight's PUT body up to its key: a new op intent
    (`old_version` 0) with an implicit subscription, the reference's
    default flow."""
    doc = json.dumps({
        "extents": [_volume(req, metro)], "old_version": 0,
        "state": "Accepted", "uss_base_url": USS_URL,
        "new_subscription": {"uss_base_url": USS_URL},
    })
    return doc[:-1].encode() + b', "key": '


def put_wire(req: Request, ovns: list) -> bytes:
    ep = ENDPOINTS["scd_put"]
    return _http(ep["method"], ep["path"] + req.id,
                 req.stem + json.dumps(ovns).encode() + b"}", req.token)


def conflicts_of(body: bytes) -> list:
    """[(id, ovn)] of the op intents a refused PUT lists; None where
    the answer is not an AirspaceConflictResponse."""
    try:
        return [(c["operation_reference"]["id"],
                 c["operation_reference"]["ovn"])
                for c in json.loads(body)["entity_conflicts"]
                if "operation_reference" in c]
    except (ValueError, KeyError, TypeError):
        return None


def wire(comp: dict, req: Request, metro) -> bytes:
    """The HTTP/1.1 request (keep-alive) for one search."""
    ep = ENDPOINTS[comp["endpoint"]]
    if comp["endpoint"] == "scd_query":
        body = json.dumps({"area_of_interest": _volume(req, metro)}).encode()
        path = ep["path"]
    else:
        body = b""
        path = ep["path"] + "?area=" + ",".join(
            f"{v['lat']!r},{v['lng']!r}" for v in metro.rect(*req.rect))
        if req.when:
            path += (f"&earliest_time={iso(req.when[0])}"
                     f"&latest_time={iso(req.when[1])}")
    return _http(ep["method"], path, body, req.token)


# ---------------------------------------------------------------------------
# the open-loop client
# ---------------------------------------------------------------------------


@dataclass
class Exchange:
    """One PUT of a chain and its answer."""
    sent: float  # seconds after the window opened
    done: float  # last byte read
    status: int
    body: bytes
    key: list  # the ids whose OVNs the PUT carried
    listed: list | None = None  # the ids a 409 listed


@dataclass
class Outcome:
    """Per request; of a chain, `sent` is its first exchange's, and
    `done`, `status` and `body` its last's."""
    sent: np.ndarray  # seconds after the window opened; nan = never sent
    done: np.ndarray  # last byte read; nan = no answer
    status: np.ndarray  # HTTP status; 0 = no answer
    body: list = field(default_factory=list)  # raw bytes or None
    # per request None, or a chain's exchanges as far as they got
    chain: list = field(default_factory=list)
    t_open: float = 0.0  # the window's opening on the monotonic clock


def blank_outcome(n: int, t_open: float) -> Outcome:
    return Outcome(np.full(n, np.nan), np.full(n, np.nan),
                   np.zeros(n, np.int32), [None] * n, [None] * n, t_open)


class Client:
    """Keep-alive connections to the public port, as a round-robin
    balancer in front of the workers holds them: `per_worker`
    connections to each worker process (the kernel spreads new
    connections over the workers by a hash; which worker one reached is
    read once from that worker's own /metrics), used in rotation,
    worker after worker.  So every run splits its load evenly over the
    workers, whatever the hash did.  When all of them are busy a fresh
    connection is opened: an open loop never waits for a free one."""

    def __init__(self, port: int):
        self.port = port
        self._lanes = []  # per worker: deque of idle pooled connections
        self._turn = 0
        self._spare = []  # idle connections beyond the pool
        self.opened = 0

    async def _open(self):
        self.opened += 1
        return await asyncio.open_connection("127.0.0.1", self.port,
                                             limit=1 << 22)

    async def balance(self, workers: int, per_worker: int) -> None:
        """Open connections until each worker holds `per_worker`."""
        lanes = {}
        for _ in range(64 * workers * per_worker):
            if (len(lanes) >= workers and
                    all(len(v) >= per_worker for v in lanes.values())):
                break
            conn = await self._open()
            _, body = await self._exchange(
                conn, b"GET /metrics HTTP/1.1\r\nHost: dss\r\n\r\n")
            m = re.search(rb'dss_build_info\{[^}]*process="([^"]+)"', body)
            who = m.group(1) if m else b"?"
            if len(lanes.setdefault(who, [])) < per_worker:
                lanes[who].append(conn)
            else:
                conn[1].close()
        else:
            raise BenchFailure(
                f"could not reach {workers} workers: {sorted(lanes)}")
        self._lanes = [collections.deque(lanes[k]) for k in sorted(lanes)]

    @staticmethod
    async def _exchange(conn, wire_bytes: bytes):
        reader, writer = conn
        writer.write(wire_bytes)
        head = await reader.readuntil(b"\r\n\r\n")
        status = int(head[9:12])
        low = head.lower()
        at = low.find(b"content-length:")
        if at < 0:
            raise BenchFailure("answer without Content-Length")
        n = int(low[at + 15:low.index(b"\r\n", at)])
        return status, (await reader.readexactly(n) if n else b"")

    async def fetch(self, wire_bytes: bytes, then=None):
        """-> (status, body).  A kept connection that the server has
        closed meanwhile is replaced once, as any HTTP client does.
        `then(status, body)` may name what to send next on the same
        connection (a chain); the last answer is returned."""
        lane = None
        for k in range(len(self._lanes)):
            cand = self._lanes[(self._turn + k) % len(self._lanes)]
            if cand:
                lane, conn = cand, cand.popleft()
                self._turn = (self._turn + k + 1) % len(self._lanes)
                break
        else:
            conn = self._spare.pop() if self._spare else None
        kept = conn is not None
        if conn is None:
            conn = await self._open()
        try:
            try:
                status, body = await self._exchange(conn, wire_bytes)
            except (ConnectionError, asyncio.IncompleteReadError) as e:
                if not kept or getattr(e, "partial", b""):
                    raise
                conn[1].close()
                conn = await self._open()
                status, body = await self._exchange(conn, wire_bytes)
            while then is not None:
                wire_bytes = then(status, body)
                if wire_bytes is None:
                    break
                status, body = await self._exchange(conn, wire_bytes)
        except BaseException:
            conn[1].close()
            if lane is not None:  # the pool keeps its size
                lane.append(await self._open())
            raise
        (lane if lane is not None else self._spare).append(conn)
        return status, body

    def drop_spares(self) -> None:
        for conn in self._spare:
            conn[1].close()
        self._spare = []

    async def close(self):
        for conn in [c for lane in self._lanes for c in lane] + self._spare:
            conn[1].close()
        self._lanes, self._spare = [], []


def next_put(req: Request, chain: list):
    """After a chain's newest exchange: the PUT to send next, or None
    where the chain has ended (anything but a conflict listing, or
    MAX_ROUNDS exchanges made)."""
    last = chain[-1]
    if last.status != 409:
        return None
    pairs = conflicts_of(last.body)
    if pairs is None:
        return None  # refused for another reason than its key
    last.listed = [i for i, _ in pairs]
    if len(chain) >= MAX_ROUNDS:
        return None
    return put_wire(req, [ovn for _, ovn in pairs])


async def _send(client: Client, req: Request, k: int, out: Outcome, clock):
    """One request, or one chain, into row k of `out`."""
    try:
        out.sent[k] = clock()
        if req.kind == "write":
            chain = out.chain[k] = []
            begun = [out.sent[k]]

            def then(status, body):
                now = clock()
                chain.append(Exchange(
                    begun[0], now, status, body,
                    chain[-1].listed if chain else []))
                begun[0] = now  # the next PUT leaves at once
                return next_put(req, chain)

            status, body = await client.fetch(req.wire, then)
            out.done[k] = chain[-1].done
        else:
            status, body = await client.fetch(req.wire)
            out.done[k] = clock()
        out.status[k] = status
        out.body[k] = body
    except (OSError, asyncio.IncompleteReadError, asyncio.LimitOverrunError):
        pass  # no answer: stays status 0


async def offer(client: Client, requests: list, *, grace_s: float = 60.0,
                on_open=None) -> Outcome:
    """Send every request at its due instant whether or not earlier
    ones were answered; wait up to `grace_s` past the last due instant
    for answers.  Times are relative to the window's opening."""
    loop = asyncio.get_running_loop()
    # no collector pause inside the window: what set-up built is frozen,
    # and what the window allocates (answers) is kept anyway
    gc.collect()
    gc.freeze()
    gc.disable()
    t_open = loop.time()
    out = blank_outcome(len(requests), t_open)
    if on_open is not None:
        on_open()

    def clock():
        return loop.time() - t_open

    tasks = []
    try:
        for k, req in enumerate(requests):
            while True:
                delay = t_open + req.due - loop.time()
                if delay <= 0:
                    break
                # a timer wakes up to a millisecond late: sleep short of
                # the instant, then yield to the loop until it has come
                await asyncio.sleep(delay - 0.002 if delay > 0.002 else 0)
            tasks.append(asyncio.create_task(
                _send(client, req, k, out, clock)))
        if tasks:
            _, pending = await asyncio.wait(
                tasks, timeout=grace_s + DEADLINE_S)
            for t in pending:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
    finally:
        gc.enable()
        gc.unfreeze()
    return out


async def prefill(client: Client, requests: list,
                  in_flight: int = 32) -> Outcome:
    """Closed loop over `requests`, `in_flight` at a time (fills caches
    during set-up; nothing is timed, but what was written is kept)."""
    loop = asyncio.get_running_loop()
    t_open = loop.time()
    out = blank_outcome(len(requests), t_open)
    it = iter(enumerate(requests))

    async def lane():
        for k, req in it:
            await _send(client, req, k, out, lambda: loop.time() - t_open)

    await asyncio.gather(*(lane() for _ in range(in_flight)))
    return out


# ---------------------------------------------------------------------------
# arithmetic on what came back
# ---------------------------------------------------------------------------


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the sample at or below it."""
    v = np.sort(np.asarray(values, float))
    if not len(v):
        raise BenchFailure("percentile of nothing")
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


def due_times(requests: list) -> np.ndarray:
    return np.array([r.due for r in requests])


def lateness_ms(requests: list, out: Outcome) -> np.ndarray:
    """How late each request that was sent left the generator."""
    late = (out.sent - due_times(requests)) * 1000.0
    return late[~np.isnan(late)]


def latencies_ms(requests: list, out: Outcome, good: np.ndarray) -> np.ndarray:
    """Per request, from the instant it was DUE to the last byte of its
    answer; a request that failed, was refused, was answered wrongly or
    came after the deadline counts as the deadline."""
    lat = (out.done - due_times(requests)) * 1000.0
    bad = ~good | np.isnan(lat) | (lat > DEADLINE_S * 1000.0)
    return np.where(bad, DEADLINE_S * 1000.0, lat)
