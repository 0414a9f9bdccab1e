"""The write in the harness (scd-write-mixed-125k.write-mixed) at the
rehearsal's size: planned flights as chains of PUTs, the reference that
changes under the window (must / may), the third control, and that
nothing the four read cells send has moved.  CPU only; one test boots a
server and is `slow`:

    JAX_PLATFORMS=cpu python -m pytest dssbench/tests/test_write.py -q
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import math
import os

import numpy as np
import pytest

from dssbench import check, deploy, run, traffic as tr
from dssbench.readers import chain as chain_reader, generator as gen_reader

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(os.path.dirname(HERE), "testdata")
T_GEN = 1_800_000_000
WRITE_CELL = "scd-write-mixed-125k.write-mixed"
READ_CELLS = {"query-wide": "scd-dense-urban-125k.query-wide",
              "poll": "scd-dense-urban-125k.poll",
              "query-mixed": "scd-dense-urban-125k.query-mixed",
              "query-mixed-vll": "scd-vll-delivery-125k.query-mixed-vll"}


def _json(name):
    with open(os.path.join(DATA, name)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def rehearsal_by_seed(tmp_path_factory):
    """seed -> (metro, reference) of testdata/tiny-config.json."""
    made = {}

    def get(seed):
        if seed not in made:
            wal = tmp_path_factory.mktemp(f"wal{seed}") / "dss.wal"
            made[seed] = deploy.generate(
                seed, _json("tiny-config.json")["generator"], T_GEN, str(wal))
        return made[seed]

    return get


@pytest.fixture(scope="module")
def write_mixed(rehearsal_by_seed):
    metro, ref = rehearsal_by_seed(7)
    return _json("tiny-traffic-write-mixed.json"), metro, ref


def _window(write_mixed, seed=7, rate=30, seconds=20, phase=(1,)):
    traffic, metro, ref = write_mixed
    return tr.build(traffic, metro, ref, {},
                    np.random.default_rng([seed, *phase]), T_GEN, rate,
                    seconds)


# ---------------------------------------------------------------------------
# nothing an accepted cell sends has moved
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", sorted(READ_CELLS))
def test_the_read_cells_requests_are_the_parents_byte_for_byte(
        name, seed, rehearsal_by_seed):
    """testdata/golden-requests.json: digests over the pools and over
    every request's due instant and wire bytes (window, first warm-up
    chunk, traced stretch), taken from the parent tree (PR 32) before
    traffic.py learnt to write."""
    metro, ref = rehearsal_by_seed(seed)
    _, _, traffic = run.load_cell(READ_CELLS[name])
    pools = tr.pools(traffic, metro, ref, seed, T_GEN)
    h = hashlib.sha256()
    h.update(repr(sorted(pools.items())).encode())
    n = 0
    for stream in ([seed, 1], [seed, 2, 0], [seed, 6]):
        reqs = tr.build(traffic, metro, ref, pools,
                        np.random.default_rng(stream), T_GEN,
                        min(traffic["rate_rps"], 200), 5)
        for r in reqs:
            assert r.kind == "search" and not r.id
            h.update(repr(r.due).encode() + b"|" + r.wire + b"\n")
        n += len(reqs)
    want = _json("golden-requests.json")[f"{name}.{seed}"]
    assert {"requests": n, "sha256": h.hexdigest()} == want


def test_search_metrics_are_over_searches_and_write_metrics_in_one_cell():
    with open(os.path.join(deploy.REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for cell in READ_CELLS.values():
        names = [m["name"] for m in run.end_to_end_readers(bench, cell)]
        assert names == ["search_p50_ms", "search_p95_ms", "goodput_rps",
                         "setup_s"]
    mine = {m["name"]: m for m in run.end_to_end_readers(bench, WRITE_CELL)}
    assert list(mine) == ["search_p50_ms", "search_p95_ms", "goodput_rps",
                          "setup_s", "write_p50_ms"]
    assert [mine[k]["args"].get("kind") for k in mine] == [
        "search", "search", None, None, "write"]
    layer = {m["name"] for m in run.load_metrics(WRITE_CELL)}
    assert {"write_first_put_p50_ms", "write_keyed_put_p50_ms",
            "write_tail_p95_ms", "write_conflicts_mean", "write_rounds_mean",
            "read_after_write_pct", "write_handler_ms_mean",
            "write_covering_ms_mean", "auth_ms_mean", "small_p50_ms",
            "small_p95_ms",
            "district_p50_ms", "wide_p50_ms", "host_scan_ms_mean",
            "serve_host_ms_mean", "serve_device_ms_mean", "ring_wake_pct",
            "wire_memo_hit_pct", "aot_warm_s", "compiles_in_window",
            "device_idle_pct", "device_route_pct", "gen_late_p95_ms",
            "http_host_ms_mean", "replay_s", "tail_p90_ms",
            "tail_p99_ms"} <= layer
    assert not any(n.startswith("write_") for n in
                   (m["name"] for m in run.load_metrics(
                       READ_CELLS["query-mixed"])))


# ---------------------------------------------------------------------------
# the traffic: planned flights
# ---------------------------------------------------------------------------


def test_the_cell_is_query_mixeds_reads_halved_and_a_write():
    _, config, traffic = run.load_cell(WRITE_CELL)
    _, dense, mixed = run.load_cell(READ_CELLS["query-mixed"])
    comps = traffic["components"]
    assert _json("tiny-traffic-write-mixed.json")["components"] == comps
    assert [c["share"] for c in comps] == [0.35, 0.09, 0.06, 0.5]
    for mine, theirs in zip(comps, mixed["components"]):
        assert dict(mine, share=2 * mine["share"]) == theirs
    assert comps[3] == {
        "share": 0.5, "endpoint": "scd_put", "w_cells": [1, 3],
        "h_cells": [1, 4], "max_candidates": 16384, "alt_band_m": 40,
        "alt_ceiling_m": 2900, "opens_in_s": [7200, 14400],
        "lasts_s": [900, 3600]}
    assert traffic["warmup"] == mixed["warmup"]
    if traffic["knee_rps"]:
        assert traffic["rate_rps"] in (math.floor(0.25 * traffic["knee_rps"]),
                                       math.floor(0.125 * traffic["knee_rps"]))
    # query-mixed's data and server, and every write fsynced
    assert config["generator"] == dense["generator"]
    # every write fsynced, and every caller known: one anonymous owner
    # would run into the 10 subscriptions a cell allows it
    assert config["server"]["flags"] == ["--enable_scd", "--wal_fsync"]
    assert config["server"]["auth"]["owners"] == dense["generator"]["owners"]
    assert dict(config["server"], flags=0, auth=0) == dict(
        dense["server"], flags=0, auth=0)


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_build_deals_planned_flights_from_a_stream_of_their_own(
        write_mixed, seed):
    traffic, metro, ref = write_mixed
    reqs = _window(write_mixed, seed)
    assert len(reqs) == 600
    which = np.array([r.comp for r in reqs])
    assert [int((which == c).sum()) for c in range(4)] == [210, 54, 36, 300]
    flights = [r for r in reqs if r.kind == "write"]
    assert [r.comp for r in flights] == [3] * 300
    assert len({r.id for r in flights}) == 300
    put = traffic["components"][3]
    for r in flights:
        assert put["w_cells"][0] <= r.rect[2] <= put["w_cells"][1]
        assert put["h_cells"][0] <= r.rect[3] <= put["h_cells"][1]
        assert r.alt[1] - r.alt[0] == 40 and r.when is not None  # timed
        assert 7200 <= r.when[0] - T_GEN < 14400
        assert 900 <= r.when[1] - r.when[0] < 3600
        assert ref["op"].candidates(metro.rect_flat(*r.rect)) <= 16384
    # the same sides for every seed, in another order
    def sides(rs):
        return sorted((r.comp, r.rect[2], r.rect[3]) for r in rs)

    assert sides(reqs) == sides(_window(write_mixed, 99))
    # the reads take from the main stream exactly what they would take
    # were the flights not there: their rectangles, bands and windows
    # are those of the same file with the write's draws thrown away
    again = _window(write_mixed, seed)
    assert [(r.due, r.wire) for r in again] == [(r.due, r.wire) for r in reqs]


def test_a_planned_flight_is_a_new_op_intent_with_an_implicit_subscription(
        write_mixed):
    _, metro, _ = write_mixed
    req = next(r for r in _window(write_mixed) if r.kind == "write")
    head, body = req.wire.split(b"\r\n\r\n", 1)
    assert head.startswith(
        f"PUT /dss/v1/operation_references/{req.id} HTTP/1.1\r\n".encode())
    assert f"Content-Length: {len(body)}\r\n".encode() in head + b"\r\n"
    doc = json.loads(body)
    assert doc["key"] == [] and doc["old_version"] == 0
    assert doc["state"] == "Accepted" and doc["uss_base_url"] == tr.USS_URL
    assert doc["new_subscription"] == {"uss_base_url": tr.USS_URL}
    (ext,) = doc["extents"]
    assert ext["volume"]["outline_polygon"]["vertices"] == metro.rect(
        *req.rect)
    assert ext["volume"]["altitude_lower"]["value"] == req.alt[0]
    assert ext["time_start"]["value"] == tr.iso(req.when[0])
    # the same flight with a key: only the key and the length differ
    head2, body2 = tr.put_wire(req, ["ovn-a", "ovn-b"]).split(b"\r\n\r\n", 1)
    assert json.loads(body2) == dict(doc, key=["ovn-a", "ovn-b"])
    assert f"Content-Length: {len(body2)}\r\n".encode() in head2 + b"\r\n"
    # a version-4 UUID, as the API demands
    assert req.id[14] == "4" and req.id[19] == "8" and len(req.id) == 36


def test_ids_never_collide_across_the_phases_of_a_run(write_mixed):
    """Warm-up burst, warm-up chunks, window, traced stretch and the
    sweep's rungs each seed their own stream: (seed, phase, sequence)."""
    seen = {}
    for phase in ((1,), (4,), (6,), (2, 0), (2, 1), (2, 8), (5, 0), (5, 1)):
        for r in _window(write_mixed, 7, 30, 10, phase):
            if r.kind == "write":
                assert r.id not in seen, (phase, seen[r.id])
                seen[r.id] = phase
    assert len(seen) == 8 * 150
    again = {r.id for r in _window(write_mixed, 7, 30, 10, (2, 1))
             if r.kind == "write"}
    assert again <= set(seen)  # and the same phase gives the same ids
    assert not again & {r.id for r in _window(write_mixed, 8, 30, 10, (2, 1))}


def test_a_deployment_that_authenticates_gets_a_token_on_every_request(
        write_mixed, tmp_path, monkeypatch):
    """64 USSs, each with an RS256 token that the program's own
    verifier accepts; without tokens not a byte of a request moves."""
    from dss_tpu.auth import jwt as program_jwt

    _, config, _ = run.load_cell(WRITE_CELL)
    auth = config["server"]["auth"]
    key, pem = deploy.make_keys(str(tmp_path))
    tokens = [deploy.mint(key, f"uss{k}", auth["audience"], auth["scope"],
                          auth["ttl_s"]) for k in range(3)]
    with open(pem, "rb") as fh:
        public = program_jwt.load_public_key(fh.read())
    claims = program_jwt.verify_rs256(tokens[1].decode(), public)
    assert claims["sub"] == "uss1" and claims["aud"] == "localhost"
    assert "utm.strategic_coordination" in claims["scope"].split()
    import time

    left = claims["exp"] - time.time()  # under the hour the DSS admits
    assert auth["ttl_s"] - 60 < left <= auth["ttl_s"] < 3600
    plain = _window(write_mixed, 5, 30, 4)
    monkeypatch.setattr(tr, "TOKENS", tokens)
    signed = _window(write_mixed, 5, 30, 4)
    for k, (a, b) in enumerate(zip(plain, signed)):
        line = b"Authorization: Bearer " + tokens[k % 3] + b"\r\n"
        assert b.token == tokens[k % 3] and line in b.wire
        assert b.wire.replace(line, b"") == a.wire and not a.token
        if b.kind == "write":  # the chain's later PUTs are the same USS's
            assert line in tr.put_wire(b, ["ovn"])


# ---------------------------------------------------------------------------
# the chain, on one connection
# ---------------------------------------------------------------------------


def _listing(pairs):
    return json.dumps({"entity_conflicts": [
        {"operation_reference": {"id": i, "ovn": o}} for i, o in pairs]
    }).encode()


def test_next_put_keys_the_flight_with_what_the_409_listed(write_mixed):
    req = next(r for r in _window(write_mixed) if r.kind == "write")
    first = tr.Exchange(0.0, 0.1, 409, _listing([("a", "ovn-a"),
                                                 ("b", "ovn-b")]), [])
    nxt = tr.next_put(req, [first])
    assert first.listed == ["a", "b"]
    assert json.loads(nxt.split(b"\r\n\r\n", 1)[1])["key"] == ["ovn-a",
                                                                 "ovn-b"]
    assert tr.next_put(req, [tr.Exchange(0, 0, 200, b"{}", [])]) is None
    assert tr.next_put(req, [tr.Exchange(0, 0, 503, b"", [])]) is None
    # a 409 that is no conflict listing (the id exists already) ends it
    other = tr.Exchange(0, 0, 409, b'{"message": "already exists"}', [])
    assert tr.next_put(req, [other]) is None and other.listed is None
    # the empty key, the key, and at most three rounds more
    full = [tr.Exchange(0, 0, 409, _listing([("a", "o")]), [])
            for _ in range(tr.MAX_ROUNDS)]
    assert tr.next_put(req, full[:-1]) is not None
    assert tr.next_put(req, full) is None


def test_offer_drives_a_chain_over_one_connection(write_mixed):
    """Against a stand-in server that refuses an empty key twice over
    (another writer landed in between) and accepts the third PUT."""
    reqs = [r for r in _window(write_mixed, 7, 30, 2)]
    flights = [r for r in reqs if r.kind == "write"]
    conns, puts = [], {}

    async def serve(reader, writer):
        conns.append(0)
        mine = len(conns) - 1
        try:
            while True:
                head = await reader.readuntil(b"\r\n\r\n")
                n = int(head.lower().split(b"content-length:")[1].split(
                    b"\r\n")[0])
                body = await reader.readexactly(n) if n else b""
                method, path = head.split(b" ", 2)[:2]
                conns[mine] += 1
                if method == b"PUT":
                    fid = path.rsplit(b"/", 1)[1].decode()
                    key = json.loads(body)["key"]
                    puts.setdefault(fid, []).append((mine, key))
                    want = [["ovn-a"], ["ovn-a", "ovn-b"]]
                    if len(puts[fid]) <= 2:
                        status, ans = b"409 Conflict", _listing(
                            [(o[4:], o) for o in want[len(puts[fid]) - 1]])
                    else:
                        status, ans = b"200 OK", json.dumps({
                            "operation_reference": {
                                "id": fid, "subscription_id": "sub-" + fid},
                            "subscribers": []}).encode()
                else:
                    status, ans = b"200 OK", b'{"operation_references": []}'
                writer.write(b"HTTP/1.1 " + status + b"\r\nContent-Length: "
                             + str(len(ans)).encode() + b"\r\n\r\n" + ans)
        except (asyncio.IncompleteReadError, ConnectionError):
            pass
        finally:
            writer.close()

    async def drive():
        server = await asyncio.start_server(serve, "127.0.0.1", 0,
                                            limit=1 << 22)
        port = server.sockets[0].getsockname()[1]
        client = tr.Client(port)
        out = await tr.offer(client, reqs, grace_s=5.0)
        closed = await tr.prefill(client, flights[:3], 2)
        await client.close()
        server.close()
        await server.wait_closed()
        return out, closed

    out, closed = asyncio.run(drive())
    assert (out.status == 200).all() and out.t_open > 0
    for k, r in enumerate(reqs):
        if r.kind != "write":
            assert out.chain[k] is None
            continue
        chain = out.chain[k]
        assert [e.status for e in chain] == [409, 409, 200]
        assert [e.key for e in chain] == [[], ["a"], ["a", "b"]]
        assert [e.listed for e in chain] == [["a"], ["a", "b"], None]
        assert [key for _, key in puts[r.id][:3]] == [
            [], ["ovn-a"], ["ovn-a", "ovn-b"]]
        assert len({c for c, _ in puts[r.id][:3]}) == 1  # one connection
        # sent and done in order, the chain's ends are the request's
        times = [t for e in chain for t in (e.sent, e.done)]
        assert times == sorted(times)
        assert out.sent[k] == chain[0].sent and out.done[k] == chain[-1].done
        assert out.sent[k] >= r.due
    # the closed loop of the warm-up keeps its chains too
    assert [len(c) for c in closed.chain] == [1, 1, 1]  # a fourth PUT: 200
    written = deploy.Written()
    written.absorb(reqs, out)
    written.absorb(flights[:3], closed)
    cols = written.columns()
    assert len(written) == len(flights) + 3
    assert (cols["acked"] > cols["first_sent"]).all()
    assert cols["subs"][0] == "sub-" + cols["ids"][0]


# ---------------------------------------------------------------------------
# the comparison: must / may
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def sparse(tmp_path_factory):
    """A 16 x 16 metro with a few hundred op intents, two planned
    flights that conflict with each other and one search over both."""
    gen = _json("tiny-config.json")["generator"]
    gen = dict(gen, classes={k: dict(v, n=max(4, v["n"] // 100))
                             for k, v in gen["classes"].items()})
    wal = tmp_path_factory.mktemp("sparse") / "dss.wal"
    metro, ref = deploy.generate(3, gen, T_GEN, str(wal))
    traffic = {"components": [
        {"endpoint": "scd_query", "share": 0.5},
        {"endpoint": "scd_put", "share": 0.5}]}
    when = (T_GEN + 8000, T_GEN + 9000)

    def flight(due, fid, rect=(4, 4, 2, 2)):
        r = tr.Request(due, 1, rect, (100.0, 140.0), when, kind="write",
                       id=fid)
        r.stem = tr.put_stem(r, metro)
        r.wire = tr.put_wire(r, [])
        return r

    return traffic, metro, ref, flight


def _judge(sparse, reqs, out):
    traffic, metro, ref, _ = sparse
    written = deploy.Written()
    written.absorb(reqs, out)
    cmp = check.compare(traffic, reqs, out, metro, ref, written)
    return cmp


def _search(due, rect=(3, 3, 4, 4), alt=(90.0, 150.0), when=None):
    return tr.Request(due, 0, rect, alt, when)


def _scene(sparse, search_due=2.0):
    """[flight A due 1.0, a search over it, flight B over A due 3.0]
    as the sound reference answers them."""
    traffic, metro, ref, flight = sparse
    reqs = sorted([flight(1.0, "flight-a"), _search(search_due),
                   flight(3.0, "flight-b", (5, 5, 1, 3))],
                  key=lambda r: r.due)
    return reqs, check.answers_of(traffic, reqs, metro, ref)


def _drop(body: bytes, fid: str) -> bytes:
    doc = json.loads(body)
    for key, val in doc.items():
        if isinstance(val, list):
            doc[key] = [e for e in val if e.get("id", e.get(
                "operation_reference", {}).get("id")) != fid]
    return json.dumps(doc).encode()


def test_the_sound_stand_in_passes_and_reads_its_flights_back(sparse):
    reqs, out = _scene(sparse)
    cmp = _judge(sparse, reqs, out)
    assert cmp["numbers"] == {"wrong_answers": 0, "never_answered": 0}
    assert cmp["good"].all() and cmp["facts"]["compared"] == 3
    assert cmp["read_back"].tolist() == [False, True, False]
    assert cmp["facts"]["flights_written"] == 2
    # B met A: listed in its 409, carried in its key, A's implicit
    # subscription among its subscribers
    b = out.chain[2]
    assert "flight-a" in b[0].listed and "flight-a" in b[-1].key
    assert "sub-flight-a" in check.notified(b[-1].body)[0]


def test_a_flight_acknowledged_before_a_search_must_be_in_it(sparse):
    reqs, out = _scene(sparse)
    out.body[1] = _drop(out.body[1], "flight-a")
    cmp = _judge(sparse, reqs, out)
    assert cmp["numbers"]["wrong_answers"] == 1 and not cmp["good"][1]
    assert "1 missing" in cmp["first_wrong"]


@pytest.mark.parametrize("listed", [True, False])
def test_a_flight_in_flight_during_a_search_may_be_in_it(sparse, listed):
    reqs, out = _scene(sparse)
    out.sent[1], out.done[1] = 0.5, 2.5  # A: sent 1.0, acknowledged 1.0+
    if not listed:
        out.body[1] = _drop(out.body[1], "flight-a")
    cmp = _judge(sparse, reqs, out)
    assert cmp["numbers"]["wrong_answers"] == 0 and cmp["good"].all()
    assert not cmp["read_back"][1]  # nothing was due: the overlay decided 0


@pytest.mark.parametrize("listed", [True, False])
def test_a_flight_without_a_final_answer_may_stand_for_ever(sparse, listed):
    reqs, out = _scene(sparse, search_due=50.0)
    a = out.chain[0]
    out.chain[0] = a[:-1]  # the 409 came, the keyed PUT's answer never
    out.status[0], out.body[0], out.done[0] = 0, None, np.nan
    if not listed:
        out.body[2] = _drop(out.body[2], "flight-a")
        # B then neither lists A nor keys it, and is told of no
        # subscription of A's (whose id nobody knows)
        b = out.chain[1]
        b[0].listed.remove("flight-a")
        b[1].key.remove("flight-a")
        doc = json.loads(b[1].body)
        doc["subscribers"][0]["subscriptions"] = [
            s for s in doc["subscribers"][0]["subscriptions"]
            if s["subscription_id"] != "sub-flight-a"]
        b[1].body = out.body[1] = json.dumps(doc).encode()
    cmp = _judge(sparse, reqs, out)
    assert cmp["numbers"] == {"wrong_answers": 0, "never_answered": 1}
    assert cmp["good"].tolist() == [False, True, True]
    assert cmp["facts"]["flights_unknown"] == 1


def test_nothing_unwritten_appears(sparse):
    reqs, out = _scene(sparse)
    out.sent[1], out.done[1] = 0.2, 0.4  # done before A was first sent
    cmp = _judge(sparse, reqs, out)
    assert cmp["numbers"]["wrong_answers"] == 1
    assert "1 unexpected" in cmp["first_wrong"]


def test_a_200_past_a_conflict_is_wrong(sparse):
    reqs, out = _scene(sparse)
    b = out.chain[2]
    b[1].key = [i for i in b[1].key if i != "flight-a"]
    cmp = _judge(sparse, reqs, out)
    assert cmp["numbers"]["wrong_answers"] == 1 and not cmp["good"][2]
    assert "accepted past 1 conflicts" in cmp["first_wrong"]
    # and so is a 409 whose listing leaves an acknowledged flight out
    reqs, out = _scene(sparse)
    out.chain[2][0].listed.remove("flight-a")
    out.chain[2][1].key.remove("flight-a")
    cmp = _judge(sparse, reqs, out)
    assert cmp["numbers"]["wrong_answers"] == 1
    assert "1 missing" in cmp["first_wrong"]


def test_a_409_for_nothing_is_wrong(sparse):
    reqs, out = _scene(sparse)
    b = out.chain[2]
    again = tr.Exchange(b[1].sent, b[1].done, 409, b[0].body, b[1].key,
                        list(b[0].listed))
    out.chain[2] = [b[0], again]
    out.status[2] = 409
    cmp = _judge(sparse, reqs, out)
    assert cmp["numbers"] == {"wrong_answers": 1, "never_answered": 0}
    assert "refused though its key held all" in cmp["first_wrong"]
    # refused at the end of its rounds by a listing that is right: the
    # chain never ended, which is for never_answered, and nothing wrong
    reqs, out = _scene(sparse)
    out.chain[2] = out.chain[2][:1]
    out.status[2] = 409
    cmp = _judge(sparse, reqs, out)
    assert cmp["numbers"] == {"wrong_answers": 0, "never_answered": 1}
    # 503: late, not wrong
    out.status[2] = 503
    cmp = _judge(sparse, reqs, out)
    assert cmp["numbers"] == {"wrong_answers": 0, "never_answered": 0}
    assert cmp["facts"]["refused"] == 1 and not cmp["good"][2]


@pytest.mark.parametrize("case", ["missing", "unexpected", "own_missing",
                                  "unknown_allows_one"])
def test_subscribers_of_a_200(sparse, case):
    """Every subscription that shares a cell with the flight, whatever
    its altitudes and hours: the WAL's, the flight's own, and those of
    the flights acknowledged before it."""
    traffic, metro, ref, flight = sparse
    reqs, out = _scene(sparse)
    if case == "unknown_allows_one":
        # a third flight over B's cells whose answer never came
        reqs.append(flight(2.5, "flight-c", (5, 6, 1, 1)))
        reqs.sort(key=lambda r: r.due)
        out = check.answers_of(traffic, reqs, metro, ref)
        c = reqs.index(next(r for r in reqs if r.id == "flight-c"))
        out.chain[c] = out.chain[c][:-1]
        out.status[c], out.body[c], out.done[c] = 0, None, np.nan
    b = next(k for k, r in enumerate(reqs) if r.id == "flight-b")
    last = out.chain[b][-1]
    doc = json.loads(last.body)
    subs = doc["subscribers"][0]["subscriptions"]
    # the WAL's subscriptions count by their cells alone
    static = ref["scd_sub"].search(metro.rect_flat(5, 5, 1, 3),
                                   now=T_GEN * deploy.NS)
    assert static | {"sub-flight-a", "sub-flight-b"} <= {
        s["subscription_id"] for s in subs}
    if case == "missing":
        subs[:] = [s for s in subs if s["subscription_id"] != "sub-flight-a"]
    elif case == "own_missing":
        subs[:] = [s for s in subs if s["subscription_id"] != "sub-flight-b"]
    elif case == "unexpected":
        subs.append({"subscription_id": "nobody"})
    last.body = out.body[b] = json.dumps(doc).encode()
    cmp = _judge(sparse, reqs, out)
    if case == "unknown_allows_one":
        assert cmp["numbers"]["wrong_answers"] == 0
        subs.append({"subscription_id": "nobody"})  # and one more is too many
        last.body = json.dumps(doc).encode()
        cmp = _judge(sparse, reqs, out)
    assert cmp["numbers"]["wrong_answers"] == 1 and not cmp["good"][b]
    if case != "unknown_allows_one":  # there the lost answer comes first
        assert "subscribers" in cmp["first_wrong"]


# ---------------------------------------------------------------------------
# the controls, the readers
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("served_by", ["sound", *check.CONTROLS])
def test_controls_on_the_write_mixed_traffic(write_mixed, served_by):
    traffic, metro, ref = write_mixed
    assert set(check.controls_for(traffic)) == {"stale", "lowprec",
                                                "lost_write"}
    reqs = _window(write_mixed, 7)
    served = ref if served_by == "sound" else check.CONTROLS[served_by](ref)
    correct, checks = check.judge_control(traffic, reqs, metro, ref, served)
    assert correct is (served_by == "sound")
    assert checks["answers_compared"]["value"] == len(reqs)
    assert checks["never_answered"]["value"] == 0
    assert (checks["wrong_answers"]["value"] > 0) is (served_by != "sound")


def test_the_stand_in_answers_a_chain_in_the_endpoints_own_form(write_mixed):
    traffic, metro, ref = write_mixed
    reqs = _window(write_mixed, 7, 30, 10)
    out = check.answers_of(traffic, reqs, metro, ref)
    lost = check.answers_of(traffic, reqs, metro, check.lost_write(ref))
    rounds = set()
    for k, r in enumerate(reqs):
        if r.kind != "write":
            assert out.chain[k] is None
            continue
        chain = out.chain[k]
        rounds.add(len(chain))
        assert chain[-1].status == 200 and out.status[k] == 200
        if len(chain) == 2:
            assert tr.conflicts_of(chain[0].body) == [
                (i, "ovn-" + i) for i in chain[0].listed]
            assert chain[1].key == chain[0].listed
            assert tr.next_put(r, chain[:1]) is not None
        got, own = check.notified(chain[-1].body)
        assert own in got and own == "sub-" + r.id
    assert rounds == {1, 2}  # empty airspace, and the usual two
    # the control differs from the sound stand-in only after its first ack
    assert [b for b in lost.body] != [b for b in out.body]
    written = deploy.Written()
    written.absorb(reqs, lost)
    assert len(written) == 150  # every flight was acknowledged, one lost


def test_generator_reader_with_kind(write_mixed):
    reqs = _window(write_mixed, 3, 30, 10)
    n = len(reqs)
    due = tr.due_times(reqs)
    took = np.where([r.kind == "write" for r in reqs], 0.030, 0.005)
    out = tr.Outcome(due.copy(), due + took, np.full(n, 200, np.int32),
                     [b"{}"] * n, [None] * n)
    ctx = {"requests": reqs, "out": out, "good": np.ones(n, bool),
           "seconds": 10.0}
    stat = "latency_percentile_ms"
    assert gen_reader.read(ctx, stat, q=50, kind="search") == pytest.approx(5)
    assert gen_reader.read(ctx, stat, q=95, kind="write") == pytest.approx(30)
    assert gen_reader.read(ctx, stat, q=99) == pytest.approx(30)  # all
    assert gen_reader.read(ctx, stat, q=25) == pytest.approx(5)
    assert gen_reader.read(ctx, "goodput_rps") == 30.0  # a chain counts once
    reads = [r for r in reqs if r.kind == "search"]
    ctx = {"requests": reads, "out": tr.Outcome(
        np.zeros(len(reads)), np.full(len(reads), 0.005),
        np.full(len(reads), 200, np.int32), [b"{}"] * len(reads)),
        "good": np.ones(len(reads), bool), "seconds": 10.0}
    assert gen_reader.read(ctx, stat, q=50, kind="write") is None
    assert gen_reader.read(ctx, stat, q=50, kind="search") == gen_reader.read(
        ctx, stat, q=50)  # a read-only cell: the same number as before


def test_chain_reader_on_the_stand_ins_answers(write_mixed):
    traffic, metro, ref = write_mixed
    reqs = _window(write_mixed, 7)
    out = check.answers_of(traffic, reqs, metro, ref)
    written = deploy.Written()
    written.absorb(reqs, out)
    cmp = check.compare(traffic, reqs, out, metro, ref, written)
    ctx = {"requests": reqs, "out": out, "good": cmp["good"],
           "read_back": cmp["read_back"]}
    chains = [c for c in out.chain if c]
    two = [c for c in chains if len(c) == 2]
    assert chain_reader.read(ctx, "rounds_mean") == pytest.approx(
        np.mean([len(c) for c in chains]))
    assert chain_reader.read(ctx, "conflicts_mean") == pytest.approx(
        np.mean([len(c[0].listed) for c in two]))
    assert chain_reader.read(ctx, "exchange_percentile_ms", exchange=1
                             ) == pytest.approx(check.TICK * 1000)
    assert chain_reader.read(ctx, "exchange_percentile_ms", exchange=4) is None
    share = chain_reader.read(ctx, "read_after_write_pct")
    assert share == pytest.approx(
        100 * cmp["facts"]["searches_read_back"] / 300)
    assert 5 < share < 100  # the overlay decides a real part of it
    # a cell without writes: nothing to read, never 0
    reads = [r for r in reqs if r.kind == "search"]
    quiet = {"requests": reads, "good": np.ones(len(reads), bool),
             "read_back": np.zeros(len(reads), bool),
             "out": tr.Outcome(None, None, np.full(len(reads), 200), [])}
    assert chain_reader.read(quiet, "read_after_write_pct") is None
    for stat in ("rounds_mean", "conflicts_mean", "exchange_percentile_ms"):
        assert chain_reader.read(quiet, stat) is None


# ---------------------------------------------------------------------------
# the whole cell command, on the CPU rehearsal
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("fault", ["", "forget_write", "alter_answer"])
def test_the_write_cell_runs_whole_on_the_cpu_rehearsal(fault):
    """Boot, warm-up with writes in it, window, comparison against the
    overlay, and the readers of every number that needs no trace; with
    the run's flights dropped from its searches, or an answer altered,
    `correct` comes out false."""
    bench, cell_config, _ = run.load_cell(WRITE_CELL)
    config = _json("tiny-config.json")
    config["server"].update({k: cell_config["server"][k]
                             for k in ("flags", "auth")})
    traffic = _json("tiny-traffic-write-mixed.json")
    layer = [m for m in run.load_metrics(WRITE_CELL) if m["reader"] in (
        "chain", "stage_mean", "population") or m["name"].startswith("write_")]
    result, facts = run.run_cell(
        WRITE_CELL, 2**31 + 7, 8.0, False, config=config, traffic=traffic,
        metrics=[], end_to_end=run.end_to_end_readers(bench, WRITE_CELL)
        + layer, platform="cpu", fault=fault)
    assert result["attempted"] == 240
    assert result["correct"] is (fault == ""), facts["first_wrong"]
    assert list(result)[-1] == "checks"
    assert {"search_p50_ms", "search_p95_ms", "goodput_rps", "setup_s",
            "write_p50_ms", "write_tail_p95_ms", "write_first_put_p50_ms",
            "write_keyed_put_p50_ms", "write_conflicts_mean",
            "write_rounds_mean", "read_after_write_pct",
            "write_handler_ms_mean", "write_covering_ms_mean",
            "write_proxy_ms_mean", "write_service_ms_mean",
            "auth_ms_mean", "small_p50_ms", "wide_p50_ms"} <= set(
        result["metrics"])
    assert result["metrics"]["read_after_write_pct"]["value"] > 0
    assert facts["flights_written"] >= 120 + 6  # the warm-up's are kept
    assert result["device"]["platform"] == "cpu"
