"""Cut a profiler capture down to a file small enough to commit:

    python3 dssbench/testdata/trim_xplane.py <in.xplane.pb> <out.xplane.pb> [events]

keeps, of every device plane, the first `events` (default 400) events
of the "XLA Ops" line and the "XLA Modules" events that start before
the last of them, and of the host plane the events of a millisecond or
more inside that span; writes them as a minimal XSpace protobuf (planes,
lines, events, event names) and prints the reduction of the result, which
goes into <out>.json for the test to hold the reader to.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from dssbench.readers import xplane  # noqa: E402


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint((num << 3) | 2) + _varint(len(value)) + value


def encode(planes: list) -> bytes:
    """planes: [(name, [(line name, [(event name, start_ns, dur_ns)])])]"""
    space = b""
    for pid, (pname, lines) in enumerate(planes, 1):
        names = {}
        body = _field(1, pid) + _field(2, pname)
        for lid, (lname, events) in enumerate(lines, 1):
            t0 = int(min((e[1] for e in events), default=0))
            lbody = _field(1, lid) + _field(2, lname) + _field(3, t0)
            for ename, start, dur in events:
                mid = names.setdefault(ename, len(names) + 1)
                lbody += _field(4, _field(1, mid)
                                + _field(2, int(round((start - t0) * 1000)))
                                + _field(3, int(round(dur * 1000))))
            body += _field(3, lbody)
        for ename, mid in names.items():
            body += _field(4, _field(1, mid)
                           + _field(2, _field(1, mid) + _field(2, ename)))
        space += _field(1, body)
    return space


def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    src, dst = sys.argv[1], sys.argv[2]
    keep = int(sys.argv[3]) if len(sys.argv) > 3 else 400
    planes, span = [], [None, None]
    data = ProfileData.from_file(src)
    for plane in data.planes:
        if not plane.name.startswith(xplane.DEVICE_PLANE):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if xplane.OPS_LINE not in lines:
            continue
        ops = [(e.name, e.start_ns, e.duration_ns)
               for e in lines[xplane.OPS_LINE].events]
        ops = sorted(ops, key=lambda e: e[1])[:keep]
        end = ops[-1][1] + ops[-1][2]
        span = [min(span[0] or ops[0][1], ops[0][1]), max(span[1] or 0, end)]
        mods = [(e.name, e.start_ns, e.duration_ns)
                for e in lines.get(xplane.MODULES_LINE).events
                if e.start_ns < end] if xplane.MODULES_LINE in lines else []
        planes.append((plane.name, [(xplane.OPS_LINE, ops),
                                    (xplane.MODULES_LINE, mods)]))
    for plane in data.planes:
        if plane.name.startswith(xplane.HOST_PLANE) and span[0] is not None:
            host = []
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.duration_ns)
                       for e in line.events
                       if e.duration_ns >= 1_000_000
                       and span[0] <= e.start_ns <= span[1]][:50]
                if evs:
                    host.append((line.name, evs))
            planes.append((plane.name, host[:8]))
    with open(dst, "wb") as fh:
        fh.write(encode(planes))
    red = xplane.reduce_trace(dst)
    want = {**{k: red[k] for k in ("capture_s", "window_s", "lead_s",
                                   "tail_s", "busy_s", "modules")},
            "idle_pct": 100 * (1 - red["busy_s"] / red["window_s"]),
            "top_op": red["breakdown"]["device_ops"][0][0]}
    with open(dst.replace(".xplane.pb", ".json"), "w") as fh:
        json.dump(want, fh, indent=1)
        fh.write("\n")
    print(json.dumps(want))
    return 0


if __name__ == "__main__":
    sys.exit(main())
