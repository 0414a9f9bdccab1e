"""The program's own spans on the capture's clock: the `dss.*` host
events that obs/trace.annotate writes into the profiler's trace while
POST /debug/profile captures (one vocabulary with the flight recorder's
spans and the owner's span slots), read beside the device's ops.

  owner_busy_pct      union of the intervals in which at least one
                      `dss.owner.serve` was open (a ring request being
                      served by the device owner), over window_s
  idle_with_work_pct  the part of that union in which no op ran on the
                      device, over window_s: the device idle while a
                      request sat in the owner.  device_idle_pct less
                      this is "idle because nothing was asked"

window_s is readers/xplane.py's: first device op to last, and only the
parts of spans inside it count.  A capture without a `dss.*` event (a
program that has none, or a capture nobody's request crossed) reads
nothing.

Only jax.profiler.ProfileData is used, and only after the server has
stopped; no backend is initialised.
"""

from __future__ import annotations

import os

from . import xplane

PREFIX = "dss."
SERVE = "dss.owner.serve"
# waits: a span that is open while a thread has nothing to do.  Device
# idle time goes to a working span before it goes to one of these.
WAITS = ("dss.owner.idle", "dss.owner.scan_idle", "dss.coalesce.idle")


def load(path: str, device_plane: str = xplane.DEVICE_PLANE) -> dict:
    """{'spans': [(start_ns, end_ns, name, line)] of every dss.* host
    event, 'ops': per device plane the [(start_ns, end_ns)] of its XLA
    ops}."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # never reach for a chip
    from jax.profiler import ProfileData

    spans, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(xplane.HOST_PLANE):
            for lid, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        a = int(ev.start_ns)
                        spans.append((a, a + int(ev.duration_ns), ev.name,
                                      f"{lid}:{line.name}"))
        elif plane.name.startswith(device_plane):
            for line in plane.lines:
                if line.name == xplane.OPS_LINE:
                    ops.append([(int(e.start_ns),
                                 int(e.start_ns) + int(e.duration_ns))
                                for e in line.events])
    return {"spans": spans, "ops": [o for o in ops if o]}


def _merge(intervals: list) -> list:
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(intervals: list, lo: int, hi: int) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _minus(intervals: list, holes: list) -> list:
    """The parts of merged `intervals` that no merged `holes` covers."""
    out, k = [], 0
    for a, b in intervals:
        while k < len(holes) and holes[k][1] <= a:
            k += 1
        j, at = k, a
        while j < len(holes) and holes[j][0] < b:
            if holes[j][0] > at:
                out.append((at, holes[j][0]))
            at = max(at, holes[j][1])
            j += 1
        if at < b:
            out.append((at, b))
    return out


def device_window(ops: list):
    """(first op's start, last op's end) over the device planes: the
    bounds of xplane's window_s."""
    return (min(a for o in ops for a, _ in o),
            max(b for o in ops for _, b in o))


def owner_serve(cap: dict) -> dict:
    """Seconds inside the device window: with a dss.owner.serve open
    (busy_s), and of those with no op on the device (idle_s, averaged
    over the device planes)."""
    lo, hi = device_window(cap["ops"])
    open_ = _merge(_clip([(a, b) for a, b, name, _ in cap["spans"]
                          if name == SERVE], lo, hi))
    busy = sum(b - a for a, b in open_)
    idle = sum(sum(b - a for a, b in _minus(open_, _merge(o)))
               for o in cap["ops"]) / len(cap["ops"])
    return {"busy_s": busy / 1e9, "idle_s": idle / 1e9}


def self_times(spans: list) -> dict:
    """{name: [count, total_s, self_s]}: a span's self time is its
    duration minus the part its children on the same line cover (the
    dss.* spans opened inside it by the same thread)."""
    out = {}
    by_line = {}
    for a, b, name, line in spans:
        by_line.setdefault(line, []).append((a, -b, name))
    for evs in by_line.values():
        stack = []  # [end, name, start, covered by children]
        for a, nb, name in sorted(evs):
            b = -nb
            while stack and stack[-1][0] <= a:
                _close(out, stack)
            if stack:
                stack[-1][3] += min(b, stack[-1][0]) - a
            stack.append([b, name, a, 0])
        while stack:
            _close(out, stack)
    return out


def _close(out: dict, stack: list) -> None:
    b, name, a, covered = stack.pop()
    row = out.setdefault(name, [0, 0.0, 0.0])
    row[0] += 1
    row[1] += (b - a) / 1e9
    row[2] += (b - a - covered) / 1e9


def idle_by_span(cap: dict) -> dict:
    """{name: seconds of the (first) device plane's idle time inside
    the device window, each instant given to the innermost dss.* span
    open then}: of the
    working spans open on any thread the one opened last, else of the
    waits the one opened last, else '(none)'."""
    lo, hi = device_window(cap["ops"])
    idle = _minus([(lo, hi)], _merge(cap["ops"][0]))
    marks = []
    for k, (a, b, _name, _line) in enumerate(cap["spans"]):
        if b > lo and a < hi:
            marks.append((max(a, lo), 1, k))
            marks.append((min(b, hi), 0, k))
    marks.sort()
    out, open_, m = {}, set(), 0
    for a, b in idle:
        at = a
        while at < b:
            while m < len(marks) and marks[m][0] <= at:
                (open_.add if marks[m][1] else open_.discard)(marks[m][2])
                m += 1
            nxt = min(b, marks[m][0]) if m < len(marks) else b
            who = _innermost(cap["spans"], open_)
            out[who] = out.get(who, 0.0) + (nxt - at) / 1e9
            at = nxt
    return out


def _innermost(spans: list, open_: set) -> str:
    best = None
    for k in open_:
        a, _b, name, _line = spans[k]
        key = (name not in WAITS, a)
        if best is None or key > best[0]:
            best = (key, name)
    return best[1] if best else "(none)"


def capture(ctx: dict) -> dict:
    """The capture of this run, loaded once and kept in ctx."""
    if "_spans" not in ctx:
        ctx["_spans"] = load(ctx["trace_file"])
    return ctx["_spans"]


def read(ctx: dict, stat: str):
    if not ctx.get("trace_file"):
        return None
    if stat not in ("owner_busy_pct", "idle_with_work_pct"):
        raise ValueError(f"spans reader has no stat {stat!r}")
    cap = capture(ctx)
    window_s = xplane.reduction(ctx)["window_s"]
    if not cap["spans"] or not cap["ops"] or window_s <= 0:
        return None  # no dss.* event, or no device op to set a window
    serve = owner_serve(cap)
    return 100.0 * serve["busy_s" if stat == "owner_busy_pct"
                         else "idle_s"] / window_s


def main() -> int:
    """python3 -m dssbench.readers.spans <file.xplane.pb>: per span
    name its count, total and self time, then the device's idle time
    inside its window by the innermost span open in it."""
    import sys

    cap = load(sys.argv[1])
    if not cap["spans"]:
        print("no dss.* event in this capture")
        return 1
    print(f"{'span':32} {'count':>7} {'total_s':>10} {'self_s':>10}")
    for name, (n, total, own) in sorted(
            self_times(cap["spans"]).items(), key=lambda kv: -kv[1][2]):
        print(f"{name:32} {n:7d} {total:10.4f} {own:10.4f}")
    if not cap["ops"]:
        print("no device op in this capture")
        return 0
    lo, hi = device_window(cap["ops"])
    serve = owner_serve(cap)
    print(f"device window {(hi - lo) / 1e9:.4f} s; dss.owner.serve open "
          f"{serve['busy_s']:.4f} s of it, the device idle in "
          f"{serve['idle_s']:.4f} s of that")
    print(f"{'device idle under':32} {'idle_s':>10}")
    for name, s in sorted(idle_by_span(cap).items(), key=lambda kv: -kv[1]):
        print(f"{name:32} {s:10.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
