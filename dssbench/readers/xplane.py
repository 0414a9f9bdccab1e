"""The reduction from one profiler capture (.xplane.pb, taken by the
leader's POST /debug/profile over a stretch of the cell's traffic that
follows the window: run.traced_stretch) to device numbers.

  capture_s   the capture's own span: from the first event's start to
              the last event's end, over every line of every plane
  window_s    the part of it in which the device was given work: first
              device op to last.  The capture starts before the
              stretch's traffic and outlasts it; lead_s and tail_s are
              the quiet ends
  busy_s      union of the intervals in which an XLA op ran on a device
              plane's "XLA Ops" line, averaged over the device planes
  modules     per executable on the "XLA Modules" line (the jitted
              function's name, its hash cut off): runs and device
              seconds, averaged over the device planes
  breakdown   the ten ops with most device time, and the ten longest
              gaps between ops, each named by the innermost host event
              that spans it (or, failing that, the op before it)

Stats read from it: device_idle_pct (1 - busy_s / window_s), and per
kernel, named by the metric file's `kernel` (the jitted function, as
the Modules line has it): kernel_ms_per_launch, kernel_launches_per_request
and kernel_roofline_pct, all over that executable's own runs and
device time and no other's, and only where the whole stretch lies
inside the capture (lead_s and tail_s of `margin_s` or more: else the
runs are not those requests' and nothing is read).  The last counts the
needed bytes from the benchmark's own reference (check.needed_bytes) for
every right answer of that stretch whose covering holds at least
`min_candidates` postings, and takes the peak from dssbench/peaks.json
by device kind: it is a share of the HBM bandwidth roofline (these
kernels are bandwidth-bound: a compare and a mask per 24 bytes read).

Only jax.profiler.ProfileData is used, and only after the server has
stopped; no backend is initialised.
"""

from __future__ import annotations

import json
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


def _union(intervals: list) -> float:
    total, end = 0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def reduce_trace(path: str, device_plane: str = DEVICE_PLANE) -> dict:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # never reach for a chip
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    busy, planes = [], 0
    per_op, ops_all, host, modules = {}, [], [], {}
    first, last = None, None  # of the capture
    dev_first, dev_last = None, None  # of what ran on a device
    for plane in data.planes:
        device = plane.name.startswith(device_plane)
        is_host = plane.name.startswith(HOST_PLANE)
        spans = None
        for line in plane.lines:
            for ev in line.events:
                a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                first = a if first is None or a < first else first
                last = b if last is None or b > last else last
                if is_host and ev.duration_ns >= 1_000_000:
                    host.append((a, b, f"{line.name}:{ev.name}"))
                elif device and line.name == OPS_LINE:
                    spans = [] if spans is None else spans
                    spans.append((a, b))
                    dev_first = a if dev_first is None else min(dev_first, a)
                    dev_last = b if dev_last is None else max(dev_last, b)
                    per_op[ev.name] = per_op.get(ev.name, 0) + ev.duration_ns
                    ops_all.append((a, b, ev.name))
                elif device and line.name == MODULES_LINE:
                    m = modules.setdefault(ev.name.split("(")[0],
                                           {"runs": 0, "device_s": 0.0})
                    m["runs"] += 1
                    m["device_s"] += ev.duration_ns / 1e9
        if spans is not None:
            planes += 1
            busy.append(_union(spans))
    capture_s = (last - first) / 1e9 if first is not None else 0.0
    if not planes:
        return {"busy_s": 0.0, "window_s": capture_s, "capture_s": capture_s,
                "lead_s": capture_s, "tail_s": 0.0, "modules": {},
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    for m in modules.values():
        m["runs"] /= planes
        m["device_s"] /= planes
    ops_all.sort()
    gaps = []
    end, prev = None, ""
    for a, b, name in ops_all:
        if end is not None and a > end:
            gaps.append((a - end, end, a, prev))
        if end is None or b > end:
            end, prev = b, name
    gaps.sort(reverse=True)

    def blame(a: int, b: int, prev: str) -> str:
        # the innermost host event that spans most of the gap: of those
        # covering half of it or more the shortest, else the widest overlap
        best, who, short = 0, "", None
        for ha, hb, name in host:
            ov = min(b, hb) - max(a, ha)
            if 2 * ov >= b - a and (short is None or hb - ha < short):
                short, who, best = hb - ha, name, b - a
            elif short is None and ov > best:
                best, who = ov, name
        return _name(f"host:{who}" if who else f"after:{prev}")

    return {
        "busy_s": sum(busy) / planes / 1e9,
        # the capture is asked to start before the stretch's traffic and
        # to outlast it (run.traced_stretch), so the traced window is
        # the part of it in which the device was given work: first op
        # to last op.  lead_s and tail_s are the quiet ends; both well
        # above 0 say that the whole stretch lies inside the capture.
        "window_s": (dev_last - dev_first) / 1e9,
        "capture_s": capture_s,
        "lead_s": (dev_first - first) / 1e9,
        "tail_s": (last - dev_last) / 1e9,
        "modules": modules,
        "breakdown": {
            "device_ops": [
                [_name(k), v / 1e9] for k, v in
                sorted(per_op.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[blame(a, b, prev), g / 1e9]
                          for g, a, b, prev in gaps[:10]],
        },
    }


def _name(s: str) -> str:
    return "".join(c if c.isalnum() or c in "._-:" else "_" for c in s)[:64]


def reduction(ctx: dict) -> dict:
    """The capture of this run, reduced once and kept in ctx."""
    if "_xplane" not in ctx:
        ctx["_xplane"] = reduce_trace(ctx["trace_file"])
    return ctx["_xplane"]


def peak_bytes_per_s(device_kind: str) -> float:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "peaks.json"), encoding="utf-8") as fh:
        peaks = json.load(fh)
    if device_kind not in peaks:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "dssbench/peaks.json")
    return float(peaks[device_kind]["hbm_bytes_per_s"])


def stretch_needed_bytes(ctx: dict, min_candidates: int) -> int:
    """Needed bytes of every right answer of the stretch the capture
    ran over whose covering holds at least `min_candidates` postings."""
    from .. import check, traffic as tr

    total = 0
    comps = ctx["traffic"]["components"]
    traced = ctx["traced"]
    for k, req in enumerate(traced["requests"]):
        if not traced["good"][k]:
            continue
        cls = tr.ENDPOINTS[comps[req.comp]["endpoint"]]["class"]
        cand = ctx["ref"][cls].candidates(ctx["metro"].rect_flat(*req.rect))
        if cand >= min_candidates:
            results = len(check.answered_ids(comps[req.comp],
                                             traced["out"].body[k]))
            total += check.needed_bytes(cand, results)
    return total


def read(ctx: dict, stat: str, kernel: str = "", min_candidates: int = 0,
         margin_s: float = 0.1):
    if not ctx.get("trace_file"):
        return None
    red = reduction(ctx)
    if stat == "device_idle_pct":
        if red["busy_s"] <= 0 or red["window_s"] <= 0:
            return None
        return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    if stat not in ("kernel_ms_per_launch", "kernel_launches_per_request",
                    "kernel_roofline_pct"):
        raise ValueError(f"xplane reader has no stat {stat!r}")
    mod = red["modules"].get(kernel)
    if not mod or mod["device_s"] <= 0:
        return None  # that executable did not run under the capture
    if min(red["lead_s"], red["tail_s"]) < margin_s:
        # the capture began after the stretch's traffic or ended before
        # it: its runs are not the stretch's requests', and a ratio of
        # the two would count work that the time leaves out
        return None
    if stat == "kernel_ms_per_launch":
        return mod["device_s"] * 1000.0 / mod["runs"]
    if stat == "kernel_launches_per_request":
        due = len(ctx["traced"]["requests"])
        return mod["runs"] / due if due else None
    need = stretch_needed_bytes(ctx, min_candidates)
    if not need:
        return None
    least_s = need / peak_bytes_per_s(ctx["device_kind"])
    return 100.0 * least_s / mod["device_s"]


def main() -> int:
    """python3 -m dssbench.readers.xplane <file.xplane.pb>: what the
    capture holds (planes, lines, event counts, first names) and its
    reduction."""
    import sys

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    data = ProfileData.from_file(sys.argv[1])
    for plane in data.planes:
        print(plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print(f"    {line.name!r}: {len(evs)} events"
                  + (f", first {evs[0].name[:70]!r}" if evs else ""))
    print(json.dumps(reduce_trace(sys.argv[1]), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
