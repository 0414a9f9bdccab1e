"""Seconds read from the leader's boot log: either between the first
records whose messages start with `start` and `end`, or the number a
regular expression's first group finds in a message."""

from __future__ import annotations

import re


def read(ctx: dict, start: str = "", end: str = "", pattern: str = "",
         logger: str = "dss.server"):
    recs = [r for r in ctx["bootlog"] if r.get("logger") == logger]
    if pattern:
        for r in recs:
            m = re.search(pattern, r.get("msg", ""))
            if m:
                return float(m.group(1))
        return None
    ts = [next((r["ts"] for r in recs if r.get("msg", "").startswith(k)),
               None) for k in (start, end)]
    if None in ts:
        return None
    return float(ts[1] - ts[0])
