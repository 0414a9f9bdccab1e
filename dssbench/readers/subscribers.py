"""What a 200 tells the writer: the subscriptions to notify, as the
load generator read them off the answers of the window's planned
flights (traffic.py `scd_put`; the 200 of PutOperationReference lists
`subscribers[*].subscriptions[*].subscription_id`, grouped by USS URL).

stat: mean (subscription ids a chain's 200 named, over the chains of
the window that ended 200: the write's fan-out as its caller sees it,
the flight's own implicit subscription among them).  No chain ended
200 -> nothing to read."""

from __future__ import annotations

import json

import numpy as np


def read(ctx: dict, stat: str = "mean"):
    if stat != "mean":
        raise ValueError(f"subscribers reader has no stat {stat!r}")
    counts = []
    for chain in ctx["out"].chain or []:
        if not chain or chain[-1].status != 200:
            continue
        try:
            counts.append(sum(
                len(g["subscriptions"])
                for g in json.loads(chain[-1].body)["subscribers"]))
        except (ValueError, KeyError, TypeError):
            continue  # the comparison calls that answer unreadable
    return float(np.mean(counts)) if counts else None
