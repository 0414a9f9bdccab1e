"""What the load generator saw of the window's planned flights, chain
by chain (traffic.py `scd_put`: PUT with an empty key -> 409 with the
conflicts -> PUT with their OVNs as the key -> 200), and how much of
the comparison the run's own writes decided.

stats: exchange_percentile_ms (the `exchange`-th PUT of every chain
that got that far, sent to last byte; `q`), conflicts_mean (ids the
first 409 of a chain listed: the key's size), rounds_mean (exchanges a
chain that ended in a 200 took: 2.0 unless writers collide or the
airspace is empty), read_after_write_pct (of the searches compared,
those in whose answer a flight acknowledged earlier in this run was
due: the share the overlay decided).  No chain, or no search -> nothing
to read."""

from __future__ import annotations

import numpy as np

from .. import traffic as tr


def read(ctx: dict, stat: str, exchange: int = 0, q: float = 50.0):
    out = ctx["out"]
    chains = [c for c in (out.chain or []) if c]
    if stat == "read_after_write_pct":
        judged = np.array([r.kind == "search" for r in ctx["requests"]]
                          ) & (np.asarray(out.status) == 200)
        if not judged.any() or not chains:
            return None
        return 100.0 * float((ctx["read_back"] & judged).sum()) / float(
            judged.sum())
    if stat == "exchange_percentile_ms":
        took = [(c[exchange].done - c[exchange].sent) * 1000.0
                for c in chains if len(c) > exchange]
        return tr.percentile(took, q) if took else None
    if stat == "conflicts_mean":
        listed = [len(c[0].listed) for c in chains if c[0].listed is not None]
        return float(np.mean(listed)) if listed else None
    if stat == "rounds_mean":
        rounds = [len(c) for c in chains if c[-1].status == 200]
        return float(np.mean(rounds)) if rounds else None
    raise ValueError(f"chain reader has no stat {stat!r}")
