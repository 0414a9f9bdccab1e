"""A latency percentile of ONE of the traffic's populations: the
nearest-rank percentile of the window's latencies over the requests of
one component, by its index in the traffic file's `components` (the
arithmetic run.run_cell uses for facts.latency_ms_by_component).  A
component the traffic does not have, or that got no request -> nothing
to read."""

from __future__ import annotations

import numpy as np

from .. import traffic as tr


def read(ctx: dict, component: int, q: float = 50.0):
    reqs = ctx["requests"]
    mine = np.array([r.comp == component for r in reqs], bool)
    if not mine.any():
        return None
    lat = tr.latencies_ms(reqs, ctx["out"], ctx["good"])
    return tr.percentile(lat[mine], q)
