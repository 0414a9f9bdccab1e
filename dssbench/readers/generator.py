"""Numbers the load generator takes itself, by its own clock.

`kind` ('search' | 'write') holds a latency percentile to the requests
of that kind: searches, or planned flights (a chain of PUTs, timed from
the instant it was due to the last byte of its last answer).  Without
it the percentile is over every request of the window."""

from __future__ import annotations

import numpy as np

from .. import traffic as tr


def read(ctx: dict, stat: str, q: float = 50.0, kind: str = ""):
    reqs, out, good = ctx["requests"], ctx["out"], ctx["good"]
    if stat == "latency_percentile_ms":
        # of ALL requests due in the window; failed or late = deadline
        lat = tr.latencies_ms(reqs, out, good)
        if kind:
            lat = lat[np.array([r.kind == kind for r in reqs], bool)]
        return tr.percentile(lat, q) if len(lat) else None
    if stat == "goodput_rps":
        lat = tr.latencies_ms(reqs, out, good)
        return float((lat < tr.DEADLINE_S * 1000.0).sum()) / ctx["seconds"]
    if stat == "late_percentile_ms":
        late = tr.lateness_ms(reqs, out)
        return tr.percentile(late, q) if len(late) else None
    if stat == "setup_s":
        return float(ctx["setup_s"])
    raise ValueError(f"generator reader has no stat {stat!r}")
