"""Numbers the load generator takes itself, by its own clock."""

from __future__ import annotations

from .. import traffic as tr


def read(ctx: dict, stat: str, q: float = 50.0):
    reqs, out, good = ctx["requests"], ctx["out"], ctx["good"]
    if stat == "latency_percentile_ms":
        # of ALL requests due in the window; failed or late = deadline
        return tr.percentile(tr.latencies_ms(reqs, out, good), q)
    if stat == "goodput_rps":
        lat = tr.latencies_ms(reqs, out, good)
        return float((lat < tr.DEADLINE_S * 1000.0).sum()) / ctx["seconds"]
    if stat == "late_percentile_ms":
        late = tr.lateness_ms(reqs, out)
        return tr.percentile(late, q) if len(late) else None
    if stat == "setup_s":
        return float(ctx["setup_s"])
    raise ValueError(f"generator reader has no stat {stat!r}")
