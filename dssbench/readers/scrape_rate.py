"""The difference of /metrics gauges over the window, as a count
(per='window') or a rate (per='second'), or their value at the window's
end (per='end').  A gauge the process does not export -> nothing to
read."""

from __future__ import annotations

from .scrape_ratio import delta


def read(ctx: dict, proc: str, names: list, per: str = "window"):
    if not any(n in ctx["scrape1"][proc] for n in names):
        return None
    if per == "end":
        return sum(ctx["scrape1"][proc].get(n, 0.0) for n in names)
    d = delta(ctx, proc, names)
    return d / ctx["seconds"] if per == "second" else d
