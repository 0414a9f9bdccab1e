"""A ratio of differences of /metrics gauges over the window.

args: proc ('leader' | 'front'), num and den (lists of gauge names, or
fnmatch patterns over 'name{labels}' keys; each list is summed), scale.
Nothing counted in the denominator -> nothing to read."""

from __future__ import annotations

import fnmatch


def delta(ctx: dict, proc: str, names: list) -> float:
    s0, s1 = ctx["scrape0"][proc], ctx["scrape1"][proc]
    total = 0.0
    for pat in names:
        keys = ([pat] if pat in s1 else
                fnmatch.filter(s1, pat) if any(c in pat for c in "*?[")
                else [])
        total += sum(s1[k] - s0.get(k, 0.0) for k in keys)
    return total


def read(ctx: dict, proc: str, num: list, den: list, scale: float = 1.0):
    d = delta(ctx, proc, den)
    if d <= 0:
        return None
    return delta(ctx, proc, num) / d * scale
