"""The mean time of one stage of one route class over the window, from
the whole front's `dss_stage_duration_seconds{route, stage}` histogram:
the difference of its `_sum` over the difference of its `_count`
between the window's two scrapes, in milliseconds.

args: route ('search' | 'write' | 'other'), stage (e.g. 'handler_ms'),
proc ('front').  The program exports a (route, stage) row only once it
has observed it, so a cell that sends no such request reads nothing."""

from __future__ import annotations

from .scrape_ratio import read as ratio


def read(ctx: dict, route: str, stage: str, proc: str = "front"):
    labels = f'{{route="{route}",stage="{stage}"}}'
    return ratio(ctx, proc, ["dss_stage_duration_seconds_sum" + labels],
                 ["dss_stage_duration_seconds_count" + labels], 1000.0)
