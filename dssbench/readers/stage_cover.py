"""The share of one stage's time that none of its listed leaf stages
covers, over the window: 100 x (1 - sum over the leaves of the
difference of `dss_stage_duration_seconds_sum{route, stage}` between
the window's two scrapes / the same difference for the whole).  The
leaves have to be disjoint parts of the whole for the number to mean
"what the measurement cannot see yet": the program's write stages are,
by construction (`dss_tpu/obs/stages.py stage`: a stage opened inside a
stage marks nothing), and `exec_wait_ms` by arithmetic (`service_ms`
less the executor's run, which holds every stage).

args: route ('search' | 'write' | 'other'), whole (e.g. 'service_ms'),
leaves (stage names), proc ('front').  A leaf the program never
observed covers nothing (a program from before its legs were lit reads
near 100); a whole it never observed -> nothing to read."""

from __future__ import annotations

from .scrape_ratio import read as ratio


def _sum_row(route: str, stage: str) -> str:
    return (f'dss_stage_duration_seconds_sum{{route="{route}",'
            f'stage="{stage}"}}')


def read(ctx: dict, route: str, whole: str, leaves: list,
         proc: str = "front"):
    covered = ratio(ctx, proc, [_sum_row(route, leaf) for leaf in leaves],
                    [_sum_row(route, whole)])
    return None if covered is None else 100.0 * (1.0 - covered)
