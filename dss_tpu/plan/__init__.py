"""The query-plan layer: one planner over every serving route.

PR 5 taught the coalescer to route by measured cost, PR 6 added the
resident stream, PR 7 the read cache, PR 8 the rebalanced mesh — and
by then route choice was if/else chains threaded through
dar/coalesce.py and ops/fastpath.py, with the drain sizing, the
Retry-After estimate, and the inline lone-caller path each re-deriving
costs on their own.  This package lifts all of that into an explicit
mapping (the GOMA / data-placement-mapper frame from PAPERS.md):

  costs.CostModel   — the online EWMA cost estimates (device floor /
                      per-item / host chunk / resident floor+latency),
                      moved here verbatim from dar/coalesce.py.
  ModelState        — an immutable snapshot of (cost estimates +
                      pipeline pressure + route availability): the
                      full input of a routing decision.
  BatchShape        — what is being routed (size, staleness, owner
                      scoping, inline-ness).
  Plan              — the decision record: chosen route, predicted
                      cost, every candidate considered, deadline and
                      freshness class.
  Planner           — produces Plans, owns the CostModel, sizes
                      drains, and answers Retry-After throughput from
                      the route it would actually choose.  `decide`
                      is a pure function of (shape, state, headroom):
                      unit-testable with no live coalescer, no
                      device, no threads, and replayable against
                      recorded model states.
  autotune          — the offline mapping-space search: measured
                      microbenchmarks over the DSS_CO_EST_* seeds,
                      host chunk size, resident ring/inflight, and
                      the DSS_RES_* bucket grids, emitted as a
                      machine-readable host profile that
                      cmds/server.py --autotune_profile loads at boot
                      (knob precedence: env > profile > defaults).

Adding a route touches ONE file: planner.py (a candidate in
`enumerate_candidates` + an arm in `route_qps`).
"""

from dss_tpu.plan.costs import CostModel
from dss_tpu.plan.planner import (
    HEADROOM_SAFETY,
    ROUTES,
    BatchShape,
    ModelState,
    Plan,
    Planner,
    decide,
    plan_drain_cap,
)

__all__ = [
    "BatchShape",
    "CostModel",
    "HEADROOM_SAFETY",
    "ModelState",
    "Plan",
    "Planner",
    "ROUTES",
    "decide",
    "plan_drain_cap",
]
