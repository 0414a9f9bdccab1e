"""Thread-local per-stage timing sink for request handling.

The serving stack (api/app.py `_call`) installs a per-request dict as
this thread's sink before invoking the synchronous service layer;
service code brackets its phases with `stage("covering_ms")` etc.  The
access-log middleware then emits the collected stages to the trace log,
the X-Dss-Stages response header (when tracing), and aggregate
counters in /metrics — so "where does the p50 go" is measured per
stage instead of guessed (the per-RPC latency breakdown the reference
gets from its SQL tracing).
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from dss_tpu.obs import trace

_tls = threading.local()


def set_sink(sink) -> None:
    """Install (or clear, with None) this thread's stage sink."""
    _tls.sink = sink


def get_sink():
    return getattr(_tls, "sink", None)


def mark(name: str, duration_ms: float, span: bool = True) -> None:
    """Record an externally-measured duration into the current sink
    (no-op without one).  For callers that cannot bracket the timed
    region with `stage` — e.g. the coalescer recording how long an
    item waited for its micro-batch.  Repeated marks accumulate.
    A mark is outside `stage`'s depth rule: what it measured lies
    inside whatever stage is open (coalesce_wait_ms inside store_ms or
    precheck_ms), so a marked name is a detail of that stage and no
    term of a sum of stages.
    When a trace is recording on this thread the mark also lands as a
    span (start back-dated by the duration); span=False skips that for
    callers that record a richer span of their own for the same
    region (the shm ring round trip)."""
    sink = getattr(_tls, "sink", None)
    if sink is None:
        return
    sink[name] = round(sink.get(name, 0.0) + duration_ms, 3)
    if not span:
        return
    h = trace.current()
    if h is not None:
        trace.add_span(
            h, name, time.time_ns() - int(duration_ms * 1e6),
            duration_ms,
        )


class stage:
    """Time a block: stage `<name>` of the request on this thread,
    `dss.<seam>` (the name itself where no seam is given) on a running
    capture, and a span `<name>` of a sampled request, with real
    nesting (spans opened inside the block parent under it).  Repeated
    stages accumulate.

    THE way a block gets into the sink, and stages are disjoint by
    construction: a stage opened inside another stage is the span
    alone (on the capture, nested on the timeline, and in the sampled
    request's tree) and marks NO stage, so the stages of one request
    never overlap and their sum can be held against service_ms.
    Without a sink (replay, boot, a follower's catch-up) a stage is
    annotate's one global read and marks nothing."""

    __slots__ = ("_name", "_seam", "_sink", "_depth", "_span", "_t0")

    def __init__(self, name: str, seam: Optional[str] = None):
        self._name = name
        self._seam = seam

    def __enter__(self) -> None:
        self._sink = sink = getattr(_tls, "sink", None)
        if sink is None:
            span = trace.annotate(self._seam or self._name)
        else:
            self._depth = depth = getattr(_tls, "depth", 0)
            _tls.depth = depth + 1
            span = trace.span(self._name, seam=self._seam)
        if span is trace.NOOP:  # no capture, no sampled request
            span = None
        else:
            span.__enter__()
        self._span = span
        self._t0 = time.perf_counter()

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur_ms = (time.perf_counter() - self._t0) * 1000.0
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
        sink = self._sink
        if sink is not None:
            _tls.depth = self._depth
            if self._depth == 0:
                name = self._name
                sink[name] = round(sink.get(name, 0.0) + dur_ms, 3)
        return False
