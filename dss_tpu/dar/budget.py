"""Host-only read budget: lets the event loop run reads inline safely.

The inline-reads optimization (api/app.py `_call_read`) executes a read
handler directly on the event loop — a win on single-core hosts where
the two executor handoffs are pure overhead — but ONLY host-bounded
work may run there: a device dispatch (a full round trip) or a
fresh XLA compile (seconds) on the loop would starve
/healthy and every other request.

The loop-side caller sets the thread-local host_only flag; the store
layers raise NeedsDevice instead of entering any path that would
dispatch to the device or block on another thread's batch.  The caller
catches NeedsDevice and re-runs the (pure) read on the executor.
"""

from __future__ import annotations

import threading

_tls = threading.local()


class NeedsDevice(Exception):
    """Read would leave the host-bounded budget; re-run off the loop."""


def set_host_only(flag: bool) -> None:
    _tls.host_only = flag


def is_host_only() -> bool:
    return bool(getattr(_tls, "host_only", False))
