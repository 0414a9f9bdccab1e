"""JAX kernels for the DAR hot path.

x64 is enabled globally: entity times are exact int64 unix-nanoseconds
on device, matching the reference's timestamp comparison semantics
(pkg/scd/store/cockroach/operations.go:374-435).

Importing this package also places JAX's persistent compilation cache
(place_compile_cache) — every process that compiles a kernel imports
it first, so the placement lands before the first compile.
"""

import contextlib
import os
import threading
from typing import Optional

import jax

jax.config.update("jax_enable_x64", True)

# the cache directory is part of the cache key's lookup path: it must
# be the same in every run, so it is derived from the checkout alone —
# never from tempfile, a pid, the cwd or the clock
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def place_compile_cache(environ=os.environ) -> Optional[str]:
    """Place JAX's persistent compilation cache.  With
    JAX_COMPILATION_CACHE_DIR set, JAX itself reads the directory from
    the environment and this sets nothing (returns None); without it,
    the one fixed directory inside the checkout is used (returned)."""
    if environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


place_compile_cache()


# where a compile happens decides what it costs: a warm on a thread of
# its own costs no request, a jit miss under a request stalls it for
# seconds.  The site is the compiling thread's (compile_site).  The
# threads that compile for no request are few and marked (the boot's
# build and warm, the fold thread, the AOT compiler on their behalf);
# the threads a request waits on are many (the executor pool, the
# coalescer's and the resident pipeline's, the ring's servers), so
# `request` is every thread nobody marked: an unmarked thread can read
# as a stall that was none, and never hide one.  The warm-up's own
# first requests compile there too, so it is the count's rise after
# the warm-up that says a request waited.
COMPILE_SITES = ("request", "fold_warm", "boot_warm")
_site = threading.local()


def current_compile_site() -> str:
    return getattr(_site, "name", "request")


@contextlib.contextmanager
def compile_site(name: str):
    """Count this thread's compiles under `name` while the block runs:
    `boot_warm` around the boot's build and warm (cmds/server.py,
    DSSStore.warm_resident), `fold_warm` on the fold thread
    (dar/snapshot.py), around a fold's `_resident_warm` hook wherever
    it runs and, handed on with the bucket, on the compiler thread
    that builds it (ops/resident.py AotCache)."""
    if name not in COMPILE_SITES:
        raise ValueError(f"unknown compile site {name!r}")
    prev = current_compile_site()
    _site.name = name
    try:
        yield
    finally:
        _site.name = prev


class _CompileCounter:
    """Process-wide count of XLA backend compiles, fed by JAX's own
    monitoring events: every executable build (jit first call or AOT
    .compile()) and every persistent-cache hit among them, in total
    and by the site of the thread that compiled.  A compile after boot
    warm under site `request` is a compile on somebody's request path
    — the dss_jax_* gauges (DSSStore.stats) make that countable."""

    def __init__(self):
        self._lock = threading.Lock()
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.by_site = {site: 0 for site in COMPILE_SITES}
        self.request_s = 0.0

    def on_duration(self, event: str, duration_secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            site = current_compile_site()
            with self._lock:
                self.compiles += 1
                self.compile_s += duration_secs
                self.by_site[site] += 1
                if site == "request":
                    self.request_s += duration_secs

    def on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def stats(self) -> dict:
        with self._lock:
            return {
                "dss_jax_compiles": self.compiles,
                "dss_jax_compile_seconds": round(self.compile_s, 3),
                "dss_jax_compile_cache_hits": self.cache_hits,
                **{f"dss_jax_compiles_{site}": n
                   for site, n in self.by_site.items()},
                "dss_jax_compile_seconds_request": round(
                    self.request_s, 3
                ),
            }


_COMPILES = _CompileCounter()
jax.monitoring.register_event_duration_secs_listener(_COMPILES.on_duration)
jax.monitoring.register_event_listener(_COMPILES.on_event)
compile_stats = _COMPILES.stats


def device_memory_stats() -> dict:
    """The allocator's own view of this process's devices at this
    instant (device.memory_stats()): bytes in use summed over them, and
    the highest peak any one of them has seen since the process
    started.  A backend that reports nothing (the CPU) or lacks a key
    gives no series — never a 0 that would read as "empty"."""
    found = [d.memory_stats() or {} for d in jax.local_devices()]
    out = {}
    in_use = [s["bytes_in_use"] for s in found if "bytes_in_use" in s]
    peaks = [
        s["peak_bytes_in_use"] for s in found if "peak_bytes_in_use" in s
    ]
    if in_use:
        out["dss_device_bytes_in_use"] = int(sum(in_use))
    if peaks:
        out["dss_device_peak_bytes_in_use"] = int(max(peaks))
    return out

from dss_tpu.ops.conflict import (  # noqa: F401,E402
    EntityTable,
    Postings,
    QuerySpec,
    conflict_query,
    conflict_query_batch,
    max_count_per_cell,
    NO_TIME_LO,
    NO_TIME_HI,
)
