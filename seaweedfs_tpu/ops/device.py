"""Which device the EC device engines run on, and their compile cache.

`-ec.engine=tpu|mesh` exists to run the GF(2^8) kernels on a TPU.  A
process that finds no TPU must not quietly encode on JAX's CPU backend:
that is only allowed when the process was pinned to the CPU on purpose
(``JAX_PLATFORMS=cpu``, as the tests and ``__graft_entry__.py`` do).

The persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says; without it, at ``<repo>/.jax_cache`` (a fixed path: the path is
part of JAX's cache key, so a moving directory never hits).

The same once-per-process set-up puts the program's spans on the
profiler's clock: every span the tracer opens then also enters a
``jax.profiler.TraceAnnotation``, so a profiler trace of this process
shows it on the ``/host:CPU`` plane beside the device ops.  Each compile
request is recorded as a ``device.compile`` span.
"""

from __future__ import annotations

import os
import threading

from ..observability import tracer as _tracer

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_lock = threading.Lock()
_compile_counts = {"compile_requests": 0, "cache_hits": 0}  # guarded-by: _lock
_cache_set_up = False  # guarded-by: _lock

# every jit compile request (compiled or read from the cache) is timed
# under the first event; the second fires on a persistent-cache hit
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoTpuError(RuntimeError):
    """A device engine was asked for, no TPU was found, and the process
    was not pinned to the CPU."""


def pinned_to_cpu() -> bool:
    import jax

    return (os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
            or (jax.config.jax_platforms or "").strip() == "cpu")


def device_backend() -> str:
    """'tpu' on a TPU; 'cpu' only when the process is pinned to it.
    Anything else raises NoTpuError."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return backend
    if backend == "cpu" and pinned_to_cpu():
        return backend
    raise NoTpuError(
        f"the tpu/mesh EC engines need a TPU, but JAX found backend "
        f"{backend!r}; set JAX_PLATFORMS=cpu to run them on the CPU "
        f"on purpose (tests), or use -ec.engine=cpu")


def _count_hit(event: str, **_kw) -> None:  # thread-entry
    if event == _HIT_EVENT:
        with _lock:
            _compile_counts["cache_hits"] += 1


def _count_compile(event: str, secs: float, **_kw) -> None:  # thread-entry
    if event == _COMPILE_EVENT:
        with _lock:
            _compile_counts["compile_requests"] += 1
        tr = _tracer.get_tracer()
        if tr.enabled:
            # JAX reports the duration once the compile is done: the
            # span ends now, under the step that compiled
            t1 = _tracer.now()
            tr.add_span("device.compile", t1 - secs, t1,
                        parent_id=tr.current_span_id())


def ensure_compile_cache() -> None:
    """Turn on JAX's persistent compile cache once per process: left to
    JAX when JAX_COMPILATION_CACHE_DIR is set, else at CACHE_DIR.  Only
    on a TPU — the CPU backend is a correctness surface and its
    executables are not worth keeping.  Also counts compile requests
    and cache hits for device_doc(), and installs the tracer's
    profiler annotation."""
    global _cache_set_up
    import jax

    with _lock:
        if _cache_set_up:
            return
        _cache_set_up = True
    _tracer.annotation_hook = jax.profiler.TraceAnnotation
    jax.monitoring.register_event_listener(_count_hit)
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    if "JAX_COMPILATION_CACHE_DIR" in os.environ \
            or jax.default_backend() != "tpu":
        return
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # the kernels compile in well under JAX's 1 s default threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def device_doc() -> dict:
    """The device this process computes on, as JAX reports it, with the
    compile-cache counters and each device's peak memory."""
    import jax

    devs = jax.devices()
    # the cpu backend keeps no memory stats (None)
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    with _lock:
        counts = dict(_compile_counts)
    counts["compiles"] = counts["compile_requests"] - counts["cache_hits"]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "peak_bytes_in_use": peaks,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir or "",
            **counts}
