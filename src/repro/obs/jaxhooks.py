"""jax.monitoring / device hooks feeding the MetricsRegistry.

Retraces are counted through `jax.monitoring`'s event-duration stream:
every fresh jaxpr trace of a jitted function fires one
`/jax/core/compile/jaxpr_trace_duration` event (warm cache hits fire
none), and every backend compile fires
`/jax/core/compile/backend_compile_duration`. Each backend compile is
also counted under the program that compiled, from the event's
`fun_name` (`jax.backend_compiles.jit(_step)`), so a compile inside a
timed window names its program. The listener is installed once per
process and is inert while metrics are disabled, so other listeners and
the uninstrumented fast path are untouched.
"""

from __future__ import annotations

from repro.obs import metrics as _metrics

RETRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

RETRACES = "jax.retraces"
BACKEND_COMPILES = "jax.backend_compiles"

_installed = False


def _on_event_duration(event: str, duration: float, **kwargs) -> None:
    if not _metrics.active():
        return
    if event == RETRACE_EVENT:
        _metrics.inc(RETRACES)
        _metrics.observe("jax.trace_seconds", duration)
    elif event == BACKEND_COMPILE_EVENT:
        _metrics.inc(BACKEND_COMPILES)
        fun_name = kwargs.get("fun_name")
        if fun_name:
            _metrics.inc(f"{BACKEND_COMPILES}.{fun_name}")
        _metrics.observe("jax.compile_seconds", duration)


def install() -> None:
    """Register the compile-event listener (idempotent; never removed —
    jax.monitoring's clear would nuke third-party listeners too)."""
    global _installed
    if _installed:
        return
    try:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_event_duration)
    except Exception:      # jax without monitoring: counters just stay 0
        return
    _installed = True


def record_device_memory() -> None:
    """Gauge per-device peak memory where the backend reports it
    (`device.memory_stats()` is None on CPU — silently skipped)."""
    if not _metrics.active():
        return
    try:
        import jax
        devices = jax.devices()
    except Exception:
        return
    for i, d in enumerate(devices):
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if not ms:
            continue
        peak = ms.get("peak_bytes_in_use", ms.get("bytes_in_use"))
        if peak is not None:
            _metrics.gauge_set(f"device{i}.peak_bytes_in_use", float(peak))
