"""Serving telemetry: per-query traces, route metrics, drift-driven recal,
and quality observability (shadow-oracle recall, traversal introspection,
pipeline spans, the serving health report).

Attach to any index with ``index.attach_telemetry()`` (off by default,
detach with ``attach_telemetry(None)``).  Everything is host-side and
post-execution — compiled routes are bit-identical with telemetry on,
which rule JAG006 and the compiled-route auditor enforce statically.
The introspective graph route (``Telemetry(introspect=True)``) is the
one deliberate exception: it compiles a *separate* cache entry whose
extra outputs are pure device counters — still zero callbacks, zero
collectives, and bit-identical (ids, keys).
"""
import importlib

# every public name and the submodule that defines it. Submodules load on
# first use (PEP 562), so importing ``repro.obs.spans`` from the lowest
# layers (core, serve, stream) does not pull in the modules here that
# import those layers back.
_HOME = {
    "Counter": "metrics",
    "DriftReport": "drift",
    "HealthSLO": "health",
    "Histogram": "metrics",
    "MetricsRegistry": "metrics",
    "RecalReport": "recal",
    "ShadowAuditor": "shadow",
    "ShadowRecord": "shadow",
    "Span": "spans",
    "SpanRecorder": "spans",
    "Telemetry": "telemetry",
    "TraceBuffer": "trace",
    "TraceRecord": "trace",
    "cells_from_records": "shadow",
    "detect_drift": "drift",
    "health_report": "health",
    "heldout_error": "recal",
    "introspection_summary": "introspect",
    "load_buffer": "trace",
    "load_jsonl": "trace",
    "load_shadow_jsonl": "shadow",
    "observations_from_traces": "recal",
    "recalibrate": "recal",
    "relative_error": "drift",
    "render_health": "health",
    "sel_band": "shadow",
    "span": "spans",
    "stats_to_host": "introspect",
    "wilson_interval": "shadow",
}
__all__ = sorted(_HOME)


def __getattr__(name):
    if name not in _HOME:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(f".{_HOME[name]}", __name__), name)
