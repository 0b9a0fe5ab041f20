"""sentinel_tpu.obs — the observability plane.

Three always-importable, dependency-light pieces:

* ``obs.trace``    — lock-light fixed-capacity span tracer (ring buffer,
  Chrome-trace/Perfetto export, optional jax.profiler passthrough) plus
  the distributed trace context (``new_trace_id`` / ``trace_ctx``) that
  rides the cluster wire so client and server spans share a trace id;
* ``obs.registry`` — counters / gauges / power-of-two latency histograms
  with Prometheus text exposition (incl. the ``sentinel_build_info``
  identity gauge);
* ``obs.flight``   — always-on black-box flight recorder: a bounded
  journal of state transitions and triggered post-mortem bundles.

Instrumented subsystems (runtime tick stages, engine compile events,
cluster RPC + degrade transitions, remote-shard chunks) record through
the process-global ``TRACER``, ``REGISTRY``, and ``FLIGHT``; the command
center serves them at ``GET /metrics``, ``GET /api/traces``, and ``GET
/api/flight``; the CLI (``python -m sentinel_tpu.obs``) dumps and
summarizes trace rings, joins multi-process dumps (``--merge``), and
analyzes flight bundles (``--postmortem``).

Tracing defaults OFF: call ``obs.enable()`` (or set ``SENTINEL_TRACE=1``)
to start recording.  Disabled-mode cost at every instrumented call site
is a single flag check — no allocation, no formatting, no clock read.
``obs.enable()`` also starts the process's own recorders (``obs.proc``:
how late a waiting thread wakes, the collector's pauses) and
``obs.disable()`` stops them; off, no thread and no callback exists.
The flight journal is always on (rare events, O(1) appends).
"""

from __future__ import annotations

from sentinel_tpu.obs.registry import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    register_build_info,
    register_scrape_id,
)
from sentinel_tpu.obs.flight import FLIGHT, FlightRecorder, load_bundle
from sentinel_tpu.obs.profile import (
    LEDGER,
    RETRACE,
    MemoryLedger,
    RetraceObservatory,
    SketchAudit,
    capture_profile,
    expected_retrace,
    ledger_owner,
)
from sentinel_tpu.obs import proc as _proc
from sentinel_tpu.obs.trace import (
    TRACER,
    SpanTracer,
    current_ctx,
    event,
    load_spans,
    maybe_ctx,
    new_span_id,
    new_trace_id,
    now_ns,
    stage,
    stage_ns,
    summarize,
    t0,
    trace_ctx,
)

#: every process that imports the obs plane identifies itself on /metrics
register_build_info()
register_scrape_id()
if TRACER.enabled:  # SENTINEL_TRACE=1: on from import, the process's recorders with it
    _proc.start()


def enable(jax_annotations: bool = False) -> None:
    """Turn span recording on (optionally mirroring spans into
    ``jax.profiler.TraceAnnotation`` so they land in XLA device traces),
    and the process's recorders with it (``obs.proc``: one thread, one
    ``gc.callbacks`` entry, however often this is called)."""
    TRACER.enable(jax_annotations=jax_annotations)
    _proc.start()


def disable() -> None:
    _proc.stop()
    TRACER.disable()


def enabled() -> bool:
    return TRACER.enabled


def span(name: str, trace: int = 0, **attrs):
    """Context-manager span on the default tracer (no-op when disabled)."""
    return TRACER.span(name, trace, **attrs)


__all__ = [
    "FLIGHT",
    "LEDGER",
    "REGISTRY",
    "RETRACE",
    "TRACER",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MemoryLedger",
    "MetricRegistry",
    "RetraceObservatory",
    "SketchAudit",
    "SpanTracer",
    "capture_profile",
    "current_ctx",
    "expected_retrace",
    "ledger_owner",
    "enable",
    "disable",
    "enabled",
    "event",
    "load_bundle",
    "load_spans",
    "maybe_ctx",
    "new_span_id",
    "new_trace_id",
    "now_ns",
    "register_build_info",
    "register_scrape_id",
    "span",
    "stage",
    "stage_ns",
    "summarize",
    "t0",
    "trace_ctx",
]
