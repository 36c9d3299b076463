"""Observability for the fused serving engine (DESIGN.md §6.5).

``trace``          — the step tracer: ``serve.*`` host spans inside each
                     engine step, opened as ``jax.profiler``
                     annotations so they land on the profiler's clock
                     beside the device's programs, plus request stamps
                     (enqueue, submit, admit, prefill_done, first_token,
                     finish) and per-device-call events; Chrome-trace/
                     Perfetto export + aggregate summaries.  ``POST
                     /debug/trace/start`` turns it on, and a
                     ``jax.profiler`` session running meanwhile shows the
                     spans.
``prometheus``     — Prometheus text exposition of
                     ``ServerMetrics.snapshot()`` (Accept-negotiated on
                     ``GET /metrics``).
``slo``            — log-bucketed latency histograms (unbiased tail
                     percentiles) + per-instance TTFT/ITL/availability
                     objectives with error-budget burn rate (§6.9).
``accounting``     — per-tenant device-time attribution with a
                     conservation invariant, plus head-of-line
                     interference reporting (§6.9).
``flight``         — flight recorder: crash/watchdog/quarantine dumps
                     of the last-N trace events + metrics + queue
                     depths + SLO state to JSON artifacts (§6.9).
"""
from repro.serving.obs.accounting import TenantAccounting
from repro.serving.obs.flight import FlightRecorder
from repro.serving.obs.prometheus import render as render_prometheus
from repro.serving.obs.slo import (
    LogHistogram,
    SLOConfig,
    evaluate_availability,
    evaluate_objective,
    worst_state,
)
from repro.serving.obs.trace import DeviceCallEvent, RequestEvent, Tracer

__all__ = [
    "DeviceCallEvent",
    "FlightRecorder",
    "LogHistogram",
    "RequestEvent",
    "SLOConfig",
    "TenantAccounting",
    "Tracer",
    "evaluate_availability",
    "evaluate_objective",
    "render_prometheus",
    "worst_state",
]
