"""Unified telemetry: trace spans, training metrics, recompile watchdog.

One master switch drives the whole subsystem (param ``telemetry=True``,
or :func:`configure` directly):

  * **span tracer** (:mod:`.tracer`) — nested host-side spans exported as
    Chrome/Perfetto trace-event JSON via :func:`export_trace`;
  * **metrics registry** (:mod:`.metrics`) — counters/gauges/time
    histograms plus the per-iteration training records the GBDT loop
    emits, streamed to a JSONL sink (param ``telemetry_out``);
  * **recompile watchdog** (:mod:`.watchdog`) — always-on compile
    counting per jitted entry point with threshold warnings (param
    ``telemetry_recompile_threshold``), and one ``Runtime::Compile`` ring
    record for every program the process compiles or fetches;
  * **multi-host straggler detection** lives in
    :mod:`lightgbm_tpu.parallel.straggler` (it needs the process mesh,
    which is the parallel layer's concern) and reports through the
    registry here.

Everything is a no-op behind a single boolean check when disabled, so
instrumentation can stay in hot paths unconditionally.
"""
from __future__ import annotations

import atexit
from typing import Any, Dict, Optional

from . import costmodel
from .context import (TRACE_HEADER, AccessLog, TailRing, TraceContext,
                      new_trace_id, request_complete, request_instant,
                      request_span)
from .costmodel import cost_summary, machine_balance
from .metrics import (MetricsRegistry, device_hbm_bytes, device_memory_gb,
                      global_registry, host_rss_gb, memory_snapshot)
from .prometheus import registry_text, render_parts, render_prometheus
from .quality import (QualityMonitor, QualityProfile, js_divergence,
                      psi, quality_sidecar_path)
from .tracer import SpanRecord, SpanTracer, global_tracer
from .watchdog import (WatchEntry, get_recompile_threshold,
                       hist_comm_counts, hist_pass_count,
                       hist_pass_iteration, hist_small_pass_count,
                       host_sync_count, launch_count,
                       note_hist_passes, note_host_sync, note_launch,
                       recompile_counts, reset_counters,
                       reset_watchdog, scan_slot_count,
                       set_recompile_threshold,
                       watchdog_summary, watched_jit)

__all__ = [
    "SpanTracer", "SpanRecord", "MetricsRegistry", "WatchEntry",
    "global_tracer", "global_registry",
    "configure", "enabled", "enabled_source", "enable", "disable", "reset",
    "span", "boundary", "recent_spans", "instant", "inc", "gauge", "observe",
    "quantiles", "record", "export_trace", "flush", "summary",
    "watched_jit", "recompile_counts", "watchdog_summary",
    "set_recompile_threshold", "get_recompile_threshold", "reset_watchdog",
    "launch_count", "host_sync_count", "note_host_sync", "note_launch",
    "hist_pass_count", "hist_pass_iteration", "hist_small_pass_count",
    "note_hist_passes", "scan_slot_count", "hist_comm_counts",
    "reset_counters", "costmodel", "cost_summary", "machine_balance",
    "memory_snapshot", "device_hbm_bytes", "device_memory_gb", "host_rss_gb",
    "TraceContext", "TailRing", "AccessLog", "TRACE_HEADER",
    "new_trace_id", "request_span", "request_complete", "request_instant",
    "render_prometheus", "render_parts", "registry_text",
    "QualityMonitor", "QualityProfile", "psi", "js_divergence",
    "quality_sidecar_path",
]

_trace_out: Optional[str] = None
# who enabled telemetry: "api" (user called configure/enable directly) or
# "params" (a Booster's construction params). Param-driven enablement is
# per-model: constructing a later Booster WITHOUT telemetry params turns it
# off again, so model B never inherits model A's sinks or per-iteration
# sync overhead; an explicit API enable is never clobbered by a Booster.
_enabled_source: Optional[str] = None


def configure(enabled: bool = True, metrics_out: Optional[str] = None,
              trace_out: Optional[str] = None,
              recompile_threshold: Optional[int] = None,
              cost_capture: Optional[str] = None,
              _source: str = "api") -> None:
    """Turn telemetry on/off and point its sinks.

    ``metrics_out`` — JSONL path for streamed records; ``trace_out`` —
    Chrome trace JSON written by :func:`flush` (training calls it at the
    end of ``train()``); ``recompile_threshold`` — watchdog warn level;
    ``cost_capture`` — XLA cost-model mode (``auto``/``off``/``lowered``/
    ``full``, see :mod:`.costmodel`; env ``LGBTPU_COST`` overrides)."""
    global _trace_out, _enabled_source
    if enabled:
        global_tracer.enable()
        global_registry.enable()
        _enabled_source = _source
    else:
        global_tracer.disable()
        global_registry.disable()
        _enabled_source = None
    costmodel.configure(enabled=enabled, mode=cost_capture)
    if metrics_out is not None:
        global_registry.set_sink(metrics_out or None)
    if trace_out is not None:
        _trace_out = trace_out or None
    if recompile_threshold is not None:
        set_recompile_threshold(recompile_threshold)


def enabled_source() -> Optional[str]:
    return _enabled_source


def enabled() -> bool:
    return global_tracer.enabled or global_registry.enabled


def enable() -> None:
    configure(enabled=True)


def disable() -> None:
    configure(enabled=False)


def reset() -> None:
    """Clear collected spans/metrics/cost records (keeps enabled state
    and sinks)."""
    global_tracer.reset()
    global_registry.reset()
    costmodel.reset()


# -- thin instrument aliases (the hot-path entry points) --------------------
span = global_tracer.span
boundary = global_tracer.boundary
recent_spans = global_tracer.recent_spans
instant = global_tracer.instant
inc = global_registry.inc
gauge = global_registry.gauge
observe = global_registry.observe
quantiles = global_registry.quantiles
record = global_registry.record


def export_trace(path: str) -> str:
    """Write the span buffer as Chrome/Perfetto trace-event JSON."""
    return global_tracer.export_trace(path)


def trace_out_path() -> Optional[str]:
    return _trace_out


def flush() -> None:
    """Write the configured trace file (if any). Safe to call repeatedly."""
    if _trace_out:
        try:
            export_trace(_trace_out)
        except OSError:
            pass


@atexit.register
def _flush_at_exit() -> None:   # best-effort for CLI / script runs
    flush()


def summary() -> Dict[str, Any]:
    """One dict with everything: metrics snapshot, span phase totals,
    recompile rollup, memory, and sink locations."""
    phases = global_tracer.phase_snapshot()
    counts = global_tracer.phase_counts()
    out: Dict[str, Any] = {
        "enabled": enabled(),
        **global_registry.snapshot(),
        "phases": {k: {"total_s": round(v, 6), "calls": counts.get(k, 0),
                       "mean_s": round(v / max(counts.get(k, 1), 1), 6)}
                   for k, v in sorted(phases.items(),
                                      key=lambda kv: -kv[1])},
        "recompiles": watchdog_summary(),
        # XLA flops/HBM accounting + roofline verdicts per watched entry
        # (docs/OBSERVABILITY.md "Cost model & profiling")
        "cost": cost_summary(),
        "memory": memory_snapshot(),
        # events the bounded span buffer had to drop (the tracer warns
        # once when this first goes nonzero)
        "trace_dropped_events": global_tracer.dropped,
        # the always-on boundary-span ring: its newest records (the whole
        # ring is recent_spans()) and how many older ones it has overwritten
        "recent_spans": [r._asdict()
                         for r in global_tracer.recent_spans()[-128:]],
        "recent_spans_overwritten": global_tracer.ring_overwritten,
        # histogram passes the fused iterations grew, as of the flag poll
        # at `iteration` (telemetry/watchdog.py)
        "hist_passes": {"count": hist_pass_count(),
                        "small": hist_small_pass_count(),
                        "scan_slots": scan_slot_count(),
                        "iteration": hist_pass_iteration()},
    }
    if global_registry.sink_path:
        out["telemetry_out"] = global_registry.sink_path
    if _trace_out:
        out["trace_out"] = _trace_out
    return out
