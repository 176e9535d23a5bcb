"""Metrics registry: counters, gauges, time histograms, JSONL sink.

Holds the per-run training metrics the span tracer cannot express —
monotonic counters (iterations, recompiles), point-in-time gauges (peak
HBM), and log-bucketed time histograms — plus the stream of per-iteration
training records the GBDT loop emits. Records append to an optional JSONL
sink as they arrive, so a crashed run still leaves its telemetry behind.

The device/host memory probes are the ones bench.py reports
(peak_bytes_in_use from ``device.memory_stats()``, ru_maxrss for host RSS).
"""
from __future__ import annotations

import json
import os
import threading
from typing import Any, Dict, List, Optional

# time-histogram bucket upper bounds, seconds (last bucket is +inf)
_HIST_BOUNDS = (0.0001, 0.0003, 0.001, 0.003, 0.01, 0.03, 0.1, 0.3,
                1.0, 3.0, 10.0, 30.0, 100.0, 300.0)
_MAX_RECORDS = int(os.environ.get("LIGHTGBM_TPU_METRICS_MAX_RECORDS",
                                  1_000_000))


class MetricsRegistry:
    """Thread-safe named counters/gauges/histograms + record stream."""

    def __init__(self) -> None:
        self.enabled = False
        self._lock = threading.Lock()
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._hists: Dict[str, Dict[str, Any]] = {}
        self._records: List[Dict[str, Any]] = []
        self._sink_path: Optional[str] = None
        self._sink_fh = None

    # -- control -----------------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._counters = {}
            self._gauges = {}
            self._hists = {}
            self._records = []

    def set_sink(self, path: Optional[str]) -> None:
        """Point the JSONL record sink at ``path`` (None closes it)."""
        with self._lock:
            if self._sink_fh is not None:
                try:
                    self._sink_fh.close()
                except OSError:
                    pass
                self._sink_fh = None
            self._sink_path = path

    @property
    def sink_path(self) -> Optional[str]:
        return self._sink_path

    # -- instruments -------------------------------------------------------
    def inc(self, name: str, value: float = 1.0) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + value

    def gauge(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._gauges[name] = float(value)

    def observe(self, name: str, seconds: float,
                bounds: Optional[tuple] = None) -> None:
        """Add one sample to the named histogram.  ``bounds`` overrides
        the log-time bucket upper bounds for value-shaped distributions
        (queue depths, batch sizes); only the FIRST observation's bounds
        stick for a given name."""
        if not self.enabled:
            return
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                bb = tuple(bounds) if bounds is not None else _HIST_BOUNDS
                h = self._hists[name] = {
                    "count": 0, "sum": 0.0, "min": float("inf"),
                    "max": 0.0, "bounds": bb,
                    "buckets": [0] * (len(bb) + 1)}
            h["count"] += 1
            h["sum"] += seconds
            h["min"] = min(h["min"], seconds)
            h["max"] = max(h["max"], seconds)
            for i, bound in enumerate(h["bounds"]):
                if seconds <= bound:
                    h["buckets"][i] += 1
                    break
            else:
                h["buckets"][-1] += 1

    def quantiles(self, name: str, qs=(0.5, 0.95, 0.99)
                  ) -> Dict[str, float]:
        """Approximate quantiles from the named histogram's buckets
        (linear interpolation inside the hit bucket, clamped to the
        observed min/max) — {"p50": ..., "p95": ..., "p99": ...}."""
        with self._lock:
            h = self._hists.get(name)
            if h is None or not h["count"]:
                return {}
            buckets = list(h["buckets"])
            bounds = list(h["bounds"])
            total, vmin, vmax = h["count"], h["min"], h["max"]
        out: Dict[str, float] = {}
        for q in qs:
            # nudge the rank target down by an epsilon: q*total lands
            # EXACTLY on a cumulative-bucket boundary whenever the
            # quantile value sits on a bucket bound (0.95*20 is
            # 19.000000000000004 in binary), and without the nudge the
            # walk would step past the bucket actually holding the value
            # and report from the NEXT one
            target = q * total - 1e-9
            cum = 0.0
            val = vmax
            for i, c in enumerate(buckets):
                if c and cum + c >= target:
                    lo = bounds[i - 1] if i > 0 else 0.0
                    hi = bounds[i] if i < len(bounds) else vmax
                    val = lo + (target - cum) / c * (hi - lo)
                    break
                cum += c
            out[f"p{int(q * 100)}"] = round(min(max(val, vmin), vmax), 6)
        return out

    def record(self, obj: Dict[str, Any]) -> None:
        """Append one structured record and stream it to the JSONL sink."""
        if not self.enabled:
            return
        with self._lock:
            if len(self._records) < _MAX_RECORDS:
                self._records.append(obj)
            if self._sink_path is not None:
                if self._sink_fh is None:
                    try:
                        self._sink_fh = open(self._sink_path, "a")
                    except OSError:
                        self._sink_path = None
                        return
                try:
                    self._sink_fh.write(json.dumps(obj) + "\n")
                    self._sink_fh.flush()
                except (OSError, TypeError, ValueError):
                    pass

    # -- introspection -----------------------------------------------------
    @property
    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._records)

    def tail(self, n: int, event: Optional[str] = None
             ) -> List[Dict[str, Any]]:
        """Last ``n`` records (optionally of one event type) without
        copying the whole buffer — per-iteration callbacks poll this."""
        with self._lock:
            if event is None:
                return list(self._records[-n:])
            out: List[Dict[str, Any]] = []
            for r in reversed(self._records):
                if r.get("event") == event:
                    out.append(r)
                    if len(out) == n:
                        break
            return out[::-1]

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            hists = {}
            for k, h in self._hists.items():
                mean = h["sum"] / h["count"] if h["count"] else 0.0
                # bounds + per-bucket counts ride along: any cumulative-
                # bucket exporter (the Prometheus text endpoint) needs
                # them, and the summary stats alone cannot rebuild them
                hists[k] = {"count": h["count"],
                            "sum_s": round(h["sum"], 6),
                            "mean_s": round(mean, 6),
                            "min_s": round(h["min"], 6),
                            "max_s": round(h["max"], 6),
                            "bounds": list(h["bounds"]),
                            "buckets": list(h["buckets"])}
            return {"counters": dict(self._counters),
                    "gauges": dict(self._gauges),
                    "histograms": hists,
                    "num_records": len(self._records)}


def host_rss_gb() -> float:
    """Host resident-set peak in GB (0.0 where /usr/bin getrusage missing)."""
    try:
        import resource
        return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                     / 2 ** 20, 4)
    except Exception:
        return 0.0


def device_hbm_bytes() -> Dict[str, int]:
    """The allocator's ``bytes_in_use`` and ``peak_bytes_in_use`` as
    ``{"hbm_in_use_bytes", "hbm_peak_bytes"}``, each the LARGEST over this
    process's devices (under a row mesh the fullest chip is not always the
    first), in bytes.  ``{}`` where the platform keeps no allocator
    statistics (XLA:CPU).  The one HBM reading of the package: the
    boundary records of a ship, a bind, a compile and a flag poll carry
    these two fields, and every GB figure below is a view of them."""
    import jax
    out: Dict[str, int] = {}
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        for field, key in (("hbm_in_use_bytes", "bytes_in_use"),
                           ("hbm_peak_bytes", "peak_bytes_in_use")):
            if key in stats:
                out[field] = max(out.get(field, 0), int(stats[key]))
    return out


def device_memory_gb() -> Dict[str, float]:
    """Peak device HBM (``peak_hbm_gb``) in GB of 1e9, the benchmark's
    unit, over the fullest local device.  XLA:CPU keeps no allocator
    stats, so the field is simply absent there; a TPU that reports none is
    an error, never a differently named estimate."""
    peak = device_hbm_bytes().get("hbm_peak_bytes")
    if peak is None:
        import jax
        dev = jax.local_devices()[0]
        if dev.platform == "tpu":
            raise RuntimeError(
                f"{dev.device_kind} reported no peak_bytes_in_use "
                f"(memory_stats keys: {sorted(dev.memory_stats() or {})})")
        return {}
    return {"peak_hbm_gb": round(peak / 1e9, 4)}


def memory_snapshot() -> Dict[str, float]:
    """Combined device + host memory fields for iteration records."""
    out = device_memory_gb()
    rss = host_rss_gb()
    if rss:
        out["host_rss_gb"] = rss
    return out


global_registry = MetricsRegistry()
