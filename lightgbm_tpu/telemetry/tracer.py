"""Nested span tracer with Chrome/Perfetto trace-event export.

The host-side analog of the reference's ``Common::Timer``/``FunctionTimer``
RAII scopes (include/LightGBM/utils/common.h:980) — but structured: spans
nest, carry attributes, and export to the Chrome trace-event JSON format
(the ``chrome://tracing`` / https://ui.perfetto.dev schema), so a training
run can be inspected on the same timeline tooling used for device profiles.

Design constraints:
  * zero overhead when disabled — ``span()`` returns one shared no-op
    context manager behind a single boolean check, allocating nothing;
  * thread-safe — events append under a lock, nesting is tracked per
    thread (trace-event "B"/"E" pairs nest per ``tid`` by construction);
  * bounded — the event buffer is capped; overflow increments a drop
    counter instead of growing without limit.

Layer boundaries (:meth:`SpanTracer.boundary`) are the one exception to
"off unless enabled": a boundary span is always live.  It enters a
``jax.profiler.TraceAnnotation`` (``lgbtpu.<name>``), so inside ANY
profiler session the span lands in the host plane of the same
``.xplane.pb`` as the device's operations — one clock, nothing to align —
and on exit it appends one :class:`SpanRecord` to a bounded in-process
ring (:meth:`SpanTracer.recent_spans`, the flight recorder an operator
reads after a stall).  With telemetry enabled it also emits the Chrome
"B"/"E" pair exactly as :meth:`SpanTracer.span` does.
"""
from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional

import jax

# Chrome trace-event phases used here: B/E = nested begin/end duration
# events, C = counter track, i = instant event, M = metadata.
_MAX_EVENTS = int(os.environ.get("LIGHTGBM_TPU_TRACE_MAX_EVENTS", 2_000_000))
# boundary-span ring: hours of training (3 records a tree) or thousands of
# predict calls (8 records a call) at ~200 bytes a record
_RING_SIZE = 16384
# prefix of every boundary span in a profiler trace (the benchmark's own
# spans are `bench.*`)
ANNOTATION_PREFIX = "lgbtpu."


class _NullSpan:
    """Shared no-op context manager for the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span: records a "B" event on enter and an "E" on exit."""

    __slots__ = ("_tracer", "_name", "_args", "_t0")

    def __init__(self, tracer: "SpanTracer", name: str, args: Optional[dict]):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._tracer._emit("B", self._name, self._t0, self._args)
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._tracer._emit("E", self._name, t1, None)
        self._tracer._account(self._name, t1 - self._t0)
        return False


class SpanRecord(NamedTuple):
    """One finished boundary span in the ring.  ``start_unix_ns`` is
    ``time.time_ns()`` at entry (the domain of any wall-clock stamp a
    caller holds); ``duration_ns`` is a ``perf_counter_ns`` difference;
    ``parent`` is the boundary span that was open on the same thread."""
    seq: int
    name: str
    parent: Optional[str]
    start_unix_ns: int
    duration_ns: int
    args: Optional[Dict[str, Any]]


class _Boundary:
    """One live boundary span (see :meth:`SpanTracer.boundary`)."""

    __slots__ = ("_tracer", "_name", "_args", "_ann", "_parent", "_start",
                 "_t0")

    def __init__(self, tracer: "SpanTracer", name: str, args: dict,
                 step: bool) -> None:
        self._tracer = tracer
        self._name = name
        self._args = args
        ann = (jax.profiler.StepTraceAnnotation if step
               else jax.profiler.TraceAnnotation)
        self._ann = ann(ANNOTATION_PREFIX + name, **args)

    def set(self, **args: Any) -> None:
        """Attributes known only at the end (which path a predict took,
        what a poll read): they go to the ring record and the Chrome "E"
        event, not to the profiler annotation, whose arguments are fixed
        on entry."""
        self._args = {**self._args, **args}

    def __enter__(self):
        tr = self._tracer
        stack = tr._stack()
        self._parent = stack[-1] if stack else None
        stack.append(self._name)
        self._ann.__enter__()
        if tr.enabled:
            tr._emit("B", self._name, time.perf_counter(),
                     self._args or None)
        self._start = time.time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self._t0
        tr = self._tracer
        if tr.enabled:
            tr._emit("E", self._name, time.perf_counter(),
                     self._args or None)
        self._ann.__exit__(*exc)
        tr._stack().pop()
        tr._record(self._name, self._parent, self._start, dur,
                   self._args or None)
        return False


class SpanTracer:
    """Nested, thread-safe span recorder (low-overhead when disabled)."""

    def __init__(self) -> None:
        self.enabled = False
        self._lock = threading.Lock()
        self._events: List[Dict[str, Any]] = []
        self._dropped = 0
        self._drop_warned = False
        # wall-clock anchor: the unix time captured at the SAME instant as
        # the perf_counter epoch — perf_counter is monotonic but has an
        # arbitrary per-process zero, so two processes' traces can only be
        # merged onto one timeline through this pairing (the collector,
        # telemetry/collect.py, aligns shards on it)
        self._epoch = time.perf_counter()
        self._epoch_unix = time.time()
        self._phase_totals: Dict[str, float] = {}
        self._phase_counts: Dict[str, int] = {}
        self._local = threading.local()
        # boundary-span ring (always on): the newest _RING_SIZE records
        self._ring: "deque[SpanRecord]" = deque(maxlen=_RING_SIZE)
        self._seq = 0

    # -- control -----------------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._events = []
            self._dropped = 0
            self._drop_warned = False
            # re-anchor: both halves of the clock pairing move together
            self._epoch = time.perf_counter()
            self._epoch_unix = time.time()
            self._phase_totals = {}
            self._phase_counts = {}
            self._ring.clear()
            self._seq = 0

    # -- recording ---------------------------------------------------------
    def span(self, name: str, **args: Any):
        """Context manager for a traced region; no-op when disabled.

        The disabled path is a single boolean check returning a shared
        object — safe to leave in hot loops."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args or None)

    def boundary(self, name: str, step: bool = False, **args: Any):
        """Context manager for a LAYER BOUNDARY: always live, whatever
        ``enabled`` says (module docstring).  ``step=True`` enters a
        ``StepTraceAnnotation`` (pass ``step_num=``), which gives a
        profiler trace its Steps line.  Costs about 2 us a span outside
        a profiler session; it belongs round a dispatch, a blocking read
        or a host phase — never inside a jitted function, a kernel or a
        per-row loop."""
        return _Boundary(self, name, args, step)

    def _stack(self) -> List[str]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _record(self, name: str, parent: Optional[str], start_unix_ns: int,
                duration_ns: int, args: Optional[dict]) -> None:
        with self._lock:
            self._ring.append(SpanRecord(self._seq, name, parent,
                                         start_unix_ns, duration_ns, args))
            self._seq += 1
            self._account_locked(name, duration_ns / 1e9)

    def recent_spans(self, name: Optional[str] = None,
                     since_unix_ns: Optional[int] = None
                     ) -> List[SpanRecord]:
        """The ring's records, oldest first, optionally of one span name
        and/or started at or after a ``time.time_ns()`` stamp."""
        with self._lock:
            records = list(self._ring)
        return [r for r in records
                if (name is None or r.name == name)
                and (since_unix_ns is None
                     or r.start_unix_ns >= since_unix_ns)]

    @property
    def ring_overwritten(self) -> int:
        """Boundary records the bounded ring has overwritten (``seq`` of
        the oldest record it still holds)."""
        with self._lock:
            return self._seq - len(self._ring)

    def instant(self, name: str, **args: Any) -> None:
        """Point-in-time marker (watchdog warnings, stop events, ...)."""
        if not self.enabled:
            return
        self._emit("i", name, time.perf_counter(), args or None,
                   extra={"s": "t"})

    def counter(self, name: str, **values: float) -> None:
        """Counter-track sample: renders as a stacked area in Perfetto."""
        if not self.enabled:
            return
        self._emit("C", name, time.perf_counter(),
                   {k: float(v) for k, v in values.items()})

    def complete(self, name: str, start: float, duration: float,
                 **args: Any) -> None:
        """One finished span as a single "X" (complete) event.

        ``start`` is an absolute ``time.perf_counter`` point and
        ``duration`` is in seconds.  Unlike :meth:`span`, the begin and
        end may have happened on DIFFERENT threads (a request enqueued by
        an HTTP handler and dispatched by the batcher worker) — the event
        is attributed to the emitting thread's track."""
        if not self.enabled:
            return
        self._emit("X", name, start, args or None,
                   extra={"dur": max(duration, 0.0) * 1e6})
        self._account(name, duration)

    def _emit(self, ph: str, name: str, t: float, args: Optional[dict],
              extra: Optional[dict] = None) -> None:
        ev: Dict[str, Any] = {
            "name": name, "ph": ph, "pid": os.getpid(),
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "ts": (t - self._epoch) * 1e6,
        }
        if args:
            ev["args"] = args
        if extra:
            ev.update(extra)
        with self._lock:
            if len(self._events) < _MAX_EVENTS:
                self._events.append(ev)
                return
            self._dropped += 1
            warn = not self._drop_warned
            self._drop_warned = True
        if warn:
            from ..utils.log import log_warning
            log_warning(
                f"telemetry: trace event buffer full ({_MAX_EVENTS} "
                "events) — further spans are DROPPED, not recorded "
                "(raise LIGHTGBM_TPU_TRACE_MAX_EVENTS or lower "
                "serve_trace_sample); the drop count is in "
                "telemetry_summary()['trace_dropped_events']")

    def _account(self, name: str, dt: float) -> None:
        with self._lock:
            self._account_locked(name, dt)

    def _account_locked(self, name: str, dt: float) -> None:
        self._phase_totals[name] = self._phase_totals.get(name, 0.0) + dt
        self._phase_counts[name] = self._phase_counts.get(name, 0) + 1

    # -- introspection -----------------------------------------------------
    @property
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    @property
    def dropped(self) -> int:
        """Events dropped after the bounded buffer filled."""
        with self._lock:
            return self._dropped

    def clock_sync(self) -> Dict[str, Any]:
        """The wall-clock anchor record: ``unix_time_s`` is the
        ``time.time()`` captured at the same instant the ``perf_counter``
        epoch (event ``ts`` zero-point) was taken, plus the process
        identity a multi-process merge needs."""
        with self._lock:
            anchor = {"unix_time_s": self._epoch_unix,
                      "perf_epoch_s": self._epoch}
        anchor["pid"] = os.getpid()
        rank = os.environ.get("LGBTPU_REPLICA_RANK")
        if rank is not None:
            try:
                anchor["replica_rank"] = int(rank)
            except ValueError:
                pass
        return anchor

    def phase_snapshot(self) -> Dict[str, float]:
        """Copy of cumulative per-span-name wall totals (seconds)."""
        with self._lock:
            return dict(self._phase_totals)

    def phase_counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._phase_counts)

    # -- export ------------------------------------------------------------
    def export_trace(self, path: str) -> str:
        """Write the collected events as Chrome trace-event JSON.

        The output object is the standard ``{"traceEvents": [...]}``
        envelope (plus process/thread metadata), loadable directly in
        Perfetto or chrome://tracing."""
        with self._lock:
            events = list(self._events)
            dropped = self._dropped
        anchor = self.clock_sync()
        rank = anchor.get("replica_rank")
        proc_name = ("lightgbm_tpu host" if rank is None
                     else f"lightgbm_tpu replica {rank}")
        meta: List[Dict[str, Any]] = [
            {"name": "process_name", "ph": "M", "pid": os.getpid(),
             "tid": 0, "args": {"name": proc_name}},
            # wall-clock anchor as a metadata event at ts 0: every ts in
            # this file is relative to anchor.unix_time_s, which is what
            # lets the collector align shards from different processes
            {"name": "clock_sync", "ph": "M", "pid": os.getpid(),
             "tid": 0, "ts": 0.0, "args": anchor},
        ]
        for tid in sorted({e["tid"] for e in events}):
            meta.append({"name": "thread_name", "ph": "M",
                         "pid": os.getpid(), "tid": tid,
                         "args": {"name": f"host-thread-{tid}"}})
        blob = {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"producer": "lightgbm_tpu.telemetry",
                          "dropped_events": dropped,
                          "clock_sync": anchor},
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            # default=str: span attributes are user-supplied (numpy scalars,
            # paths, ...) and must never make the end-of-run export raise
            json.dump(blob, fh, default=str)
        os.replace(tmp, path)
        return path


global_tracer = SpanTracer()
