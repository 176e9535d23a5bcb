"""Recompile watchdog: count XLA traces per jitted entry point.

Retraces are the #1 silent TPU perf killer — a shape or dtype drift turns
a cached dispatch into a multi-second XLA compile in the middle of
training, with nothing in the logs. ``watched_jit`` wraps ``jax.jit`` so
every trace of the underlying function increments a per-entry counter
(tracing happens exactly once per compilation-cache miss; steady-state
dispatches go through jit's C++ fast path and never touch the wrapper),
and an entry that retraces beyond a configurable threshold logs a warning
carrying the offending argument shapes/dtypes.

Entries are identified by (name, owner): engine-owned jits pass their
engine instance as ``owner`` so a rebuild of the same logical entry point
(e.g. ``Booster.reset_parameter`` re-jitting the grower mid-training)
keeps counting against the same entry, while a fresh model's first
compile does not inherit another model's count. Module-level kernel jits
that legitimately re-specialize per shape (pallas kernels, ranking
buckets) pass ``warn_after=0`` to count without ever warning.

Every program the process compiles, or fetches from the persistent cache,
also leaves one ``Runtime::Compile`` record in the boundary-span ring
(:func:`_on_compile_event`): which watched entry asked for it (none: the
eager op-by-op set-up), which trace of that entry, whether the cache had
it, and how long tracing, lowering and the backend took.
"""
from __future__ import annotations

import functools
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

import jax

from ..utils.log import log_warning
from . import costmodel as _costmodel
from .metrics import device_hbm_bytes
from .tracer import global_tracer

_lock = threading.Lock()
# weak enumeration for summaries: an entry stays alive exactly as long as
# something can still trace it (the jitted closure and, for owned entries,
# the owner's `_telemetry_watches` dict hold the strong references), so a
# dead model's counters neither leak nor get inherited by an unrelated new
# model that happens to reuse its memory address
_entries: "weakref.WeakSet[WatchEntry]" = weakref.WeakSet()
_default_threshold = 2

# ---- dispatch / host-sync accounting (docs/OBSERVABILITY.md) ----
# `launches` counts every dispatch of a watched_jit entry point (one XLA
# program execution request); `host_syncs` counts device->host transfers
# noted by the engine (device_get, blocking flag reads).  Both are plain
# int increments on the dispatch path — the GIL makes the += effectively
# atomic and the cost (~100 ns) vanishes against any real launch.  The
# straggler report derives launches/iter and host_syncs/iter from window
# diffs, which is what lets `bottleneck:` tell a dispatch-bound loop from
# a link-bound one.
_launches = 0
_host_syncs = 0
# `hist_passes` is counted ON THE DEVICE: the fused iteration's state
# carries an int32 the grower increments once per histogram-building pass
# over the rows (root pass + every budget round; the route-only last round
# is not one), and the engine publishes it here from the batched flag
# fetch it already makes — so the count is as of `_hist_pass_iteration`,
# up to eval_fetch_freq - 1 trees behind the device.
_hist_passes = 0
_hist_small_passes = 0      # those of them that took the small-slot pass
_scan_slots = 0             # pairs the rounds' split scans ran over
_hist_comm_rounds = 0       # passes that reduced their histogram over a mesh
_hist_comm_bytes = 0        # payload a device materialised in them
_hist_pass_iteration = 0


def launch_count() -> int:
    """Cumulative watched_jit dispatches in this process."""
    return _launches


def host_sync_count() -> int:
    """Cumulative engine-noted device->host transfers."""
    return _host_syncs


def hist_pass_count() -> int:
    """Cumulative histogram passes grown by fused iterations in this
    process, as of the last flag poll (:func:`hist_pass_iteration`)."""
    return _hist_passes


def hist_small_pass_count() -> int:
    """Those of :func:`hist_pass_count` that took the stream kernel's
    small-slot pass (rounds that split one or two leaves)."""
    return _hist_small_passes


def scan_slot_count() -> int:
    """Cumulative pairs (a split leaf and its new sibling) the histogram
    rounds' subtraction and child split scan ran over, as of the same poll:
    whole chunks of ops/grow.py ``tail_chunk`` where a round adapts to its
    own split count, the round's budget elsewhere."""
    return _scan_slots


def hist_comm_counts() -> tuple:
    """(rounds, bytes): the histogram passes that reduced across a mesh
    and the reduced payload one device materialised in them
    (parallel/comms.py ``hist_comms_bytes_per_round`` a pass, a limb), as
    of the same poll: the engine's arithmetic on :func:`hist_pass_count`,
    not a count of the device's own; (0, 0) on one device."""
    return _hist_comm_rounds, _hist_comm_bytes


def hist_pass_iteration() -> int:
    """The boosting iteration at which :func:`hist_pass_count` was last
    read off the device."""
    return _hist_pass_iteration


def note_hist_passes(n: int, iteration: int, small: int = 0,
                     scan_slots: int = 0, comm_rounds: int = 0,
                     comm_bytes: int = 0) -> None:
    """Add ``n`` passes, ``small`` of them small-slot ones, and the
    ``scan_slots`` their rounds scanned, read off the device at
    ``iteration`` (the engine's flag poll calls this with the deltas since
    its last poll), and the ``comm_rounds`` of them that reduced across a
    mesh with the ``comm_bytes`` that delivered to a device."""
    global _hist_passes, _hist_small_passes, _scan_slots, \
        _hist_comm_rounds, _hist_comm_bytes, _hist_pass_iteration
    _hist_passes += n
    _hist_small_passes += small
    _scan_slots += scan_slots
    _hist_comm_rounds += comm_rounds
    _hist_comm_bytes += comm_bytes
    _hist_pass_iteration = iteration


def note_host_sync(n: int = 1) -> None:
    """Record ``n`` device->host transfers (called at the engine's
    sanctioned readback sites — the batched flag fetch, score pulls)."""
    global _host_syncs
    _host_syncs += n


def note_launch(n: int = 1) -> None:
    """Record ``n`` dispatches issued OUTSIDE watched_jit — the engine
    notes its known eager op groups (each eager jnp op on device arrays
    is one XLA execution) with conservative lower-bound counts, so the
    launches/iter figure stays comparable between the fused one-launch
    path and the eager pipeline it replaces."""
    global _launches
    _launches += n


def reset_counters() -> None:
    """Zero the module-global ``launches``/``host_syncs`` dispatch
    counters.  Per-entry compile counters have :func:`reset_watchdog`;
    this is the A/B counterpart for the globals — bench arms call it at
    the start of each timed arm so launches/iter and host_syncs/iter are
    attributable to THAT arm, not contaminated by the previous one."""
    global _launches, _host_syncs, _hist_passes, _hist_small_passes, \
        _scan_slots, _hist_comm_rounds, _hist_comm_bytes, \
        _hist_pass_iteration
    _launches = 0
    _host_syncs = 0
    _hist_passes = 0
    _hist_small_passes = 0
    _scan_slots = 0
    _hist_comm_rounds = 0
    _hist_comm_bytes = 0
    _hist_pass_iteration = 0


# ---- Runtime::Compile: one ring record a compiled or fetched program ----
# What jax 0.9.0 reports round a compile, on the compiling thread, through
# `dispatch.log_elapsed_time` (jax/_src/pjit.py, interpreters/pxla.py,
# compiler.py): a scalar (the start stamp) when tracing, lowering to MLIR
# or the backend compile BEGINS and a duration when it ends.  Tracing nests
# (a jit called inside a traced body is traced inside it and ends first),
# helpers are traced DURING lowering (Pallas bodies, lowering rules), and
# the backend compile encloses the persistent cache's read: on a cache hit
# the retrieval's duration fires inside it, so the backend's duration is
# the last event on a hit and on a miss alike and closes the record.  A
# program found in jit's in-memory caches fires none.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILE_SPAN = "Runtime::Compile"


class _Compiling(threading.local):
    """One thread's compile in progress, between jax's events."""
    open_traces = 0      # jit tracings begun and not ended (they nest)
    lowering = False     # inside a lowering to MLIR
    tracing = 0          # watched entries whose Python body is being traced
    entry: Optional["WatchEntry"] = None   # who asked for the next program
    armed = False        # `entry` was set since the last outermost trace
    trace_ns = 0
    lower_ns = 0
    retrieval_ns: Optional[int] = None     # set by a persistent-cache hit

    def clear(self) -> None:
        self.entry, self.armed = None, False
        self.trace_ns, self.lower_ns, self.retrieval_ns = 0, 0, None

    def traced(self, entry: Optional["WatchEntry"]) -> None:
        """A watched entry's body has been traced (``None``: it raised).
        The program is the outermost jit's: an entry traced inside another
        entry, or inside an unwatched jit (more than its own tracing is
        open), is inlined there, and a program compiled while a body is
        still being traced is an eager one of the trace's own."""
        self.tracing -= 1
        if not self.tracing:
            owns = entry is not None and self.open_traces == 1
            self.entry, self.armed = (entry, True) if owns else (None, False)

    def expect(self, entry: "WatchEntry", timing: Tuple[int, int]) -> None:
        """The next backend compile on this thread is ``entry``'s (the AOT
        path: its ``.lower()`` may be long past)."""
        self.clear()
        self.entry = entry
        self.trace_ns, self.lower_ns = timing

    def take_timing(self, traced: bool) -> Tuple[int, int]:
        """(trace_ns, lower_ns) of the ``.lower()`` that just returned."""
        timing = (self.trace_ns if traced else 0, self.lower_ns)
        self.clear()
        return timing


_compiling = _Compiling()


def _on_compile_start(event: str, value: float, **_: Any) -> None:
    """Scalar listener: the start stamps, which alone tell what nests."""
    if event == _TRACE_EVENT:
        _compiling.open_traces += 1
    elif event == _LOWER_EVENT:
        _compiling.lowering = True


def _on_compile_event(event: str, duration: float, **_: Any) -> None:
    """Duration listener: the ends; the backend's closes the record."""
    st = _compiling
    if event == _TRACE_EVENT:
        st.open_traces = max(st.open_traces - 1, 0)
        if st.open_traces or st.lowering:
            return           # a jit inside the one traced, or a helper
        # an entry survives its own outermost trace and no other: a second
        # one is another program's (the first was traced and not compiled)
        if not st.armed:
            st.entry = None
        st.armed = False
        st.trace_ns, st.lower_ns = int(duration * 1e9), 0
        st.retrieval_ns = None
    elif event == _LOWER_EVENT:
        st.lowering = False
        st.lower_ns = int(duration * 1e9)
    elif event == _CACHE_HIT_EVENT:
        st.retrieval_ns = int(duration * 1e9)
    elif event == _BACKEND_EVENT:
        entry = None if st.tracing else st.entry
        hit = st.retrieval_ns is not None
        took = st.retrieval_ns if hit else int(duration * 1e9)
        args: Dict[str, Any] = {
            "entry": entry.name if entry else None,
            "trace": entry.count if entry else None,
            "cache": "hit" if hit else "miss",
            "trace_ns": st.trace_ns, "lower_ns": st.lower_ns,
            **device_hbm_bytes()}
        if entry is not None and entry.count > 1 and entry.signatures:
            args["signature"] = entry.signatures[-1]
        st.clear()
        stack = global_tracer._stack()
        # retroactive, so no TraceAnnotation: the ring record alone
        global_tracer._record(COMPILE_SPAN, stack[-1] if stack else None,
                              time.time_ns() - took, took, args)


def _listen_for_compiles() -> None:
    """Register the two listeners once: when the package is imported, so
    that the eager programs of a Dataset's set-up are on record before the
    first entry exists, and again at a ``watched_jit`` if something cleared
    jax's listeners since."""
    from jax._src import monitoring
    if _on_compile_event not in monitoring.get_event_duration_listeners():
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)
        jax.monitoring.register_scalar_listener(_on_compile_start)


_listen_for_compiles()


class WatchEntry:
    """Compile counter for one watched entry point."""

    def __init__(self, name: str, warn_after: Optional[int]) -> None:
        self.name = name
        self.warn_after = warn_after   # None = use the global threshold
        self.count = 0
        self.signatures: List[str] = []   # last few trace signatures
        self.warned = 0
        # trace count already cost-captured (telemetry/costmodel.py);
        # count > cost_seen means a fresh compile awaits capture
        self.cost_seen = 0

    def effective_threshold(self) -> int:
        return _default_threshold if self.warn_after is None else self.warn_after

    def note_trace(self, args: tuple, kwargs: dict) -> None:
        sig = _signature(args, kwargs)
        with _lock:
            self.count += 1
            self.signatures.append(sig)
            if len(self.signatures) > 4:
                del self.signatures[0]
            count = self.count
            prev = self.signatures[-2] if len(self.signatures) >= 2 else None
        thr = self.effective_threshold()
        if thr > 0 and count > thr:
            with _lock:
                self.warned += 1
            msg = (f"telemetry: {self.name!r} recompiled (trace #{count}, "
                   f"threshold {thr}) — mid-training retraces stall the "
                   f"device for the full XLA compile; new signature {sig}")
            if prev is not None and prev != sig:
                msg += f"; previous signature {prev}"
            log_warning(msg)
            global_tracer.instant(f"recompile:{self.name}", count=count,
                                  signature=sig)
        from .metrics import global_registry
        global_registry.inc(f"recompile/{self.name}")


def _abbrev(x: Any) -> str:
    aval = getattr(x, "aval", None)
    if aval is not None and hasattr(aval, "shape"):
        return f"{getattr(aval.dtype, 'name', aval.dtype)}{list(aval.shape)}"
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return f"{getattr(x.dtype, 'name', x.dtype)}{list(x.shape)}"
    r = repr(x)
    return r if len(r) <= 24 else r[:21] + "..."


def _signature(args: tuple, kwargs: dict) -> str:
    try:
        leaves = jax.tree_util.tree_leaves((args, kwargs))
        return "(" + ", ".join(_abbrev(v) for v in leaves[:24]) + \
            (", ..." if len(leaves) > 24 else "") + ")"
    except Exception:
        return "(?)"


def set_recompile_threshold(n: int) -> None:
    """Global warn threshold: entries warn on trace count > n (0 = never)."""
    global _default_threshold
    _default_threshold = int(n)


def get_recompile_threshold() -> int:
    return _default_threshold


def watched_jit(fun=None, *, name: Optional[str] = None, owner: Any = None,
                warn_after: Optional[int] = None, **jit_kwargs):
    """``jax.jit`` with per-entry-point compile counting.

    Usable directly (``watched_jit(f, name=...)``) or as a decorator
    factory (``@watched_jit(name=..., static_argnames=...)``). ``owner``
    scopes the counter: passing the same (name, owner) pair again — e.g.
    when an engine re-jits one of its entry points — reuses the counter,
    which is exactly what turns a parameter-reset retrace into a warning.
    """
    def wrap(f):
        wname = name or getattr(f, "__name__", "jit_fn")
        entry = None
        if owner is not None:
            watches = owner.__dict__.setdefault("_telemetry_watches", {})
            entry = watches.get(wname)
        if entry is None:
            entry = WatchEntry(wname, warn_after)
            if owner is not None:
                watches[wname] = entry
        with _lock:
            _entries.add(entry)
        _listen_for_compiles()

        @functools.wraps(f)
        def traced(*args, **kwargs):
            # runs ONLY while jax traces (i.e. on a compilation-cache miss)
            entry.note_trace(args, kwargs)
            _compiling.tracing += 1
            try:
                out = f(*args, **kwargs)
            except BaseException:
                _compiling.traced(None)
                raise
            _compiling.traced(entry)
            return out

        jitted = jax.jit(traced, **jit_kwargs)

        @functools.wraps(f)
        def dispatched(*args, **kwargs):
            # one extra Python frame per dispatch buys the launches counter
            # (straggler `bottleneck: dispatch` classification); the jit's
            # C++ fast path still runs inside
            global _launches
            _launches += 1
            out = jitted(*args, **kwargs)
            if _costmodel.active():
                _costmodel.after_dispatch(entry, jitted, args, kwargs)
            return out

        dispatched._telemetry_watch = entry
        dispatched._jitted = jitted
        # forward the jit AOT/introspection surface the wrapper would
        # otherwise hide — with the compile/execute path WATCHED: a
        # `.lower(...).compile()` entry compile counts against the same
        # entry (and feeds the cost model), and calls on the compiled
        # executable count as launches, so the AOT surface cannot bypass
        # the recompile/dispatch accounting
        def lower(*args, **kwargs):
            c0 = entry.count
            lowered = jitted.lower(*args, **kwargs)
            # a jaxpr-cache miss runs `traced` during lower and already
            # counted; the wrapper must then NOT count the .compile() too
            counted = entry.count > c0
            return _WatchedLowered(lowered, entry, args, kwargs,
                                   counted=counted,
                                   timing=_compiling.take_timing(counted))

        dispatched.lower = lower
        for attr in ("trace", "eval_shape", "clear_cache"):
            bound = getattr(jitted, attr, None)
            if bound is not None:
                setattr(dispatched, attr, bound)
        return dispatched

    return wrap if fun is None else wrap(fun)


class _WatchedLowered:
    """Forwarded ``.lower(...)`` result whose ``.compile()`` stays on the
    books: the AOT entry compile increments the entry's trace counter
    (``recompile/<name>`` included) and hands the compiled executable to
    the cost model — the full analysis for free, since the caller paid
    for the compile anyway."""

    __slots__ = ("_lowered", "_entry", "_args", "_kwargs", "_counted",
                 "_timing")

    def __init__(self, lowered, entry: WatchEntry, args: tuple,
                 kwargs: dict, counted: bool = False,
                 timing: Tuple[int, int] = (0, 0)) -> None:
        self._lowered = lowered
        self._entry = entry
        self._args = args
        self._kwargs = kwargs
        self._counted = counted
        self._timing = timing      # (trace_ns, lower_ns) of the .lower()

    def compile(self, *args, **kwargs):
        if not self._counted:
            # lower() hit the jaxpr cache, so nothing counted this entry
            # compile yet — an AOT compile of an already-traced signature
            # is still a real XLA compile (counted before it, so that its
            # Runtime::Compile record holds this trace's number)
            self._entry.note_trace(self._args, self._kwargs)
        self._counted = False   # a second .compile() of this Lowered counts
        _compiling.expect(self._entry, self._timing)
        try:
            compiled = self._lowered.compile(*args, **kwargs)
        finally:
            # jax found the executable in memory, or the compile raised:
            # no event came, and the next program is not this entry's
            _compiling.clear()
        _costmodel.note_compiled(self._entry, compiled)
        return _WatchedCompiled(compiled, self._entry)

    def __getattr__(self, name):
        return getattr(self._lowered, name)


class _WatchedCompiled:
    """AOT executable wrapper: every call is one XLA program execution,
    so it lands in the ``launches`` counter like a jit dispatch."""

    __slots__ = ("_compiled", "_entry")

    def __init__(self, compiled, entry: WatchEntry) -> None:
        self._compiled = compiled
        self._entry = entry

    def __call__(self, *args, **kwargs):
        global _launches
        _launches += 1
        out = self._compiled(*args, **kwargs)
        if _costmodel.active():
            _costmodel.note_dispatch(self._entry)
        return out

    def __getattr__(self, name):
        return getattr(self._compiled, name)


def recompile_counts() -> Dict[str, int]:
    """Aggregate trace counts by entry-point name (live entries; the
    metrics registry's ``recompile/<name>`` counters are cumulative)."""
    out: Dict[str, int] = {}
    with _lock:
        for entry in _entries:
            out[entry.name] = out.get(entry.name, 0) + entry.count
    return out


def watchdog_summary() -> Dict[str, Any]:
    """Per-name {entries, compiles, max_per_entry, warned} rollup."""
    out: Dict[str, Dict[str, int]] = {}
    with _lock:
        for entry in _entries:
            s = out.setdefault(entry.name, {"entries": 0, "compiles": 0,
                                            "max_per_entry": 0, "warned": 0})
            s["entries"] += 1
            s["compiles"] += entry.count
            s["max_per_entry"] = max(s["max_per_entry"], entry.count)
            s["warned"] += entry.warned
    return out


def reset_watchdog() -> None:
    """Zero every live entry's counters. Entries stay registered — the
    module-level kernel jits were wrapped once at import and can never
    re-register, so clearing the set would blind the watchdog to them."""
    with _lock:
        for entry in _entries:
            entry.count = 0
            entry.signatures = []
            entry.warned = 0
            entry.cost_seen = 0
