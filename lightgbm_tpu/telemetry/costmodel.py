"""Compiled-program cost model: XLA flops/HBM accounting + roofline verdicts.

The watchdog (:mod:`.watchdog`) can say *that* an entry point compiled and
*how often* it dispatches; this module says *what each dispatch costs*.
At every compilation-cache miss of a ``watched_jit`` entry it captures the
XLA executable's own accounting:

  * ``Lowered.cost_analysis()`` — flops, transcendentals, bytes accessed
    (cheap: the jaxpr trace is cached, lowering is ~1 ms, no XLA compile);
  * ``Compiled.cost_analysis()`` + ``Compiled.memory_analysis()`` —
    optimized-HLO cost plus argument/output/temp buffer sizes whose sum is
    the program's peak HBM footprint (``full`` mode only: the AOT
    ``.compile()`` is a SECOND XLA compile of the entry).

From flops and bytes it derives the arithmetic intensity (flops/byte) and
a roofline verdict against the device's machine balance — ``compute-bound``
when the intensity clears the ridge point (peak_flops / peak_HBM_bandwidth),
``hbm-bound`` below it — so an s/tree regression is attributable: did the
program get more flops, more bytes, or neither (dispatch/comms)?

Dispatch-weighted totals feed the per-iteration training record
(``flops`` / ``hbm_bytes`` fields, docs/OBSERVABILITY.md) and the
``cost/<name>/*`` gauge family on ``/metrics``; ``cost_summary()`` is the
rollup in ``telemetry_summary()["cost"]`` and ``/stats``.

Degradation contract: on backends where cost/memory analysis raises or
returns nothing (older jaxlib, exotic plugins) the entry is recorded as
``available: false`` with ``verdict: "unavailable"`` — never a zero that a
budget gate (scripts/perf_sentinel.py) could mistake for a 100%
improvement.

Modes (param ``telemetry_cost``, env ``LGBTPU_COST`` overrides):
``auto``/``lowered`` capture from the lowered module whenever telemetry is
on; ``full`` additionally AOT-compiles for the memory analysis; ``off``
disables capture.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional, Tuple

_VALID_MODES = ("auto", "off", "lowered", "full")

_lock = threading.Lock()
_enabled = False            # master switch (follows telemetry.configure)
_mode = "auto"              # configured mode (param); env wins at resolve
_resolved = "off"           # effective mode after the env override
_records: Dict[str, Dict[str, Any]] = {}     # entry name -> latest record
_flops_total = 0.0          # dispatch-weighted running totals
_bytes_total = 0.0
_balance: Optional[Dict[str, Any]] = None    # cached machine balance

# Published per-chip peaks by device kind: (bf16 flop/s, int8 op/s or None
# where this table has no sourced figure, HBM bytes/s).  The roofline ridge
# is bf16 peak / bandwidth; the int8 peak is what the quantized stream
# kernel's one-hot contraction runs against.  Matched by prefix on jax's
# ``device_kind`` (a v5e reports "TPU v5 lite").  Source for v5e: Google
# Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 393 TOP/s int8, 819
# GB/s HBM; the other rows are the same pages' per-chip bf16/HBM figures.
# A TPU kind that is not listed is an error, not a default
# (LGBTPU_PEAK_FLOPS + LGBTPU_PEAK_BW together stand in for a new part).
_DEVICE_PEAKS = {
    "TPU v2": (45e12, None, 700e9),
    "TPU v3": (123e12, None, 900e9),
    "TPU v4": (275e12, None, 1228e9),
    "TPU v5 lite": (197e12, 393e12, 819e9),
    "TPU v5e": (197e12, 393e12, 819e9),
    "TPU v5p": (459e12, None, 2765e9),
    "TPU v6": (918e12, None, 1640e9),
}
# conservative single-socket CPU estimate (AVX fma) — the exact numbers
# matter less than a stable ridge so CPU verdicts are deterministic
_CPU_DEFAULT = (5e11, None, 5e10)
_GENERIC_DEFAULT = (1e13, None, 1e12)   # non-TPU accelerators only


# -- control ----------------------------------------------------------------
def configure(enabled: Optional[bool] = None,
              mode: Optional[str] = None) -> None:
    """Set the capture switch and/or mode; re-resolves the env override."""
    global _enabled, _mode, _resolved
    with _lock:
        if enabled is not None:
            _enabled = bool(enabled)
        if mode is not None:
            m = str(mode).strip().lower()
            if m not in _VALID_MODES:
                raise ValueError(
                    f"telemetry_cost={mode!r} is not one of "
                    f"{', '.join(_VALID_MODES)}")
            _mode = m
        env = os.environ.get("LGBTPU_COST", "").strip().lower()
        eff = env if env in _VALID_MODES else _mode
        if eff == "auto":
            eff = "lowered"
        _resolved = eff if _enabled and eff != "off" else "off"


def set_enabled(on: bool) -> None:
    configure(enabled=on)


def active() -> bool:
    """Fast hot-path check: is capture on right now?"""
    return _resolved != "off"


def mode() -> str:
    """Effective capture mode ("off" | "lowered" | "full")."""
    return _resolved


def reset() -> None:
    """Drop captured records and dispatch-weighted totals (keeps the
    enabled state and mode — a new Booster's telemetry reset)."""
    global _flops_total, _bytes_total
    with _lock:
        _records.clear()
        _flops_total = 0.0
        _bytes_total = 0.0


# -- roofline ---------------------------------------------------------------
def machine_balance() -> Dict[str, Any]:
    """Peak flops, HBM bandwidth, and the roofline ridge intensity for
    device 0 (cached; env LGBTPU_PEAK_FLOPS/LGBTPU_PEAK_BW override)."""
    global _balance
    if _balance is not None:
        return dict(_balance)
    import jax
    dev = jax.local_devices()[0]
    kind, platform = str(dev.device_kind), str(dev.platform)
    env_flops = os.environ.get("LGBTPU_PEAK_FLOPS")
    env_bw = os.environ.get("LGBTPU_PEAK_BW")
    peaks = None
    for prefix, row in _DEVICE_PEAKS.items():
        if kind.lower().startswith(prefix.lower()):
            peaks = row
            break
    if peaks is None:
        if platform == "tpu" and not (env_flops and env_bw):
            raise ValueError(
                f"no published peaks for TPU device_kind {kind!r} in "
                "telemetry/costmodel._DEVICE_PEAKS — add the row with its "
                "source (or set LGBTPU_PEAK_FLOPS and LGBTPU_PEAK_BW)")
        peaks = _CPU_DEFAULT if platform == "cpu" else _GENERIC_DEFAULT
    peak_flops, peak_int8, peak_bw = peaks
    try:
        peak_flops = float(env_flops or peak_flops)
        peak_bw = float(env_bw or peak_bw)
    except ValueError:
        pass
    _balance = {
        "device_kind": kind,
        "platform": platform,
        "peak_flops_per_s": peak_flops,
        "peak_int8_ops_per_s": peak_int8,
        "peak_hbm_bytes_per_s": peak_bw,
        "ridge_intensity": round(peak_flops / max(peak_bw, 1.0), 3),
    }
    return dict(_balance)


def roofline_verdict(flops: float, bytes_accessed: float) -> Dict[str, Any]:
    """Classify one program against the device roofline."""
    if bytes_accessed <= 0.0:
        return {"intensity": None, "verdict": "unavailable"}
    bal = machine_balance()
    intensity = flops / bytes_accessed
    verdict = ("compute-bound" if intensity >= bal["ridge_intensity"]
               else "hbm-bound")
    return {"intensity": round(intensity, 4), "verdict": verdict}


# -- capture ----------------------------------------------------------------
def _normalize_cost(ca: Any) -> Optional[Dict[str, float]]:
    """``cost_analysis()`` returns a dict (Lowered) or a list of dicts
    (Compiled, one per partition) depending on backend/version."""
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict) or not ca:
        return None
    if "flops" not in ca and "bytes accessed" not in ca:
        return None
    return ca


def _build_record(name: str, ca: Any, source: str,
                  mem: Any = None) -> Dict[str, Any]:
    norm = _normalize_cost(ca)
    if norm is None:
        return _unavailable_record(
            name, f"{source} cost_analysis returned no flops/bytes")
    flops = float(norm.get("flops", 0.0))
    bytes_accessed = float(norm.get("bytes accessed", 0.0))
    if flops < 0.0 or bytes_accessed < 0.0:
        # XLA reports -1 for "unknown" on some backends — that is an
        # unavailable measurement, not a negative cost
        return _unavailable_record(
            name, f"{source} cost_analysis reported unknown (-1) cost")
    rec: Dict[str, Any] = {
        "name": name,
        "available": True,
        "source": source,
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "transcendentals": float(norm.get("transcendentals", 0.0)),
        **roofline_verdict(flops, bytes_accessed),
    }
    if mem is not None:
        arg = float(getattr(mem, "argument_size_in_bytes", 0))
        out = float(getattr(mem, "output_size_in_bytes", 0))
        tmp = float(getattr(mem, "temp_size_in_bytes", 0))
        alias = float(getattr(mem, "alias_size_in_bytes", 0))
        rec.update({
            "argument_bytes": arg, "output_bytes": out, "temp_bytes": tmp,
            # aliased (donated) buffers are counted once on the argument
            # side; peak = everything resident while the program runs
            "peak_hbm_bytes": arg + out + tmp - alias,
        })
    return rec


def _unavailable_record(name: str, error: str) -> Dict[str, Any]:
    return {"name": name, "available": False, "verdict": "unavailable",
            "error": error[:200]}


def _store(rec: Dict[str, Any]) -> None:
    from .metrics import global_registry
    name = rec["name"]
    with _lock:
        prev = _records.get(name)
        rec["captures"] = (prev.get("captures", 0) if prev else 0) + 1
        # a compiled/aot capture carries the memory analysis a later
        # lowered-only capture lacks — keep the richer fields current
        if prev and prev.get("available"):
            if not rec.get("available"):
                rec = {**prev, "captures": rec["captures"]}
            else:
                # a lowered re-capture (fresh trace in auto mode) must
                # not DROP the memory fields a previous full/aot capture
                # measured: carry them forward (stamped as such) so the
                # record, the gauges, and the sentinel's peak-HBM check
                # stay populated
                for k in ("argument_bytes", "output_bytes", "temp_bytes",
                          "peak_hbm_bytes"):
                    if k not in rec and k in prev:
                        rec[k] = prev[k]
                        rec["memory_source"] = prev.get(
                            "memory_source", prev.get("source"))
        _records[name] = rec
    if rec.get("available"):
        global_registry.gauge(f"cost/{name}/flops", rec["flops"])
        global_registry.gauge(f"cost/{name}/bytes", rec["bytes_accessed"])
        if rec.get("intensity") is not None:
            global_registry.gauge(f"cost/{name}/intensity",
                                  rec["intensity"])
        if "peak_hbm_bytes" in rec:
            global_registry.gauge(f"cost/{name}/peak_hbm_bytes",
                                  rec["peak_hbm_bytes"])


def _capture(entry, jitted, args: tuple, kwargs: dict) -> None:
    """Capture cost for one freshly traced entry from its concrete args.

    ``jitted.lower`` hits the cached jaxpr trace (the compile that just
    happened populated it), so ``lowered`` mode costs ~1 ms; ``full``
    mode pays one extra XLA compile for ``memory_analysis``."""
    import jax
    try:
        from jax.core import Tracer
    except ImportError:   # moved in newer jax
        from jax._src.core import Tracer
    leaves = jax.tree_util.tree_leaves((args, kwargs))
    if any(isinstance(x, Tracer) for x in leaves):
        # dispatched inside an OUTER trace: abstract args cannot be
        # lowered here — leave cost_seen behind so a later concrete
        # dispatch captures
        return
    name = entry.name
    try:
        lowered = jitted.lower(*args, **kwargs)
    except Exception as e:
        _store(_unavailable_record(
            name, f"lower failed: {type(e).__name__}: {e}"))
        entry.cost_seen = entry.count
        return
    rec = None
    if _resolved == "full":
        try:
            compiled = lowered.compile()
            rec = _build_record(name, compiled.cost_analysis(), "compiled",
                                mem=compiled.memory_analysis())
        except Exception:
            rec = None   # fall back to the lowered-module analysis
    if rec is None:
        try:
            rec = _build_record(name, lowered.cost_analysis(), "lowered")
        except Exception as e:
            rec = _unavailable_record(
                name, f"cost_analysis failed: {type(e).__name__}: {e}")
    _store(rec)
    entry.cost_seen = entry.count


def note_compiled(entry, compiled) -> None:
    """Capture from an already-compiled AOT executable (the forwarded
    ``.lower(...).compile()`` surface) — the full analysis for free."""
    if not active():
        return
    try:
        rec = _build_record(entry.name, compiled.cost_analysis(), "aot",
                            mem=compiled.memory_analysis())
    except Exception as e:
        rec = _unavailable_record(
            entry.name, f"aot analysis failed: {type(e).__name__}: {e}")
    try:
        _store(rec)
        entry.cost_seen = entry.count
    except Exception:
        pass


def note_dispatch(entry) -> None:
    """Add one dispatch of ``entry`` to the flops/bytes running totals.

    Runs on the dispatch hot path — no lock: like the watchdog's
    ``_launches += 1``, the GIL makes the float adds effectively atomic
    and a once-in-a-blue-moon lost increment costs an epsilon of
    attribution, not correctness."""
    global _flops_total, _bytes_total
    rec = _records.get(entry.name)
    if rec is None or not rec.get("available"):
        return
    _flops_total += rec["flops"]
    _bytes_total += rec["bytes_accessed"]


def after_dispatch(entry, jitted, args: tuple, kwargs: dict) -> None:
    """Post-dispatch hook from watched_jit: capture on a fresh trace,
    then account the dispatch.  Must never break the dispatch path."""
    try:
        if entry.count > entry.cost_seen:
            _capture(entry, jitted, args, kwargs)
        note_dispatch(entry)
    except Exception:    # noqa: BLE001 — observability never raises
        pass


# -- introspection ----------------------------------------------------------
def dispatch_totals() -> Tuple[float, float]:
    """(flops, bytes) executed so far across all captured entries,
    dispatch-weighted — the per-iteration record diffs this."""
    with _lock:
        return _flops_total, _bytes_total


def cost_records() -> Dict[str, Dict[str, Any]]:
    with _lock:
        return {k: dict(v) for k, v in _records.items()}


def cost_summary() -> Dict[str, Any]:
    """Everything the cost model knows: per-entry records, dispatch-
    weighted totals, and the device roofline they were judged against."""
    with _lock:
        entries = {k: dict(v) for k, v in sorted(_records.items())}
        totals = {"flops": _flops_total, "hbm_bytes": _bytes_total}
    out: Dict[str, Any] = {
        "enabled": active(),
        "mode": _resolved,
        "entries": entries,
        "totals": totals,
    }
    if entries or active():
        out["roofline"] = machine_balance()
    return out
