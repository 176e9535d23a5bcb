"""Serving fleet: replica pool supervisor with fleet-wide promotion.

PR 4's server is one Python process — one crash, hang, or hot-reload
hiccup takes 100% of traffic down.  This module turns it into a FLEET
(docs/SERVING.md "Fleet architecture"):

  * **replica pool** — N single-replica :class:`ServingApp` processes,
    each importing jax on its own, so a wedged XLA dispatch or a killed
    interpreter costs 1/N of capacity, not all of it.  Where the kernel
    supports ``SO_REUSEPORT`` the replicas can share one listen port
    (kernel load-balancing, ``serve_fleet_mode=reuseport``); everywhere
    else — and whenever retry/breaker routing is wanted — the tiny
    fanout front (:mod:`.front`) is the client-facing port
    (``serve_fleet_mode=front``, the default);
  * **liveness + restart** — every replica heartbeats a per-rank file
    (the existing :mod:`..robustness.heartbeat` machinery) every
    ``_BEAT_S``; the supervisor polls process exits AND heartbeat ages,
    SIGKILLs replicas wedged past ``hang_timeout_s``, and restarts dead
    ones with jittered exponential backoff (doubling per consecutive
    restart, decaying after a healthy period);
  * **fleet-wide promotion** — a shared registry directory holds a
    ``promote.json`` pointer (generation, model path, sha256).  Any
    ``/reload`` — on the front or on any replica — VALIDATES the
    candidate first (manifest sha256, truncation parse, finite trees),
    then atomically replaces the pointer; every replica's watcher thread
    re-validates (pointer sha256 + the full registry checks) before its
    own atomic swap.  A replica that fails validation keeps serving its
    old version and reports itself degraded via ``/ready``; the fleet
    never half-applies a poisoned candidate.

The supervisor owns only the replica processes and the state directory —
request routing, deadlines, retries and circuit breaking live in
:mod:`.front`.

State directory layout (``serve_fleet_dir``; a private tmpdir when
unset)::

    promote.json       {"generation", "path", "sha256", "promoted_unix"}
    promote_<id>.json  per-tenant pointer of a multi-tenant fleet —
                       promotion is keyed (model_id, generation); one
                       tenant's pointer advances without its siblings
                       reloading anything (docs/SERVING.md "Multi-tenant
                       serving")
    replica_<r>.json   {"rank", "host", "port", "pid", "started_unix"}
    hb_<r>             heartbeat file (mtime = liveness)
    replica_<r>.log    stdout/stderr of the replica process
"""
from __future__ import annotations

import json
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

from ..robustness.checkpoint import atomic_write_text
from ..robustness.heartbeat import heartbeat_age, write_heartbeat
from ..utils.log import LightGBMError, log_debug, log_info, log_warning

PROMOTE_NAME = "promote.json"
_MID_RE = re.compile(r"^[A-Za-z0-9._-]{1,64}$")


def pointer_name(model_id: str = "") -> str:
    """Pointer file for one tenant: the flat ``promote.json`` when
    ``model_id`` is empty (single-model fleets; also the boot gate of a
    multi-model fleet), ``promote_<id>.json`` per tenant otherwise —
    promotion is keyed by ``(model_id, generation)`` so one tenant's
    pointer advances without its siblings ever re-validating, reloading,
    or recompiling anything."""
    if not model_id:
        return PROMOTE_NAME
    if not _MID_RE.match(model_id):
        raise LightGBMError(
            f"model_id {model_id!r} is not a valid tenant id "
            "(1-64 chars of [A-Za-z0-9._-])")
    return f"promote_{model_id}.json"
_BEAT_S = 0.25           # replica heartbeat-loop period (chaos beat unit)
_SUPERVISE_S = 0.2       # supervisor poll period
_RESTART_CAP_S = 30.0    # backoff ceiling
_HEALTHY_DECAY_S = 60.0  # a replica alive this long forgets its restarts


# ---------------------------------------------------------------------------
# candidate validation + the shared promotion pointer
# ---------------------------------------------------------------------------

def validate_candidate(path: str) -> str:
    """The promotion pre-flight every promoter runs BEFORE touching the
    pointer: manifest sha256 (when a sidecar exists), truncation/
    corruption parse, finite-tree guard.  Returns the candidate's sha256.

    Replicas re-run the same checks (plus a sha match against the
    pointer) before their own swap — promotion is validated twice by
    design: once so a garbage file never enters the pointer, once so a
    file that changed on disk between pointer write and replica read is
    rejected per-replica instead of served."""
    from ..model_io import load_model_string
    from ..robustness.guards import check_model_trees
    from .registry import _check_manifest

    try:
        with open(path, "rb") as fh:
            data = fh.read()
    except OSError as e:
        raise LightGBMError(f"cannot read serving candidate {path!r}: {e}")
    sha = _check_manifest(str(path), data)
    try:
        loaded = load_model_string(data.decode("utf-8"))
    except UnicodeDecodeError as e:
        raise LightGBMError(f"serving candidate {path!r} is not a text "
                            f"model file: {e}")
    check_model_trees(loaded.trees, what=f"serving candidate {path!r}")
    return sha


def read_pointer(fleet_dir: str,
                 model_id: str = "") -> Optional[Dict[str, Any]]:
    try:
        with open(os.path.join(fleet_dir, pointer_name(model_id))) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


HISTORY_NAME = "generations.jsonl"


def generation_history(fleet_dir: str,
                       model_id: Optional[str] = None
                       ) -> List[Dict[str, Any]]:
    """Append-only promotion audit trail (one JSON line per pointer
    write, every tenant interleaved in promotion order).  Survives a
    torn/corrupt pointer file: the next promoter recovers the generation
    counter from here instead of resetting to 1 (which the monotonicity
    guard would then refuse fleet-wide).  ``model_id=None`` returns the
    full interleaved trail; ``""`` filters to the flat (single-model)
    pointer's entries, a tenant id to that tenant's."""
    out: List[Dict[str, Any]] = []
    try:
        with open(os.path.join(fleet_dir, HISTORY_NAME)) as fh:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue   # torn final line of a killed writer
                if model_id is None \
                        or str(rec.get("model_id", "")) == model_id:
                    out.append(rec)
    except OSError:
        pass
    return out


def write_pointer(fleet_dir: str, path: str, sha: str, generation: int,
                  prev: Optional[Dict[str, Any]] = None,
                  rollback_from: Optional[int] = None,
                  model_id: str = "") -> Dict[str, Any]:
    """Atomically replace the promotion pointer (tmp + ``os.replace``:
    a replica's watcher never reads a half-written pointer).  ``prev``
    records the generation being replaced (the rollback target);
    ``rollback_from`` marks an intentional downgrade so replicas accept
    the backwards generation; ``model_id`` selects a tenant's pointer
    file (generation counters are per-tenant)."""
    pointer: Dict[str, Any] = {
        "generation": int(generation), "path": str(path),
        "sha256": sha, "promoted_unix": time.time()}
    if model_id:
        pointer["model_id"] = str(model_id)
    if prev:
        pointer["prev"] = {"generation": int(prev["generation"]),
                           "path": str(prev["path"]),
                           "sha256": prev["sha256"]}
    if rollback_from is not None:
        pointer["rollback_from"] = int(rollback_from)
    # history first, pointer second: a writer killed in between leaves a
    # history entry with no pointer — harmless — while the reverse order
    # could leave a served generation with no audit trail
    try:
        with open(os.path.join(fleet_dir, HISTORY_NAME), "a") as fh:
            fh.write(json.dumps(pointer) + "\n")
    except OSError as e:
        log_warning(f"fleet: generation history append failed: {e}")
    from ..robustness import chaos
    text = json.dumps(pointer)
    if chaos.maybe_tear_pointer(fleet_dir, text,
                                name=pointer_name(model_id)):
        return pointer
    atomic_write_text(os.path.join(fleet_dir, pointer_name(model_id)),
                      text)
    return pointer


def _current_generation(fleet_dir: str, model_id: str = "") -> int:
    """Last written generation of one tenant's pointer (the flat pointer
    when ``model_id`` is empty): the pointer file, or (torn/missing
    pointer) that tenant's newest history entry."""
    cur = read_pointer(fleet_dir, model_id)
    if cur is not None:
        return int(cur["generation"])
    hist = generation_history(fleet_dir, model_id)
    return int(hist[-1]["generation"]) if hist else 0


def promote_pointer(fleet_dir: str, path: str,
                    sha: Optional[str] = None,
                    model_id: str = "") -> Dict[str, Any]:
    """Validate ``path`` and advance the shared pointer one generation —
    only ``model_id``'s pointer when set, so promoting one tenant never
    touches (or re-validates) its siblings.  Any process with the fleet
    directory can promote — the supervisor, a replica's ``/reload``, or
    an external deploy tool."""
    checked = validate_candidate(path)
    if sha is not None and sha != checked:
        raise LightGBMError(
            f"serving candidate {path!r} sha256 mismatch (expected "
            f"{sha[:12]}..., file {checked[:12]}...)")
    cur = read_pointer(fleet_dir, model_id)
    gen = _current_generation(fleet_dir, model_id) + 1
    return write_pointer(fleet_dir, path, checked, gen, prev=cur,
                         model_id=model_id)


def rollback_pointer(fleet_dir: str, reason: str = "",
                     model_id: str = "") -> Dict[str, Any]:
    """Revert one tenant (the flat pointer when ``model_id`` is empty)
    to its previous generation: re-validate the prior target and write
    it back with a ``rollback_from`` marker (the only thing that lets a
    replica accept a backwards generation).  The target comes from the
    current pointer's ``prev`` record, or — when the pointer is torn —
    the tenant's history trail."""
    from .. import telemetry

    cur = read_pointer(fleet_dir, model_id)
    target = (cur or {}).get("prev")
    cur_gen = _current_generation(fleet_dir, model_id)
    if target is None:
        hist = generation_history(fleet_dir, model_id)
        for rec in reversed(hist):
            if int(rec.get("generation", 0)) < cur_gen:
                target = rec
                break
    if target is None:
        raise LightGBMError(
            f"fleet dir {fleet_dir!r} has no prior generation to roll "
            "back to" + (f" for model {model_id!r}" if model_id else ""))
    sha = validate_candidate(str(target["path"]))
    if sha != target.get("sha256"):
        raise LightGBMError(
            f"rollback target {target['path']!r} sha256 changed since its "
            f"promotion ({sha[:12]}... != "
            f"{str(target.get('sha256'))[:12]}...)")
    pointer = write_pointer(fleet_dir, str(target["path"]), sha,
                            int(target["generation"]),
                            rollback_from=cur_gen, model_id=model_id)
    telemetry.instant("fleet:rollback", generation=pointer["generation"],
                      rollback_from=cur_gen, sha256=sha,
                      model_id=model_id or "",
                      reason=reason or "unspecified")
    telemetry.inc("fleet/rollbacks")
    log_warning(f"fleet: rolled back "
                + (f"model {model_id!r} " if model_id else "")
                + f"generation {cur_gen} -> {pointer['generation']} "
                f"({reason or 'unspecified'})")
    return pointer


# ---------------------------------------------------------------------------
# replica process
# ---------------------------------------------------------------------------

def pointer_transition(applied: int, pointer: Optional[Dict[str, Any]]
                       ) -> str:
    """The promotion watcher's decision for a freshly read pointer, given
    the generation this replica last applied: ``"apply"``, ``"ignore"``
    (unreadable/unchanged), or ``"refuse"`` (backwards generation with no
    ``rollback_from`` marker — a stale or duplicate promoter must not
    silently downgrade the fleet; only ``rollback_pointer`` writes the
    marker that makes a downgrade intentional)."""
    if pointer is None:
        return "ignore"
    gen = int(pointer["generation"])
    if gen == applied:
        return "ignore"
    if gen < applied and pointer.get("rollback_from") is None:
        return "refuse"
    return "apply"


def _replica_main(spec_path: str, rank: int) -> int:
    """Entry point of one replica process (spawned by the supervisor as
    ``python -m lightgbm_tpu.serving.fleet --replica <spec> <rank>``)."""
    from .. import telemetry
    from ..robustness import chaos
    from .server import ServingApp

    with open(spec_path) as fh:
        spec = json.load(fh)
    # a replica serving blind (no latency histograms, no /metrics, no
    # trace spans) is undebuggable from the fleet — telemetry is on in
    # every replica; per-request span emission still follows the
    # propagated head-sampling decision (serve_trace_sample)
    telemetry.configure(enabled=True)
    # shared persistent compile cache: replica warmups after the first pay
    # file reads, not XLA compiles (placed by the supervisor's child_env)
    from ..runtime import configure_compile_cache
    configure_compile_cache()
    fleet_dir = spec["fleet_dir"]
    hb_path = os.path.join(fleet_dir, f"hb_{rank}")
    stop = threading.Event()

    # the heartbeat loop starts BEFORE the model loads: a replica stuck
    # waiting for a valid pointer (below) must look alive to the
    # supervisor, not wedged
    def _beat() -> None:
        n = 0
        while not stop.is_set():
            n += 1
            chaos.replica_beat_hook(n)
            try:
                write_heartbeat(hb_path, n)
            except OSError as e:
                log_debug(f"replica {rank} heartbeat write failed: {e}")
            if stop.wait(_BEAT_S):
                break

    beat_thread = threading.Thread(target=_beat,
                                   name=f"lgbtpu-replica{rank}-beat",
                                   daemon=True)
    beat_thread.start()

    # boot from the CURRENT pointer(s), but only after the same
    # re-validation the promotion watcher performs — a candidate the
    # fleet rejected (file tampered after promotion) must not be served
    # just because this replica restarted; wait for a pointer that
    # validates instead of crash-looping on a dead one.  A multi-tenant
    # spec carries a model roster: every tenant boots from ITS OWN
    # promote_<id>.json (the supervisor writes them before spawning).
    roster: Dict[str, str] = {str(k): str(v)
                              for k, v in (spec.get("models") or {}).items()}
    default_mid = str(spec.get("default_model", "") or "")
    if roster and not default_mid:
        default_mid = next(iter(roster))
    applied: Dict[str, int] = {}
    pointer = None
    if roster:
        boot_roster: Dict[str, str] = {}
        for mid in roster:
            while mid not in boot_roster:
                p = read_pointer(fleet_dir, mid)
                if p is None:
                    # shared dir predating this tenant: serve the spec
                    # roster path; generation 0 until someone promotes
                    boot_roster[mid] = roster[mid]
                    applied[mid] = 0
                    break
                try:
                    sha = validate_candidate(str(p["path"]))
                    if sha != p.get("sha256"):
                        raise LightGBMError(
                            f"model {mid!r} pointer generation "
                            f"{p['generation']} sha256 mismatch "
                            f"({sha[:12]}... != "
                            f"{str(p.get('sha256'))[:12]}...) — the file "
                            "changed after promotion")
                    boot_roster[mid] = str(p["path"])
                    applied[mid] = int(p["generation"])
                except LightGBMError as e:
                    log_warning(f"replica {rank}: promoted model failed "
                                f"boot validation ({e}); waiting for a "
                                "valid promotion")
                    if stop.wait(1.0):
                        return 0
    else:
        while pointer is None:
            p = read_pointer(fleet_dir)
            if p is None:
                raise LightGBMError(
                    f"fleet dir {fleet_dir!r} has no promotion pointer; "
                    "the supervisor writes it before spawning replicas")
            try:
                sha = validate_candidate(str(p["path"]))
                if sha != p.get("sha256"):
                    raise LightGBMError(
                        f"pointer generation {p['generation']} sha256 "
                        f"mismatch ({sha[:12]}... != "
                        f"{str(p.get('sha256'))[:12]}...) — the file "
                        "changed after promotion")
                pointer = p
            except LightGBMError as e:
                log_warning(f"replica {rank}: promoted model failed boot "
                            f"validation ({e}); waiting for a valid "
                            "promotion")
                if stop.wait(1.0):
                    return 0
        applied[""] = int(pointer["generation"])
    reuseport = bool(spec.get("reuseport"))
    access_dir = str(spec.get("access_log_dir", "") or "")
    app = ServingApp(
        str(pointer["path"]) if pointer is not None else "",
        host=spec["host"],
        port=int(spec["shared_port"]) if reuseport else 0,
        max_batch=int(spec["max_batch"]),
        max_delay_ms=float(spec["max_delay_ms"]),
        queue_size=int(spec["queue_size"]),
        buckets_spec=str(spec.get("buckets", "")),
        warmup=bool(spec.get("warmup", True)),
        heartbeat_path=hb_path,
        deadline_ms=float(spec.get("deadline_ms", 0.0)),
        reuse_port=reuseport,
        trace_sample=float(spec.get("trace_sample", 0.01)),
        trace_tail=int(spec.get("trace_tail", 256)),
        access_log=(os.path.join(access_dir,
                                 f"access_replica_{rank}.jsonl")
                    if access_dir else ""),
        slo_availability=float(spec.get("slo_availability", 0.999)),
        slo_p99_ms=float(spec.get("slo_p99_ms", 0.0)),
        slo_window_s=float(spec.get("slo_window_s", 60.0)),
        slo_burn=float(spec.get("slo_burn", 14.4)),
        # binary wire: every replica opens its OWN ephemeral wire port
        # (published in replica_<r>.json below) — replica-aware clients
        # (wire.FleetBinaryClient) discover and route around failures
        binary_port=(0 if int(spec.get("binary_port", -1)) >= 0 else -1),
        binary_accept_threads=int(spec.get("binary_accept_threads", 2)),
        quality_sample=float(spec.get("quality_sample", 0.01)),
        quality_audit_sample=float(spec.get("quality_audit_sample", 0.01)),
        drift_threshold=float(spec.get("drift_threshold", 0.2)),
        drift_window_s=float(spec.get("drift_window_s", 60.0)),
        quality_min_rows=int(spec.get("quality_min_rows", 200)),
        quality_topk=int(spec.get("quality_topk", 5)),
        models=(boot_roster if roster else None),
        hbm_budget_mb=float(spec.get("hbm_budget_mb", 0.0)),
        default_model_id=default_mid,
        explain_max_batch=int(spec.get("explain_max_batch", 16)),
        explain_queue_size=int(spec.get("explain_queue_size", 64)),
        explain_max_delay_ms=float(spec.get("explain_max_delay_ms", 2.0)))
    app.replica_rank = rank
    # per-replica drift snapshot export (merged by `python -m
    # lightgbm_tpu.telemetry.quality report <fleet_dir>`)
    app.drift_export_path = os.path.join(fleet_dir,
                                         f"drift_replica_{rank}.json")
    app.generation = applied[default_mid if roster else ""]
    app.seen_generation = app.generation
    if roster:
        for mid, gen in applied.items():
            reg = app.registry.tenant(mid)
            reg.generation = gen
            reg.seen_generation = gen

    # the watcher polls ONE pointer per tenant (the flat promote.json in
    # single-model mode): a promotion of tenant A swaps A's registry and
    # NOTHING else — siblings keep their device arrays, compiled
    # programs and version counters bitwise untouched
    sources: List[str] = list(roster) if roster else [""]
    tenant_degraded: Dict[str, str] = {}

    def _apply_pointer(mid: str) -> None:
        p = read_pointer(fleet_dir, mid)
        decision = pointer_transition(applied[mid], p)
        if decision == "ignore":
            return
        gen = int(p["generation"])
        who = f"model {mid!r} " if mid else ""
        if decision == "refuse":
            log_warning(
                f"replica {rank}: refusing {who}pointer generation "
                f"{gen} < applied {applied[mid]} without a "
                "rollback_from marker (stale promoter?)")
            return
        if gen < applied[mid]:
            log_warning(f"replica {rank}: {who}rollback generation "
                        f"{gen} (from {p['rollback_from']})")
        applied[mid] = gen
        reg = app.registry.tenant(mid) if roster else None
        try:
            # re-validate against the POINTER's sha first: a file
            # swapped after promotion must not be served even if it
            # parses
            sha = validate_candidate(str(p["path"]))
            if sha != p.get("sha256"):
                raise LightGBMError(
                    f"candidate {p['path']!r} does not match the "
                    f"promoted sha256 ({sha[:12]}... != "
                    f"{str(p.get('sha256'))[:12]}...) — the file "
                    "changed after promotion")
            if roster:
                app.registry.load(str(p["path"]), mid)
            else:
                app.registry.load(str(p["path"]))
        except LightGBMError as e:
            msg = f"{who}candidate generation {gen} rejected: {e}"
            tenant_degraded[mid] = msg
            app.degraded = "; ".join(tenant_degraded.values())
            if reg is not None:
                reg.seen_generation = gen
            if not mid or mid == default_mid:
                app.seen_generation = gen
            log_warning(f"replica {rank}: {msg}; still serving "
                        f"{who}generation "
                        f"{reg.generation if reg is not None else app.generation}")
            return
        if reg is not None:
            reg.generation = gen
            reg.seen_generation = gen
        if not mid or mid == default_mid:
            app.generation = gen
            app.seen_generation = gen
        tenant_degraded.pop(mid, None)
        app.degraded = "; ".join(tenant_degraded.values()) or None
        log_info(f"replica {rank}: promoted {who}to generation {gen} "
                 f"(sha {str(p['sha256'])[:12]})")

    def _watch_promotions() -> None:
        while not stop.wait(float(spec.get("poll_s", _BEAT_S))):
            for mid in sources:
                _apply_pointer(mid)

    def _promote_fn(path: str, model_id: str = ""):
        # any replica's /reload promotes FLEET-WIDE through the shared
        # pointer (its own watcher applies the swap like everyone else's);
        # in a multi-tenant fleet an un-addressed reload targets the
        # default tenant's pointer
        mid = str(model_id or "") or (default_mid if roster else "")
        if roster and mid not in roster:
            raise LightGBMError(f"unknown model_id {mid!r} (roster: "
                                f"{', '.join(sorted(roster))})")
        p = promote_pointer(fleet_dir, path, model_id=mid)
        out = {"promoted_generation": p["generation"],
               "sha256": p["sha256"], "fleet_wide": True}
        if mid:
            out["model_id"] = mid
        return out

    app.promote_fn = _promote_fn
    app.start()
    atomic_write_text(
        os.path.join(fleet_dir, f"replica_{rank}.json"),
        json.dumps({"rank": rank, "host": app.host, "port": app.port,
                    "binary_port": app.binary_port,
                    "pid": os.getpid(), "started_unix": time.time()}))
    threading.Thread(target=_watch_promotions,
                     name=f"lgbtpu-replica{rank}-promote",
                     daemon=True).start()

    def _graceful(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)
    log_info(f"replica {rank} serving on http://{app.host}:{app.port} "
             f"(generation {app.generation}, pid {os.getpid()})")
    while not stop.wait(0.2):
        pass
    app.shutdown(drain=True)
    # leave this process's span shard behind for the cross-process
    # collector (python -m lightgbm_tpu.telemetry.collect <fleet_dir>) —
    # unless the fleet dir is a private tmpdir the supervisor removes on
    # stop, where the shard would be destroyed moments after the write
    if not spec.get("ephemeral_dir"):
        try:
            telemetry.export_trace(
                os.path.join(fleet_dir, f"trace_replica_{rank}.json"))
        except OSError as e:
            log_debug(f"replica {rank} trace export failed: {e}")
    return 0


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------

class ServingFleet:
    """N replica processes + state dir + (front mode) the fanout front.

    ``start()`` spawns everything and blocks until the fleet answers;
    ``promote()`` advances the shared pointer and waits for replicas to
    converge; ``stop()`` drains and reaps.  The supervisor thread
    restarts dead/hung replicas with jittered exponential backoff."""

    def __init__(self, model_path: str, *, replicas: int = 2,
                 host: str = "127.0.0.1", port: int = 0,
                 mode: str = "front", fleet_dir: str = "",
                 max_batch: int = 256, max_delay_ms: float = 2.0,
                 queue_size: int = 512, buckets_spec: str = "",
                 warmup: bool = True, deadline_ms: float = 0.0,
                 retries: int = 2, retry_backoff_ms: float = 25.0,
                 breaker_failures: int = 5, breaker_cooldown_s: float = 2.0,
                 restart_backoff_s: float = 0.5,
                 hang_timeout_s: float = 10.0,
                 startup_timeout_s: float = 180.0,
                 trace_sample: float = 0.01, trace_tail: int = 256,
                 access_log: str = "",
                 slo_availability: float = 0.999, slo_p99_ms: float = 0.0,
                 slo_window_s: float = 60.0, slo_burn: float = 14.4,
                 binary_port: int = -1, binary_accept_threads: int = 2,
                 quality_sample: float = 0.01,
                 quality_audit_sample: float = 0.01,
                 drift_threshold: float = 0.2, drift_window_s: float = 60.0,
                 quality_min_rows: int = 200, quality_topk: int = 5,
                 models=None, hbm_budget_mb: float = 0.0,
                 default_model_id: str = "",
                 explain_max_batch: int = 16,
                 explain_queue_size: int = 64,
                 explain_max_delay_ms: float = 2.0,
                 python: str = sys.executable, platform: str = ""):
        from ..runtime import child_platform
        from .server import reuseport_available

        if replicas < 1:
            raise LightGBMError("serve_replicas must be >= 1")
        if mode not in ("front", "reuseport"):
            raise LightGBMError(
                f"serve_fleet_mode must be 'front' or 'reuseport', "
                f"got {mode!r}")
        if mode == "reuseport" and not reuseport_available():
            log_warning("SO_REUSEPORT is unavailable on this platform; "
                        "the fleet falls back to the fanout front")
            mode = "front"
        self.mode = mode
        self.replicas = int(replicas)
        # every replica is TOLD its JAX platform; a chip belongs to one
        # process, so platform "tpu" needs a chip per replica and a
        # supervisor that never initialised JAX (checked in start())
        self.platform = str(platform or child_platform())
        self.host = str(host)
        self.port = int(port)
        if self.mode == "reuseport" and self.port == 0:
            # port 0 would hand every replica its OWN kernel-assigned
            # port — SO_REUSEPORT shares nothing and the fleet has no
            # addressable endpoint; pick one concrete free port for the
            # whole group instead
            import socket
            with socket.socket() as s:
                s.bind((self.host, 0))
                self.port = s.getsockname()[1]
            log_info(f"fleet: reuseport mode picked shared port "
                     f"{self.port}")
        self.deadline_ms = float(deadline_ms or 0.0)
        self.retries = int(retries)
        self.retry_backoff_ms = float(retry_backoff_ms)
        self.breaker_failures = int(breaker_failures)
        self.breaker_cooldown_s = float(breaker_cooldown_s)
        self.restart_backoff_s = max(float(restart_backoff_s), 0.05)
        self.hang_timeout_s = float(hang_timeout_s or 0.0)
        self.startup_timeout_s = float(startup_timeout_s)
        self._python = python
        self._own_dir = not fleet_dir
        self.dir = fleet_dir or tempfile.mkdtemp(prefix="lgb_tpu_fleet_")
        os.makedirs(self.dir, exist_ok=True)
        # multi-tenant fleet: the roster maps model_id -> model file;
        # every tenant gets its OWN promote_<id>.json generation counter
        self.roster: Dict[str, str] = {}
        self.default_model_id = str(default_model_id or "")
        if models:
            from .multimodel import parse_model_roster
            self.roster = dict(parse_model_roster(models))
            if not self.default_model_id:
                self.default_model_id = next(iter(self.roster))
            if self.default_model_id not in self.roster:
                raise LightGBMError(
                    f"default model_id {self.default_model_id!r} is not "
                    f"in the roster ({', '.join(sorted(self.roster))})")
            if not model_path:
                model_path = self.roster[self.default_model_id]
        elif not model_path:
            raise LightGBMError(
                "ServingFleet needs a model_path or a model roster")
        # gen 1 (or continue a pre-existing shared dir's count): the
        # pointer(s) exist BEFORE any replica starts, so every replica
        # boots on the same validated version.  The flat promote.json is
        # always written (single-model fleets, plus back-compat tooling
        # that reads it); a roster adds one pointer per tenant
        sha = validate_candidate(model_path)
        cur = read_pointer(self.dir)
        gen = _current_generation(self.dir) + 1
        self._pointer = write_pointer(self.dir, model_path, sha, gen,
                                      prev=cur)
        for mid, mpath in self.roster.items():
            msha = validate_candidate(mpath)
            mcur = read_pointer(self.dir, mid)
            if mcur is not None and str(mcur.get("sha256")) == msha:
                continue   # shared dir already points at these bytes
            mgen = _current_generation(self.dir, mid) + 1
            write_pointer(self.dir, mpath, msha, mgen, prev=mcur,
                          model_id=mid)
        # observability knobs ride to every replica via the spec; the
        # access log treats the configured path as a DIRECTORY in fleet
        # mode (access_front.jsonl + access_replica_<r>.jsonl inside)
        self.trace_sample = float(trace_sample)
        self.slo_params = {"slo_availability": float(slo_availability),
                           "slo_p99_ms": float(slo_p99_ms),
                           "slo_window_s": float(slo_window_s),
                           "slo_burn": float(slo_burn)}
        self.access_dir = str(access_log or "")
        if self.access_dir:
            os.makedirs(self.access_dir, exist_ok=True)
        self._spec = {
            "fleet_dir": self.dir, "host": self.host,
            "shared_port": self.port, "reuseport": mode == "reuseport",
            "max_batch": int(max_batch),
            "max_delay_ms": float(max_delay_ms),
            "queue_size": int(queue_size), "buckets": str(buckets_spec),
            "warmup": bool(warmup), "deadline_ms": self.deadline_ms,
            "poll_s": _BEAT_S,
            "trace_sample": self.trace_sample,
            "trace_tail": int(trace_tail),
            "access_log_dir": self.access_dir,
            # a private tmpdir is rmtree'd on stop — exporting trace
            # shards into it would be wasted work destroyed moments
            # later; set serve_fleet_dir to keep shards for the
            # collector (docs/OBSERVABILITY.md)
            "ephemeral_dir": self._own_dir,
            "binary_port": int(binary_port),
            "binary_accept_threads": int(binary_accept_threads),
            # data/model quality knobs ride to every replica; the
            # .quality.json sidecar itself travels with the model path,
            # so promotion carries it without fleet help
            "quality_sample": float(quality_sample),
            "quality_audit_sample": float(quality_audit_sample),
            "drift_threshold": float(drift_threshold),
            "drift_window_s": float(drift_window_s),
            "quality_min_rows": int(quality_min_rows),
            "quality_topk": int(quality_topk),
            # multi-tenant serving: replicas boot every tenant from its
            # own pointer; the roster here is only the fallback for a
            # tenant whose pointer a shared dir does not have yet
            "models": self.roster,
            "default_model": self.default_model_id,
            "hbm_budget_mb": float(hbm_budget_mb),
            "explain_max_batch": int(explain_max_batch),
            "explain_queue_size": int(explain_queue_size),
            "explain_max_delay_ms": float(explain_max_delay_ms),
            **self.slo_params,
        }
        self._spec_path = os.path.join(self.dir, "replica_spec.json")
        # atomic: a replica that races the supervisor must never read a
        # half-written spec
        atomic_write_text(self._spec_path, json.dumps(self._spec))
        self._lock = threading.Lock()
        self._procs: Dict[int, subprocess.Popen] = {}
        self._restarts: Dict[int, int] = {}
        self._last_spawn: Dict[int, float] = {}
        self._restart_due: Dict[int, float] = {}
        self.restarts_total = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.front = None
        # jitter keeps a mass-restart from thundering-herding the model
        # load; seeded per-fleet so runs are reproducible
        self._rng = random.Random(0xF1EE7 ^ self.replicas)

    # -- process plumbing --------------------------------------------------
    def _endpoint_path(self, rank: int) -> str:
        return os.path.join(self.dir, f"replica_{rank}.json")

    def endpoint(self, rank: int) -> Optional[Dict[str, Any]]:
        try:
            with open(self._endpoint_path(rank)) as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return None

    def endpoints(self) -> Dict[int, Dict[str, Any]]:
        """rank -> endpoint record for replicas with a LIVE process."""
        out: Dict[int, Dict[str, Any]] = {}
        with self._lock:
            live = [r for r, p in self._procs.items() if p.poll() is None]
        for r in live:
            ep = self.endpoint(r)
            if ep is not None:
                out[r] = ep
        return out

    def binary_endpoints(self) -> Dict[int, Any]:
        """rank -> (host, binary_port) of live replicas with an open
        binary wire — the discovery hook wire.FleetBinaryClient routes
        off (re-read per call: a restarted replica publishes a NEW port)."""
        out: Dict[int, Any] = {}
        for r, ep in self.endpoints().items():
            bp = ep.get("binary_port")
            if bp:
                out[r] = (ep["host"], int(bp))
        return out

    def _spawn(self, rank: int) -> None:
        for stale in (self._endpoint_path(rank),
                      os.path.join(self.dir, f"hb_{rank}")):
            if os.path.exists(stale):
                os.unlink(stale)
        from ..runtime import child_env
        env = child_env(self.platform)
        env["LGBTPU_REPLICA_RANK"] = str(rank)
        env["PYTHONUNBUFFERED"] = "1"
        log_path = os.path.join(self.dir, f"replica_{rank}.log")
        with open(log_path, "ab") as logf:
            proc = subprocess.Popen(
                [self._python, "-m", "lightgbm_tpu.serving.fleet",
                 "--replica", self._spec_path, str(rank)],
                env=env, stdout=logf, stderr=subprocess.STDOUT)
        with self._lock:
            self._procs[rank] = proc
            self._last_spawn[rank] = time.monotonic()
        log_debug(f"fleet: spawned replica {rank} (pid {proc.pid})")

    def _schedule_restart(self, rank: int, why: str) -> None:
        from .. import telemetry

        with self._lock:
            healthy_for = time.monotonic() - self._last_spawn.get(rank, 0.0)
            if healthy_for > _HEALTHY_DECAY_S:
                self._restarts[rank] = 0
            n = self._restarts.get(rank, 0)
            self._restarts[rank] = n + 1
            self.restarts_total += 1
            delay = min(self.restart_backoff_s * (2 ** n), _RESTART_CAP_S)
            delay *= 0.75 + 0.5 * self._rng.random()   # +/-25% jitter
            self._restart_due[rank] = time.monotonic() + delay
        telemetry.inc("fleet/restarts")
        log_warning(f"fleet: replica {rank} {why}; restart "
                    f"{self._restarts[rank]} in {delay:.2f}s")

    def _tail_log(self, rank: int, n: int = 2000) -> str:
        try:
            with open(os.path.join(self.dir, f"replica_{rank}.log"),
                      "rb") as fh:
                fh.seek(0, os.SEEK_END)
                size = fh.tell()
                fh.seek(max(0, size - n))
                return fh.read().decode(errors="replace")
        except OSError:
            return "<no replica log>"

    def _supervise(self) -> None:
        """The babysitter: poll exits + heartbeat ages, reap hung
        replicas, respawn dead ones once their backoff elapses."""
        from .. import telemetry

        while not self._stop.wait(_SUPERVISE_S):
            now = time.monotonic()
            with self._lock:
                snapshot = dict(self._procs)
                due = dict(self._restart_due)
            alive = 0
            for rank, proc in snapshot.items():
                rc = proc.poll()
                telemetry.gauge(f"fleet/replica/{rank}/up",
                                1.0 if rc is None else 0.0)
                if rc is not None:
                    if rank not in due:
                        self._schedule_restart(rank, f"exited (rc {rc})")
                    continue
                alive += 1
                if self.hang_timeout_s > 0:
                    age = heartbeat_age(os.path.join(self.dir, f"hb_{rank}"))
                    if age is not None:
                        telemetry.gauge(
                            f"fleet/replica/{rank}/heartbeat_age_s", age)
                    started = self._last_spawn.get(rank, now)
                    if age is None:
                        # no beat yet: give the interpreter+jax import
                        # the startup window before declaring it wedged
                        if now - started > max(self.startup_timeout_s,
                                               self.hang_timeout_s):
                            log_warning(f"fleet: replica {rank} never "
                                        "heartbeat; killing")
                            proc.kill()
                    elif age > self.hang_timeout_s:
                        log_warning(f"fleet: replica {rank} heartbeat "
                                    f"stale ({age:.1f}s > "
                                    f"{self.hang_timeout_s:.1f}s); killing")
                        proc.kill()
            telemetry.gauge("fleet/replicas_alive", float(alive))
            for rank, when in due.items():
                if now >= when:
                    with self._lock:
                        self._restart_due.pop(rank, None)
                    self._spawn(rank)

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "ServingFleet":
        from ..runtime import require_chips
        require_chips(self.replicas, self.platform,
                      f"ServingFleet(replicas={self.replicas})")
        for r in range(self.replicas):
            self._spawn(r)
        deadline = time.monotonic() + self.startup_timeout_s
        pending = set(range(self.replicas))
        while pending:
            for r in sorted(pending):
                proc = self._procs.get(r)
                if proc is not None and proc.poll() is not None:
                    raise LightGBMError(
                        f"fleet replica {r} died during startup "
                        f"(rc {proc.returncode}):\n{self._tail_log(r)}")
                if self.endpoint(r) is not None:
                    pending.discard(r)
            if not pending:
                break
            if time.monotonic() > deadline:
                raise LightGBMError(
                    f"fleet replicas {sorted(pending)} not up within "
                    f"{self.startup_timeout_s:.0f}s")
            time.sleep(0.1)
        self._thread = threading.Thread(target=self._supervise,
                                        name="lgbtpu-fleet-supervisor",
                                        daemon=True)
        self._thread.start()
        if self.mode == "front":
            from .front import FanoutFront
            self.front = FanoutFront(
                self, host=self.host, port=self.port,
                retries=self.retries,
                retry_backoff_ms=self.retry_backoff_ms,
                breaker_failures=self.breaker_failures,
                breaker_cooldown_s=self.breaker_cooldown_s,
                deadline_ms=self.deadline_ms,
                trace_sample=self.trace_sample,
                trace_tail=int(self._spec["trace_tail"]),
                access_log=(os.path.join(self.access_dir,
                                         "access_front.jsonl")
                            if self.access_dir else ""),
                slo_availability=self.slo_params["slo_availability"],
                slo_p99_ms=self.slo_params["slo_p99_ms"],
                slo_window_s=self.slo_params["slo_window_s"],
                slo_burn=self.slo_params["slo_burn"]).start()
            self.port = self.front.port
        else:
            self.port = int(self._spec["shared_port"])
        log_info(f"fleet: {self.replicas} replicas up "
                 f"({self.mode} mode, http://{self.host}:{self.port}, "
                 f"dir {self.dir})")
        return self

    def _pointer_mid(self, model_id: Optional[str]) -> str:
        """Resolve a promote/rollback target to a pointer key: the named
        tenant in a roster fleet (un-addressed calls hit the DEFAULT
        tenant's pointer — the flat promote.json is not watched by
        multi-tenant replicas), the flat pointer otherwise."""
        mid = str(model_id or "")
        if self.roster:
            mid = mid or self.default_model_id
            if mid not in self.roster:
                raise LightGBMError(
                    f"unknown model_id {mid!r} (roster: "
                    f"{', '.join(sorted(self.roster))})")
            return mid
        if mid:
            raise LightGBMError(
                "model_id promotion needs a multi-tenant fleet "
                "(serve_models)")
        return ""

    @property
    def generation(self) -> int:
        p = read_pointer(self.dir, self._pointer_mid(None))
        return int(p["generation"]) if p else 0

    def current_pointer(self, model_id: Optional[str] = None
                        ) -> Optional[Dict[str, Any]]:
        return read_pointer(self.dir, self._pointer_mid(model_id))

    def _replica_gen_state(self, st: Optional[Dict[str, Any]],
                           mid: str) -> Dict[str, Any]:
        """(seen_generation, generation, degraded) of one tenant in one
        replica's /ready payload — the per-model record when addressing
        a roster tenant, the flat fields otherwise."""
        if st is None:
            return {}
        if mid:
            return (st.get("models") or {}).get(mid) or {}
        return st

    def promote(self, path: str, timeout_s: float = 60.0,
                model_id: Optional[str] = None) -> Dict[str, Any]:
        """Validate + write one tenant's pointer, then wait for every
        live replica to process the new generation.  Returns the
        per-replica outcome; raises only when the CANDIDATE fails
        validation (the fleet is untouched in that case).  Sibling
        tenants are never touched — their registries, versions and
        compiled programs stay bitwise identical through the promotion."""
        mid = self._pointer_mid(model_id)
        pointer = promote_pointer(self.dir, path, model_id=mid)
        gen = int(pointer["generation"])
        deadline = time.monotonic() + timeout_s
        promoted: Dict[int, bool] = {}
        rejected: Dict[int, str] = {}
        while time.monotonic() < deadline:
            states = self._ready_states()
            pending = False
            for rank, st in states.items():
                rec = self._replica_gen_state(st, mid)
                if not rec or int(rec.get("seen_generation", 0)) < gen:
                    pending = True
                    continue
                if int(rec.get("generation", 0)) == gen:
                    promoted[rank] = True
                    rejected.pop(rank, None)
                else:
                    rejected[rank] = str((st or {}).get("degraded",
                                                        "rejected"))
            if not pending and states:
                break
            time.sleep(0.1)
        unreachable = [
            r for r, st in self._ready_states().items()
            if int(self._replica_gen_state(st, mid)
                   .get("seen_generation", 0)) < gen]
        out = {"generation": gen, "sha256": pointer["sha256"],
               "promoted": sorted(promoted),
               "rejected": {str(r): m for r, m in sorted(rejected.items())},
               "unreachable": sorted(set(unreachable) - set(promoted))}
        if mid:
            out["model_id"] = mid
        return out

    def rollback(self, reason: str = "", timeout_s: float = 60.0,
                 model_id: Optional[str] = None) -> Dict[str, Any]:
        """Revert one tenant to its previous generation and wait for the
        live replicas to converge on the rollback target's sha256 (the
        generation number moves DOWN, so the promote() wait — which keys
        on seen_generation advancing — does not apply)."""
        mid = self._pointer_mid(model_id)
        pointer = rollback_pointer(self.dir, reason=reason, model_id=mid)
        sha = str(pointer["sha256"])
        deadline = time.monotonic() + timeout_s
        reverted: Dict[int, bool] = {}
        while time.monotonic() < deadline:
            states = self._ready_states()
            reverted = {
                r: (str(self._replica_gen_state(st, mid)
                        .get("sha256" if mid else "model_sha256")) == sha)
                for r, st in states.items()}
            if states and all(reverted.values()):
                break
            time.sleep(0.1)
        out = {"generation": int(pointer["generation"]),
               "rollback_from": pointer.get("rollback_from"),
               "sha256": sha,
               "reverted": sorted(r for r, ok in reverted.items() if ok)}
        if mid:
            out["model_id"] = mid
        return out

    def _ready_states(self) -> Dict[int, Optional[Dict[str, Any]]]:
        """rank -> /ready payload (None when unreachable) for every live
        replica."""
        from .front import http_json

        import http.client

        out: Dict[int, Optional[Dict[str, Any]]] = {}
        for rank, ep in self.endpoints().items():
            try:
                _, obj, _ = http_json(ep["host"], ep["port"], "GET",
                                      "/ready", timeout=1.0)
                out[rank] = obj
            except (OSError, http.client.HTTPException):
                # a replica dying mid-response (IncompleteRead) must read
                # as unreachable, not abort a promote()/describe() whose
                # pointer already advanced
                out[rank] = None
        return out

    def describe(self, states: Optional[Dict[int, Optional[Dict[str, Any]]]]
                 = None) -> Dict[str, Any]:
        """Fleet snapshot.  ``states`` lets a caller that already holds
        fresh /ready payloads (the front's background cache) avoid N
        synchronous per-replica probes per /stats scrape."""
        if states is None:
            states = self._ready_states()
        with self._lock:
            restarts = dict(self._restarts)
            total = self.restarts_total
        reps: List[Dict[str, Any]] = []
        for rank in range(self.replicas):
            st = states.get(rank)
            rec: Dict[str, Any] = {"rank": rank,
                                   "reachable": st is not None,
                                   "restarts": restarts.get(rank, 0)}
            if st:
                rec.update({k: st[k] for k in
                            ("ready", "queue_depth", "model_version",
                             "model_sha256", "generation", "degraded",
                             "heartbeat_age_s") if k in st})
            reps.append(rec)
        return {"mode": self.mode, "replicas": reps,
                "generation": self.generation,
                "restarts_total": total, "dir": self.dir}

    def stop(self, timeout_s: float = 30.0) -> None:
        from .. import telemetry

        self._stop.set()
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(5.0)
        if self.front is not None:
            self.front.stop()
        if telemetry.global_tracer.enabled and not self._own_dir:
            # this process's shard (front routing + supervisor events);
            # replicas export theirs during their SIGTERM drain below.
            # A private tmpdir fleet is skipped — it is rmtree'd at the
            # end of this method; set serve_fleet_dir to collect shards
            try:
                telemetry.export_trace(
                    os.path.join(self.dir, "trace_front.json"))
            except OSError as e:
                log_debug(f"fleet front trace export failed: {e}")
        with self._lock:
            procs = dict(self._procs)
        for proc in procs.values():
            if proc.poll() is None:
                proc.terminate()        # SIGTERM: replicas drain
        deadline = time.monotonic() + timeout_s
        for proc in procs.values():
            left = max(deadline - time.monotonic(), 0.1)
            try:
                proc.wait(timeout=left)
            except subprocess.TimeoutExpired:
                proc.kill()
                try:
                    proc.wait(timeout=5.0)
                except subprocess.TimeoutExpired:  # pragma: no cover
                    pass
        if self._own_dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def fleet_from_params(params: Dict[str, Any]) -> ServingFleet:
    """Build (not start) a ServingFleet from resolved CLI/conf params."""
    from ..config import Config

    cfg = Config.from_params(params)
    model_path = str(params.get("input_model", "") or "")
    if not model_path and not cfg.serve_models:
        raise LightGBMError("task=serve requires input_model=<model file> "
                            "(or serve_models=<id=path,...>)")
    return ServingFleet(
        model_path, replicas=cfg.serve_replicas,
        host=cfg.serve_host, port=cfg.serve_port,
        mode=cfg.serve_fleet_mode, fleet_dir=cfg.serve_fleet_dir,
        max_batch=cfg.serve_max_batch, max_delay_ms=cfg.serve_max_delay_ms,
        queue_size=cfg.serve_queue_size, buckets_spec=cfg.serve_buckets,
        warmup=cfg.serve_warmup, deadline_ms=cfg.serve_deadline_ms,
        retries=cfg.serve_retries,
        retry_backoff_ms=cfg.serve_retry_backoff_ms,
        breaker_failures=cfg.serve_breaker_failures,
        breaker_cooldown_s=cfg.serve_breaker_cooldown_s,
        restart_backoff_s=cfg.serve_restart_backoff_s,
        hang_timeout_s=cfg.serve_hang_timeout_s,
        trace_sample=cfg.serve_trace_sample,
        trace_tail=cfg.serve_trace_tail,
        access_log=cfg.serve_access_log,
        slo_availability=cfg.serve_slo_availability,
        slo_p99_ms=cfg.serve_slo_p99_ms,
        slo_window_s=cfg.serve_slo_window_s,
        slo_burn=cfg.serve_slo_burn,
        binary_port=cfg.serve_binary_port,
        binary_accept_threads=cfg.serve_binary_accept_threads,
        quality_sample=cfg.quality_sample,
        quality_audit_sample=cfg.quality_audit_sample,
        drift_threshold=cfg.drift_threshold,
        drift_window_s=cfg.drift_window_s,
        quality_min_rows=cfg.quality_min_rows,
        quality_topk=cfg.quality_topk,
        models=cfg.serve_models or None,
        hbm_budget_mb=cfg.serve_hbm_budget_mb,
        default_model_id=cfg.serve_default_model,
        explain_max_batch=cfg.serve_explain_max_batch,
        explain_queue_size=cfg.serve_explain_queue_size,
        explain_max_delay_ms=cfg.serve_explain_max_delay_ms)


def run_fleet(params: Dict[str, Any]) -> int:
    """Blocking CLI entry: serve the fleet until SIGTERM/SIGINT."""
    from .. import telemetry

    if not telemetry.enabled():
        telemetry.configure(enabled=True,
                            metrics_out=str(params.get("telemetry_out", ""))
                            or None)
    fleet = fleet_from_params(params).start()
    stop = threading.Event()

    def _graceful(signum, frame):
        log_info(f"signal {signum}: draining serving fleet")
        stop.set()

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)
    try:
        stop.wait()
    finally:
        fleet.stop()
        log_info("serving fleet stopped")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if len(argv) == 3 and argv[0] == "--replica":
        return _replica_main(argv[1], int(argv[2]))
    print("usage: python -m lightgbm_tpu.serving.fleet --replica "
          "<spec.json> <rank>\n(the fleet supervisor spawns this; start "
          "a fleet with: python -m lightgbm_tpu.serve "
          "input_model=model.txt serve_replicas=3)")
    return 1


if __name__ == "__main__":
    sys.exit(main())
