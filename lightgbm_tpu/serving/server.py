"""JSON-over-HTTP serving front end (stdlib ``http.server`` only).

HTTP/1.1 with keep-alive: a client reusing its connection pays the TCP
handshake once, not per request.  ``serve_binary_port >= 0`` additionally
opens the persistent-connection binary row wire (:mod:`.wire`) next to
HTTP — same registry and micro-batcher, length-prefixed f32 frames
instead of JSON (docs/SERVING.md "Binary wire protocol") — the 10k+ QPS
path.

Endpoints:

  ``POST /predict``  body {"rows": [[...], ...]} or {"row": [...]},
                     optional "raw_score" (bool), "fast" (bool — run a
                     single row synchronously on the native walk, no
                     queueing) and "model_id" (multi-tenant routing;
                     unknown ids reply 400); replies {"predictions",
                     "model_version", "batched_rows", "latency_ms"} plus
                     "model_id"/"model_sha256".  A full queue replies
                     503 with the structured overload payload; shape
                     errors reply 400.
  ``POST /explain``  same body shape (no "fast"); replies per-row SHAP
                     contributions under "contributions" — exactly the
                     reference's ``pred_contrib`` layout, k*(n_features
                     +1) values per row with the expected value last per
                     class.  Runs on its OWN micro-batcher lane
                     (``serve_explain_*`` knobs) so heavy explanation
                     traffic cannot starve the predict path.
  ``GET  /health``   LIVENESS only: is the process up and the batch
                     worker thread alive (503 when the worker died).
  ``GET  /ready``    READINESS: queue depth, active model version +
                     sha256, promotion generation, degraded state and
                     heartbeat age — what a fleet front or supervisor
                     keys routing off (503 while draining / dead /
                     model-less).
  ``POST /reload``   {"path": optional} — validated atomic hot-swap; a
                     rejected candidate replies 409 and the old version
                     keeps serving.
  ``GET  /stats``    latency/queue-depth percentiles from the telemetry
                     registry, request counters, recompile watchdog
                     counts, model + registry info, SLO burn state and
                     the tail-capture ring.
  ``GET  /metrics``  Prometheus text exposition of the process metrics
                     registry (counters/gauges/cumulative-bucket
                     histograms); ``?format=json`` returns the raw
                     snapshot (what the fleet aggregate scrapes).

Distributed tracing (docs/OBSERVABILITY.md "Serving observability"): a
``/predict`` request carries its trace context in the ``X-LGBTPU-Trace``
header — accepted from the front (which minted the id and the
head-sampling decision) or minted here for direct clients.  Sampled
requests emit spans through admission -> batcher queue wait -> device
dispatch; errored and SLO-violating requests are tail-captured into a
bounded ring regardless of sampling; every request can be access-logged
as JSONL (``serve_access_log``).

Request resilience (docs/SERVING.md "Fleet architecture"): a ``/predict``
body may carry ``deadline_ms`` — the client's remaining budget.  The
budget propagates through queue admission and the batcher's pre-dispatch
check, so expired requests are shed as structured 503s instead of being
scored for nobody.  Every shed 503 carries a ``Retry-After`` header.

Shutdown: ``shutdown(drain=True)`` (wired to SIGTERM/SIGINT by
``run_server``) stops accepting connections, lets the batcher drain
everything already queued, then returns — a rolling restart loses zero
admitted requests.
"""
from __future__ import annotations

import json
import math
import signal
import socket
import threading
import time
from concurrent.futures import CancelledError
from concurrent.futures import TimeoutError as FutureTimeoutError
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

import numpy as np

from ..robustness import chaos
from ..utils.log import LightGBMError, log_debug, log_info
from .batcher import DeadlineError, MicroBatcher, OverloadError
from .registry import ModelRegistry

_REQUEST_TIMEOUT_S = 30.0


def _jsonable(values: np.ndarray):
    v = np.asarray(values)
    return v.tolist()


def reuseport_available() -> bool:
    """Can several sockets share one listen port on this platform?
    (SO_REUSEPORT kernel load-balancing — Linux >= 3.9 and the BSDs;
    absent on some platforms, where the fleet uses the fanout front.)"""
    if not hasattr(socket, "SO_REUSEPORT"):
        return False
    try:
        with socket.socket() as a, socket.socket() as b:
            for s in (a, b):
                s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
            a.bind(("127.0.0.1", 0))
            b.bind(("127.0.0.1", a.getsockname()[1]))
        return True
    except OSError:
        return False


class _ReusePortHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer that joins an SO_REUSEPORT group before bind,
    so N replica processes share one listen port and the kernel balances
    accepted connections across them."""

    def server_bind(self):
        self.socket.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
        ThreadingHTTPServer.server_bind(self)


class ServingApp:
    """Registry + batcher + HTTP server, wired together."""

    def __init__(self, model_path: str, *, host: str = "127.0.0.1",
                 port: int = 0, max_batch: int = 256,
                 max_delay_ms: float = 2.0, queue_size: int = 512,
                 buckets_spec: str = "", warmup: bool = True,
                 heartbeat_path: str = "", deadline_ms: float = 0.0,
                 reuse_port: bool = False, trace_sample: float = 0.01,
                 trace_tail: int = 256, access_log: str = "",
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
                 explain_max_delay_ms: float = 2.0):
        from ..telemetry import AccessLog, TailRing
        from ..telemetry.quality import QualityMonitor
        from .slo import SLOMonitor

        # multi-tenant: serve_models roster -> HBM-resident LRU cache of
        # tenant registries (docs/SERVING.md "Multi-tenant serving");
        # single-model keeps the flat registry surface unchanged
        self.multi = bool(models)
        if self.multi:
            from .multimodel import MultiModelRegistry
            self.registry = MultiModelRegistry(
                models, max_batch=max_batch, buckets_spec=buckets_spec,
                warmup=warmup, hbm_budget_mb=hbm_budget_mb,
                default_id=default_model_id or None)
        else:
            self.registry = ModelRegistry(model_path, max_batch=max_batch,
                                          buckets_spec=buckets_spec,
                                          warmup=warmup)
        self.batcher = MicroBatcher(self.registry, max_batch=max_batch,
                                    max_delay_ms=max_delay_ms,
                                    queue_size=queue_size,
                                    heartbeat_path=heartbeat_path)
        # the explain lane: its own bounded queue + worker + bucket
        # ladder, so deadline-bounded SHAP traffic coalesces on device
        # without starving /predict
        self.explain_batcher = MicroBatcher(
            self.registry, max_batch=explain_max_batch,
            max_delay_ms=explain_max_delay_ms,
            queue_size=explain_queue_size, mode="explain")
        server_cls = _ReusePortHTTPServer if reuse_port \
            else ThreadingHTTPServer
        self._httpd = server_cls((host, int(port)), _Handler)
        self._httpd.daemon_threads = True
        self._httpd.app = self          # handler back-pointer
        # binary row wire next to HTTP (serve_binary_port >= 0; 0 picks
        # an ephemeral port) — same registry + batcher, frames instead of
        # JSON (docs/SERVING.md "Binary wire protocol")
        self.binary = None
        if int(binary_port) >= 0:
            from .wire import BinaryServer
            self.binary = BinaryServer(self, host=host,
                                       port=int(binary_port),
                                       accept_threads=binary_accept_threads,
                                       reuse_port=reuse_port)
        self._thread: Optional[threading.Thread] = None
        self._draining = False
        # default per-request budget (ms) when the body carries no
        # deadline_ms; 0 = unbounded (legacy 30 s future-wait only)
        self.deadline_ms = float(deadline_ms or 0.0)
        # fleet-runtime state (set by serving.fleet's replica loop;
        # standalone servers keep the defaults)
        self.replica_rank: Optional[int] = None
        self.generation: Optional[int] = None
        self.seen_generation: Optional[int] = None
        self.degraded: Optional[str] = None
        # fleet replicas route /reload through the shared promotion
        # pointer so ANY replica's reload is fleet-wide; standalone
        # servers keep the registry-local swap
        self.promote_fn = None
        # request observability (docs/OBSERVABILITY.md "Serving
        # observability"): head-sampled trace spans, tail capture of
        # errored/SLO-violating requests, JSONL access log, and the
        # error-budget burn monitor feeding /ready + /metrics
        self.trace_sample = max(float(trace_sample), 0.0)
        self.tail = TailRing(trace_tail)
        self.access_log = AccessLog(access_log) if access_log else None
        self.slo = SLOMonitor(availability_target=slo_availability,
                              p99_target_ms=slo_p99_ms,
                              window_s=slo_window_s,
                              burn_threshold=slo_burn)
        # per-tenant SLO isolation (multi only): one burn monitor per
        # model_id so one tenant's chaos fires ITS alert while siblings
        # stay green; the flat self.slo keeps judging the whole replica
        self.slo_by_model: Dict[str, Any] = {}
        if self.multi:
            self.slo_by_model = {
                mid: SLOMonitor(availability_target=slo_availability,
                                p99_target_ms=slo_p99_ms,
                                window_s=slo_window_s,
                                burn_threshold=slo_burn)
                for mid in self.registry.model_ids()}
        # data/model quality: drift monitor + shadow audit riding the
        # batcher dispatch path; the sidecar profile follows the registry
        # model (docs/OBSERVABILITY.md "Data & model quality").  Multi-
        # tenant apps run one monitor per model_id — each tenant's drift
        # window accumulates only its own traffic — and self.quality
        # aliases the default tenant's monitor so the flat /drift surface
        # keeps working
        self.quality_by_model: Dict[str, Any] = {}
        if self.multi:
            for mid in self.registry.model_ids():
                self.quality_by_model[mid] = QualityMonitor(
                    threshold=drift_threshold, window_s=drift_window_s,
                    sample=quality_sample,
                    audit_sample=quality_audit_sample,
                    min_rows=quality_min_rows, topk=quality_topk)
            self.quality = self.quality_by_model[self.registry.default_id]
        else:
            self.quality = QualityMonitor(threshold=drift_threshold,
                                          window_s=drift_window_s,
                                          sample=quality_sample,
                                          audit_sample=quality_audit_sample,
                                          min_rows=quality_min_rows,
                                          topk=quality_topk)
        if self.quality.enabled:
            if self.multi:
                self.batcher.quality_lookup = self._quality_for
            else:
                self.batcher.quality = self.quality
        # per-replica drift snapshot export for the fleet report CLI
        # (set by serving.fleet's replica loop)
        self.drift_export_path: str = ""
        # the SLO ticker runs on its own loop (not per-request) so an
        # alert also CLEARS while the replica is idle — e.g. when the
        # front stopped routing here because of the very burn that fired
        self._slo_stop = threading.Event()
        self._slo_thread: Optional[threading.Thread] = None
        self.t0 = time.time()

    @property
    def host(self) -> str:
        return self._httpd.server_address[0]

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    @property
    def binary_port(self) -> Optional[int]:
        return self.binary.port if self.binary is not None else None

    @property
    def draining(self) -> bool:
        return self._draining

    def _quality_for(self, model_id: str):
        """Batcher hook: route quality accumulation to the tenant's own
        monitor (falls back to the default tenant's for legacy "")."""
        q = self.quality_by_model.get(model_id) if model_id \
            else self.quality
        return q if (q is not None and q.enabled) else None

    def _slo_loop(self) -> None:
        while not self._slo_stop.wait(1.0):
            # per-model monitors tick FIRST so the aggregate's gauges win
            # the shared slo/* gauge names
            for mon in self.slo_by_model.values():
                mon.tick()
            self.slo.tick()
            if self.quality.enabled:
                try:
                    if self.multi:
                        for mid, q in self.quality_by_model.items():
                            # peek, never current(): a 1 Hz tick must not
                            # readmit evicted tenants or touch the LRU
                            model = self.registry.peek(mid)
                            if model is not None:
                                q.tick(model=model)
                            q.audit_once()
                    else:
                        self.quality.tick(model=self.registry.current())
                        self.quality.audit_once()
                    if self.drift_export_path:
                        from ..telemetry.quality import write_snapshot
                        write_snapshot(self.drift_export_path,
                                       self.quality.snapshot())
                except Exception as e:   # noqa: BLE001 — ticker survives
                    log_debug(f"quality tick failed: {e}")

    def start(self) -> "ServingApp":
        """Non-blocking start (tests, embedding); ``run_server`` blocks."""
        self.batcher.start()
        self.explain_batcher.start()
        self._slo_thread = threading.Thread(target=self._slo_loop,
                                            name="lgbtpu-serve-slo",
                                            daemon=True)
        self._slo_thread.start()
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="lgbtpu-serve-http",
                                        daemon=True)
        self._thread.start()
        if self.binary is not None:
            self.binary.start()
        log_info(f"serving on http://{self.host}:{self.port} "
                 + (f"+ binary :{self.binary.port} "
                    if self.binary is not None else "")
                 + f"(model v{self.registry.version})")
        return self

    def shutdown(self, drain: bool = True) -> None:
        """Stop accepting, drain the queue (unless ``drain=False``), stop
        the worker.  Idempotent."""
        self._draining = True
        self._slo_stop.set()
        if self.binary is not None:
            self.binary.stop_accepting()
        self._httpd.shutdown()
        self._httpd.server_close()
        self.batcher.stop(drain=drain)
        self.explain_batcher.stop(drain=drain)
        if self.binary is not None:
            self.binary.stop()      # after the drain: futures resolved
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(5.0)
        if self._slo_thread is not None and self._slo_thread.is_alive():
            self._slo_thread.join(2.0)
        if self.access_log is not None:
            self.access_log.close()

    def note_request(self, ctx, status: int, latency_ms: float,
                     deadline_ms: float, obj: Dict[str, Any]) -> None:
        """Per-request bookkeeping after the response is decided: SLO
        outcome, access-log line, tail capture of the interesting ones.
        Must never raise — it runs on the answer path."""
        from ..telemetry.context import note_outcome

        extra: Dict[str, Any] = {"rows": obj.get("batched_rows")}
        if self.replica_rank is not None:
            extra["replica"] = self.replica_rank
        # drift snapshot rides the access log only while the alert is
        # active — healthy traffic logs stay lean
        drift = self.quality.brief()
        if drift is not None:
            extra["drift"] = drift
        # per-tenant SLO isolation: the request's model_id (stamped into
        # the response, error paths included) burns ONLY that model's
        # window — chaos against tenant A never pages tenant B
        mid = obj.get("model_id")
        mon = self.slo_by_model.get(mid) if mid else None
        if mon is not None:
            mon.record(status, latency_ms)
        # replicas see single attempts (retries=0); the front stamps
        # real retry counts in ITS log
        note_outcome(ctx=ctx, status=status, latency_ms=latency_ms,
                     deadline_ms=deadline_ms, obj=obj, slo=self.slo,
                     tail=self.tail, access_log=self.access_log,
                     extra=extra)


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    # -- plumbing ----------------------------------------------------------
    def log_message(self, fmt, *args):   # route access logs off stderr
        log_debug("serve http: " + fmt % args)

    @property
    def app(self) -> ServingApp:
        return self.server.app

    def _send(self, code: int, obj: Dict[str, Any],
              headers: Optional[Dict[str, str]] = None) -> None:
        body = json.dumps(obj).encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _send_text(self, code: int, text: str, content_type: str) -> None:
        body = text.encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _drop_connection(self) -> None:
        """Chaos ``drop_conn``: reset the client socket mid-request —
        the transport failure the fanout front must absorb as a retry."""
        try:
            self.connection.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.close_connection = True

    def _read_json(self) -> Dict[str, Any]:
        length = int(self.headers.get("Content-Length", 0) or 0)
        raw = self.rfile.read(length) if length else b"{}"
        try:
            obj = json.loads(raw.decode("utf-8") or "{}")
        except ValueError as e:
            raise LightGBMError(f"request body is not valid JSON: {e}")
        if not isinstance(obj, dict):
            raise LightGBMError("request body must be a JSON object")
        return obj

    # -- routes ------------------------------------------------------------
    def do_GET(self):   # noqa: N802 — http.server API
        from .. import telemetry

        path = self.path.split("?")[0]
        try:
            chaos.request_hook()
        except chaos.DropConnection:
            self._drop_connection()
            return
        if path == "/health":
            self._send(*self._health())
        elif path == "/ready":
            self._send(*self._ready())
        elif path == "/stats":
            with telemetry.span("serve/stats"):
                self._send(200, self._stats())
        elif path == "/drift":
            # data/model quality surface: alert state, top-k drifted
            # features with PSI/JS, shadow-audit totals; available:false
            # (never zeros) when the model has no quality sidecar
            self._send(200, self.app.quality.snapshot())
        elif path == "/metrics":
            # Prometheus text exposition of the process registry;
            # ?format=json returns the raw snapshot (what the fleet
            # aggregator scrapes to relabel under replica="<r>")
            from ..telemetry.prometheus import CONTENT_TYPE, registry_text
            query = self.path.partition("?")[2]
            if "format=json" in query:
                self._send(200, telemetry.global_registry.snapshot())
            else:
                labels = {}
                if self.app.replica_rank is not None:
                    labels["replica"] = str(self.app.replica_rank)
                self._send_text(200, registry_text(labels=labels),
                                CONTENT_TYPE)
        else:
            self._send(404, {"error": f"unknown path {self.path!r}"})

    def do_POST(self):   # noqa: N802
        from .. import telemetry

        path = self.path.split("?")[0]
        headers: Dict[str, str] = {}
        ctx = None
        t_req = time.perf_counter()
        deadline_ms = 0.0
        req_model_id = ""
        try:
            # the body must be consumed on EVERY branch — HTTP/1.1
            # keep-alive leaves unread bytes in rfile and the next request
            # on the connection would parse mid-body
            body = self._read_json()
            chaos.request_hook()
            if path in ("/predict", "/explain"):
                # trace context: accept the front's (or client's) header,
                # mint locally otherwise — the head-sampling decision is
                # taken exactly once per request, at the outermost tier
                ctx = telemetry.TraceContext.from_header(
                    self.headers.get(telemetry.TRACE_HEADER))
                if ctx is None:
                    ctx = telemetry.TraceContext.mint(self.app.trace_sample)
                try:
                    deadline_ms = float(body.get("deadline_ms",
                                                 self.app.deadline_ms)
                                        or 0.0)
                except (TypeError, ValueError):
                    deadline_ms = 0.0
                req_model_id = str(body.get("model_id") or "")
                with telemetry.request_span(
                        ctx, "serve" + path,
                        replica=self.app.replica_rank):
                    if path == "/predict":
                        code, obj = self._predict(body, ctx)
                    else:
                        code, obj = self._explain(body, ctx)
            elif path == "/reload":
                with telemetry.span("serve/reload"):
                    code, obj = self._reload(body)
            else:
                code, obj = 404, {"error": f"unknown path {self.path!r}"}
        except chaos.DropConnection:
            self._drop_connection()
            return
        except OverloadError as e:
            code, obj = 503, e.payload()
            # RFC 7231 Retry-After is integer seconds; the structured
            # body carries the float for backoff-aware clients
            headers["Retry-After"] = str(
                max(int(math.ceil(e.retry_after_s)), 0))
        except LightGBMError as e:
            code, obj = 400, {"error": str(e)}
        except CancelledError:
            # shutdown(drain=False) cancelled the future mid-wait; on
            # CPython >= 3.8 CancelledError is a BaseException, so the
            # generic net below would miss it and reset the connection
            code, obj = 503, {"error": "shutting down"}
        except Exception as e:  # noqa: BLE001 — serving must answer
            code, obj = 500, {"error": f"{type(e).__name__}: {e}"}
        if req_model_id:
            # error replies carry the routing key too, so per-model SLO
            # attribution (note_request) sees failures, not just 200s
            obj.setdefault("model_id", req_model_id)
        if ctx is not None:
            obj.setdefault("trace_id", ctx.trace_id)
            headers[telemetry.TRACE_HEADER] = ctx.header_value()
            try:
                self.app.note_request(
                    ctx, code, (time.perf_counter() - t_req) * 1e3,
                    deadline_ms, obj)
            except Exception as e:  # noqa: BLE001 — never fail the answer
                log_debug(f"serve note_request failed: {e}")
        self._send(code, obj, headers or None)

    def _predict(self, body, ctx=None):
        return self._scored(body, ctx, self.app.batcher, "predictions")

    def _explain(self, body, ctx=None):
        """Device-batched SHAP on the explain lane — the values are the
        reference's ``pred_contrib`` contract verbatim."""
        return self._scored(body, ctx, self.app.explain_batcher,
                            "contributions")

    def _scored(self, body, ctx, batcher, values_key: str):
        app = self.app
        if app.draining:
            raise OverloadError(batcher.queue_depth(),
                                batcher.queue_size, reason="draining",
                                retry_after_s=1.0)
        rows = body.get("rows", body.get("row"))
        if rows is None:
            kind = "predict" if values_key == "predictions" else "explain"
            return 400, {"error": f'{kind} body needs "rows" (matrix) '
                                  'or "row" (vector)'}
        t0 = time.perf_counter()
        # client budget: body deadline_ms overrides the server default;
        # <= 0 means "no deadline" either way
        try:
            budget_ms = float(body.get("deadline_ms", app.deadline_ms) or 0.0)
        except (TypeError, ValueError):
            return 400, {"error": "deadline_ms must be a number"}
        deadline = t0 + budget_ms / 1e3 if budget_ms > 0 else None
        fut = batcher.submit(rows,
                             raw_score=bool(body.get("raw_score", False)),
                             fast=bool(body.get("fast", False)),
                             deadline=deadline, trace=ctx,
                             model_id=str(body.get("model_id") or "")
                             or None)
        wait = _REQUEST_TIMEOUT_S if deadline is None else \
            max(deadline - time.perf_counter(), 0.0)
        try:
            res = fut.result(timeout=wait)
        except FutureTimeoutError:
            # the wait itself ran out the budget: report it as the same
            # structured deadline shed the batcher would have raised
            fut.cancel()
            raise DeadlineError(batcher.queue_depth(),
                                batcher.queue_size)
        sha = res.sha256 or app.registry.sha_for_version(res.model_version)
        out = {
            values_key: _jsonable(res.values),
            "model_version": res.model_version,
            "model_sha256": sha,
            "batched_rows": res.batched_rows,
            "latency_ms": round((time.perf_counter() - t0) * 1e3, 3),
        }
        if res.model_id:
            out["model_id"] = res.model_id
        if app.replica_rank is not None:
            out["replica"] = app.replica_rank
        return 200, out

    def _reload(self, body):
        app = self.app
        mid = str(body.get("model_id") or "")
        if mid and not app.multi:
            return 400, {"error": "model_id routing needs serve_models "
                                  "(multi-tenant serving)"}
        path = str(body.get("path")
                   or app.registry.current(mid or None).path)
        if app.promote_fn is not None:
            # fleet replica: validate + advance the shared pointer; every
            # replica (this one included) applies it via its watcher
            try:
                return 200, (app.promote_fn(path, mid) if mid
                             else app.promote_fn(path))
            except LightGBMError as e:
                return 409, {"error": str(e),
                             "model_version": app.registry.version}
        try:
            model = (app.registry.load(path, mid) if mid
                     else app.registry.load(path))
        except LightGBMError as e:
            # the candidate was rejected; the old version keeps serving
            return 409, {"error": str(e),
                         "model_version": app.registry.version}
        out = {"model_version": model.version,
               "num_trees": model.num_trees,
               "sha256": model.sha256}
        if mid:
            out["model_id"] = mid
        return 200, out

    def _health(self):
        """LIVENESS: is this process worth keeping alive?  Deliberately
        ignores model/queue state — a draining or degraded replica is
        still alive; restarting it would lose work for nothing."""
        from ..robustness.heartbeat import heartbeat_age

        app = self.app
        alive = app.batcher.worker_alive
        out: Dict[str, Any] = {
            "status": ("draining" if app.draining
                       else "ok" if alive else "dead"),
            "model_version": app.registry.version,
            "uptime_s": round(time.time() - app.t0, 3),
            "queue_depth": app.batcher.queue_depth(),
            "worker_alive": alive,
        }
        if app.batcher.heartbeat_path:
            age = heartbeat_age(app.batcher.heartbeat_path)
            if age is not None:
                out["heartbeat_age_s"] = round(age, 3)
        return (200 if alive else 503), out

    def _ready(self):
        """READINESS: should traffic be routed here right now?  The
        fanout front and the fleet supervisor key off THIS (not
        liveness): a replica that is draining, model-less, or whose
        worker died gets no traffic but is reaped/restarted only on
        liveness signals.  A degraded replica (rejected promotion
        candidate) stays ready — it serves its old version — and
        surfaces the reason here."""
        from ..robustness.heartbeat import heartbeat_age

        app = self.app
        b = app.batcher
        ready = (b.worker_alive and not app.draining
                 and app.registry.version > 0)
        out: Dict[str, Any] = {
            "ready": ready,
            "queue_depth": b.queue_depth(),
            "queue_size": b.queue_size,
            "model_version": app.registry.version,
            "draining": app.draining,
        }
        cur = None
        try:
            cur = app.registry.current()
        except LightGBMError:
            pass
        if cur is not None:
            out["model_sha256"] = cur.sha256
        if app.replica_rank is not None:
            out["replica"] = app.replica_rank
        if app.generation is not None:
            out["generation"] = app.generation
        if app.seen_generation is not None:
            out["seen_generation"] = app.seen_generation
        # degraded reasons compose: a rejected promotion and a burning
        # error budget are both "degraded but still serving" states —
        # neither flips readiness (unrouting a replica because it is slow
        # would finish the outage), both must be visible to the fleet
        reasons = []
        if app.degraded:
            reasons.append(app.degraded)
        slo_state = app.slo.state()
        if slo_state["alerting"]:
            out["slo_alert"] = slo_state["alert"]
            reasons.append(f"slo burn: {slo_state['alert']} error budget "
                           f"burning >= {app.slo.burn_threshold:.1f}x")
        if not app.multi and app.quality.alerting:
            # drift is a quality degradation, not an outage: the replica
            # keeps serving (stale != broken), the reason surfaces here
            # and the refit pipeline keys off the drift/* gauges
            out["drift_alert"] = True
            reasons.append(f"data drift: PSI >= "
                           f"{app.quality.threshold:g} vs training "
                           "reference (see /drift)")
        if app.multi:
            # per-tenant readiness: each model's version/sha/residency
            # and ITS OWN alert state — one tenant's burn or drift names
            # only that tenant in the degraded reason, siblings stay
            # green (the isolation contract)
            models_out: Dict[str, Any] = {}
            for mid in app.registry.model_ids():
                reg = app.registry.tenant(mid)
                resident = reg.peek()
                m: Dict[str, Any] = {
                    "version": reg.version,
                    "resident": resident is not None,
                }
                if resident is not None:
                    m["sha256"] = resident.sha256
                if reg.generation is not None:
                    m["generation"] = reg.generation
                if reg.seen_generation is not None:
                    m["seen_generation"] = reg.seen_generation
                mon = app.slo_by_model.get(mid)
                if mon is not None:
                    mstate = mon.state()
                    if mstate["alerting"]:
                        m["slo_alert"] = mstate["alert"]
                        reasons.append(
                            f"model {mid}: slo burn {mstate['alert']}")
                q = app.quality_by_model.get(mid)
                if q is not None and q.alerting:
                    m["drift_alert"] = True
                    reasons.append(f"model {mid}: data drift (PSI >= "
                                   f"{q.threshold:g})")
                models_out[mid] = m
            out["models"] = models_out
        if reasons:
            out["degraded"] = "; ".join(reasons)
        if b.heartbeat_path:
            age = heartbeat_age(b.heartbeat_path)
            if age is not None:
                out["heartbeat_age_s"] = round(age, 3)
        return (200 if ready else 503), out

    def _stats(self) -> Dict[str, Any]:
        from .. import telemetry

        app = self.app
        out = {
            "uptime_s": round(time.time() - app.t0, 3),
            "registry": app.registry.stats(),
            "queue_depth": app.batcher.queue_depth(),
            "served": app.batcher.served,
            "batches": app.batcher.batches,
            "rejected": app.batcher.rejected,
            "deadline_expired": app.batcher.expired,
            "explain": {
                "served": app.explain_batcher.served,
                "batches": app.explain_batcher.batches,
                "rejected": app.explain_batcher.rejected,
                "deadline_expired": app.explain_batcher.expired,
                "queue_depth": app.explain_batcher.queue_depth(),
                "dispatch": telemetry.quantiles(
                    "serve/explain/dispatch_s"),
            },
            "degraded": app.degraded,
            "generation": app.generation,
            "latency": telemetry.quantiles("serve/latency_s"),
            "dispatch": telemetry.quantiles("serve/dispatch_s"),
            "batch_rows": telemetry.quantiles("serve/batch_rows"),
            "queue_depth_dist": telemetry.quantiles("serve/queue_depth"),
            "recompiles": {k: v for k, v in
                           telemetry.recompile_counts().items()
                           if k.startswith("serve")},
            # XLA cost records for the serving entry points (flops/bytes/
            # peak HBM + roofline verdict per compiled bucket program);
            # the full rollup incl. roofline peaks rides telemetry_summary
            "cost": telemetry.cost_summary(),
            "slo": app.slo.state(),
            "quality": {"available": app.quality.snapshot().get(
                            "available", False),
                        "alerting": app.quality.alerting,
                        "sample": app.quality.sample,
                        "audit_sample": app.quality.audit_sample},
            "trace_tail": app.tail.snapshot(last=20),
            "trace_sample": app.trace_sample,
            "binary": (app.binary.stats() if app.binary is not None
                       else None),
        }
        if app.multi:
            out["slo_models"] = {
                mid: {"alerting": mon.state()["alerting"],
                      "alert": mon.state()["alert"]}
                for mid, mon in app.slo_by_model.items()}
            out["quality_models"] = {
                mid: {"alerting": q.alerting}
                for mid, q in app.quality_by_model.items()}
        return out


def serve_from_params(params: Dict[str, Any]) -> ServingApp:
    """Build (not start) a ServingApp from resolved CLI/conf params."""
    from ..config import Config

    cfg = Config.from_params(params)
    model_path = str(params.get("input_model", "") or "")
    if not model_path and not cfg.serve_models:
        raise LightGBMError("task=serve requires input_model=<model file> "
                            "or serve_models=<id=path,...>")
    return ServingApp(
        model_path,
        models=cfg.serve_models or None,
        hbm_budget_mb=cfg.serve_hbm_budget_mb,
        default_model_id=cfg.serve_default_model,
        explain_max_batch=cfg.serve_explain_max_batch,
        explain_queue_size=cfg.serve_explain_queue_size,
        explain_max_delay_ms=cfg.serve_explain_max_delay_ms,
        host=cfg.serve_host, port=cfg.serve_port,
        max_batch=cfg.serve_max_batch,
        max_delay_ms=cfg.serve_max_delay_ms,
        queue_size=cfg.serve_queue_size,
        buckets_spec=cfg.serve_buckets,
        warmup=cfg.serve_warmup,
        heartbeat_path=cfg.serve_heartbeat,
        deadline_ms=cfg.serve_deadline_ms,
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
        quality_topk=cfg.quality_topk)


def run_server(params: Dict[str, Any]) -> int:
    """Blocking CLI entry: serve until SIGTERM/SIGINT, then drain.
    ``serve_replicas > 1`` dispatches to the fleet supervisor
    (docs/SERVING.md "Fleet architecture") instead of one in-process
    server."""
    from .. import telemetry
    from ..config import Config
    from ..runtime import configure_compile_cache

    configure_compile_cache()
    if Config.from_params(params).serve_replicas > 1:
        from .fleet import run_fleet
        return run_fleet(params)
    if not telemetry.enabled():
        # serving without its latency histograms is flying blind; the
        # CLI turns the registry on (spans stay off unless trace_out set)
        telemetry.configure(enabled=True,
                            metrics_out=str(params.get("telemetry_out", ""))
                            or None)
    app = serve_from_params(params).start()
    stop = threading.Event()

    def _graceful(signum, frame):
        log_info(f"signal {signum}: draining serving queue")
        stop.set()

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)
    try:
        stop.wait()
    finally:
        app.shutdown(drain=True)
        log_info(f"serving stopped after {app.batcher.served} requests "
                 f"({app.batcher.rejected} shed)")
    return 0
