"""HTTP frontend for the continuous-batching engine.

Same stdlib ThreadingHTTPServer idiom as portal/server.py — serving is an
I/O-bound request/response surface; the compute plane lives in the engine's
single stepper thread, so handler threads only enqueue and wait on token
streams.

Routes:
- ``POST /v1/generate`` — body ``{"prompt": [ids...], "max_new_tokens": N,
  "stream": bool}``. Blocking mode returns one JSON object with the
  generated tokens; ``stream=true`` returns chunked JSON-lines, one token
  object per line, ending with a ``{"done": true, ...}`` record (the
  chunked framing IS the streaming contract — no SSE dependency).
- ``GET /healthz`` — liveness (tokenless, like the portal's).
- ``GET /v1/metrics`` — engine gauge snapshot (TTFT, ITL, queue depth,
  slot occupancy, tokens/sec). Default is the JSON snapshot (the wire
  contract tools already consume); a Prometheus scraper gets text
  exposition instead — selected by ``?format=prometheus`` or an
  ``Accept`` header asking for ``text/plain``/OpenMetrics (what a real
  Prometheus sends). Bare ``GET /metrics`` is always exposition. The
  exposition carries the engine gauges (labels
  ``{app_id, task_type, index, attempt}`` when running orchestrated)
  plus this process's health registry (RPC client latency,
  metrics-push drops).

Backpressure: the engine's bounded queue + queued-token budget surface as
HTTP 429 with ``Retry-After`` (clean open-loop shedding); a request that
can NEVER fit the per-slot token budget is a 400 — retrying it would
never help.

Disaggregation (serve/kvcache.py wire format): a ``role="decode"``
replica accepts ``POST /v1/migrate`` — a packed prefill handoff — and
streams the decoded tokens back as chunked JSON lines. A
``role="prefill"`` frontend (constructed with ``migrate_targets``)
admits ``/v1/generate`` work with ``migrate_out=True``, POSTs the
resulting payload to a decode replica (round-robin, skipping refusals),
and relays the decode stream to the client behind the first token it
already holds; if EVERY decode replica refuses, it self-installs and
finishes locally — a degraded fleet slows down, it never drops work.
"""

from __future__ import annotations

import itertools
import json
import logging
import os
import threading
import time
import urllib.error
import urllib.request
from functools import partial
from http.server import BaseHTTPRequestHandler
from typing import Optional
from urllib.parse import parse_qs, urlparse

from tony_tpu.observability import reqtrace
from tony_tpu.observability.spans import span
from tony_tpu.serve import kvcache as kvc
from tony_tpu.serve.engine import (
    BudgetExceededError, ContinuousBatchingEngine, DrainingError,
    QueueFullError,
)

LOG = logging.getLogger(__name__)

# round-robin start index across this process's migrate relays, so one
# prefill replica spreads handoffs over the decode pool
_MIGRATE_RR = itertools.count()


def engine_prometheus_text(engine: ContinuousBatchingEngine,
                           collector=None) -> str:
    """Engine snapshot + this process's health registry as Prometheus
    text exposition — the serving half of the shared encoder contract
    (observability/prometheus.py). Orchestrated runs label every engine
    gauge with {app_id, task_type, index, attempt} from the task env.
    A request-trace collector contributes its TTFT-attribution rollup
    (serving_ttft_attr_<component>_ms_p50/p95)."""
    from tony_tpu import constants as C
    from tony_tpu.observability.metrics import REGISTRY
    from tony_tpu.observability.prometheus import render, task_metric_name

    labels = {}
    for key, env_name in (("app_id", C.APP_ID), ("task_type", C.JOB_NAME),
                          ("index", C.TASK_INDEX),
                          ("attempt", C.TASK_ATTEMPT)):
        value = os.environ.get(env_name)
        if value:
            labels[key] = value
    snap = engine.snapshot()
    families = []
    for key in sorted(snap):
        value = snap[key]
        if not isinstance(value, (int, float)) or isinstance(value, bool):
            continue
        name = task_metric_name(f"serving_{key}")
        families.append({"name": name, "type": "gauge", "help": "",
                         "samples": [(labels, float(value))]})
    # None gauges (no traffic yet: ttft/itl) are NaN, not absent — a
    # scraper's absent-metric alert must not fire on an idle server
    for key in sorted(k for k, v in snap.items() if v is None):
        name = task_metric_name(f"serving_{key}")
        families.append({"name": name, "type": "gauge", "help": "",
                         "samples": [(labels, float("nan"))]})
    if collector is not None:
        for key, value in sorted(collector.attribution.gauges().items()):
            families.append({
                "name": task_metric_name(f"serving_{key}"),
                "type": "gauge", "help": "",
                "samples": [(labels, float(value))]})
    return render(families + REGISTRY.families())

MAX_BODY_BYTES = 8 * 1024 * 1024
# migration payloads carry real K/V bytes (L*Hkv*pos*hd per leaf), far
# past the JSON request bound
MAX_MIGRATE_BYTES = 1024 * 1024 * 1024
# streaming stall guard: an engine wedged mid-request must not pin the
# handler thread forever (the engine emits shutdown sentinels on stop, so
# this only fires on a genuinely hung stepper)
STREAM_TOKEN_TIMEOUT_SEC = 300.0


class _Handler(BaseHTTPRequestHandler):
    engine: ContinuousBatchingEngine      # injected by ServeFrontend
    migrate_targets: tuple = ()           # decode-replica base URLs
    on_migrated = None                    # hook(target_url) per handoff
    collector = None                      # ReqTraceCollector (optional)
    # per-path request counts, exported on /v1/traces — the accounting
    # that lets a test PROVE trace export added no per-request RPCs
    path_counts: dict = {}
    path_counts_lock = threading.Lock()
    protocol_version = "HTTP/1.1"         # keep-alive + chunked streaming

    def log_message(self, fmt, *args):    # route through logging
        LOG.debug("serve: " + fmt, *args)

    def _count(self, path: str) -> None:
        with self.path_counts_lock:
            self.path_counts[path] = self.path_counts.get(path, 0) + 1

    # -- plumbing -------------------------------------------------------
    def _json(self, obj, code: int = 200,
              extra_headers: Optional[dict] = None) -> None:
        data = (json.dumps(obj) + "\n").encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", "application/json; charset=utf-8")
        self.send_header("Content-Length", str(len(data)))
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    def _error(self, code: int, message: str,
               extra_headers: Optional[dict] = None) -> None:
        self._json({"error": message}, code, extra_headers)

    # -- routes ---------------------------------------------------------
    def do_GET(self):  # noqa: N802 — BaseHTTPRequestHandler API
        parsed = urlparse(self.path)
        path = parsed.path.rstrip("/")
        self._count(path)
        if path == "/healthz":
            return self._json({"ok": True})
        if path == "/v1/traces":
            # PULL-only trace export: a non-destructive redacted
            # snapshot of the tail-sampled buffer, plus this process's
            # per-path request counts so a caller can audit that
            # tracing itself generated zero extra requests
            coll = self.collector
            with self.path_counts_lock:
                counts = dict(self.path_counts)
            return self._json({
                "process": coll.process if coll is not None else "",
                "traces": coll.export() if coll is not None else [],
                "http_requests": counts})
        if path == "/v1/load":
            # the fleet router's probe: a lock-free engine snapshot
            # (queue depth, free slots, draining, weights generation) —
            # deliberately NOT /v1/metrics, whose full percentile render
            # takes the engine lock per scrape
            return self._json({"ok": True, **self.engine.load()})
        if path in ("/v1/metrics", "/metrics"):
            if path == "/metrics" or self._wants_prometheus(parsed.query):
                from tony_tpu.observability.prometheus import CONTENT_TYPE
                data = engine_prometheus_text(
                    self.engine, self.collector).encode("utf-8")
                self.send_response(200)
                self.send_header("Content-Type", CONTENT_TYPE)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)
                return
            snap = dict(self.engine.snapshot())
            if self.collector is not None:
                snap.update(self.collector.attribution.gauges())
            return self._json(snap)
        self._error(404, "not found")

    def _wants_prometheus(self, query: str) -> bool:
        """Content negotiation on /v1/metrics: JSON stays the default
        (existing consumers send Accept: */*); a real Prometheus scraper
        asks for text/plain or OpenMetrics, and ?format=prometheus forces
        it for curl-by-hand."""
        fmt = (parse_qs(query).get("format") or [""])[0].lower()
        if fmt == "prometheus":
            return True
        if fmt == "json":
            return False
        accept = self.headers.get("Accept", "")
        return ("text/plain" in accept
                or "application/openmetrics-text" in accept)

    def do_POST(self):  # noqa: N802
        path = urlparse(self.path).path.rstrip("/")
        self._count(path)
        if path == "/v1/drain":
            # operator plane: begin connection draining (in-flight
            # requests finish, new submissions answer 503). Idempotent —
            # the response is the post-drain load snapshot so the caller
            # can poll queue_depth/active_slots down to zero. Drain is
            # irreversible (it precedes a stop), so on a secured cluster
            # it demands the task token — the request-plane endpoints
            # stay open, but anonymous traffic must not be able to take
            # the replica out of rotation (request_preemption parity).
            self._drain_body()
            import os

            from tony_tpu.security.tokens import TOKEN_ENV
            token = os.environ.get(TOKEN_ENV)
            if token and self.headers.get(
                    "Authorization", "") != f"Bearer {token}":
                return self._error(403, "drain requires the task token")
            self.engine.begin_drain()
            return self._json({"ok": True, **self.engine.load()})
        if path == "/v1/migrate":
            return self._handle_migrate()
        if path != "/v1/generate":
            # consume the body before answering: HTTP/1.1 keep-alive
            # would otherwise parse the unread bytes as the next request
            self._drain_body()
            return self._error(404, "not found")
        try:
            req = self._read_body()
        except ValueError as e:
            return self._error(400, str(e))
        try:
            prompt = [int(t) for t in req["prompt"]]
            max_new = int(req.get("max_new_tokens", 16))
            temperature = (float(req["temperature"])
                           if "temperature" in req else None)
        except (KeyError, TypeError, ValueError):
            return self._error(
                400, "body must be {'prompt': [token ids...], "
                     "'max_new_tokens': int, 'stream': bool}")
        # sampling is an ENGINE property (one compiled step, no
        # per-request variants): a mismatched ask is a contract error,
        # not something to silently coerce
        if temperature is not None and \
                temperature != self.engine.temperature:
            return self._error(
                400, f"engine is configured with temperature="
                     f"{self.engine.temperature}; per-request sampling "
                     f"overrides are not supported")
        migrate = bool(self.engine.role == "prefill"
                       and self.migrate_targets)
        # request-scoped trace: adopt the router's (or client's) context
        # from X-Tony-Trace, or mint a root — hop appends are in-process
        # list writes, the tail sampler decides keep/drop at completion
        ctx, _ = reqtrace.adopt_or_mint(
            self.headers.get(reqtrace.HEADER))
        t_ingress = time.monotonic()
        trace = (self.collector.trace(ctx)
                 if self.collector is not None else None)
        try:
            handle = self.engine.submit(prompt, max_new,
                                        migrate_out=migrate)
        except BudgetExceededError as e:
            self._finish_rejected(trace, t_ingress, 400)
            return self._error(400, str(e))
        except QueueFullError as e:
            self._finish_rejected(trace, t_ingress, 429, spilled=True)
            return self._error(429, str(e), {"Retry-After": "1"})
        except DrainingError as e:
            # the connection-draining contract: the router treats this as
            # "stop sending here" and fails the request over — the header
            # makes the state machine-readable without re-probing
            self._finish_rejected(trace, t_ingress, 503)
            return self._error(503, str(e), {"X-Tony-Draining": "1"})
        except RuntimeError as e:           # engine stopped
            self._finish_rejected(trace, t_ingress, 503)
            return self._error(503, str(e))
        if trace is not None:
            trace.request_id = str(handle.request_id)
        handle.trace = trace
        handle.trace_ctx = ctx
        if migrate:
            return self._generate_migrating(handle, req)
        if req.get("stream"):
            return self._stream(handle)
        try:
            tokens = handle.result(timeout=STREAM_TOKEN_TIMEOUT_SEC)
        except TimeoutError as e:
            # nobody is waiting anymore: free the slot/queue budget
            # instead of generating the rest into the void
            handle.cancel()
            return self._error(504, str(e))
        if handle.finish_reason == "shutdown":
            return self._error(503, "engine shut down mid-request")
        self._json({"tokens": tokens,
                    "finish_reason": handle.finish_reason,
                    "ttft_s": handle.ttft_s})

    def _finish_rejected(self, trace, t_ingress: float, status: int,
                         spilled: bool = False) -> None:
        """Sample a request that never got an engine slot: 429 spills
        and hard errors are unconditional keeps — exactly the traces an
        operator wants when the fleet is shedding."""
        if trace is None or self.collector is None:
            return
        now = time.monotonic()
        trace.hop("frontend.reject",
                  reqtrace.mono_to_wall_ms(t_ingress),
                  reqtrace.mono_to_wall_ms(now),
                  attrs={"http_status": status}, status="ERROR")
        self.collector.finish(trace, (now - t_ingress) * 1000.0,
                              error=not spilled, spilled=spilled)

    def _drain_body(self) -> None:
        """Read and discard the request body (bounded); an oversized one
        closes the connection instead — either way the next keep-alive
        request starts at a clean boundary."""
        length = int(self.headers.get("Content-Length", 0) or 0)
        if length <= 0:
            return
        if length > MAX_BODY_BYTES:
            self.close_connection = True
            return
        self.rfile.read(length)

    def _read_body(self) -> dict:
        length = int(self.headers.get("Content-Length", 0) or 0)
        if length <= 0:
            raise ValueError("missing request body")
        if length > MAX_BODY_BYTES:
            # unread body: this connection cannot carry another request
            self.close_connection = True
            raise ValueError("request body too large")
        try:
            body = json.loads(self.rfile.read(length).decode("utf-8"))
        except (UnicodeDecodeError, json.JSONDecodeError):
            raise ValueError("request body is not valid JSON") from None
        if not isinstance(body, dict):
            raise ValueError("request body must be a JSON object")
        return body

    def _write_chunk(self, request_id: int, obj) -> None:
        """One JSON line of a chunked body. `tony.frontend.write` is the
        handler threads' side of any GIL hand-off with the engine's loop:
        on the profiler's clock, beside the loop's tony.engine.* spans."""
        with span("tony.frontend.write", request_id=request_id):
            data = (json.dumps(obj) + "\n").encode("utf-8")
            self.wfile.write(f"{len(data):x}\r\n".encode("ascii")
                             + data + b"\r\n")

    def _stream(self, handle) -> None:
        """Chunked token stream: one JSON line per token, then the done
        record. A broken client connection just stops the writes — the
        engine finishes the request into the handle regardless."""
        self.send_response(200)
        self.send_header("Content-Type", "application/json; charset=utf-8")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        chunk = partial(self._write_chunk, handle.request_id)

        try:
            for token in handle.iter_tokens(
                    timeout=STREAM_TOKEN_TIMEOUT_SEC):
                chunk({"token": token})
            chunk({"done": True, "finish_reason": handle.finish_reason,
                   "n_tokens": len(handle.tokens),
                   "ttft_s": handle.ttft_s})
            self.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError, TimeoutError):
            LOG.debug("stream aborted (request %d)", handle.request_id)
            # the reader is gone: stop generating for it, and close this
            # keep-alive connection — its chunked body was never
            # terminated, so it cannot carry another request
            handle.cancel()
            self.close_connection = True

    # -- disaggregation: decode side ------------------------------------
    def _handle_migrate(self) -> None:
        """POST /v1/migrate: adopt a prefill replica's handoff (packed
        K/V + sampler state) and stream the decoded tokens back."""
        length = int(self.headers.get("Content-Length", 0) or 0)
        if length <= 0:
            return self._error(400, "missing migration body")
        if length > MAX_MIGRATE_BYTES:
            self.close_connection = True
            return self._error(413, "migration payload too large")
        body = self.rfile.read(length)
        try:
            meta, leaves = kvc.unpack_migration(body)
        except (ValueError, KeyError, TypeError) as e:
            return self._error(400, f"bad migration payload: {e}")
        # the decode replica CONTINUES the prefill replica's trace: the
        # forwarded X-Tony-Trace parents this process's hops under the
        # sender's migrate span
        ctx, _ = reqtrace.adopt_or_mint(
            self.headers.get(reqtrace.HEADER))
        t_ingress = time.monotonic()
        trace = (self.collector.trace(ctx)
                 if self.collector is not None else None)
        try:
            handle = self.engine.submit_migration(meta, leaves)
        except BudgetExceededError as e:
            self._finish_rejected(trace, t_ingress, 400)
            return self._error(400, str(e))
        except QueueFullError as e:
            self._finish_rejected(trace, t_ingress, 429, spilled=True)
            return self._error(429, str(e), {"Retry-After": "1"})
        except DrainingError as e:
            self._finish_rejected(trace, t_ingress, 503)
            return self._error(503, str(e), {"X-Tony-Draining": "1"})
        except RuntimeError as e:
            self._finish_rejected(trace, t_ingress, 503)
            return self._error(503, str(e))
        if trace is not None:
            trace.request_id = str(handle.request_id)
        handle.trace = trace
        handle.trace_ctx = ctx
        return self._stream(handle)

    # -- disaggregation: prefill side -----------------------------------
    def _generate_migrating(self, handle, req: dict) -> None:
        """Finish a migrate_out admission: wait for the prefill, POST the
        handoff to a decode replica, relay its stream to the client
        behind the first token this replica computed. Every decode
        replica refusing falls back to finishing locally."""
        try:
            handle.result(timeout=STREAM_TOKEN_TIMEOUT_SEC)
        except TimeoutError as e:
            handle.cancel()
            return self._error(504, str(e))
        if handle.finish_reason == "shutdown":
            return self._error(503, "engine shut down mid-request")
        if handle.finish_reason != "migrated" or handle.migration is None:
            # finished at admission (eos / max_new==1): answer directly
            if req.get("stream"):
                return self._stream(handle)
            return self._json({"tokens": list(handle.tokens),
                               "finish_reason": handle.finish_reason,
                               "ttft_s": handle.ttft_s})
        meta = handle.migration["meta"]
        leaves = handle.migration["leaves"]
        trace = getattr(handle, "trace", None)
        t_pack = time.monotonic()
        payload = kvc.pack_migration(meta, leaves)
        t_packed = time.monotonic()
        pack_span = None
        if trace is not None:
            pack_span = trace.hop(
                "migrate.pack", reqtrace.mono_to_wall_ms(t_pack),
                reqtrace.mono_to_wall_ms(t_packed),
                attrs={"bytes": len(payload)})
        t_send = time.monotonic()
        resp, target = self._post_migration(
            payload, trace=getattr(handle, "trace_ctx", None),
            parent_span=pack_span)
        if resp is not None:
            if trace is not None:
                # transfer = POST issued → response headers back (the
                # decode replica admitted the handoff); the token relay
                # after this is the decode hop, recorded on ITS side
                trace.hop("migrate.transfer",
                          reqtrace.mono_to_wall_ms(t_send),
                          reqtrace.mono_to_wall_ms(time.monotonic()),
                          attrs={"bytes": len(payload),
                                 "target": str(target)},
                          parent_id=pack_span)
            self._finish_migrated(handle, self._lines_from(resp),
                                  bool(req.get("stream")))
            return self._finish_out_trace(handle)
        # degraded: no decode replica took it — self-install and finish
        LOG.warning("request %d: no decode replica accepted the "
                    "migration; finishing locally", handle.request_id)
        try:
            local = self.engine.submit_migration(meta, leaves)
        except (BudgetExceededError, QueueFullError, DrainingError,
                RuntimeError) as e:
            return self._error(
                503, f"migration failed and local fallback refused: {e}")
        self._finish_migrated(handle, self._lines_from_handle(local),
                              bool(req.get("stream")))
        return self._finish_out_trace(handle)

    def _finish_out_trace(self, handle) -> None:
        """Tail-sample a migrated-out request AFTER the decode relay —
        its duration is the client-observed total, so a slow decode
        replica shows up in the prefill side's slowest table too."""
        coll, trace = self.collector, getattr(handle, "trace", None)
        if coll is None or trace is None:
            return
        duration_ms = 1000.0 * (time.monotonic() - handle.submitted_at)
        coll.finish(trace, duration_ms, migrated=True)

    # tony: disable=redact-on-egress -- data-plane handoff: the payload is the request's own K/V bytes + sampler state, verbatim by contract
    def _post_migration(self, payload: bytes, trace=None,
                        parent_span: Optional[str] = None):
        """Round-robin the decode pool; 4xx/5xx/transport refusals try
        the next target. Returns (open streaming response, target base),
        or (None, None) when every target refused. The request trace
        context rides X-Tony-Trace so the decode replica continues the
        same trace, parented under this side's migrate.pack span."""
        targets = [t.rstrip("/") for t in self.migrate_targets if t]
        if not targets:
            return None, None
        headers = {"Content-Type": "application/octet-stream"}
        if trace is not None:
            fwd = (trace.child(parent_span, trace.route_ms)
                   if parent_span else trace)
            headers[reqtrace.HEADER] = fwd.header_value()
        first = next(_MIGRATE_RR) % len(targets)
        for i in range(len(targets)):
            base = targets[(first + i) % len(targets)]
            rq = urllib.request.Request(
                base + "/v1/migrate", data=payload, headers=headers)
            try:
                resp = urllib.request.urlopen(
                    rq, timeout=STREAM_TOKEN_TIMEOUT_SEC)
            except urllib.error.HTTPError as e:
                LOG.debug("migrate to %s refused: HTTP %s", base, e.code)
                e.close()
                continue
            except OSError as e:
                LOG.debug("migrate to %s failed: %s", base, e)
                continue
            hook = self.on_migrated
            if hook is not None:
                try:
                    hook(base)
                except Exception:  # noqa: BLE001 — observability only
                    LOG.debug("on_migrated hook failed", exc_info=True)
            return resp, base
        return None, None

    @staticmethod
    def _lines_from(resp):
        """JSON objects from a decode replica's chunked line stream."""
        with resp:
            for raw in resp:
                raw = raw.strip()
                if raw:
                    yield json.loads(raw)

    @staticmethod
    def _lines_from_handle(local):
        """The local-fallback equivalent of the decode line stream."""
        for token in local.iter_tokens(timeout=STREAM_TOKEN_TIMEOUT_SEC):
            yield {"token": token}
        yield {"done": True, "finish_reason": local.finish_reason}

    def _finish_migrated(self, handle, lines, stream: bool) -> None:
        """Relay the decode-side token lines to the client behind the
        prefill token. n_tokens/tokens include it; ttft_s is the PREFILL
        replica's — the client saw its first token before the handoff."""
        tok0 = handle.tokens[0]
        tokens = [tok0]
        finish = "length"
        if not stream:
            try:
                for obj in lines:
                    if obj.get("done"):
                        finish = str(obj.get("finish_reason") or finish)
                        break
                    tokens.append(int(obj["token"]))
            except (OSError, ValueError, KeyError, TimeoutError):
                finish = "migrate_error"
            return self._json({"tokens": tokens, "finish_reason": finish,
                               "ttft_s": handle.ttft_s,
                               "migrated": True})
        self.send_response(200)
        self.send_header("Content-Type",
                         "application/json; charset=utf-8")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        chunk = partial(self._write_chunk, handle.request_id)

        try:
            chunk({"token": tok0})
            try:
                for obj in lines:
                    if obj.get("done"):
                        finish = str(obj.get("finish_reason") or finish)
                        break
                    token = int(obj["token"])
                    tokens.append(token)
                    chunk({"token": token})
            except (OSError, ValueError, KeyError, TimeoutError):
                finish = "migrate_error"
            chunk({"done": True, "finish_reason": finish,
                   "n_tokens": len(tokens), "ttft_s": handle.ttft_s,
                   "migrated": True})
            self.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError):
            LOG.debug("migrated stream aborted (request %d)",
                      handle.request_id)
            self.close_connection = True


def install_engine_tracing(engine: ContinuousBatchingEngine,
                           collector) -> None:
    """Compose request-trace recording onto engine.on_request_finished:
    engine-phase hops off the handle's stamps, the tail-sampling finish,
    and the TTFT-attribution rollup. A migrated-OUT handle is NOT
    finished here — the frontend finishes it after the decode relay so
    its duration is the client-observed total. Chains any hook already
    installed."""
    prev = engine.on_request_finished

    def _on_finished(handle) -> None:
        trace = getattr(handle, "trace", None)
        if trace is not None:
            reqtrace.record_engine_phases(trace, handle)
            if handle.finish_reason != "migrated":
                ctx = getattr(handle, "trace_ctx", None)
                route_ms = ctx.route_ms if ctx is not None else 0.0
                finished = getattr(handle, "finished_at", None)
                submitted = getattr(handle, "submitted_at", None)
                duration_ms = (1000.0 * (finished - submitted)
                               if finished and submitted else 0.0)
                collector.finish(
                    trace, duration_ms,
                    error=handle.finish_reason in ("error", "shutdown"),
                    migrated=getattr(handle, "migrated_in", False))
                collector.attribution.record(
                    reqtrace.attribution_from_handle(
                        handle, route_ms=route_ms))
        if prev is not None:
            prev(handle)

    engine.on_request_finished = _on_finished


class ServeFrontend:
    """Owns the HTTP server; the engine's lifecycle belongs to the caller
    (serve/__main__ starts the engine loop, tests may drive it manually)."""

    def __init__(self, engine: ContinuousBatchingEngine, port: int = 0,
                 host: str = "0.0.0.0", migrate_targets=(),
                 on_migrated=None, collector=None):
        self.engine = engine
        self.collector = collector
        self.request_counts: dict = {}
        from tony_tpu.serve.router import BurstBacklogHTTPServer
        handler = type("BoundHandler", (_Handler,), {
            "engine": engine,
            "migrate_targets": tuple(migrate_targets or ()),
            "on_migrated": staticmethod(on_migrated)
            if on_migrated is not None else None,
            "collector": collector,
            "path_counts": self.request_counts,
            "path_counts_lock": threading.Lock(),
        })
        self._httpd = BurstBacklogHTTPServer((host, port), handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="serve-http", daemon=True)

    def start(self) -> None:
        self._thread.start()
        LOG.info("serving /v1/generate on port %d (%d slots, budget %d, "
                 "queue %d)", self.port, self.engine.n_slots,
                 self.engine.token_budget, self.engine.queue_depth)

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
