"""HTTP frontend for the serving subsystem (docs/serving.md).

Same stdlib pattern as the telemetry Prometheus endpoint
(telemetry/core.py `start_http_server`): a `ThreadingHTTPServer`, one
handler thread per connection, zero new dependencies. Handler threads
block cheaply on their request's event while the per-model batcher
worker drives the accelerator.

Routes (triton/KServe-shaped):

  * ``POST /v1/models/<name>:predict``            (newest version)
  * ``POST /v1/models/<name>/versions/<v>:predict``
      body: ``{"inputs": {"<input>": <nested list>}, "timeout_ms": opt}``
      or ``{"instances": <nested list>}`` for single-input models;
      reply: ``{"outputs": [...], "model": ..., "version": ...}``.
  * ``POST /v1/models/<name>[:versions/<v>]:generate``  (LM models,
      docs/serving.md §Generation)
      body: ``{"tokens": [int...], "max_new_tokens": opt,
      "temperature": opt, "top_k": opt, "top_p": opt, "timeout_ms": opt}``
      reply: ``{"tokens": [generated ids], "num_generated": ...,
      "finish_reason": "eos"|"length", ...}`` (non-streaming; requests
      join the model's running decode batch at token granularity).
  * ``GET /v1/models``        repository listing (buckets, signatures,
      warm state, pending counts)
  * ``GET /v1/models/<name>`` one model (``?version=``)
  * ``GET /healthz``          200 ``ok`` / 503 ``draining``
  * ``GET|POST /drainz``      start draining (idempotent); reply shows
      remaining pending work — poll until 0

Admission control is deterministic: a full queue answers 429
(`MXTPU_SERVE_QUEUE_DEPTH`), an expired deadline answers 504
(`MXTPU_SERVE_TIMEOUT_MS`, per-request override via ``timeout_ms``),
draining answers 503, an unknown model 404, a malformed request 400.
SIGTERM (via `install_signal_handlers`) drains queued + in-flight
requests, then stops the server so the launcher sees exit 0.
"""
from __future__ import annotations

import json
import math
import signal
import threading
import time

import numpy as _np

from .. import env as _env
from .. import telemetry
from ..telemetry import slo as _slo
from ..telemetry import tracing as _tracing
from ..base import MXNetError
from .batcher import DrainingError, ServingError, drain_timeout_s

__all__ = ["ServingServer"]


def _int_version(raw):
    """URL version component -> int; malformed is the CLIENT's error
    (400), not a 500 from a bare ValueError."""
    try:
        return int(raw)
    except ValueError:
        raise MXNetError("version %r is not an integer" % (raw,))


class ServingServer:
    """The HTTP frontend over a `ModelRepository`."""

    def __init__(self, repository, port=None, addr="0.0.0.0"):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.repository = repository
        self._autoscaler = None
        self._draining = False
        self._drain_failed = False
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self._m_codes = {}
        # the drain WAITER is pre-started so the SIGTERM handler only has
        # to set an Event: the main thread spawns handler threads inside
        # `serve_forever` (ThreadingHTTPServer), so a handler that called
        # Thread.start() itself could deadlock on the threading module's
        # own locks if the signal landed mid-spawn. mxlint's signal-safety
        # checker walks `_on_signal` to keep it that trivial.
        self._closed = False
        self._drain_shutdown = False
        self._drain_event = threading.Event()
        self._drain_waiter = threading.Thread(
            target=self._drain_when_signaled, name="mxtpu-serve-drain",
            daemon=True)
        self._drain_waiter.start()
        if port is None:
            port = _env.get("MXTPU_SERVE_PORT")

        outer = self

        class _Handler(BaseHTTPRequestHandler):
            # HTTP/1.1 keep-alive: steady clients reuse their connection
            # (and its handler thread) instead of paying TCP setup + a
            # thread spawn per request; every reply carries Content-Length
            protocol_version = "HTTP/1.1"

            def do_GET(self):
                outer._route(self, "GET")

            def do_POST(self):
                outer._route(self, "POST")

            def log_message(self, fmt, *args):  # no per-request stderr spam
                pass

        class _Server(ThreadingHTTPServer):
            daemon_threads = True
            # stdlib default backlog is 5: a burst of concurrent clients
            # overflows the accept queue and eats 1-3s TCP SYN retransmits
            request_queue_size = 128

        self._http = _Server((addr, int(port)), _Handler)
        self.port = self._http.server_address[1]
        self._serve_thread = None

    # -- lifecycle ---------------------------------------------------------
    def serve_forever(self):
        """Block serving requests until `shutdown` (tools/serve.py)."""
        self._http.serve_forever(poll_interval=0.1)

    def start(self):
        """Serve on a daemon thread (tests, chipbench/, chip_smoke.py).
        Returns self."""
        self._serve_thread = threading.Thread(
            target=self.serve_forever, name="mxtpu-serve-http", daemon=True)
        self._serve_thread.start()
        return self

    def attach_autoscaler(self, autoscaler):
        """Adopt this server's autoscaling controller (docs/serving.md
        §Autoscaling): its decision trail joins ``/statusz`` and
        `shutdown` stops (and joins) its thread — the PR-12 hygiene
        contract for the per-server controller. Returns the autoscaler."""
        self._autoscaler = autoscaler
        return autoscaler

    @property
    def autoscaler(self):
        return self._autoscaler

    def shutdown(self):
        # monotonic False->True flag (drain waiter + api callers race
        # benignly: both write the same value, readers poll)
        self._closed = True  # mxlint: gil-atomic — monotonic shutdown flag
        self._drain_event.set()  # release an idle drain waiter
        if self._autoscaler is not None:
            # scaling decisions must stop before models start dropping
            self._autoscaler.stop()
        self._http.shutdown()
        self._http.server_close()
        if self._serve_thread is not None:
            self._serve_thread.join(timeout=5.0)

    @property
    def draining(self):
        return self._draining

    def drain(self, timeout=None, shutdown=False):
        """Stop admitting work, wait for queued + in-flight requests (and
        their handler threads) to finish, optionally stop the server.
        Returns True when everything completed within ``timeout``.

        The wait is BOUNDED (`MXTPU_SERVE_DRAIN_TIMEOUT_MS`): a wedged
        executor must not wedge shutdown forever. On expiry every stranded
        request is force-completed with a deterministic 503 (the waiter
        gets an answer, not a connection reset), `drain_failed` is set, and
        the `tools/serve.py` process exits nonzero so the supervisor knows
        the drain was not clean."""
        # monotonic admission flag: the /drainz waiter thread and direct
        # api callers both only ever flip it False->True
        self._draining = True  # mxlint: gil-atomic — monotonic drain flag
        if self._autoscaler is not None:
            # scaling decisions stop BEFORE models drain: the controller
            # must not spawn (or drain) replicas into a server that is
            # shutting down — stop() joins its thread, so no lap is
            # mid-flight when drain_all starts; idempotent for the later
            # shutdown() call
            self._autoscaler.stop()
        if timeout is None:
            # drain_timeout_s honors the deprecated seconds-typed
            # MXTPU_SERVE_DRAIN_TIMEOUT_S with a one-time warning
            timeout = drain_timeout_s()
        telemetry.record_event("serve_drain_start",
                               pending=self.repository.pending())
        deadline = time.monotonic() + timeout
        ok = self.repository.drain_all(timeout)
        while self._inflight and time.monotonic() < deadline:
            time.sleep(0.01)  # let handler threads finish writing replies
        ok = ok and not self._inflight
        if not ok:
            aborted = self.repository.abort_pending()
            self._drain_failed = True  # mxlint: gil-atomic — monotonic flag
            telemetry.record_event("serve_drain_forced", aborted=aborted,
                                   timeout_s=timeout)
            # the 503s are resolved; give handler threads a moment to
            # write them out before the listener dies
            force_deadline = time.monotonic() + 2.0
            while self._inflight and time.monotonic() < force_deadline:
                time.sleep(0.01)
        telemetry.record_event("serve_drain_done", complete=ok)
        if shutdown:
            self.shutdown()
        return ok

    @property
    def drain_failed(self):
        """True when a drain timed out and force-completed requests (the
        process should exit nonzero)."""
        return self._drain_failed

    def _drain_when_signaled(self):
        """The pre-started drain waiter: parked on `_drain_event` until a
        signal handler or `/drainz` releases it, then runs the (bounded)
        drain — with shutdown when the trigger was a signal. Loops after a
        `/drainz` drain so a later SIGTERM still shuts the server down; a
        signal landing mid-drain re-sets the event and is picked up on the
        next lap."""
        while True:
            self._drain_event.wait()
            if self._closed:
                return  # plain shutdown(), nothing to drain
            self._drain_event.clear()
            shutdown = self._drain_shutdown
            telemetry.record_event("serve_drain_triggered",
                                   shutdown=shutdown)
            self.drain(shutdown=shutdown)
            if shutdown or self._closed:
                return

    def install_signal_handlers(self, signals=(signal.SIGTERM, signal.SIGINT)):
        """Graceful-drain on SIGTERM/SIGINT: the handler only flips a flag
        and sets the Event the pre-started waiter parks on — it is walked
        by the mxlint signal-safety checker, so it must stay free of
        locks, logging, allocation and thread starts (the interrupted
        main thread spawns HTTP handler threads, so Thread.start() here
        could deadlock on the threading module's internals).
        `serve_forever` returns once the drain finishes and the caller
        exits 0 (or nonzero when `drain_failed`)."""

        def _on_signal(signum, frame):
            self._drain_shutdown = True
            self._drain_event.set()

        for s in signals:
            signal.signal(s, _on_signal)

    # -- routing -----------------------------------------------------------
    def _route(self, handler, method):
        try:
            path = handler.path.split("?", 1)[0]
            query = handler.path[len(path) + 1:] if "?" in handler.path else ""
            if path.rstrip("/") == "/healthz" and method == "GET":
                if self._draining:
                    self._text(handler, 503, "draining\n")
                else:
                    self._text(handler, 200, "ok\n")
            elif path.rstrip("/") == "/statusz" and method == "GET":
                # the "what is wrong right now" page (docs/observability.md
                # §SLOs): SLO verdicts + windowed rates + pool/memory/
                # compile state. Reads lock-free snapshots only — it must
                # answer even when a model's batcher is wedged, so it
                # never touches repository/batcher locks (admission-free:
                # works while draining too)
                extra = {"server": {"port": self.port,
                                    "draining": self._draining,
                                    "drain_failed": self._drain_failed,
                                    "inflight": self._inflight}}
                if self._autoscaler is not None:
                    # the decision trail that explains every replica-count
                    # change (lock-free snapshot reads)
                    extra["autoscaler"] = self._autoscaler.describe()
                ctype, body = _slo.render_statusz(
                    "text" if "format=text" in query else "json",
                    extra=extra)
                self._count(200)
                handler.send_response(200)
                handler.send_header("Content-Type", ctype)
                handler.send_header("Content-Length", str(len(body)))
                handler.end_headers()
                handler.wfile.write(body)
            elif path.rstrip("/") == "/drainz":
                self._drain_event.set()  # idempotent: wakes the waiter
                self._json(handler, 200, {
                    "draining": True,
                    "pending": self.repository.pending(),
                    "inflight": self._inflight,
                })
            elif path == "/v1/models" and method == "GET":
                self._json(handler, 200, self.repository.describe())
            elif path.startswith("/v1/models/"):
                self._model_route(handler, method, path[len("/v1/models/"):],
                                  query)
            else:
                self._json(handler, 404, {"error": "no route %s %s"
                                          % (method, path)})
        except BrokenPipeError:
            pass  # client went away mid-reply
        except ServingError as e:
            payload = {"error": str(e)}
            details = getattr(e, "details", None)
            if details:
                # 507s carry the footprint breakdown (what to evict) —
                # docs/serving.md §Autoscaling
                payload["details"] = details
            self._json(handler, e.status, payload,
                       retry_after=e.retry_after)
        except MXNetError as e:
            self._json(handler, 400, {"error": str(e)})
        except Exception as e:  # the server must answer, never unwind
            self._json(handler, 500, {"error": "%s: %s"
                                      % (type(e).__name__, e)})

    def _model_route(self, handler, method, rest, query):
        version = None
        if ":" in rest:
            rest, verb = rest.split(":", 1)
        else:
            verb = None
        if "/versions/" in rest:
            rest, v = rest.split("/versions/", 1)
            version = _int_version(v)
        name = rest.strip("/")
        if version is None and query.startswith("version="):
            version = _int_version(query.split("=", 1)[1].split("&")[0])
        if verb == "predict" and method == "POST":
            self._predict(handler, name, version)
        elif verb == "generate" and method == "POST":
            self._generate(handler, name, version)
        elif verb is None and method == "GET":
            model = self.repository.get(name, version)
            self._json(handler, 200, model.describe())
        else:
            self._json(handler, 404, {"error": "no route %s /v1/models/%s%s"
                                      % (method, name,
                                         ":" + verb if verb else "")})

    # -- predict -----------------------------------------------------------
    def _predict(self, handler, name, version):
        # trace context is minted AT ADMISSION (or honored from an
        # incoming `x-mxtpu-trace` header — a proxy/client that already
        # traces keeps its ids); the reply always carries the header so
        # callers can link any outcome to its trace
        ref = _tracing.parse_header(
            handler.headers.get(_tracing.HEADER) or "")
        ref = _tracing.mint(ref)
        handler._mxtpu_trace = _tracing.header_value(ref)
        with _tracing.root("serve.request", component="server", ref=ref,
                           attrs={"model": name}):
            self._predict_traced(handler, name, version)

    def _predict_traced(self, handler, name, version):
        # consume the body FIRST: replying before the read would desync a
        # keep-alive connection (next request line = leftover body bytes)
        length = int(handler.headers.get("Content-Length") or 0)
        raw_body = handler.rfile.read(length) if length > 0 else b""
        if self._draining:
            raise DrainingError("server is draining")
        model = self.repository.get(name, version)
        if not hasattr(model, "predict"):
            raise MXNetError(
                "model %r is a generation model; use :generate" % name)
        if not raw_body:
            raise MXNetError("empty request body")
        try:
            body = json.loads(raw_body.decode())
        except (ValueError, UnicodeDecodeError) as e:
            raise MXNetError("request body is not JSON: %s" % e)
        if "inputs" in body:
            raw = body["inputs"]
            if not isinstance(raw, dict):
                raise MXNetError("'inputs' must be an object of "
                                 "input-name -> array")
        elif "instances" in body:
            names = sorted(model.example_shapes)
            if len(names) != 1:
                raise MXNetError(
                    "'instances' shorthand needs a single-input model; "
                    "%r has inputs %s — use 'inputs'" % (name, names))
            raw = {names[0]: body["instances"]}
        else:
            raise MXNetError("request needs 'inputs' or 'instances'")
        try:
            arrays = {k: _np.asarray(v, dtype=model.input_dtypes.get(k))
                      for k, v in raw.items()}
        except (ValueError, TypeError, KeyError) as e:
            raise MXNetError("malformed input array: %s" % e)
        timeout_ms = body.get("timeout_ms")
        if timeout_ms is not None:
            timeout_ms = float(timeout_ms)
        with self._inflight_lock:
            self._inflight += 1
        try:
            outputs = model.predict(arrays, timeout_ms=timeout_ms)
            self._json(handler, 200, {
                "model": model.name,
                "version": model.version,
                "outputs": [o.tolist() for o in outputs],
            })
        finally:
            with self._inflight_lock:
                self._inflight -= 1

    # -- generate ----------------------------------------------------------
    def _generate(self, handler, name, version):
        ref = _tracing.parse_header(
            handler.headers.get(_tracing.HEADER) or "")
        ref = _tracing.mint(ref)
        handler._mxtpu_trace = _tracing.header_value(ref)
        with _tracing.root("serve.request", component="server", ref=ref,
                           attrs={"model": name, "verb": "generate"}):
            self._generate_traced(handler, name, version)

    def _generate_traced(self, handler, name, version):
        # body FIRST (keep-alive desync, same as predict)
        length = int(handler.headers.get("Content-Length") or 0)
        raw_body = handler.rfile.read(length) if length > 0 else b""
        if self._draining:
            raise DrainingError("server is draining")
        model = self.repository.get(name, version)
        gen = getattr(model, "generate", None)
        if gen is None:
            raise MXNetError(
                "model %r does not serve :generate (it is a predict "
                "model; load an LM artifact with generate=True)" % name)
        if not raw_body:
            raise MXNetError("empty request body")
        try:
            body = json.loads(raw_body.decode())
        except (ValueError, UnicodeDecodeError) as e:
            raise MXNetError("request body is not JSON: %s" % e)
        tokens = body.get("tokens")
        if not isinstance(tokens, list) or not tokens \
                or not all(isinstance(t, int) and not isinstance(t, bool)
                           for t in tokens):
            raise MXNetError("'tokens' must be a non-empty list of int "
                             "token ids")
        kwargs = {}
        for field, cast in (("max_new_tokens", int), ("temperature", float),
                            ("top_k", int), ("top_p", float),
                            ("timeout_ms", float)):
            if body.get(field) is None:
                continue
            try:
                value = cast(body[field])
            except (TypeError, ValueError):
                value = None
            # json.loads accepts NaN/Infinity literals; a non-finite knob
            # would silently poison the sampling masks — it is the
            # CLIENT's error (400), never a garbage 200 or a 500
            if value is None or not math.isfinite(value):
                raise MXNetError("%r must be a finite number, got %r"
                                 % (field, body[field]))
            kwargs[field] = value
        with self._inflight_lock:
            self._inflight += 1
        try:
            result = gen(tokens, **kwargs)
            self._json(handler, 200, {
                "model": model.name,
                "version": model.version,
                "tokens": result["tokens"],
                "num_generated": len(result["tokens"] or ()),
                "finish_reason": result.get("finish_reason"),
            })
        finally:
            with self._inflight_lock:
                self._inflight -= 1

    # -- replies -----------------------------------------------------------
    def _count(self, code):
        m = self._m_codes.get(code)
        if m is None:
            m = telemetry.counter("mxtpu_serve_http_requests_total",
                                  {"code": str(code)})
            # racing handler threads both miss and both store the SAME
            # object (the telemetry registry is the point of truth), so
            # the last-wins dict store is harmless memoization
            self._m_codes[code] = m  # mxlint: gil-atomic — idempotent memo
        m.inc()

    def _text(self, handler, code, text):
        body = text.encode()
        self._count(code)
        handler.send_response(code)
        handler.send_header("Content-Type", "text/plain; charset=utf-8")
        handler.send_header("Content-Length", str(len(body)))
        handler.end_headers()
        handler.wfile.write(body)

    def _json(self, handler, code, payload, retry_after=None):
        body = (json.dumps(payload) + "\n").encode()
        self._count(code)
        if code >= 400:
            # error replies may precede a full body read on some routes;
            # closing keeps the keep-alive stream from desyncing
            handler.close_connection = True
        handler.send_response(code)
        handler.send_header("Content-Type", "application/json")
        handler.send_header("Content-Length", str(len(body)))
        trace = getattr(handler, "_mxtpu_trace", None)
        if trace is not None:
            # header contract: every predict reply (success or error)
            # names its trace so a slow/failed request is renderable
            handler.send_header(_tracing.HEADER, trace)
        if retry_after is None and code == 429:
            retry_after = 1
        if retry_after is not None:
            # load-shed contract: 503s carry a Retry-After scaled to the
            # healthy-replica count (OverloadedError.retry_after)
            handler.send_header("Retry-After", str(int(retry_after)))
        handler.end_headers()
        handler.wfile.write(body)
