"""Minimal WSGI micro-framework over werkzeug.

The reference exposes its services as Flask apps (e.g. reference:
microservices/database_api_image/server.py:31). Flask is not available in
this environment, so this module provides the thin slice of that surface
our services need — routing with URL parameters, JSON request/response
helpers, file responses, a test client, and a threaded dev server — on
top of werkzeug, which is available.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Any, Callable

from werkzeug.exceptions import HTTPException, NotFound
from werkzeug.routing import Map, Rule
from werkzeug.serving import make_server
from werkzeug.test import Client
from werkzeug.wrappers import Request, Response

from learningorchestra_tpu.telemetry import metrics as _metrics
from learningorchestra_tpu.telemetry import tracing as _tracing
from learningorchestra_tpu.utils import webloop as _webloop
from learningorchestra_tpu.utils.webloop import (  # noqa: F401 — re-export
    Upstream,
    Waiter,
)


def jsonify(payload: Any) -> Response:
    return Response(
        json.dumps(payload), mimetype="application/json", status=200
    )


def too_many_requests(error) -> Response:
    """HTTP 429 for a :class:`~learningorchestra_tpu.sched.scheduler.
    QueueFullError`: admission control's REST face. ``Retry-After``
    carries the scheduler's backlog-drain estimate so well-behaved
    clients pace themselves instead of hammering a full queue."""
    response = Response(
        json.dumps(
            {
                "result": "queue_full",
                "job_class": error.job_class,
                "retry_after_s": error.retry_after_s,
            }
        ),
        mimetype="application/json",
        status=429,
    )
    response.headers["Retry-After"] = str(error.retry_after_s)
    return response


def send_file(path: str, mimetype: str) -> Response:
    with open(path, "rb") as handle:
        data = handle.read()
    return Response(data, mimetype=mimetype, status=200)


class WebApp:
    """A WSGI application with Flask-like ``route`` registration.

    Handlers receive the ``werkzeug`` ``Request`` as their first argument
    (instead of Flask's implicit request global) plus any URL parameters,
    and may return a ``Response``, or a ``(payload, status)`` tuple where
    the payload is JSON-serialised.
    """

    def __init__(self, name: str, registry=None):
        self.name = name
        self.url_map = Map()
        self._handlers: dict[str, Callable] = {}
        # set by register_observability(): the store whose __lo_metrics__
        # ring backs /metrics/history, /debug/slo and /health's degraded
        self._obs_store = None
        # Telemetry: every app reports into the process registry (one
        # shared registry when services co-habit a process — families
        # are labelled by service) and serves it at GET /metrics.
        self.registry = registry or _metrics.global_registry()
        self._requests_total = self.registry.counter(
            "lo_http_requests_total",
            "HTTP requests handled",
            labels=("service", "route", "method", "status"),
        )
        self._request_seconds = self.registry.histogram(
            "lo_http_request_duration_seconds",
            "Wall-clock per request",
            labels=("service", "route", "method"),
        )
        self._in_flight = self.registry.gauge(
            "lo_http_requests_in_flight",
            "Requests currently being handled",
            labels=("service",),
        )
        # Flight-recorder byte-flow families declare eagerly so every
        # service's /metrics shows them from boot (dashboards see the
        # family before its first byte moves) — profile.py declares
        # lazily on its own to stay import-light for library embedders.
        from learningorchestra_tpu.telemetry import profile as _profile

        _profile._flow_metrics()

        @self.route("/metrics")
        def serve_metrics(request):
            return Response(
                self.registry.render(),
                content_type=_metrics.CONTENT_TYPE,
                status=200,
            )

        @self.route("/debug/profile")
        def debug_profile(request):
            """Sampling profiler (telemetry/profile.py): sample every
            thread's stack for ``?seconds=N`` (default 5, clamped to
            ``LO_PROF_WINDOW_S``) and answer folded flamegraph stacks —
            a live stall is diagnosable without a restart. Plain text
            by default (pipe to flamegraph.pl / speedscope);
            ``?format=json`` wraps the stacks with sample metadata.
            403 when disabled (``LO_PROF_HZ=0``)."""
            from learningorchestra_tpu.telemetry import profile as _profile

            try:
                seconds = float(request.args.get("seconds", "5"))
            except ValueError:
                return {"result": "bad_seconds"}, 400
            if not seconds > 0 or seconds != seconds:  # NaN included
                return {"result": "bad_seconds"}, 400
            try:
                stacks, samples = _profile.sample_stacks(seconds)
            except RuntimeError:
                return {"result": "profiler_disabled"}, 403
            except ValueError as error:
                # malformed LO_PROF_* in a process that skipped the
                # run.sh preflight (library embedder, hand-launched
                # service): clean JSON, never a traceback — this is the
                # endpoint for diagnosing an already-sick process
                return {
                    "result": "invalid_prof_config",
                    "error": str(error),
                }, 500
            if request.args.get("format") == "json":
                return {
                    "result": {
                        "stacks": stacks,
                        "samples": samples,
                        "hz": _profile.prof_hz(),
                    }
                }, 200
            return Response(
                _profile.folded_text(stacks),
                mimetype="text/plain",
                status=200,
            )

        @self.route("/debug/spans")
        def debug_spans(request):
            """This process's span export buffer (telemetry/tracing.py)
            — the per-member feed the fleet stitcher drains.
            ``?cid=`` filters to one correlation ID, ``?since=`` to
            entries updated after an epoch timestamp."""
            cid = request.args.get("cid")
            since = request.args.get("since")
            if since is not None:
                try:
                    since = float(since)
                except ValueError:
                    return {"result": "bad_since"}, 400
            return {"result": _tracing.exported_spans(cid, since)}, 200

        @self.route("/traces/<cid>")
        def read_stitched_trace(request, cid):
            """ONE Chrome trace for one correlation ID, stitched across
            every plane member in ``LO_PLANE_MEMBERS`` (telemetry/
            stitch.py): one process row per ``service@pid``, so a
            client-driven multi-service pipeline renders as a single
            timeline. 404 when no member holds spans for the cid."""
            from learningorchestra_tpu.telemetry import stitch as _stitch

            trace = _stitch.stitched_trace(cid)
            if not trace["otherData"]["processes"]:
                return {"result": "not_found"}, 404
            return trace, 200

    def register_observability(self, store) -> None:
        """The store-backed half of the fleet observability plane
        (docs/observability.md "Fleet plane"):

        - ``GET /metrics/history?family=…`` — the ``__lo_metrics__``
          ring's fold-forward series plus server-side windowed rollups
          (rate / p50 / p99 per instance — telemetry/tsdb.py);
        - ``POST /metrics/ingest`` — raw Prometheus exposition text in,
          one retention tick out (what deploy/cluster.py's collector
          posts per scraped member);
        - ``GET /debug/slo`` — ok/burning per SLO rule with the
          offending instance (telemetry/slo.py); also arms ``/health``'s
          ``degraded`` field.
        """
        from learningorchestra_tpu.telemetry import slo as _slo
        from learningorchestra_tpu.telemetry import tsdb as _tsdb

        self._obs_store = store
        ingest_tsdb = _tsdb.TSDB(store)

        @self.route("/metrics/history")
        def metrics_history(request):
            family = request.args.get("family")
            if not family:
                return {"result": "bad_family"}, 400
            try:
                since = (
                    float(request.args["since"])
                    if "since" in request.args
                    else None
                )
                window_s = float(
                    request.args.get("window", _slo.slo_window_s())
                )
            except ValueError:
                return {"result": "bad_window"}, 400
            instance = request.args.get("instance")
            series = _tsdb.history(store, family, instance=instance)
            return {
                "result": {
                    "family": family,
                    "series": {
                        inst: [
                            [ts, value]
                            for ts, value in points
                            if since is None or ts >= since
                        ]
                        for inst, points in series.items()
                    },
                    "rollup": _tsdb.window_rollups(
                        store, family, window_s=window_s, instance=instance
                    ),
                    "services": _tsdb.services_of(store),
                }
            }, 200

        @self.route("/metrics/ingest", methods=("POST",))
        def metrics_ingest(request):
            body = request.get_json()
            instance = body.get("instance")
            text = body.get("text")
            if not instance or not isinstance(text, str):
                return {"result": "bad_ingest"}, 400
            try:
                vals = _tsdb.parse_samples(text)
            except ValueError as error:
                # a member scraped mid-restart: ITS tick is dropped,
                # the collection stays consistent
                return {"result": "unparseable", "error": str(error)}, 400
            ingest_tsdb.append(
                instance,
                body.get("service") or "unknown",
                vals,
                ts=body.get("ts"),
            )
            return {"result": "ok", "families": len(vals)}, 200

        @self.route("/debug/slo")
        def debug_slo(request):
            try:
                return {"result": _slo.status(store)}, 200
            except Exception as error:  # noqa: BLE001 — a store mid-
                # failover must yield a diagnosable payload, not a 500
                # traceback from the diagnosis endpoint itself
                return {
                    "result": "slo_unavailable",
                    "error": f"{type(error).__name__}: {error}",
                }, 503

    def slo_degraded(self) -> bool:
        """``/health``'s SLO verdict: True when any rule burns. False
        without a registered store or on any evaluation error — health
        must keep answering while the plane itself is sick."""
        if self._obs_store is None:
            return False
        try:
            from learningorchestra_tpu.telemetry import slo as _slo

            return bool(_slo.status(self._obs_store)["degraded"])
        except Exception:  # noqa: BLE001
            return False

    def register_job_traces(self, jobs) -> None:
        """Serve ``GET /jobs/<name>/trace``: the span tree (with the
        request's correlation ID) of a tracked job — the per-request
        "where did the time go" answer (core/jobs.py grows the trace)."""

        @self.route("/jobs/<job_name>/trace")
        def read_job_trace(request, job_name):
            record = jobs.get(job_name)
            if record is None:
                return {"result": "not_found"}, 404
            return {"result": record.trace_dict()}, 200

        @self.route("/jobs/<job_name>/profile")
        def read_job_profile(request, job_name):
            """The job's merged timeline as Chrome trace-event JSON
            (load in Perfetto: one row per thread, byte counter
            tracks); ``?format=summary`` returns the per-phase
            seconds/bytes/rows-per-s rollup instead, for an operator
            to compare two runs by phase (docs/profiling.md)."""
            from learningorchestra_tpu.telemetry import profile as _profile

            record = jobs.get(job_name)
            if record is None:
                return {"result": "not_found"}, 404
            if record.trace is None:
                return {"result": "no_trace"}, 404
            if request.args.get("format") == "summary":
                summary = _profile.trace_summary(record.trace)
                summary["job"] = record.as_dict()
                return {"result": summary}, 200
            return _profile.chrome_trace(record.trace), 200

    def register_job_routes(self, jobs) -> None:
        """The full job surface for a service holding a JobManager:

        - ``GET /jobs`` — every tracked job's state, class, priority,
          attempt count, timings, error, and correlation ID;
        - ``GET /jobs/<name>`` — one tracked job's record (404 unknown);
        - ``GET /jobs/<name>/wait?timeout=S`` — push job completion:
          long-poll (or SSE with ``Accept: text/event-stream``) until
          the job goes terminal, released by the job's ``done`` event —
          no client-side 3-second polling. Immediate return for
          already-terminal jobs; a bare dataset filename resolves to
          the newest job materialising it (``titanic`` →
          ``ingest:titanic``); 404 parity with ``GET /jobs/<name>``;
          a timeout answers a clean ``{"result": "timeout"}`` re-poll
          hint (docs/web.md);
        - ``GET /jobs/<name>/trace`` — its correlated span tree;
        - ``GET /health`` — liveness + feature probe: ``job_wait: true``
          tells clients the push route exists (client.py prefers it
          over metadata polling);
        - ``DELETE /jobs/<name>`` — cooperative cancellation: a queued
          job terminates without running, a running one at its next
          cancel check (ml/builder.py's phase loop checks); 202 while
          the cancel propagates, 409 once the job is already terminal.
          A cancel also wakes the job's parked waiters.
        """
        self.register_job_traces(jobs)
        # terminal-state names live with the manager; imported here (not
        # at module top) to keep this transport module import-light
        from learningorchestra_tpu.core.jobs import TERMINAL_STATES

        @self.route("/jobs")
        def read_jobs(request):
            return {"result": jobs.all_jobs()}, 200

        @self.route("/jobs/<job_name>", methods=("DELETE",))
        def cancel_job(request, job_name):
            outcome = jobs.cancel(job_name)
            if outcome == "unknown":
                return {"result": "not_found"}, 404
            if outcome == "terminal":
                return {"result": "already_terminal"}, 409
            return {"result": "cancelling"}, 202

        @self.route("/jobs/<job_name>", methods=("GET",))
        def read_job(request, job_name):
            record = jobs.get(job_name)
            if record is None:
                return {"result": "not_found"}, 404
            return {"result": record.as_dict()}, 200

        @self.route("/jobs/<job_name>/wait", methods=("GET",))
        def wait_job(request, job_name):
            try:
                timeout_s = float(request.args.get("timeout", "25"))
            except ValueError:
                return {"result": "bad_timeout"}, 400
            if timeout_s != timeout_s or timeout_s < 0:  # NaN included
                return {"result": "bad_timeout"}, 400
            timeout_s = min(timeout_s, _webloop.wait_cap_s())
            record = jobs.resolve_wait(job_name)
            if record is None:
                # parity with GET /jobs/<name>: unknown job is a 404,
                # clients fall back to metadata polling
                return {"result": "not_found"}, 404
            sse = "text/event-stream" in (request.headers.get("Accept") or "")

            def poll(_record=record):
                if _record.state in TERMINAL_STATES:
                    return {"result": _record.as_dict()}, 200
                return None

            def on_timeout(_record=record):
                # a clean re-poll hint: the job is alive, ask again
                return {
                    "result": "timeout",
                    "job": _record.name,
                    "state": _record.state,
                }, 200

            waiter = Waiter(poll, timeout_s, on_timeout, sse=sse)
            jobs.add_done_callback(record.name, waiter.notify)
            return waiter

        if not any(
            rule.rule == "/health" for rule in self.url_map.iter_rules()
        ):

            @self.route("/health")
            def health(request):
                return {
                    "result": "ok",
                    "service": self.name,
                    # feature probe: client.py checks this once per
                    # cluster before preferring /wait over polling
                    "job_wait": True,
                    # SLO verdict (telemetry/slo.py): liveness is not
                    # healthiness — a serving replica can answer 200s
                    # while its p99 burns
                    "degraded": self.slo_degraded(),
                }, 200

    def route(self, rule: str, methods: tuple[str, ...] = ("GET",)):
        def decorator(handler: Callable) -> Callable:
            endpoint = f"{handler.__name__}|{rule}|{'|'.join(methods)}"
            self.url_map.add(Rule(rule, endpoint=endpoint, methods=list(methods)))
            self._handlers[endpoint] = handler
            return handler

        return decorator

    def _dispatch(self, request: Request) -> Response:
        adapter = self.url_map.bind_to_environ(request.environ)
        try:
            endpoint, args = adapter.match()
            # the RULE (not the concrete path) labels request metrics, so
            # /files/<filename> is one series, not one per dataset
            request.environ["lo.route"] = endpoint.split("|")[1]
        except NotFound:
            return Response(
                json.dumps({"result": "not_found"}),
                mimetype="application/json",
                status=404,
            )
        except HTTPException as error:
            return error.get_response(request.environ)

        try:
            result = self._handlers[endpoint](request, **args)
        except HTTPException as error:
            # e.g. BadRequest from request.get_json() on a malformed
            # body — keep its real status code, don't convert to a 500.
            return error.get_response(request.environ)
        if isinstance(result, (Waiter, Upstream)):
            # the answer isn't ready / lives on another server:
            # __call__ parks or proxies it (event loop) or resolves it
            # blocking (threaded server / test client)
            return result
        if isinstance(result, Response):
            return result
        if isinstance(result, tuple):
            payload, status = result
            if isinstance(payload, Response):
                payload.status_code = status
                return payload
            return Response(
                json.dumps(payload), mimetype="application/json", status=status
            )
        return Response(
            json.dumps(result), mimetype="application/json", status=200
        )

    def __call__(self, environ, start_response):
        request = Request(environ)
        # Correlation middleware: honour a caller-supplied ID (a client
        # stitching multi-service flows) or mint one; the request runs
        # under an active trace so spans anywhere below (job submit,
        # SPMD dispatch, PhaseTimer phases) correlate, and the ID echoes
        # back on the response.
        correlation_id = (
            request.headers.get(_tracing.CORRELATION_HEADER)
            or _tracing.mint_correlation_id()
        )
        trace = _tracing.Trace(
            correlation_id, name=f"{request.method} {request.path}"
        )
        self._in_flight.labels(self.name).inc()
        started = time.perf_counter()
        try:
            with _tracing.activate(trace), _tracing.span(
                f"http:{request.method} {request.path}"
            ):
                try:
                    response = self._dispatch(request)
                except Exception as error:  # mirror Flask's 500 text
                    response = Response(
                        f"{type(error).__name__}: {error}",
                        status=500,
                        mimetype="text/plain",
                    )
        finally:
            self._in_flight.labels(self.name).dec()
        # feed the cross-process stitcher: this request's spans land in
        # the cid-keyed export buffer GET /debug/spans drains
        _tracing.export_trace(trace, service=self.name)
        route = environ.get("lo.route", "<unmatched>")
        method = request.method
        if isinstance(response, Upstream):
            upstream = response
            upstream.correlation_id = correlation_id
            if environ.get("lo.async"):
                # Event-loop server: the loop proxies on its own thread
                # — this pooled thread is released immediately. Metrics
                # record at relay time, like a parked waiter's. A
                # route-set on_complete (the router's own families)
                # chains in front rather than being replaced.
                route_complete = upstream.on_complete

                def complete(status, _route=route, _method=method):
                    if route_complete is not None:
                        route_complete(status)
                    self._requests_total.labels(
                        self.name, _route, _method, status
                    ).inc()
                    self._request_seconds.labels(
                        self.name, _route, _method
                    ).observe(time.perf_counter() - started)

                upstream.on_complete = complete
                environ["lo.upstream"] = upstream
                start_response("204 No Content", [])
                return [b""]
            # Threaded server / test client: walk the targets blocking
            # on this request thread.
            status, headers, body = upstream.resolve_blocking()
            response = Response(body, status=status, headers=headers)
        if isinstance(response, Waiter):
            waiter = response
            waiter.correlation_id = correlation_id
            if environ.get("lo.async"):
                # Event-loop server: park the CONNECTION, not a thread.
                # Metrics record at resolution — a long-poll's latency
                # IS its parked time.
                def complete(status, _route=route, _method=method):
                    self._requests_total.labels(
                        self.name, _route, _method, status
                    ).inc()
                    self._request_seconds.labels(
                        self.name, _route, _method
                    ).observe(time.perf_counter() - started)

                waiter.on_complete = complete
                environ["lo.waiter"] = waiter
                start_response("204 No Content", [])
                return [b""]
            # Threaded server / test client: reference-parity blocking —
            # this request thread parks until ready or timeout.
            result, kind = waiter.resolve_blocking()
            body, status, content_type = _webloop.waiter_body(
                waiter, result, kind
            )
            response = Response(body, status=status, mimetype=content_type)
        self._requests_total.labels(
            self.name, route, method, response.status_code
        ).inc()
        self._request_seconds.labels(
            self.name, route, method
        ).observe(time.perf_counter() - started)
        response.headers[_tracing.CORRELATION_HEADER] = correlation_id
        return response(environ, start_response)

    def test_client(self) -> Client:
        return Client(self, Response)


class ServerThread:
    """Run a WSGI app on a background thread (integration tests, dev).

    ``LO_WEB_ASYNC=1`` (the default) serves through the event-loop core
    (utils/webloop.LoopServer): one selectors loop owns every socket and
    a bounded handler pool runs the route functions. ``LO_WEB_ASYNC=0``
    is the escape hatch back to werkzeug's thread-per-request server —
    byte-compatible routes, reference-parity blocking waits."""

    def __init__(self, app: WebApp, host: str, port: int):
        self.host = host
        if _webloop.web_async_enabled():
            self._server = None
            self._loop = _webloop.LoopServer(app, host, port)
            self.port = self._loop.port
            self._thread = self._loop._thread
        else:
            self._loop = None
            self._server = make_server(host, port, app, threaded=True)
            self.port = self._server.server_port
            self._thread = threading.Thread(
                target=self._server.serve_forever,
                daemon=True,
                name=f"{app.name}-server",
            )

    def start(self) -> "ServerThread":
        if self._loop is not None:
            self._loop.start()
        else:
            self._thread.start()
        return self

    def stop(self) -> None:
        if self._loop is not None:
            self._loop.stop()
        else:
            self._server.shutdown()
        self._thread.join(timeout=5)


def run_app(app: WebApp, host: str, port: int) -> None:
    """Serve forever in the foreground (container entrypoint)."""
    if _webloop.web_async_enabled():
        _webloop.LoopServer(app, host, port).serve_forever()
        return
    make_server(host, port, app, threaded=True).serve_forever()
