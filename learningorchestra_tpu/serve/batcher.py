"""Request micro-batching: many waiting clients, one forward dispatch.

A flood of single-row predicts is the worst case for the batch
pipeline: each would pay its own dispatch and its own device-to-host
fetch. The accelerator does not care whether
a forward pass carries 1 row or 64 — so the executor here collects
requests that arrive within a short window (``LO_SERVE_BATCH_WINDOW_MS``)
into ONE padded forward per model and scatters the outputs back to the
waiting request threads. This is the SPMD dispatch shape from the fit
path (matched in/out specs, mask-padded rows) applied at request
granularity.

Admission: the inbox is bounded (``LO_SERVE_QUEUE_CAP``). Past the cap
:meth:`MicroBatcher.submit` raises the scheduler's own
:class:`~learningorchestra_tpu.sched.scheduler.QueueFullError` with a
drain-rate Retry-After estimate, which the REST layer renders as the
same 429 contract the job queues use — the serving class bypasses the
scheduler's device queue (latency), not its admission discipline
(overload honesty).

Batches always dispatch with a fixed padded row count
(``LO_SERVE_MAX_BATCH`` rows minimum): XLA compiles one program per
shape, and letting every distinct batch size compile its own program
would turn the first traffic burst into a compile storm. Padding rows
are sliced off before scatter; the models' masked kernels make the
extra rows free.
"""

from __future__ import annotations

import math
import queue
import threading
import time
from typing import Optional

import numpy as np

from learningorchestra_tpu.sched.scheduler import QueueFullError
from learningorchestra_tpu.telemetry import tracing as _tracing
from learningorchestra_tpu.testing import faults as _faults
from learningorchestra_tpu.utils.shapegrid import grid_size, pad_axis0

SERVE_CLASS = "serve"

# One forward in TRACE_EVERY runs under its own trace, remembered in
# tracing's bounded in-process ring (remember_trace, 256 entries): the
# serving lane's timeline evidence without per-request trace cost.
TRACE_EVERY = 16

_CLOSE = object()  # inbox sentinel

_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)
# Shared by the queue-wait histogram here and the route's end-to-end
# lo_serve_request_seconds: serving latencies live in the millisecond
# range the job-oriented DEFAULT_BUCKETS (5 ms floor) cannot resolve.
LATENCY_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0,
)


class PredictRequest:
    """One waiting client: input rows in, ``(labels, probs)`` or an
    exception out, handed across threads via the done event."""

    __slots__ = (
        "path", "rows", "labels", "probs", "error", "abandoned",
        "submitted_at", "_done",
    )

    def __init__(self, path: str, rows: np.ndarray):
        self.path = path
        self.rows = rows
        self.labels: Optional[np.ndarray] = None
        self.probs: Optional[np.ndarray] = None
        self.error: Optional[BaseException] = None
        self.abandoned = False
        self.submitted_at = time.monotonic()
        self._done = threading.Event()

    def finish(self) -> None:
        self._done.set()

    def abandon(self) -> None:
        """The waiting client gave up (route timeout → 503). Checked at
        dispatch: an overloaded batcher drains its dead backlog cheaply
        instead of burning device time on results nobody will read."""
        self.abandoned = True

    def wait(self, timeout: float) -> bool:
        return self._done.wait(timeout)


class MicroBatcher:
    """One daemon worker draining a bounded inbox into batched forwards.

    Single worker thread by design: one dispatch in flight per process
    keeps serving's device footprint bounded (the fit path's
    device-width-1 discipline, applied to the bypass lane), and while a
    forward runs the next burst piles into the inbox — which is exactly
    what makes the next dispatch a batch.
    """

    def __init__(
        self,
        registry,
        window_s: Optional[float] = None,
        max_batch: Optional[int] = None,
        inbox_cap: Optional[int] = None,
        trace_every: int = TRACE_EVERY,
    ):
        from learningorchestra_tpu.serve import config

        self.registry = registry
        # sample 1-in-N forwards into the bounded trace ring (0 = off;
        # tests pass 1 to trace every dispatch)
        self.trace_every = trace_every
        self.window_s = config.batch_window_s() if window_s is None else window_s
        self.max_batch = config.max_batch() if max_batch is None else max_batch
        cap = config.queue_cap() if inbox_cap is None else inbox_cap
        self._inbox: "queue.Queue" = queue.Queue(maxsize=cap)
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._closed = False
        # EWMA of batch service seconds, seeding Retry-After estimates
        self.avg_batch_s = 0.05
        self.batches = 0
        self.batched_requests = 0
        self.rejected = 0
        # worker-thread-private dispatch counter driving the 1-in-N
        # trace sampling: only the worker loop touches it, so it needs
        # no lock — unlike self.batches, which stats() reads under the
        # lock and must therefore also be WRITTEN under it (LO203)
        self._dispatches = 0
        self._metrics = _serve_batch_metrics()

    # --- submission (request threads) ----------------------------------------
    def submit(self, path: str, rows: np.ndarray) -> PredictRequest:
        """Enqueue one request; raises :class:`QueueFullError` when the
        inbox is at its cap (the 429 + Retry-After admission contract)
        and ``ValueError`` for a malformed ``rows`` — rejected HERE, on
        the caller's thread, so a bad submission can never poison the
        shared worker loop."""
        rows = np.asarray(rows)
        if rows.ndim != 2 or rows.shape[0] == 0:
            raise ValueError(
                f"rows must be a non-empty 2-D array, got shape {rows.shape}"
            )
        request = PredictRequest(path, rows)
        with self._lock:
            if self._closed:
                raise RuntimeError("serving batcher is closed")
            try:
                self._inbox.put_nowait(request)
            except queue.Full:
                self.rejected += 1
                self._metrics["rejected"].inc()
                depth = self._inbox.qsize()
                retry_after = max(
                    1,
                    min(
                        60,
                        math.ceil(
                            self.avg_batch_s * depth / max(1, self.max_batch)
                        ),
                    ),
                )
                raise QueueFullError(SERVE_CLASS, depth, retry_after) from None
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name="lo-serve-batcher"
                )
                self._thread.start()
        return request

    # --- the batching loop (worker thread) ------------------------------------
    def _loop(self) -> None:
        while True:
            first = self._inbox.get()
            if first is _CLOSE:
                return
            batch = [first]
            # Belt-and-braces guard: _forward already owns per-group
            # errors, but a bug anywhere else in collection/grouping
            # must fail THIS batch's waiters and keep the lane alive —
            # this is the process's only serving thread, and a dead one
            # turns every future predict into a 503-until-restart.
            try:
                if self._collect(batch) == "closed":
                    self._run_batches(batch)
                    return
                self._run_batches(batch)
            except BaseException as error:  # noqa: BLE001
                import traceback

                traceback.print_exc()
                for request in batch:
                    if not request._done.is_set():  # already-delivered
                        # results stay delivered; only waiters fail
                        request.error = error
                        request.finish()

    def _collect(self, batch: list) -> Optional[str]:
        """Fill ``batch`` from the inbox until the window closes or the
        request/row budget is reached; returns "closed" on shutdown."""
        rows_total = len(batch[0].rows)
        deadline = time.monotonic() + self.window_s
        # max_batch bounds BOTH requests and accumulated rows per
        # dispatch: multi-row requests stop the collection early, so
        # a dispatch never exceeds max_batch + one request's rows
        # (itself capped by the route's LO_SERVE_MAX_ROWS)
        while len(batch) < self.max_batch and rows_total < self.max_batch:
            remaining = deadline - time.monotonic()
            try:
                # remaining <= 0 still drains an already-full inbox
                # without sleeping (window 0 = pure backlog batching)
                item = (
                    self._inbox.get_nowait()
                    if remaining <= 0
                    else self._inbox.get(timeout=remaining)
                )
            except queue.Empty:
                break
            if item is _CLOSE:
                return "closed"
            batch.append(item)
            rows_total += len(item.rows)
        return None

    def _run_batches(self, batch: list) -> None:
        started = time.monotonic()
        for request in batch:
            self._metrics["queue_wait"].observe(started - request.submitted_at)
        # one dispatch per (model, feature width): a request whose width
        # does not match its model's fails alone, not its batch-mates.
        # Abandoned requests (client already answered 503) are dropped
        # here — their forward would compute results nobody reads.
        groups: "dict[tuple, list]" = {}
        for request in batch:
            if request.abandoned:
                self._metrics["abandoned"].inc()
                request.error = TimeoutError("request abandoned by client")
                request.finish()
                continue
            groups.setdefault(
                (request.path, request.rows.shape[1]), []
            ).append(request)
        for group in groups.values():
            self._forward(group)
        with self._lock:
            self.avg_batch_s = (
                0.8 * self.avg_batch_s + 0.2 * (time.monotonic() - started)
            )

    def _forward(self, group: list) -> None:
        import contextlib

        from learningorchestra_tpu.telemetry import span

        # The worker thread runs outside any request context, so by
        # default span() is a no-op here. Sample 1-in-trace_every
        # forwards into their own trace, parked in the bounded
        # in-process ring (remember_trace) — the serving lane's
        # flight-recorder evidence: batch rows/bytes and the registry
        # hit/miss verdict ride the serve:forward span.
        trace = None
        if self.trace_every and self._dispatches % self.trace_every == 0:
            trace = _tracing.Trace(name=f"serve:{group[0].path}")
        self._dispatches += 1
        context = (
            _tracing.activate(trace)
            if trace is not None
            else contextlib.nullcontext()
        )
        try:
            with context:
                self._forward_traced(group, span)
        finally:
            if trace is not None:
                _tracing.remember_trace(trace)
                # sampled forwards also feed the fleet stitcher's
                # export buffer (GET /debug/spans)
                _tracing.export_trace(trace, service="serve")

    def _forward_traced(self, group: list, span) -> None:
        try:
            # chaos point: an injected error here must land as
            # per-request errors via the finish() path below, never a
            # dropped group (testing/faults.py)
            _faults.fire(
                "serve.forward", path=group[0].path, requests=len(group)
            )
            # the span covers the registry lookup too, so its
            # hit/miss verdict (registry.get annotates the ambient
            # span) and a miss's serve:load_model child both land here
            forward_started = time.monotonic()
            with span("serve:forward", requests=len(group)):
                model = self.registry.get(group[0].path)
                rows = np.concatenate([request.rows for request in group])
                total = len(rows)
                # fixed dispatch shape via the shared padded-shape grid
                # (utils/shapegrid.py, the coalescer rides it too):
                # every small batch runs the ONE compiled max_batch-row
                # program (padding rows sliced off below; zero rows are
                # finite through every model), and larger totals (a
                # multi-row request joined) round up to the
                # quarter-octave grid, which bounds distinct compiled
                # shapes logarithmically.
                rows = pad_axis0(rows, grid_size(total, self.max_batch))
                _tracing.annotate(
                    rows=total,
                    bytes=int(rows.nbytes),
                    dtype=str(rows.dtype),
                )
                labels, probs = model.predict_both(rows)
            # registry lookup to results on the host: the lane's service
            # time, beside the queue wait observed at dispatch start
            self._metrics["forward"].observe(
                time.monotonic() - forward_started
            )
        except BaseException as error:  # noqa: BLE001 — delivered to the
            # waiting request threads; the route maps it to an HTTP error
            for request in group:
                request.error = error
                request.finish()
            return
        # published under the lock: stats() reads these two together
        # under self._lock, and a bare increment here could hand it a
        # mean_batch_size computed from a torn pair (LO203)
        with self._lock:
            self.batches += 1
            self.batched_requests += len(group)
        self._metrics["batch_size"].observe(len(group))
        self._metrics["batches"].inc()
        self._metrics["predictions"].inc(total)
        offset = 0
        for request in group:
            n = len(request.rows)
            request.labels = labels[offset : offset + n]
            request.probs = probs[offset : offset + n]
            offset += n
            request.finish()

    # --- lifecycle / stats -----------------------------------------------------
    def close(self) -> None:
        """Stop the worker and fail anything still queued (tests;
        production relies on the daemon thread dying with the process)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            thread = self._thread
        if thread is not None:
            self._inbox.put(_CLOSE)
            thread.join(timeout=10)
        while True:
            try:
                item = self._inbox.get_nowait()
            except queue.Empty:
                break
            if item is not _CLOSE:
                item.error = RuntimeError("serving batcher closed")
                item.finish()

    def depth(self) -> int:
        return self._inbox.qsize()

    def stats(self) -> dict:
        with self._lock:
            return {
                "depth": self._inbox.qsize(),
                "batches": self.batches,
                "batched_requests": self.batched_requests,
                "rejected": self.rejected,
                "mean_batch_size": (
                    round(self.batched_requests / self.batches, 3)
                    if self.batches
                    else None
                ),
            }


_METRICS: Optional[dict] = None
_METRICS_LOCK = threading.Lock()


def _serve_batch_metrics() -> dict:
    global _METRICS
    with _METRICS_LOCK:
        if _METRICS is None:
            from learningorchestra_tpu.telemetry import global_registry

            registry = global_registry()
            _METRICS = {
                "batch_size": registry.histogram(
                    "lo_serve_batch_size",
                    "Requests coalesced per forward dispatch",
                    buckets=_BATCH_BUCKETS,
                ),
                "queue_wait": registry.histogram(
                    "lo_serve_queue_wait_seconds",
                    "Seconds between request admission and dispatch start",
                    buckets=LATENCY_BUCKETS,
                ),
                "forward": registry.histogram(
                    "lo_serve_forward_seconds",
                    "Seconds from registry lookup to results on the host, "
                    "per forward dispatch",
                    buckets=LATENCY_BUCKETS,
                ),
                "batches": registry.counter(
                    "lo_serve_batches_total",
                    "Batched forward dispatches run",
                ),
                "predictions": registry.counter(
                    "lo_serve_predictions_total",
                    "Rows predicted by the serving path",
                ),
                "rejected": registry.counter(
                    "lo_serve_rejected_total",
                    "Requests refused at the inbox cap (HTTP 429)",
                ),
                "abandoned": registry.counter(
                    "lo_serve_abandoned_total",
                    "Timed-out requests dropped before their forward ran",
                ),
            }
        return _METRICS
