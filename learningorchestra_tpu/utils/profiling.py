"""Tracing and per-phase timing.

The reference's only observability artifact is a wall-clock ``fit_time``
in the prediction metadata (reference: model_builder.py:198-203;
SURVEY.md §5 "Tracing / profiling: absent"). Here timings are
first-class: a :class:`PhaseTimer` accumulates named phase durations that
jobs attach to their result metadata, and :func:`trace` wraps the JAX
profiler so any block can emit a TensorBoard-loadable device trace.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

from learningorchestra_tpu.telemetry import tracing as _tracing


class PhaseTimer:
    """Accumulates ``{phase: seconds}``; reentrant per phase.

    Each phase ENTRY lands as its own timestamped span in the active
    trace (a no-op outside one): a phase entered twice is two events
    with distinct start/end boundaries on the timeline — summing them
    into one bucket would smear ``GET /jobs/<name>/profile``'s Chrome
    trace. The summed ``as_metadata()`` contract is unchanged: stored
    job metadata keeps one total per phase name. ``**attrs`` become
    typed span attributes (rows, bytes, dtype) on that entry's span."""

    def __init__(self):
        self.timings: dict[str, float] = {}

    @contextlib.contextmanager
    def phase(self, name: str, **attrs) -> Iterator[None]:
        start = time.perf_counter()
        try:
            with _tracing.span(f"phase:{name}", **attrs):
                yield
        finally:
            elapsed = time.perf_counter() - start
            self.timings[name] = self.timings.get(name, 0.0) + elapsed

    def as_metadata(self) -> dict[str, float]:
        """Rounded copy for inclusion in stored job metadata."""
        return {name: round(seconds, 6) for name, seconds in self.timings.items()}


@contextlib.contextmanager
def trace(log_dir: Optional[str]) -> Iterator[None]:
    """JAX profiler trace into ``log_dir`` (no-op when None) — view with
    TensorBoard's profile plugin or Perfetto."""
    if log_dir is None:
        yield
        return
    import jax

    with jax.profiler.trace(log_dir):
        # the wall clock at one instant of the capture's own clock: with
        # it, the spans of GET /jobs/<name>/trace (wall clock) lay over
        # the capture's device events (docs/profiling.md)
        with jax.profiler.TraceAnnotation("lo_clock", wall=time.time()):
            pass
        yield
