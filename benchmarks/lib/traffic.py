"""The one general traffic generator. A mix is a data file under
``benchmarks/traffic/``; this module reads its parameters and does what
they say, so a new mix of the same parts needs no code.

``build``: ``clients`` threads, each posting synchronous builds of the
configuration's classifiers back to back. A client submits no new build
once ``seconds`` have passed and it has finished ``builds`` of them (1
where the mix does not say); the builds in flight run to their 201, and
the window ends there - rates are all the work over all of that time,
so nothing is cut off where the clock stopped. One build outlasts any
permitted ``--seconds``, so ``builds`` is what sets the window's length:
host jitter of about a second a build averages out over several.

A traced window holds one build whatever the mix says: the per-layer
readers average over a window's builds, so their metrics mean the same,
and the capture and its reduction stay inside a run's limit.
"""

from __future__ import annotations

import threading
import time


class Window:
    """Runs one measured window and keeps what happened in it."""

    def __init__(self, system, cell, names: dict, traced: bool = False):
        self.system = system
        self.mix = cell.mix
        self.classifiers = cell.config["classifiers"]
        self.names = names
        self.traced = traced
        self.min_builds = 1 if traced else int(self.mix["build"].get("builds", 1))
        self.builds: list[dict] = []
        self._lock = threading.Lock()

    def _build_client(self, seconds: float) -> None:
        timeout = float(self.mix["build"].get("timeout_s", 1100))
        finished = 0
        while True:
            record = {"start": time.time(), "start_mono": time.monotonic()}
            if self.traced:
                record["counters_before"] = self.system.counters()
            status, body = self.system.build(
                self.names["train"], self.names["test"], self.classifiers, timeout
            )
            record["end"] = time.time()
            record["end_mono"] = time.monotonic()
            record["status"] = status
            record["body"] = body[:200].decode(errors="replace")
            if self.traced and status == 201:
                record["trace"] = self.system.job_trace(
                    self.names["test"], self.classifiers
                )
                record["counters_after"] = self.system.counters()
            with self._lock:
                self.builds.append(record)
            if status != 201:
                return  # a failed build ends its client: the run is not correct
            finished += 1
            if (
                finished >= self.min_builds
                and time.monotonic() - self.start_mono >= seconds
            ):
                return

    def run(self, seconds: float) -> None:
        self.start = time.time()
        self.start_mono = time.monotonic()
        clients = [
            threading.Thread(target=self._build_client, args=(seconds,), daemon=True)
            for _ in range(int(self.mix["build"].get("clients", 1)))
        ]
        for thread in clients:
            thread.start()
        for thread in clients:
            thread.join()
        self.end = time.time()
        self.end_mono = time.monotonic()
        self.length_s = self.end_mono - self.start_mono

    def end_to_end(self, train_rows: int) -> dict:
        """What the user saw: training rows of the builds that finished
        over the window's whole length."""
        done = [b for b in self.builds if b["status"] == 201]
        if not done:
            return {}
        span = max(b["end_mono"] for b in done) - self.start_mono
        return {"build_rows_per_s": len(done) * train_rows / span}

    def build_seconds(self) -> list[float]:
        """Each finished build's own seconds, in the order they ended."""
        return [
            b["end_mono"] - b["start_mono"] for b in self.builds if b["status"] == 201
        ]

    def attempted_failed(self) -> tuple[int, int]:
        return len(self.builds), sum(b["status"] != 201 for b in self.builds)
