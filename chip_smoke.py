#!/usr/bin/env python3
"""Chip smoke: the main path, once, end to end, on the accelerator.

The quickest proof that the system still starts on the chip. ONE child
process — ``python -m learningorchestra_tpu.services.runner``, the
documented entry point in its default single-process topology with an
in-process store and no ``LO_*`` knob set — owns the chip. This parent
never imports JAX (a chip belongs to one process); it drives the child
over HTTP on the reference ports with ``learningorchestra_tpu.client``,
the calls a user makes:

    POST /files x2 -> PATCH /fieldtypes x2 -> projection, histogram ->
    POST /models (sync, five classifiers, request-supplied preprocessor)
    -> stored metrics -> GET /models -> online predicts against two
    models -> one batch predict from a checkpoint -> a four-point
    lambda sweep (one vmapped dispatch) -> PCA and t-SNE image create
    + GET -> /metrics

at the full width the repo supports: 16 float features, binary label,
``lr dt rf gb nb`` at their MLlib defaults, 100,000 train and 100,000
test rows generated from a seed (:func:`synthetic`), written to CSV and
ingested by path (no network).

It FAILS (non-zero, no result line) unless the runner reports the
expected platform, every request returns its documented body, every
classifier's stored accuracy clears the floor (float32 on the chip —
the check the float64 CPU tests cannot make), the predict lane's labels
equal the labels the build stored, the registry shows a pinned model
and dispatched batches, host-to-device bytes were counted, and the
native CSV parser ran. No phase catches a failure to carry on.

Stdout is two lines, both written only after everything passed. First
``{"smoke_observations": {...}}``: the mesh, the compile-cache directory
and its hit/miss counters, the CSV parser, the accuracies, and per-phase
wall seconds — observations of one run, not benchmark numbers. Last, the
result the driver parses, with exactly these keys, the device as JAX
reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rows`` and ``--expect-platform`` exist to rehearse the script on a
CPU box at a tiny size and to run the 1M-row build; the driver runs it
with neither.
"""

from __future__ import annotations

import argparse
import contextlib
import ctypes
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
FEATURES = 16
CLASSIFIERS = ["lr", "dt", "rf", "gb", "nb"]
# round-5 record on this generator: 0.86-0.90 for every classifier
ACCURACY_FLOOR = 0.80
ONLINE_MODELS = ("lr", "gb")
ONLINE_REQUESTS_PER_MODEL = 12  # x 20 rows each (the /files page size)
PAGE = 20
# the contract allows 1200 s, compilation included; stopping the
# runner may take up to 240 s more (stop_runner)
TOTAL_BUDGET_S = 900
PREPROCESSOR = (
    "from pyspark.ml.feature import VectorAssembler\n"
    "feature_cols = [c for c in training_df.schema.names if c != 'label']\n"
    "assembler = VectorAssembler(inputCols=feature_cols, outputCol='features')\n"
    "features_training = assembler.transform(training_df)\n"
    "features_testing = assembler.transform(testing_df)\n"
    "features_evaluation = assembler.transform(testing_df)\n"
)
BANNER_DEVICE = re.compile(
    r"^device: platform=(\w+) kind=(\".*\") count=(\d+) mesh=(\S+)$", re.M
)
BANNER_CACHE = re.compile(r"^compile cache: dir=(.+)$", re.M)
BANNER_PARSER = re.compile(r"^csv parser: (.+)$", re.M)
BANNER_SERVING = "serving all services"


class SmokeFailure(Exception):
    pass


def check(condition, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def log(message: str) -> None:
    print(f"[smoke] {message}", file=sys.stderr, flush=True)


def synthetic(rows: int, seed: int):
    """The smoke's rows: 16 uniform features in [0, 20), and a label
    that two of them and uniform noise decide, so every classifier has
    something to learn and none can be perfect (``ACCURACY_FLOOR`` is
    set for this rule). Plain numpy: this process must stay off JAX."""
    rng = np.random.default_rng(seed)
    X = rng.random((rows, FEATURES), dtype=np.float32) * 20.0
    y = (
        (X[:, 0] + X[:, 1] * 0.5 + rng.random(rows, dtype=np.float32) * 8) > 22
    ).astype(np.int32)
    return X, y


def write_csv(path: str, rows: int, seed: int) -> None:
    X, y = synthetic(rows, seed)
    header = ",".join([f"f{i}" for i in range(FEATURES)] + ["label"])
    np.savetxt(
        path,
        np.column_stack([X.astype(np.float64), y]),
        fmt=["%.6f"] * FEATURES + ["%d"],
        delimiter=",",
        header=header,
        comments="",
    )


class Phases:
    """Per-phase wall clock under one total deadline: SIGALRM raises in
    the main thread, which aborts whatever request is blocking."""

    def __init__(self, total_s: float):
        self.deadline = time.monotonic() + total_s
        self.seconds: dict[str, float] = {}
        signal.signal(signal.SIGALRM, self._expired)

    def _expired(self, signum, frame):
        raise SmokeFailure(f"deadline passed during phase {self.current!r}")

    @contextlib.contextmanager
    def phase(self, name: str, budget_s: float):
        self.current = name
        remaining = self.deadline - time.monotonic()
        check(remaining > 1, f"no time left for phase {name!r}")
        signal.alarm(max(1, int(min(budget_s, remaining))))
        start = time.monotonic()
        try:
            yield
        finally:
            signal.alarm(0)
        self.seconds[name] = round(time.monotonic() - start, 2)
        log(f"{name}: {self.seconds[name]} s")


def die_with_parent() -> None:
    # PR_SET_PDEATHSIG: if this parent is killed outright, so is the
    # child — the chip is never left held by an orphan
    ctypes.CDLL(None).prctl(1, signal.SIGKILL)


def start_runner(workdir: str, log_path: str) -> subprocess.Popen:
    """The documented entry point with default knobs. cwd is a fresh
    directory so ``./lo_data`` (the default WAL/models/images root)
    starts empty; every inherited ``LO_*`` is dropped."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("LO_")}
    env["PYTHONPATH"] = REPO
    env["PYTHONUNBUFFERED"] = "1"
    return subprocess.Popen(
        [sys.executable, "-m", "learningorchestra_tpu.services.runner"],
        cwd=workdir,
        env=env,
        stdout=open(log_path, "wb"),
        stderr=subprocess.STDOUT,
        start_new_session=True,
        preexec_fn=die_with_parent,
    )


def stop_runner(proc: subprocess.Popen) -> float:
    """Stop the runner and everything it started; returns the seconds
    it took to be gone. Releasing the device is slow — with four chips
    the process outlived SIGKILL by more than 10 s — so the waits are
    long, and a runner that is still there after them fails the run."""
    start = time.monotonic()
    if proc.poll() is None:
        with contextlib.suppress(ProcessLookupError):
            os.killpg(proc.pid, signal.SIGTERM)
        with contextlib.suppress(subprocess.TimeoutExpired):
            proc.wait(timeout=60)
    with contextlib.suppress(ProcessLookupError):
        os.killpg(proc.pid, signal.SIGKILL)
    proc.wait(timeout=180)
    return round(time.monotonic() - start, 2)


def wait_for_boot(proc: subprocess.Popen, log_path: str) -> str:
    while True:  # bounded by the phase alarm
        with open(log_path, errors="replace") as handle:
            text = handle.read()
        if BANNER_SERVING in text:
            return text
        check(
            proc.poll() is None,
            f"runner exited rc={proc.returncode} before serving",
        )
        time.sleep(0.5)


def created(result, message: str, what: str) -> None:
    """A 2xx with its documented body. The client raises on 4xx and
    hands back the raw text of a 5xx — neither is this dict."""
    check(
        isinstance(result, dict) and result.get("result") == message,
        f"{what}: expected result {message!r}, got {result!r}",
    )


def metric_total(text: str, name: str) -> float:
    return sum(
        float(line.rsplit(" ", 1)[1])
        for line in text.splitlines()
        if line.startswith(name) and line[len(name)] in " {"
    )


def drive(args, phases: Phases, boot_log: str, workdir: str) -> dict:
    import requests

    from learningorchestra_tpu import client as lo

    lo.Context("127.0.0.1")
    files, types, models = lo.DatabaseApi(), lo.DataTypeHandler(), lo.Model()
    waiter = lo.AsyncronousWait()
    train, test = "smoke_train", "smoke_test"
    fields = [f"f{i}" for i in range(FEATURES)] + ["label"]

    with phases.phase("ingest", 240):
        for name, seed in ((train, 0), (test, 1)):
            path = os.path.join(workdir, f"{name}.csv")
            write_csv(path, args.rows, seed)
            created(
                files.create_file(name, path, pretty_response=False),
                "file_created",
                f"POST /files {name}",
            )
        for name in (train, test):
            waiter.wait(name, pretty_response=False)
            meta = files.read_file(name, limit=1, pretty_response=False)
            check(
                meta["result"][0].get("finished") is True
                and meta["result"][0].get("fields") == fields,
                f"ingest metadata of {name}: {meta['result'][0]!r}",
            )

    with phases.phase("fieldtypes", 240):
        for name in (train, test):
            created(
                types.change_file_type(
                    name, {f: "number" for f in fields}, pretty_response=False
                ),
                "file_changed",
                f"PATCH /fieldtypes/{name}",
            )

    with phases.phase("projection_histogram", 120):
        created(
            lo.Projection().create_projection(
                train, "smoke_projection", ["f0", "f1", "label"],
                pretty_response=False,
            ),
            "created_file",
            "POST /projections",
        )
        created(
            lo.Histogram().create_histogram(
                train, "smoke_histogram", ["label"], pretty_response=False
            ),
            "created_file",
            "POST /histograms",
        )

    with phases.phase("build_5clf", 600):
        created(
            models.create_model(
                train, test, PREPROCESSOR, CLASSIFIERS, pretty_response=False
            ),
            "created_file",
            "POST /models",
        )

    # a build FINISHES with partial results when some classifiers fail,
    # so each one's stored outcome is read back, not inferred from 201
    accuracy = {}
    for clf in CLASSIFIERS:
        meta = files.read_file(
            f"{test}_prediction_{clf}", limit=1, pretty_response=False
        )["result"]
        check(
            meta and meta[0].get("classificator") == clf,
            f"{clf}: no stored outcome ({meta!r})",
        )
        accuracy[clf] = float(meta[0]["accuracy"])
        check(
            accuracy[clf] >= ACCURACY_FLOOR,
            f"{clf}: accuracy {accuracy[clf]} under {ACCURACY_FLOOR}",
        )
    log(f"accuracy: {accuracy}")

    listing = models.list_models(pretty_response=False)
    names = {f"{test}_prediction_{clf}" for clf in CLASSIFIERS}
    check(
        names <= set(listing["result"]),
        f"GET /models misses checkpoints: {listing['result']!r}",
    )

    with phases.phase("online_predict", 180):
        ties = 0
        for clf in ONLINE_MODELS:
            name = f"{test}_prediction_{clf}"
            for request in range(ONLINE_REQUESTS_PER_MODEL):
                stored = files.read_file(
                    name, skip=1 + request * PAGE, limit=PAGE,
                    pretty_response=False,
                )["result"]
                check(len(stored) == PAGE, f"{name}: short page {len(stored)}")
                answer = models.predict(
                    name,
                    [[row[f"f{j}"] for j in range(FEATURES)] for row in stored],
                    pretty_response=False,
                )
                check(
                    isinstance(answer, dict),
                    f"POST /models/{name}/predict: {answer!r}",
                )
                for row, label in zip(stored, answer["result"]["predictions"]):
                    if abs(row["probability"][1] - 0.5) < 1e-3:
                        ties += 1  # a numerical coin-flip proves nothing
                        continue
                    check(
                        label == int(row["prediction"]),
                        f"{name} row {row['_id']}: predict lane says "
                        f"{label}, the build stored {row['prediction']}",
                    )

    with phases.phase("batch_predict", 180):
        name = f"{test}_prediction_nb"
        response = requests.post(
            f"http://127.0.0.1:5002/models/{name}/predictions",
            json={
                "training_filename": train,
                "test_filename": test,
                "preprocessor_code": PREPROCESSOR,
                "prediction_filename": "smoke_batch",
            },
            timeout=170,
        )
        check(
            response.status_code == 201,
            f"POST /models/{name}/predictions: {response.status_code} "
            f"{response.text[:300]}",
        )
        built = files.read_file(name, skip=1, limit=PAGE, pretty_response=False)
        again = files.read_file(
            "smoke_batch", skip=1, limit=PAGE, pretty_response=False
        )
        check(
            [row["prediction"] for row in again["result"]]
            == [row["prediction"] for row in built["result"]],
            "batch predict from the checkpoint disagrees with the build",
        )

    with phases.phase("sweep_lr", 240):
        grid = [{"reg_param": value} for value in (0.0, 0.01, 0.1, 1.0)]
        sweep = models.sweep(
            train, test, PREPROCESSOR, "lr", grid, "smoke_sweep",
            pretty_response=False,
        )
        check(
            isinstance(sweep, dict) and len(sweep["result"]["points"]) == 4,
            f"POST /models/sweep: {sweep!r}",
        )
        winner = sweep["result"]["points"][sweep["result"]["best"]]
        check(
            winner["accuracy"] >= ACCURACY_FLOOR,
            f"sweep winner {winner!r} under {ACCURACY_FLOOR}",
        )
        answer = models.predict(
            "smoke_sweep",
            [[row[f"f{j}"] for j in range(FEATURES)] for row in built["result"]],
            pretty_response=False,
        )
        check(
            isinstance(answer, dict)
            and set(answer["result"]["predictions"]) <= {0, 1},
            f"predict from the sweep's winner: {answer!r}",
        )

    serving = models.list_models(pretty_response=False)["serving"]
    check(
        serving["registry"]["models"] >= 1
        and serving["registry"]["bytes"] > 0
        and serving["batches"] > 0,
        f"serving plane shows no pinned model or no batch: {serving!r}",
    )

    with phases.phase("embeddings", 420):
        for plot in (lo.Pca(), lo.Tsne()):
            label = plot._METHOD_LABEL
            created(
                plot.create_image_plot(
                    "smoke_image", train, "label", pretty_response=False
                ),
                "created_file",
                f"POST /images ({label})",
            )
            image = requests.get(
                plot.read_image_plot("smoke_image", pretty_response=False),
                timeout=60,
            )
            check(
                image.status_code == 200
                and image.content.startswith(b"\x89PNG")
                and len(image.content) > 1000,
                f"GET {label} image: {image.status_code}, "
                f"{len(image.content)} bytes",
            )

    metrics = requests.get("http://127.0.0.1:5002/metrics", timeout=30)
    check(metrics.status_code == 200, f"GET /metrics: {metrics.status_code}")
    h2d = metric_total(metrics.text, "lo_h2d_bytes_total")
    check(h2d > 0, "lo_h2d_bytes_total is 0: nothing reached the device")
    return {
        "accuracy": accuracy,
        "sweep_lr": sweep["result"]["points"],
        "online_predict": {
            "requests": len(ONLINE_MODELS) * ONLINE_REQUESTS_PER_MODEL,
            "rows": len(ONLINE_MODELS) * ONLINE_REQUESTS_PER_MODEL * PAGE,
            "ties_skipped": ties,
        },
        "serving": serving,
        "h2d_bytes": int(h2d),
        "compile_cache": {
            "dir": BANNER_CACHE.search(boot_log).group(1),
            "hits": int(metric_total(metrics.text, "lo_jitcache_persistent_hits")),
            "misses": int(
                metric_total(metrics.text, "lo_jitcache_persistent_misses")
            ),
            "backend_compile_s": round(
                metric_total(metrics.text, "lo_jitcache_backend_compile_seconds"),
                2,
            ),
        },
    }


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rows", type=int, default=100_000)
    parser.add_argument("--expect-platform", default="tpu")
    args = parser.parse_args()

    phases = Phases(TOTAL_BUDGET_S)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    workdir = tempfile.mkdtemp(prefix="lo_chip_smoke_")
    log_path = os.path.join(workdir, "runner.log")
    proc = start_runner(workdir, log_path)
    try:
        with phases.phase("boot", 300):
            boot_log = wait_for_boot(proc, log_path)
        device = BANNER_DEVICE.search(boot_log)
        check(device, "runner printed no device banner")
        platform, kind, count, mesh = device.groups()
        log(f"runner device: {device.group(0)}")
        check(
            platform == args.expect_platform,
            f"runner computes on {platform!r}, not {args.expect_platform!r}",
        )
        csv_parser = BANNER_PARSER.search(boot_log).group(1)
        check(
            csv_parser.startswith("native"),
            f"ingest would not use the native parser: {csv_parser}",
        )
        result = drive(args, phases, boot_log, workdir)
        check(proc.poll() is None, f"runner died rc={proc.returncode}")
    finally:
        signal.alarm(0)
        phases.seconds["runner_shutdown"] = stop_runner(proc)
        log(f"runner_shutdown: {phases.seconds['runner_shutdown']} s")
        with open(log_path, errors="replace") as handle:
            sys.stderr.write("---- runner log (tail) ----\n")
            sys.stderr.write(handle.read()[-6000:])
        shutil.rmtree(workdir, ignore_errors=True)
    check("jax" not in sys.modules, "the smoke's parent imported jax")
    observations = {
        "mesh": mesh,
        "rows": {"train": args.rows, "test": args.rows},
        "csv_parser": csv_parser,
        **result,
        "phase_wall_s": phases.seconds,
    }
    print(json.dumps({"smoke_observations": observations}))
    # the result line: these keys and no others
    reported = {"platform": platform, "kind": json.loads(kind), "count": int(count)}
    print(json.dumps({"ok": True, "device": reported}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
