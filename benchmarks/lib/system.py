"""The system under test, started in this process, and the few calls the
harness makes on it: boot the seven services, write a dataset through
the store's columnar write path, scrape ``/metrics``, fetch a job's
trace. In-process because ``memory_stats()`` and ``jax.profiler`` are
only open to the process that holds the chip."""

from __future__ import annotations

import http.client
import json
import os
import re
import time

import numpy as np

PREPROCESSOR = (
    "from pyspark.ml.feature import VectorAssembler\n"
    "feature_cols = [c for c in training_df.schema.names if c != 'label']\n"
    "assembler = VectorAssembler(inputCols=feature_cols, outputCol='features')\n"
    "features_training = assembler.transform(training_df)\n"
    "features_testing = assembler.transform(testing_df)\n"
    "features_evaluation = assembler.transform(testing_df)\n"
)
MODEL_BUILDER_PORT = 5002


class System:
    def __init__(self, workdir: str):
        from learningorchestra_tpu.core.store import InMemoryStore
        from learningorchestra_tpu.services.runner import start_all
        from learningorchestra_tpu.utils.jitcache import enable_compile_cache

        self.cache_dir = enable_compile_cache()
        self.models_dir = os.path.join(workdir, "models")
        self.store = InMemoryStore()
        _, self.servers = start_all(
            store=self.store,
            images_dir=os.path.join(workdir, "images"),
            ephemeral=True,
            models_dir=self.models_dir,
        )
        ports = {s.canonical_port: s.port for s in self.servers}
        self.port = ports[MODEL_BUILDER_PORT]

    def stop(self) -> None:
        for server in self.servers:
            server.stop()

    # --- data -------------------------------------------------------------
    def write_dataset(self, name: str, columns, labels, fields) -> None:
        """The store's own columnar write path plus the metadata row -
        what an ingest followed by a cast to numbers leaves behind."""
        self.store.insert_one(
            name,
            {
                "_id": 0,
                "filename": name,
                "finished": True,
                "fields": list(fields) + ["label"],
            },
        )
        data = {field: column for field, column in zip(fields, columns)}
        data["label"] = labels
        self.store.insert_columns(name, data)

    def stored(self, collection: str, fields: list[str]) -> dict[str, np.ndarray]:
        """Whole columns of a collection, as the store holds them."""
        arrays = self.store.read_column_arrays(collection, fields)
        out = {}
        for field, column in arrays.items():
            if column.kind == "vec":
                out[field] = np.asarray(column.data[: column.size])
            else:
                out[field] = np.asarray(column.to_float64())
        return out

    # --- HTTP -------------------------------------------------------------
    def connection(self, timeout: float) -> http.client.HTTPConnection:
        return http.client.HTTPConnection("127.0.0.1", self.port, timeout=timeout)

    def request(self, method: str, path: str, body=None, timeout: float = 60.0):
        conn = self.connection(timeout)
        try:
            payload = None if body is None else json.dumps(body)
            conn.request(
                method, path, payload, {"Content-Type": "application/json"}
            )
            response = conn.getresponse()
            return response.status, response.read()
        finally:
            conn.close()

    def build(self, train: str, test: str, classifiers: list[str], timeout: float):
        return self.request(
            "POST",
            "/models",
            {
                "training_filename": train,
                "test_filename": test,
                "preprocessor_code": PREPROCESSOR,
                "classificators_list": classifiers,
            },
            timeout,
        )

    def job_trace(self, test: str, classifiers: list[str]) -> dict:
        name = f"build:{test}:{'+'.join(classifiers)}"
        status, body = self.request(
            "GET", "/jobs/" + name.replace("+", "%2B") + "/trace"
        )
        if status != 200:
            raise RuntimeError(f"GET /jobs/{name}/trace: {status} {body[:200]!r}")
        return json.loads(body)["result"]["trace"]

    def counters(self) -> dict:
        status, body = self.request("GET", "/metrics")
        if status != 200:
            raise RuntimeError(f"GET /metrics: {status}")
        return parse_metrics(body.decode())


_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)$")
_TYPE = re.compile(r"^# TYPE (\S+) (\S+)$")
_LE = re.compile(r'le="([^"]+)"')


def parse_metrics(text: str) -> dict:
    """Prometheus text to ``{family: value}``; a histogram family maps to
    ``{"buckets": {le: count}, "sum": s, "count": n}``. Labels other than
    ``le`` are summed over."""
    histograms = {
        m.group(1)
        for m in map(_TYPE.match, text.splitlines())
        if m and m.group(2) == "histogram"
    }
    out: dict = {
        name: {"buckets": {}, "sum": 0.0, "count": 0.0} for name in histograms
    }
    for line in text.splitlines():
        match = _SAMPLE.match(line)
        if not match:
            continue
        name, labels, raw = match.groups()
        value = float(raw)
        stem, _, suffix = name.rpartition("_")
        if stem in histograms and suffix in ("bucket", "sum", "count"):
            if suffix == "bucket":
                le = _LE.search(labels or "").group(1)
                buckets = out[stem]["buckets"]
                buckets[le] = buckets.get(le, 0.0) + value
            else:
                out[stem][suffix] += value
        else:
            out[name] = out.get(name, 0.0) + value
    return out
