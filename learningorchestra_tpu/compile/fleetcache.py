"""Fleet-shared executable cache: the ``__lo_executables__`` collection.

The persistent XLA cache (utils/jitcache.py) already holds serialized
compiled executables as content-addressed files — one per (program,
compiler version, topology) key. This module moves those files through
the store so the whole fleet shares one warm cache: a runner finishing
an AOT pass (or any request-path compile, once published) uploads its
fresh entries; a fresh runner joining the fleet — or restarting after
the kill -9 chaos drill — pulls them into its local cache dir before
its first dispatch and replays its programs with near-zero compile
misses. Cache misses fall through to local compile-then-publish, so
the plane is never load-bearing: an empty or unreachable collection
just means a cold boot.

Wire shape: each cache file becomes chunked data rows
``{artifact, seq, data(base64)}`` plus ONE meta row
``{artifact, meta: 1, chunks, sha256, fingerprint}`` written LAST —
a reader never sees an artifact whose chunks aren't all landed. The
rows ride the store's existing columnar wire (string columns compress
like any other payload). Trust is decided on the meta row alone: a
``fingerprint`` (compile/aot.py's jax/jaxlib/backend envelope) that
doesn't match the local runtime is DISCARDED without touching the
payload — a version-mismatched executable is recompiled, never
deserialized wrong — and a chunk set failing its sha256 is discarded
the same way. Rev-invalidated: :func:`fetch` is a no-op while the
collection rev hasn't moved since this process last looked.
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import threading

COLLECTION = "__lo_executables__"

# 1 MiB of raw bytes per chunk row (~1.37 MiB base64): big enough that
# real cache entries (KB..MB) take a handful of rows, small enough to
# stay friendly to the store's per-document handling.
CHUNK_BYTES = 1 << 20

# fetch() no-op guard: collection rev seen per store object
_REV_SEEN: dict[int, int] = {}
_REV_LOCK = threading.Lock()


def _fingerprint_json() -> str:
    from learningorchestra_tpu.compile.aot import backend_fingerprint

    return json.dumps(backend_fingerprint(), sort_keys=True)


def _metrics():
    from learningorchestra_tpu.compile.aot import _aot_metrics

    return _aot_metrics()


def _published_artifacts(store) -> set[str]:
    return {
        doc["artifact"]
        for doc in store.find(COLLECTION, {"meta": 1})
        if "artifact" in doc
    }


def publish(store, cache_dir: str) -> dict:
    """Upload every local cache entry the collection doesn't already
    hold. Returns ``{"published": n, "skipped": m}``."""
    stats = {"published": 0, "skipped": 0}
    if not os.path.isdir(cache_dir):
        return stats
    try:
        existing = _published_artifacts(store)
    except Exception:  # unreachable store: cold boot semantics
        return stats
    fingerprint = _fingerprint_json()
    for entry in sorted(os.listdir(cache_dir)):
        path = os.path.join(cache_dir, entry)
        if not os.path.isfile(path):
            continue
        if entry in existing:
            stats["skipped"] += 1
            continue
        with open(path, "rb") as handle:
            blob = handle.read()
        digest = hashlib.sha256(blob).hexdigest()
        rows = [
            {
                "artifact": entry,
                "seq": seq,
                "data": base64.b64encode(
                    blob[offset:offset + CHUNK_BYTES]
                ).decode("ascii"),
            }
            for seq, offset in enumerate(
                range(0, len(blob), CHUNK_BYTES)
            )
        ] or [{"artifact": entry, "seq": 0, "data": ""}]
        try:
            store.insert_many(COLLECTION, rows)
            # meta row LAST: its presence means every chunk landed
            store.insert_one(COLLECTION, {
                "artifact": entry,
                "meta": 1,
                "chunks": len(rows),
                "size": len(blob),
                "sha256": digest,
                "fingerprint": fingerprint,
            })
        except Exception:
            return stats  # partial publish: meta row absent → invisible
        stats["published"] += 1
        _metrics()["published"].inc()
    return stats


def fetch(store, cache_dir: str, force: bool = False) -> dict:
    """Pull fleet artifacts this process's cache dir is missing.
    Returns ``{"fetched": n, "discarded": d, "skipped": s}``;
    a no-op (all zeros) while the collection rev hasn't moved."""
    stats = {"fetched": 0, "discarded": 0, "skipped": 0}
    try:
        rev = store.collection_rev(COLLECTION)
    except Exception:
        return stats
    with _REV_LOCK:
        if not force and _REV_SEEN.get(id(store)) == rev:
            return stats
    os.makedirs(cache_dir, exist_ok=True)
    local_fingerprint = _fingerprint_json()
    try:
        metas = [
            doc for doc in store.find(COLLECTION, {"meta": 1})
            if "artifact" in doc
        ]
    except Exception:
        return stats
    for meta in metas:
        name = meta["artifact"]
        if os.sep in name or name in (".", ".."):
            stats["discarded"] += 1  # a path-traversal row is hostile,
            _metrics()["discarded"].inc()  # not merely stale
            continue
        path = os.path.join(cache_dir, name)
        if os.path.exists(path):
            stats["skipped"] += 1
            continue
        if meta.get("fingerprint") != local_fingerprint:
            # version mismatch: discard WITHOUT deserializing — the
            # local compiler recompiles and publishes under its own
            # fingerprint
            stats["discarded"] += 1
            _metrics()["discarded"].inc()
            continue
        chunks = sorted(
            (
                doc for doc in store.find(
                    COLLECTION, {"artifact": name}
                )
                if "data" in doc
            ),
            key=lambda doc: doc.get("seq", 0),
        )
        try:
            blob = b"".join(
                base64.b64decode(doc["data"]) for doc in chunks
            )
        except Exception:
            blob = None
        if (
            blob is None
            or len(chunks) != meta.get("chunks")
            or hashlib.sha256(blob).hexdigest() != meta.get("sha256")
        ):
            stats["discarded"] += 1  # corrupt payload: recompile locally
            _metrics()["discarded"].inc()
            continue
        tmp_path = f"{path}.tmp.{os.getpid()}"
        with open(tmp_path, "wb") as handle:
            handle.write(blob)
        os.replace(tmp_path, path)  # atomic: jax never reads a partial
        stats["fetched"] += 1
        _metrics()["fetched"].inc()
    with _REV_LOCK:
        _REV_SEEN[id(store)] = rev
    return stats
