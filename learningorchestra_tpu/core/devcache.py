"""Device-resident data plane: a rev-keyed cache of prepared arrays.

The fit kernels on resident data run far ahead of the product path
(store read → preprocess → fits → prediction write-back), and most of
the gap is the SAME dataset crossing the wire and the host-to-device
boundary once per job (the sizes are PERF.md's to state, from the
ledger). The reference is worse still —
every service re-reads its collection from Mongo per request
(reference: microservices/model_builder_image/model_builder.py:96-116,
pca_image/pca.py:74-88) and never times that tail.

This module makes a dataset cross each boundary **once per revision**:

- One process-wide :class:`DeviceCache` (``global_devcache``), a
  capacity-bounded (``LO_DEVCACHE_BYTES``) LRU over both **host-level**
  entries (decoded :class:`~learningorchestra_tpu.core.table.ColumnTable`
  columns — skip the wire read + frame decode) and **device-level**
  entries (padded, row-sharded :class:`~learningorchestra_tpu.ml.base.
  DeviceMatrix` buffers — skip the host→device transfer).
- Dataset entries are keyed by ``(store scope, collection, subkey)``
  and stamped with the collection's **mutation rev** — the same counter
  the store service
  already ships per binary frame (``core/store_service.py``
  ``read_columns_bin`` ``extra={"rev": rev}``) for torn-read detection.
  A lookup probes the live rev first; a mismatch **evicts** the stale
  entry and reloads. That makes invalidation correct for a
  :class:`RemoteStore` too, where push invalidation is impossible: every
  mutating op bumps the collection's rev server-side, so the next cached
  reader anywhere observes it.
- Preprocessed frames (whose bytes are produced by arbitrary
  ``preprocessor_code``) are cached **content-addressed** instead
  (:func:`content_device_matrix`): the key is a BLAKE2 digest of the
  host buffer plus the mesh signature, so an entry can never be stale —
  it only LRU-evicts. This is what lets a second ``build_model`` over
  the same collection skip every H2D for train/test/eval matrices.

Device entries are per-process and per-mesh (``mesh_signature``): on a
multi-host mesh every process caches its own shards, and lookups are
pure host work — no collectives — so cache hits can never desynchronize
SPMD dispatch.

Import cost: numpy + stdlib only. JAX is imported lazily inside the
device-level helpers, so the store SERVER process (which imports
``core.store_service`` → this module's invalidation hook) never pays a
jax import.
"""

from __future__ import annotations

import hashlib
import itertools
import os
import threading
from collections import OrderedDict
from typing import Any, Callable, Optional

import numpy as np

from learningorchestra_tpu.utils.dtypepolicy import dtype_policy

# Content-addressed entries live under this pseudo-collection: their key
# embeds a digest of the bytes, so they cannot go stale and are never
# rev-invalidated — only LRU-evicted.
CONTENT = "__content__"

DEFAULT_CAPACITY_BYTES = 2_000_000_000


def capacity_bytes() -> int:
    """``LO_DEVCACHE_BYTES`` validated (deploy/run.sh preflights this):
    total bytes of cached payloads, host and device entries against one
    budget. ``0`` disables caching entirely."""
    # lo: allow[LO305] this IS the validated accessor preflight calls
    raw = os.environ.get("LO_DEVCACHE_BYTES", "").strip()
    if not raw:
        return DEFAULT_CAPACITY_BYTES
    try:
        value = int(float(raw))
    except ValueError:
        raise ValueError(
            f"LO_DEVCACHE_BYTES must be a number of bytes, got {raw!r}"
        ) from None
    if value < 0:
        raise ValueError(f"LO_DEVCACHE_BYTES must be >= 0, got {value}")
    return value


def store_rev(store, collection: str) -> int:
    """The collection's mutation counter, or -1 when the backend cannot
    report one (unknown backends never cache)."""
    rev_fn = getattr(store, "collection_rev", None)
    if rev_fn is None:
        return -1
    return rev_fn(collection)


_STORE_TOKENS = itertools.count(1)
_TOKEN_LOCK = threading.Lock()


def store_token(store) -> str:
    """A per-store-instance cache scope. Revs are monotonic only WITHIN
    one store, so entries must never be shared across stores: two
    stores holding a same-named collection at a coincidentally equal
    rev (trivial for two fresh in-memory stores) would otherwise alias.
    The token is minted once and pinned on the instance — stable for
    the store's lifetime, and unlike ``id()`` it can never recycle into
    a live entry after garbage collection. Minting is locked: two
    threads racing the first lookup must agree on ONE scope, or the
    loser's entries would be stranded (unreachable for hits and for
    scoped purges) while still charging the byte budget."""
    token = getattr(store, "_lo_devcache_token", None)
    if token is None:
        with _TOKEN_LOCK:
            token = getattr(store, "_lo_devcache_token", None)
            if token is None:
                token = f"s{next(_STORE_TOKENS)}"
                try:
                    store._lo_devcache_token = token
                except AttributeError:  # __slots__ backend: no cache
                    return ""
    # shard topology dimension: a ShardedStore's rev is a SUM over
    # groups, so a re-wired topology (different shard count or stripe)
    # could reproduce an old sum over different bytes — scoping the
    # token by the shard signature invalidates every cached entry on
    # any topology change instead
    return token + getattr(store, "shard_signature", "")


def mesh_signature(mesh) -> tuple:
    """A hashable, structural mesh identity: device entries prepared for
    one mesh must never serve another (different sharding layout), and
    ``id(mesh)`` alone would alias after garbage collection."""
    return (
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


class _Entry:
    __slots__ = ("value", "nbytes", "rev")

    def __init__(self, value: Any, nbytes: int, rev: int):
        self.value = value
        self.nbytes = nbytes
        self.rev = rev


class DeviceCache:
    """Capacity-bounded LRU keyed by ``(scope, collection, subkey)``
    where ``scope`` identifies the store instance (``store_token``) —
    revs are only comparable within one store.

    Staleness is checked at lookup against the caller-probed rev: a
    mismatched entry is dropped (counted as an invalidation) and the
    lookup misses, so one key never holds two revisions and a mutating
    store op needs no push channel into this process.
    """

    def __init__(self, capacity: Optional[int] = None):
        self._lock = threading.Lock()
        self._entries: "OrderedDict[tuple, _Entry]" = OrderedDict()
        self.capacity = capacity_bytes() if capacity is None else capacity
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0

    # --- primitive get/put ----------------------------------------------------
    def get(
        self, scope: str, collection: str, subkey: tuple, rev: int
    ) -> Optional[Any]:
        entry = self._get_entry(scope, collection, subkey, rev)
        return None if entry is None else entry.value

    def _get_entry(
        self, scope: str, collection: str, subkey: tuple, rev: int
    ) -> Optional[_Entry]:
        key = (scope, collection, subkey)
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None and rev >= 0 and entry.rev == rev:
                self._entries.move_to_end(key)
                self.hits += 1
                return entry
            if entry is not None:
                # stale (a write bumped the rev, or the collection is
                # gone and the probe answered -1): evict now — rev-keyed
                # invalidation IS this line
                self._drop_locked(key)
                self.invalidations += 1
            self.misses += 1
            return None

    def put(
        self,
        scope: str,
        collection: str,
        subkey: tuple,
        rev: int,
        value: Any,
        nbytes: int,
    ) -> Any:
        self._put(scope, collection, subkey, rev, value, nbytes)
        return value

    def _put(
        self,
        scope: str,
        collection: str,
        subkey: tuple,
        rev: int,
        value: Any,
        nbytes: int,
    ) -> tuple[int, int]:
        """Cache ``value``; returns ``(entries, bytes)`` the LRU dropped
        to make room for it."""
        nbytes = max(int(nbytes), 0)
        if (
            self.capacity <= 0
            or rev < 0
            or not scope
            or nbytes > self.capacity
        ):
            return 0, 0  # uncacheable: hand the value through
        key = (scope, collection, subkey)
        evicted = evicted_bytes = 0
        with self._lock:
            if key in self._entries:
                self._drop_locked(key)
            while self.bytes + nbytes > self.capacity and self._entries:
                oldest = next(iter(self._entries))
                evicted += 1
                evicted_bytes += self._entries[oldest].nbytes
                self._drop_locked(oldest)
            self.evictions += evicted
            self._entries[key] = _Entry(value, nbytes, rev)
            self.bytes += nbytes
        return evicted, evicted_bytes

    def _answer(
        self,
        scope: str,
        collection: str,
        subkey: tuple,
        rev: int,
        loader: Callable[[], Any],
        nbytes_fn: Callable[[Any], int],
        keep: Callable[[], bool] = lambda: True,
    ) -> Any:
        """The entry, or ``loader``'s value cached where ``keep()``
        still holds after the load; the answer is stamped on the
        enclosing ``devcache:lookup`` span: ``hits`` and ``misses`` (one
        of them 1), ``bytes`` (the entry's), ``evicted`` and
        ``evicted_bytes`` (what the LRU dropped for this put; 0 on a
        hit)."""
        from learningorchestra_tpu.telemetry import annotate

        entry = self._get_entry(scope, collection, subkey, rev)
        if entry is not None:
            annotate(
                hits=1, misses=0, bytes=entry.nbytes,
                evicted=0, evicted_bytes=0,
            )
            return entry.value
        value = loader()
        nbytes = nbytes_fn(value)
        evicted, evicted_bytes = (
            self._put(scope, collection, subkey, rev, value, nbytes)
            if keep()
            else (0, 0)
        )
        annotate(
            hits=0, misses=1, bytes=int(nbytes),
            evicted=evicted, evicted_bytes=evicted_bytes,
        )
        return value

    def _drop_locked(self, key: tuple) -> None:
        entry = self._entries.pop(key, None)
        if entry is not None:
            self.bytes -= entry.nbytes

    def invalidate(
        self, collection: Optional[str] = None, scope: Optional[str] = None
    ) -> int:
        """Drop every entry for ``collection`` (all collections when
        None), restricted to one store ``scope`` when given. Mid-stream
        read failures call this — scoped to the failing store, so an
        aborted read of one store's collection never purges another
        store's same-named one — and a partially-populated entry can
        never survive a retried read. Returns the drop count."""
        with self._lock:
            keys = [
                key
                for key in self._entries
                if (collection is None or key[1] == collection)
                and (scope is None or key[0] == scope)
            ]
            for key in keys:
                self._drop_locked(key)
            self.invalidations += len(keys)
            return len(keys)

    # --- the one loader shape every helper shares -----------------------------
    def get_or_load(
        self,
        store,
        collection: str,
        subkey: tuple,
        loader: Callable[[], Any],
        nbytes_fn: Callable[[Any], int],
    ) -> Any:
        """Rev-probed lookup; on miss run ``loader`` and cache the result
        — but only when the rev is unchanged after the load (a write
        landing mid-read must not be cached under the pre-write rev).
        One ``devcache:lookup`` span (``kind``: the subkey's first
        element) covers it, the loader's spans inside."""
        from learningorchestra_tpu.telemetry import span

        with span("devcache:lookup", kind=subkey[0]):
            scope = store_token(store)
            rev = store_rev(store, collection)
            return self._answer(
                scope, collection, subkey, rev, loader, nbytes_fn,
                keep=lambda: rev >= 0 and store_rev(store, collection) == rev,
            )

    def stats(self) -> dict:
        with self._lock:
            return {
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "invalidations": self.invalidations,
                "bytes": self.bytes,
                "entries": len(self._entries),
            }

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.bytes = 0


_GLOBAL: Optional[DeviceCache] = None
_GLOBAL_LOCK = threading.Lock()


def global_devcache() -> DeviceCache:
    """The process-wide cache every data-plane consumer shares. First
    call registers the ``lo_devcache_*`` gauges on the process metrics
    registry (docs/observability.md)."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = DeviceCache()
            _register_metrics(_GLOBAL)
        return _GLOBAL


def reset_global_devcache() -> None:
    """Tests only: drop the global cache's entries and counters. The
    metrics collector holds the OLD instance, so a full replacement
    would orphan its gauges — clear in place instead."""
    with _GLOBAL_LOCK:
        if _GLOBAL is not None:
            _GLOBAL.clear()
            _GLOBAL.hits = _GLOBAL.misses = 0
            _GLOBAL.evictions = _GLOBAL.invalidations = 0


def invalidate_collection(
    collection: str, store: Optional[object] = None
) -> None:
    """Invalidation hook for writers and for mid-stream read failures
    (``RemoteStore.read_column_arrays``): cheap no-op before the global
    cache exists. ``store`` (when given) restricts the purge to that
    store's scope."""
    with _GLOBAL_LOCK:
        cache = _GLOBAL
    if cache is not None:
        scope = store_token(store) if store is not None else None
        cache.invalidate(collection, scope=scope or None)


def _register_metrics(cache: DeviceCache) -> None:
    from learningorchestra_tpu.telemetry import global_registry

    registry = global_registry()
    gauges = {
        name: registry.gauge(f"lo_devcache_{name}", help_text)
        for name, help_text in (
            ("hits", "Device-cache lookups served without a reload"),
            ("misses", "Device-cache lookups that ran the loader"),
            ("evictions", "Entries dropped by the LRU capacity bound"),
            (
                "invalidations",
                "Entries dropped because the collection rev moved "
                "(or a mid-stream read failure forced a purge)",
            ),
            ("bytes", "Bytes of cached payloads (host + device)"),
            ("entries", "Entries resident in the device cache"),
        )
    }

    def collect(_registry) -> None:
        stats = cache.stats()
        for name, gauge in gauges.items():
            gauge.set(stats[name])

    registry.register_collector(collect)


# --- dataset-level helpers (collection + rev keyed) ---------------------------


def _fields_key(fields) -> tuple:
    return ("*",) if fields is None else tuple(fields)


def _device_matrix_nbytes(dm) -> int:
    return int(dm.data.nbytes) + int(dm.mask.nbytes)


def _table_nbytes(table) -> int:
    total = 0
    for column in table.columns.values():
        total += column.nbytes
        if column.dtype == object:
            # nbytes counts pointers only; charge a rough boxed-object
            # footprint so string-heavy tables don't dodge the budget
            total += 48 * len(column)
    return total


def dataset_table(store, collection: str, fields=None, cache=None):
    """The collection as a :class:`ColumnTable`, cached by rev — the
    host half of the data plane: a warm hit skips the wire read and the
    frame decode entirely. Callers share the returned table's arrays;
    every consumer in this codebase treats columns as immutable (frame
    verbs copy-on-write), which is the same contract the per-frame
    device cache already relies on."""
    from learningorchestra_tpu.core.table import ColumnTable
    from learningorchestra_tpu.telemetry import span

    cache = cache or global_devcache()

    def load():
        # store:read wraps the whole store→host materialization (local
        # or remote backend; a RemoteStore nests its wire:read inside)
        # with rows + decoded host bytes, so the timeline attributes
        # the host-boundary cost even when no wire is involved.
        with span("store:read", collection=collection) as span_obj:
            table = ColumnTable.from_store(store, collection, fields)
            if span_obj is not None:
                span_obj.meta["rows"] = table.num_rows
                span_obj.meta["bytes"] = _table_nbytes(table)
            return table

    return cache.get_or_load(
        store,
        collection,
        ("table", _fields_key(fields)),
        load,
        _table_nbytes,
    )


def dataset_embedding_inputs(store, collection: str, mesh=None, cache=None):
    """``(encoded_table, vocabularies, DeviceMatrix)`` as ONE cache
    entry — the PCA/t-SNE image pipeline's inputs. A single entry (not
    separate encoded/devmat lookups) so the hue labels and the device
    matrix can never come from different revisions when a write lands
    between lookups: everything in the triple derives from one
    ``dataset_table`` read. With caching disabled this also stays one
    wire read per request."""
    from learningorchestra_tpu.ml.base import resolve_mesh, shard_matrix
    from learningorchestra_tpu.telemetry import span

    mesh = resolve_mesh(mesh)
    cache = cache or global_devcache()

    def load():
        table = dataset_table(store, collection, cache=cache).dropna()
        encoded, vocabularies = table.encoded()
        X = encoded.matrix()
        # h2d byte accounting happens inside shard_matrix (the
        # shard_rows funnel, parallel/sharding.py) and accumulates onto
        # this span as h2d_bytes
        with span(
            "h2d:dataset",
            collection=collection,
            rows=len(X),
            dtype=dtype_policy(),
        ):
            return encoded, vocabularies, shard_matrix(X, mesh)

    return cache.get_or_load(
        store,
        collection,
        ("embed_inputs", mesh_signature(mesh), dtype_policy()),
        load,
        lambda value: _table_nbytes(value[0]) + _device_matrix_nbytes(value[2]),
    )


# --- content-addressed helpers (preprocessed frames) --------------------------


def _content_digest(array: np.ndarray) -> tuple:
    """The content key of a host array, under a ``devcache:digest``
    span (``bytes``: the bytes hashed)."""
    from learningorchestra_tpu.telemetry import span

    array = np.ascontiguousarray(array)
    with span("devcache:digest", bytes=int(array.nbytes)):
        digest = hashlib.blake2b(array.view(np.uint8), digest_size=16)
    return (str(array.dtype), array.shape, digest.hexdigest())


def _content_lookup(kind: str, array: np.ndarray, tail: tuple, load, nbytes_fn):
    """The content-addressed entry of ``array`` (subkey ``(kind,
    digest) + tail``), made by ``load`` on a miss: digest, lookup and
    load under one ``devcache:lookup`` span (see ``DeviceCache._answer``
    for what it carries)."""
    from learningorchestra_tpu.telemetry import span

    with span("devcache:lookup", kind=kind):
        subkey = (kind, _content_digest(array)) + tail
        return global_devcache()._answer(
            CONTENT, CONTENT, subkey, 0, load, nbytes_fn
        )


def content_device_matrix(X: np.ndarray, mesh):
    """A padded + row-sharded :class:`DeviceMatrix` for ``X``, cached by
    content digest + mesh signature. Content addressing makes the entry
    stale-proof (a different matrix is a different key), so arbitrary
    ``preprocessor_code`` output can ride the cache safely: the second
    build over the same collection hashes the recomputed host matrix,
    hits, and skips the H2D. The digest is one linear pass over the
    host bytes (``devcache:digest``), paid on a hit and a miss alike.

    The copy is not waited for: the miss's ``h2d:matrix`` span, and the
    ``h2d:put`` inside it, time the dispatch and the host-side copy jax
    makes, not the transfer's landing."""
    from learningorchestra_tpu.ml.base import shard_matrix
    from learningorchestra_tpu.telemetry import span

    def load():
        with span("h2d:matrix", rows=len(X), dtype=dtype_policy()):
            return shard_matrix(X, mesh)

    return _content_lookup(
        "devmat", X, (mesh_signature(mesh), dtype_policy()),
        load, _device_matrix_nbytes,
    )


def content_device_labels(y: np.ndarray, mesh):
    """Label-vector analogue of :func:`content_device_matrix`."""
    from learningorchestra_tpu.ml.base import shard_labels
    from learningorchestra_tpu.telemetry import span

    def load():
        with span("h2d:labels", rows=len(y), dtype="i32"):
            return shard_labels(y, mesh)

    return _content_lookup(
        "devlab", y, (mesh_signature(mesh), "i32"),
        load, lambda dl: int(dl.data.nbytes),
    )
