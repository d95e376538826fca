"""Program-universe enumeration for the AOT compile plane.

Every dispatch shape in the product is already quantized onto the
quarter-octave grid (utils/shapegrid.py — shared by the data plane's
row padding, the serving MicroBatcher and the sweep coalescer), every
float matrix flows through ONE dtype policy (utils/dtypepolicy.py) and
every executable binds a mesh at its call site (the pjit contract).
That makes the set of programs a deployment can dispatch finite and
enumerable: (program kind x grid bucket x dtype policy x mesh
signature x class count). This module walks that universe and emits
:class:`ProgramSpec` rows the AOT compiler (compile/aot.py) lowers —
derived from the SAME shape math the dispatchers use
(``padded_row_count``, ``grid_size``), never a parallel re-derivation
that could drift.

Coverage is explicitly bounded (docs/compile.md): predict programs for
all five classifier kinds, the dominant build programs (the logistic
L-BFGS segment and the naive-bayes fit — the two module-level jitted
fits whose shapes the manifest can reconstruct exactly), and the sweep
plane's fused logistic segment at the job-axis pad floor. Everything
past ``LO_AOT_MAX_PROGRAMS`` lands on a RETURNED drop list the caller
logs — a silent cap would read as "precompiled everything" when it
didn't.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Optional

import numpy as np

from learningorchestra_tpu.utils.shapegrid import grid_size

# serve-path dispatch rows quantize to grid_size(total, max_batch);
# build rows ride the same grid via padded_row_count. The build ladder
# stops at this many rows by default — past it, per-program compiles
# amortize over seconds of execution and AOT buys little.
_BUILD_ROWS_CEILING = 4096
_DEFAULT_FEATURES = (8,)
_DEFAULT_CLASSES = (2,)
# the sweep plane pads its job axis to at least this (ml/sweep.py)
_SWEEP_JOB_FLOOR = 8


@dataclasses.dataclass(frozen=True)
class ProgramSpec:
    """One precompilable program: enough to rebuild the exact abstract
    arguments (`lower_args`) the live dispatcher would trace with."""

    program: str        # "predict:lr" | "build:nb" | "sweep:lr" | ...
    rows: int           # padded axis-0 dispatch rows (post mesh align)
    features: int
    num_classes: int
    dtype: str          # X dtype after the wire dtype policy
    mesh_sig: tuple     # core/devcache.mesh_signature(mesh)
    statics: tuple = () # sorted (name, value) static args, e.g. max_depth

    @property
    def key(self) -> str:
        """Stable content key — the span attribute, the fleet-cache row
        id component, and the dedup identity. Mesh signature included
        as a DETERMINISTIC digest (never ``hash()`` — string hashing is
        per-process salted, and this key must agree across the fleet):
        an executable is only valid for the topology it bound."""
        import hashlib

        statics = ",".join(f"{k}={v}" for k, v in self.statics)
        mesh_digest = hashlib.sha1(
            repr(self.mesh_sig).encode()
        ).hexdigest()[:10]
        return (
            f"{self.program}|r{self.rows}|f{self.features}"
            f"|c{self.num_classes}|{self.dtype}|{statics}"
            f"|mesh{mesh_digest}"
        )


def _policy_dtype_name() -> str:
    from learningorchestra_tpu.parallel.sharding import policy_dtype

    return np.dtype(policy_dtype(np.float32)).name


def _padded_rows(n: int, mesh) -> int:
    from learningorchestra_tpu.parallel.sharding import (
        DATA_AXIS,
        padded_row_count,
    )

    return padded_row_count(n, mesh.shape[DATA_AXIS])


def serve_row_buckets(mesh, max_batch: Optional[int] = None) -> list[int]:
    """Every axis-0 shape the serving path can dispatch: the batcher
    pads each flush to ``grid_size(total, max_batch)`` and prepare_xy
    then aligns to the mesh's data axis — the composition, deduped."""
    if max_batch is None:
        from learningorchestra_tpu.serve import config as serve_config

        max_batch = serve_config.max_batch()
    return sorted(
        {_padded_rows(grid_size(n, max_batch), mesh)
         for n in range(1, max_batch + 1)}
    )


def build_row_buckets(mesh, ceiling: int = _BUILD_ROWS_CEILING) -> list[int]:
    """The quarter-octave ladder a training set's row count pads onto,
    up to ``ceiling`` raw rows (the grid is pass-through below 8, so
    start the ladder at the first bucketed value)."""
    buckets: set[int] = set()
    n = 8
    while n <= ceiling:
        buckets.add(_padded_rows(n, mesh))
        n = grid_size(n + 1)  # hop to the next grid bucket
    return sorted(buckets)


def enumerate_programs(
    mesh,
    features: Iterable[int] = _DEFAULT_FEATURES,
    num_classes: Iterable[int] = _DEFAULT_CLASSES,
    max_batch: Optional[int] = None,
    build_rows_ceiling: int = _BUILD_ROWS_CEILING,
    max_programs: Optional[int] = None,
) -> tuple[list[ProgramSpec], list[ProgramSpec]]:
    """The (kept, dropped) program universe for ``mesh``.

    Ordered by first-request impact — serve-path predict programs
    first (they gate the first POST /predict), then build, then sweep
    — so a tight ``max_programs`` cap keeps the programs whose compile
    a user actually waits on. The drop list is returned, NEVER
    swallowed: the caller logs it (no silent caps)."""
    from learningorchestra_tpu.core.devcache import mesh_signature
    from learningorchestra_tpu.ml import trees as lo_trees

    sig = mesh_signature(mesh)
    dtype = _policy_dtype_name()
    specs: list[ProgramSpec] = []

    def add(program, rows, f, c, statics=()):
        specs.append(ProgramSpec(
            program=program, rows=rows, features=f, num_classes=c,
            dtype=dtype, mesh_sig=sig, statics=tuple(statics),
        ))

    serve_rows = serve_row_buckets(mesh, max_batch)
    fit_rows = build_row_buckets(mesh, build_rows_ceiling)
    for f in features:
        for c in num_classes:
            for rows in serve_rows:
                add("predict:lr", rows, f, c)
                add("predict:nb", rows, f, c)
                add("predict:dt", rows, f, c,
                    [("max_depth", lo_trees.MAX_DEPTH), ("trees", 1)])
                add("predict:rf", rows, f, c,
                    [("max_depth", lo_trees.MAX_DEPTH),
                     ("trees", lo_trees.NUM_TREES)])
                add("predict:gb", rows, f, c,
                    [("max_depth", lo_trees.MAX_DEPTH),
                     ("rounds", lo_trees.GBT_ROUNDS)])
            for rows in fit_rows:
                iters = lr_segment_iters(rows, f)
                for fresh in lr_segment_kinds(iters):
                    add("build:lr", rows, f, c,
                        [("fresh", fresh), ("iters", iters)])
                add("build:nb", rows, f, c)
            iters = lr_segment_iters(min(fit_rows), f)
            for fresh in lr_segment_kinds(iters):
                add("sweep:lr", _padded_rows(min(fit_rows), mesh), f, c,
                    [("fresh", fresh), ("iters", iters),
                     ("jobs", _SWEEP_JOB_FLOOR)])
    if max_programs is None:
        return specs, []
    return specs[:max_programs], specs[max_programs:]


def lr_segment_iters(
    rows: int, features: int, max_iter: int = 100
) -> int:
    """The static ``iters`` the logistic fit would segment ``max_iter``
    into at this shape — the SAME derivation as logistic._fit (budget,
    then the convergence-check cap), so the manifest's build program is
    the one the live fit dispatches, not a near miss."""
    from learningorchestra_tpu.ml import logistic as lo_logistic
    from learningorchestra_tpu.ml.base import largest_divisor, segment_steps

    iters = segment_steps(
        max_iter, rows, lo_logistic._LR_ROW_ITERS_BUDGET, features
    )
    capped = largest_divisor(
        max_iter, min(iters, lo_logistic._LR_CHECK_ITERS)
    )
    if capped >= min(iters, 5):
        iters = capped
    return iters


def lr_segment_kinds(iters: int, max_iter: int = 100) -> tuple:
    """The static ``fresh`` of the segments such a fit dispatches: its
    first seeds value and gradient, any later one carries them."""
    return (True, False) if max_iter // iters > 1 else (True,)


def specs_for_artifact(path: str, mesh) -> list[ProgramSpec]:
    """Exact predict-program specs for a published checkpoint — shapes
    read from the artifact's arrays, one spec per serve-path row
    bucket. This is what publish-time warmup precompiles so the first
    POST /models/<name>/predict never eats the compile."""
    import json
    import zipfile

    from learningorchestra_tpu.core.devcache import mesh_signature

    with zipfile.ZipFile(path) as archive:
        header = json.loads(archive.read("__model__.json"))
        shapes = {}
        with np.load(path) as data:
            for name in data.files:
                member = data[name]
                # the zip holds the JSON header next to the arrays;
                # np.load surfaces non-.npy members as raw bytes
                if hasattr(member, "shape"):
                    shapes[name] = member.shape
    kind = header["kind"]
    scalars = header.get("scalars", {})
    sig = mesh_signature(mesh)
    dtype = _policy_dtype_name()
    if kind == "logistic":
        program, f, c, statics = (
            "predict:lr", shapes["w"][0], shapes["w"][1], (),
        )
    elif kind == "naive_bayes":
        program, f, c, statics = (
            "predict:nb", shapes["theta"][1], shapes["theta"][0], (),
        )
    elif kind == "tree_ensemble":
        trees, c = shapes["leaf_probs"][0], shapes["leaf_probs"][2]
        program, f = "predict:rf", None  # features not in the heaps
        statics = (
            ("max_depth", int(scalars["max_depth"])), ("trees", trees),
        )
    elif kind == "gbt":
        program, c = "predict:gb", 2  # boosted margins are binary
        f = None
        statics = (
            ("max_depth", int(scalars["max_depth"])),
            ("rounds", shapes["features_heap"][0]),
        )
    else:
        return []
    if f is None:
        # tree checkpoints don't record the feature width; warmup calls
        # the model directly (compile/warmup.py) so the manifest row is
        # advisory — use the default width for the spec's identity.
        f = _DEFAULT_FEATURES[0]
    return [
        ProgramSpec(
            program=program, rows=rows, features=int(f),
            num_classes=int(c), dtype=dtype, mesh_sig=sig,
            statics=statics,
        )
        for rows in serve_row_buckets(mesh)
    ]


def lower_args(spec: ProgramSpec):
    """``(jitted_fn, args, static_kwargs)`` rebuilding exactly what the
    live dispatcher traces for ``spec`` — ShapeDtypeStructs with the
    call site's sharding, so the persistent-cache key the AOT compile
    writes is the one the runtime jit lookup computes."""
    import jax
    import jax.numpy as jnp

    from learningorchestra_tpu.ml.base import resolve_mesh
    from learningorchestra_tpu.parallel.sharding import row_sharded

    mesh = resolve_mesh(None)
    from learningorchestra_tpu.core.devcache import mesh_signature

    if mesh_signature(mesh) != spec.mesh_sig:
        raise ValueError(
            f"spec {spec.key} was enumerated for another mesh"
        )
    sharded = row_sharded(mesh)
    sds = jax.ShapeDtypeStruct
    rows, f, c = spec.rows, spec.features, spec.num_classes
    statics = dict(spec.statics)
    X = sds((rows, f), jnp.dtype(spec.dtype), sharding=sharded)
    f32 = jnp.float32

    if spec.program == "predict:lr":
        from learningorchestra_tpu.ml import logistic as lo

        params = {"w": sds((f, c), f32), "b": sds((c,), f32)}
        return lo._forward, (params, X, sds((f,), f32), sds((f,), f32)), {}
    if spec.program == "predict:nb":
        from learningorchestra_tpu.ml import naive_bayes as nb

        return nb._forward, (sds((c, f), f32), sds((c,), f32), X), {}
    if spec.program in ("predict:dt", "predict:rf"):
        from learningorchestra_tpu.ml import trees as lo_trees

        depth, trees = statics["max_depth"], statics["trees"]
        heap = (trees, 2 ** depth - 1)
        return (
            lo_trees._ensemble_forward,
            (X, sds(heap, jnp.int32), sds(heap, f32),
             sds((trees, 2 ** depth, c), f32)),
            {"max_depth": depth},
        )
    if spec.program == "predict:gb":
        from learningorchestra_tpu.ml import trees as lo_trees

        depth, rounds = statics["max_depth"], statics["rounds"]
        heap = (rounds, 2 ** depth - 1)
        return (
            lo_trees._gbt_forward,
            (X, sds((), f32), sds(heap, jnp.int32), sds(heap, f32),
             sds((rounds, 2 ** depth), f32), sds((), f32)),
            {"max_depth": depth},
        )
    if spec.program == "build:lr":
        from learningorchestra_tpu.ml import logistic as lo

        params = {"w": sds((f, c), f32), "b": sds((c,), f32)}
        state = jax.eval_shape(lo._lbfgs_state, params)
        return (
            lo._fit_segment_runner(),
            (params, state, X,
             sds((rows,), jnp.int32, sharding=sharded),
             sds((rows,), f32, sharding=sharded)),
            {"iters": statics["iters"], "fresh": statics["fresh"],
             "l2": sds((), f32)},
        )
    if spec.program == "build:nb":
        from learningorchestra_tpu.ml import naive_bayes as nb

        return (
            nb._fit,
            (X, sds((rows,), jnp.int32, sharding=sharded),
             sds((rows,), f32, sharding=sharded)),
            {"num_classes": c, "smoothing": sds((), f32)},
        )
    if spec.program == "sweep:lr":
        from learningorchestra_tpu.ml import logistic as lo
        from learningorchestra_tpu.ml import sweep as lo_sweep

        jobs = statics["jobs"]
        params = {
            "w": sds((jobs, f, c), f32), "b": sds((jobs, c), f32),
        }
        state = jax.eval_shape(jax.vmap(lo._lbfgs_state), params)
        return (
            lo_sweep._lr_fused_segment,
            (params, state, sds((jobs, rows, f), jnp.dtype(spec.dtype)),
             sds((jobs, rows), jnp.int32), sds((jobs, rows), f32),
             sds((jobs,), f32)),
            {"iters": statics["iters"], "fresh": statics["fresh"]},
        )
    raise ValueError(f"unknown program {spec.program!r}")
