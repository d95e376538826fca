"""Multinomial logistic regression, fitted with L-BFGS on device.

Replaces Spark MLlib's ``LogisticRegression`` (reference:
microservices/model_builder_image/model_builder.py:7,152 — MLlib also
optimizes with L-BFGS on the JVM). Defaults mirror MLlib: ``maxIter=100``,
``regParam=0.0``, fit-intercept, internal feature standardization.

TPU shape: the matrix is standardised on the device by one program
(:func:`_lr_standardize`), then each segment of the optimization is ONE
jitted program — ``lax.scan`` over L-BFGS iterations that carries the
margins ``X·W + b`` as ``(classes, rows)``, so every iteration reads the
row-sharded matrix exactly twice (the direction's margins, the
gradient) and the line search's trial steps are elementwise work on the
margins; the reductions over rows are the only cross-chip collectives
and XLA inserts them from the sharding annotations (no hand-written
NCCL/allreduce as in torch-style ports).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import ClosedJaxpr, Var
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from learningorchestra_tpu.parallel.mesh import MODEL_AXIS, model_size
from learningorchestra_tpu.ml import progress as _progress
from learningorchestra_tpu.ml.base import (
    FittedModel,
    TrainingInput,
    prepare_xy,
    resolve_mesh,
)
from learningorchestra_tpu.telemetry import tracing as _tracing


# LR's contractions are exact float32 whatever JAX_DEFAULT_MATMUL_PRECISION
# says (one bfloat16 pass is the TPU's default, and the fit is float32).
# At HIGGS's 8,388,608 x 28 x 2 each reads X at 2.1-2.2 ms against the
# 1.3 ms that reading the matrix takes (PERF.md, PR 37): no cheaper
# formulation is worth a second code path.
_PRECISION = jax.lax.Precision.HIGHEST


def _margins(X, w, b):
    """``X·w + b`` as ``(classes, rows)``: the long axis on the lanes."""
    return jnp.einsum("fk,rf->kr", w, X, precision=_PRECISION) + b[:, None]


def _weighted_columns(X, G):
    """``Xᵀ·Gᵀ`` as ``(features, classes)`` for ``G`` of ``(classes, rows)``."""
    return jnp.einsum("rf,kr->fk", X, G, precision=_PRECISION)


def _loss_fn(params, X, y, mask, l2):
    """The objective evaluated from the parameters: one pass over X."""
    logits = jnp.dot(X, params["w"], precision=_PRECISION) + params["b"]
    log_probs = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(log_probs, y[:, None], axis=1)[:, 0]
    data_term = (nll * mask).sum() / mask.sum()
    return data_term + 0.5 * l2 * (params["w"] ** 2).sum()


def _margin_loss(Z, hit, mask, count):
    """Mean negative log-likelihood of margins ``Z`` (classes, rows);
    ``hit`` is the one-hot of the labels in the same orientation.
    Elementwise work and one reduction: no pass over X."""
    lse = jax.nn.logsumexp(Z, axis=0)
    picked = jnp.where(hit, Z, 0.0).sum(axis=0)
    return ((lse - picked) * mask).sum() / count, lse


# Hand-rolled L-BFGS (two-loop recursion, Armijo backtracking) instead
# of optax.lbfgs: profiled in round 4, the optax update chain cost
# ~20-25 ms of device time per iteration against a 1.5 ms full-data
# gradient pass at 1M×16 — the optimizer bookkeeping, not the math, was
# 90%+ of the LR fit (VERDICT r4 weak #6). The minimal implementation
# keeps the round-3/4 line-search decisions (Armijo instead of
# strong-Wolfe zoom: 18.9 s -> ~6 s in round 3; 4 backtracking halvings
# max, step floor 1/16: features are standardized so the unit step is
# almost always accepted — caps 3/4/5/15 measured identical losses to
# 5 decimals in round 4). The loss is linear in the parameters through
# the margins, so a trial step t costs f(Z + t·M) on the carried
# margins Z and the direction's margins M: two passes over X an
# iteration (M, and the gradient at the accepted point) however many
# trials the seed's data asks for (:func:`_matrix_passes` counts them
# off the program). Quality is gated by the sklearn-oracle and
# Titanic-golden accuracy tests.
_LBFGS_MEMORY = 10
_BACKTRACK_STEPS = 4
_ARMIJO_C1 = 1e-4
# consecutive sub-tol loss deltas required before an early exit (see
# the history window in _fit)
_LR_STOP_DELTAS = 3


def _tree_dot(a, b):
    """Pytree inner product — one replicated scalar; on a sharded mesh
    XLA inserts the psums from the leaves' shardings."""
    return sum(
        jnp.vdot(x, y, precision=_PRECISION)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _tree_axpy(alpha, x, y):
    """``y + alpha * x`` leaf-wise (alpha a scalar)."""
    return jax.tree.map(lambda xi, yi: yi + alpha * xi, x, y)


def _tree_at(history, slot):
    return jax.tree.map(lambda h: h[slot], history)


def _lbfgs_state(params):
    """Curvature memory as fixed ``(m, *leaf.shape)`` ring buffers —
    static shapes, and every buffer inherits its leaf's sharding (the
    tensor-parallel class axis of W survives, unlike a flattened
    vector)."""
    history = jax.tree.map(
        lambda p: jnp.zeros((_LBFGS_MEMORY,) + p.shape, p.dtype), params
    )
    return {
        "S": history,
        "Y": jax.tree.map(jnp.copy, history),
        "rho": jnp.zeros((_LBFGS_MEMORY,), jnp.float32),
        "head": jnp.int32(0),       # next ring slot to write
        "filled": jnp.int32(0),     # valid pair count (<= m)
        "value": jnp.float32(0.0),  # f(x) at the current point
        "grad": jax.tree.map(jnp.zeros_like, params),
    }
    # value/grad are seeded by the first segment of a fit (``fresh``,
    # a static argument of _fit_segment, chosen on the host) and then
    # carried, rather than lazily via a lax.cond inside the first
    # iteration: the cond read nicely but under the sweep module's
    # vmap-across-jobs a BATCHED cond executes both branches, paying a
    # full extra value_and_grad pass every L-BFGS step


def _two_loop(state):
    """Search direction -H·g via the standard two-loop recursion over
    the ring buffers; unfilled slots are masked out (their alpha/beta
    contributions are zeroed)."""
    m = _LBFGS_MEMORY
    # newest-first order: slot (head-1-k) mod m
    order = jnp.mod(state["head"] - 1 - jnp.arange(m), m)
    valid = (jnp.arange(m) < state["filled"]).astype(jnp.float32)

    q = state["grad"]
    alphas = []
    for k in range(m):  # static unroll: m tiny
        s_k = _tree_at(state["S"], order[k])
        y_k = _tree_at(state["Y"], order[k])
        alpha = valid[k] * state["rho"][order[k]] * _tree_dot(s_k, q)
        q = _tree_axpy(-alpha, y_k, q)
        alphas.append(alpha)
    s_new = _tree_at(state["S"], order[0])
    y_new = _tree_at(state["Y"], order[0])
    y_dot = _tree_dot(y_new, y_new)
    gamma = jnp.where(
        (state["filled"] > 0) & (y_dot > 0.0),
        _tree_dot(s_new, y_new) / jnp.maximum(y_dot, 1e-20),
        1.0,
    )
    r = jax.tree.map(lambda qi: gamma * qi, q)
    for k in range(m - 1, -1, -1):  # oldest of the valid window first
        s_k = _tree_at(state["S"], order[k])
        y_k = _tree_at(state["Y"], order[k])
        beta = valid[k] * state["rho"][order[k]] * _tree_dot(y_k, r)
        r = _tree_axpy(alphas[k] - beta, s_k, r)
    return jax.tree.map(jnp.negative, r)


def _armijo_step(trial_loss, value, slope):
    """Armijo backtracking as a while_loop that EXITS on acceptance:
    ``(step, trials)``, the step 1, 1/2, 1/4 or 1/8 that first meets the
    sufficient-decrease test, else the floor 1/16 after
    ``_BACKTRACK_STEPS`` trials. ``trial_loss(t)`` is f at ``x + t·d``."""

    def ls_cond(carry):
        _, _, accepted, k = carry
        return (~accepted) & (k < _BACKTRACK_STEPS)

    def ls_body(carry):
        t, best_t, _, k = carry
        ok = trial_loss(t) <= value + _ARMIJO_C1 * t * slope
        return (
            t * 0.5,
            jnp.where(ok, t, best_t),
            ok,
            k + 1,
        )

    _, best_t, _, trials = jax.lax.while_loop(
        ls_cond,
        ls_body,
        (
            jnp.float32(1.0),
            jnp.float32(1.0 / (1 << _BACKTRACK_STEPS)),  # step floor
            jnp.bool_(False),
            jnp.int32(0),
        ),
    )
    return best_t, trials


def _fit_segment_impl(
    params, opt_state, X, y, mask, iters: int, l2, fresh: bool = True
):
    """``iters`` L-BFGS iterations as ONE program, optimizer state in
    and out — chained by :func:`_fit` so arbitrarily long optimizations
    never exceed a single execution's wall-clock budget while the
    L-BFGS curvature memory, value and gradient carry across segment
    boundaries.

    The margins ``Z = X·W + b`` ride in the scan as ``(classes, rows)``,
    the rows on the lanes, so an iteration reads X twice whatever its
    line search does. They are not part of ``opt_state`` (a progress
    artifact holds no row-sized array), so each segment seeds them from
    ``X·W`` at entry, one more pass: a chain of segments follows the
    single-program iteration sequence up to the float32 rounding of the
    margins at each boundary. ``fresh`` (static): ``opt_state`` holds
    no (value, grad) yet, so they are computed at entry too, the
    gradient's pass; :func:`_fit` passes it for a fit's first segment
    only."""
    count = mask.sum()
    hit = jnp.arange(params["b"].shape[-1])[:, None] == y[None, :]

    def regularizer(w):
        return 0.5 * l2 * (w ** 2).sum()

    def value_and_grad(x, Z):
        """f and its gradient at ``x`` from its margins: the gradient's
        ``Xᵀ(softmax(Z) − Y)·mask / n`` is the one pass over X."""
        data_term, lse = _margin_loss(Z, hit, mask, count)
        G = (jnp.exp(Z - lse) - hit) * (mask / count)
        grad = {
            "w": _weighted_columns(X, G) + l2 * x["w"],
            "b": G.sum(axis=1),
        }
        return data_term + regularizer(x["w"]), grad

    # seed the margins at the segment's entry point, and (value, grad)
    # where the state carries none; every scan iteration stays
    # branch-free (see _lbfgs_state)
    margins0 = _margins(X, params["w"], params["b"])
    if fresh:
        value0, grad0 = value_and_grad(params, margins0)
        opt_state = {**opt_state, "value": value0, "grad": grad0}

    def step(carry, _):
        x, Z, state = carry
        # (value, grad) at x: seeded above for the first iteration,
        # then carried from each accepted point below
        value, grad = state["value"], state["grad"]
        direction = _two_loop(state)
        slope = _tree_dot(grad, direction)
        # safeguard: a non-descent direction (stale curvature) falls
        # back to steepest descent
        descent = slope < 0.0
        direction = jax.tree.map(
            lambda d, g: jnp.where(descent, d, -g), direction, grad
        )
        slope = jnp.where(descent, slope, -_tree_dot(grad, grad))
        # the direction's margins: the iteration's first pass over X
        M = _margins(X, direction["w"], direction["b"])

        def trial_loss(t):
            data_term, _ = _margin_loss(Z + t * M, hit, mask, count)
            return data_term + regularizer(x["w"] + t * direction["w"])

        best_t, _ = _armijo_step(trial_loss, value, slope)
        x_new = _tree_axpy(best_t, direction, x)
        Z_new = Z + best_t * M
        # the gradient at the accepted point: the second pass over X
        value_new, grad_new = value_and_grad(x_new, Z_new)

        # curvature pair; the update is skipped when s·y is not positive
        s = jax.tree.map(jnp.subtract, x_new, x)
        y_vec = jax.tree.map(jnp.subtract, grad_new, grad)
        sy = _tree_dot(s, y_vec)
        keep = sy > 1e-10
        head = state["head"]

        def ring_write(history, pair):
            return jax.tree.map(
                lambda h, p: h.at[head].set(jnp.where(keep, p, h[head])),
                history,
                pair,
            )

        state = {
            **state,
            "S": ring_write(state["S"], s),
            "Y": ring_write(state["Y"], y_vec),
            "rho": state["rho"].at[head].set(
                jnp.where(
                    keep,
                    1.0 / jnp.maximum(sy, 1e-20),
                    state["rho"][head],
                )
            ),
            "head": jnp.where(keep, (head + 1) % _LBFGS_MEMORY, head),
            "filled": jnp.where(
                keep,
                jnp.minimum(state["filled"] + 1, _LBFGS_MEMORY),
                state["filled"],
            ),
            "value": value_new,
            "grad": grad_new,
        }
        return (x_new, Z_new, state), value

    (params, _, opt_state), losses = jax.lax.scan(
        step, (params, margins0, opt_state), length=iters
    )
    return params, opt_state, losses


# The shared, undonated program: what ml/sweep.py vmaps (donation inside
# an outer trace would be inert) and what CPU backends run.
_fit_segment = partial(jax.jit, static_argnames=("iters", "fresh"))(
    _fit_segment_impl
)


@lru_cache(maxsize=None)
def _donated_fit_segment():
    return jax.jit(
        _fit_segment_impl,
        static_argnames=("iters", "fresh"),
        donate_argnums=(0, 1),
    )


def _contractions_over(jaxpr, sources) -> int:
    """How many times the program ``jaxpr`` contracts one of
    ``sources`` (variables of ``jaxpr``): each ``dot_general`` that
    reads one, times the trips of the loops round it — a ``scan`` its
    ``length``, a ``while`` (the line search, the one such loop here)
    at most ``_BACKTRACK_STEPS``, a ``cond`` its costliest branch."""
    count = 0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        reads = [v in sources for v in eqn.invars if isinstance(v, Var)]
        if name == "dot_general":
            count += any(reads)
            continue
        if not any(reads):
            continue
        params = eqn.params
        if name == "scan":
            inner = [(params["jaxpr"], eqn.invars, params["length"])]
        elif name == "while":
            inner = [(
                params["body_jaxpr"],
                eqn.invars[params["cond_nconsts"]:],
                _BACKTRACK_STEPS,
            )]
        elif name == "cond":
            inner = [(b, eqn.invars[1:], 1) for b in params["branches"]]
        else:  # jit and the other calls: arguments map one to one
            inner = [
                (v, eqn.invars, 1)
                for v in params.values()
                if isinstance(v, ClosedJaxpr)
                and len(v.jaxpr.invars) == len(eqn.invars)
            ]
        counts = [
            times * _contractions_over(
                sub.jaxpr,
                {
                    inner_var
                    for outer, inner_var in zip(args, sub.jaxpr.invars)
                    if isinstance(outer, Var) and outer in sources
                },
            )
            for sub, args, times in inner
        ]
        count += max(counts, default=0)
    return count


def _matrix_passes(segment, params, opt_state, X, y, mask, iters, l2, fresh):
    """The passes over the training matrix that one call of the jitted
    ``segment`` makes on the device, counted off its traced program
    (the trace the call itself uses: no compile); 0 for a function that
    is not jitted (a test's scripted segment)."""
    if not hasattr(segment, "trace"):
        return 0
    closed = segment.trace(
        params, opt_state, X, y, mask, iters, l2, fresh=fresh
    ).jaxpr
    at = len(jax.tree.leaves((params, opt_state)))
    return _contractions_over(closed.jaxpr, {closed.jaxpr.invars[at]})


def _fit_segment_runner():
    """The segment program :func:`_fit` chains: (params, opt_state) are
    DONATED — each segment's outputs rebind exactly those arguments, so
    XLA reuses their HBM across L-BFGS segments instead of holding two
    generations of curvature ring buffers live per boundary (the
    ``donate_argnums`` discipline, SNIPPETS.md [3]). X/y/mask are NOT
    donated: every segment re-reads them. CPU backends don't implement
    donation — they fall back to the shared undonated program, read as
    the MODULE attribute at call time (tests script `_fit_segment`;
    resolving lazily also means importing this module never initializes
    the device backend)."""
    if jax.default_backend() == "cpu":
        return _fit_segment
    return _donated_fit_segment()


# Per-program budget in row*iterations: ~18 iterations per segment at
# 10M rows (see base.segment_steps for what a segment bounds).
_LR_ROW_ITERS_BUDGET = 180e6
# Convergence-check granularity: segments are capped at 25 iterations
# so the tol check below fires within a quarter of the default budget.
_LR_CHECK_ITERS = 25
# MLlib LogisticRegression default convergence tolerance (the reference
# engine stops when the objective stalls, model_builder.py:152 uses
# MLlib defaults); a fixed 100 iterations would do MORE work than the
# reference semantics.
_LR_TOL = 1e-6


def _plateaued(history: list[float], tol: float, window: int) -> bool:
    """True when the trailing ``window`` pre-step losses form a genuine
    plateau: EVERY consecutive delta is under the (relative) tolerance
    AND so is the total improvement across the window. A single
    floor-step Armijo iteration (step clamped to 1/16, objective barely
    moves once) produces one tiny delta inside an otherwise-descending
    run and must NOT stop the fit (ADVICE r5); ``window - 1``
    consecutive sub-tol deltas that also sum to nothing is a stall, not
    noise."""
    if len(history) < window:
        return False
    recent = history[-window:]
    threshold = tol * max(abs(recent[-1]), 1.0)
    return abs(recent[-1] - recent[0]) <= threshold and all(
        abs(recent[i + 1] - recent[i]) <= threshold
        for i in range(len(recent) - 1)
    )


def _fit(params, X, y, mask, max_iter: int, l2, tol: float = _LR_TOL):
    """L-BFGS fit in watchdog-safe segments (see base.segment_steps),
    stopping once the objective's per-iteration improvement stays under
    ``tol`` for several consecutive iterations (crossing segment
    boundaries) — MLlib's tol semantics made robust to a single stalled
    line-search step, checked at segment granularity so only one loss
    array crosses the wire per segment."""
    from learningorchestra_tpu.ml.base import largest_divisor, segment_steps

    if max_iter <= 0:  # MLlib allows maxIter=0: the initial model
        return params, jnp.zeros((0,), jnp.float32)
    iters = segment_steps(
        max_iter, X.shape[0], _LR_ROW_ITERS_BUDGET, X.shape[1]
    )
    if tol > 0:
        # cap segments for convergence-check granularity — but never
        # below 5 iterations (a prime max_iter would otherwise shatter
        # into per-iteration dispatches, each with a host sync)
        capped = largest_divisor(max_iter, min(iters, _LR_CHECK_ITERS))
        if capped >= min(iters, 5):
            iters = capped
    # fit:enqueue (see ml/trees.py) here and after the loop: what this
    # fit hands the device outside its segments
    with _tracing.span("fit:enqueue"):
        opt_state = _lbfgs_state(params)
    losses = []
    # Trailing pre-step losses across segment boundaries: convergence
    # requires EVERY delta in this window to be small, not just the
    # final two — a single floor-step Armijo iteration (step clamped to
    # 1/16, objective barely moves once) used to match the two-point
    # check and stop a fit mid-descent (ADVICE r5). Window of 3 deltas:
    # three consecutive sub-tol improvements is a plateau, one is noise.
    history: list[float] = []
    window = _LR_STOP_DELTAS + 1
    segment = _fit_segment_runner()
    total_segments = max_iter // iters
    passes = 0
    # Crash resume: a sink bound by ml/builder.py means this fit should
    # persist per-segment progress and pick up any prior run's artifact.
    # The artifact must match this call's segmentation exactly (iters /
    # max_iter / l2) on top of the sink's own rev/dtype/mesh key — any
    # drift restarts the fit clean.
    sink = _progress.current_sink()
    start = 0
    if sink is not None:
        restored = sink.load("logistic")
        if restored is not None:
            done, arrays, scalars = restored
            state = None
            if (
                scalars.get("iters") == iters
                and scalars.get("max_iter") == max_iter
                and scalars.get("l2") == float(np.asarray(l2))
                and 0 < done <= total_segments
                and len(arrays) >= 1
            ):
                state = _progress.device_restore(
                    (params, opt_state), arrays[:-1]
                )
            if state is None:
                sink.discard()
            else:
                params, opt_state = state
                losses.append(jnp.asarray(arrays[-1]))
                history.extend(
                    float(v) for v in scalars.get("history") or []
                )
                del history[:-window]
                start = done
                _progress.segments_skipped(done)
    for index in range(start, total_segments):
        # plateau check at the TOP so a resumed fit that had already
        # converged (crash between progress save and checkpoint write)
        # stops exactly where the uninterrupted run did — running one
        # more segment here would break bit-identity
        if tol > 0 and _plateaued(history, tol, window):
            break
        # the state carries (value, grad) but in a fit's first segment
        fresh = index == 0
        passes += _matrix_passes(
            segment, params, opt_state, X, y, mask, iters, l2, fresh
        )
        # dispatch to the host sync that fetches the losses (with tol
        # off nothing syncs, and the span holds the dispatch alone)
        with _tracing.span("fit:segment", iters=iters):
            params, opt_state, segment_losses = segment(
                params, opt_state, X, y, mask, iters, l2, fresh=fresh
            )
            losses.append(segment_losses)
            if tol > 0:
                # One host transfer either way: the losses come back as
                # one array.
                history.extend(float(v) for v in np.asarray(segment_losses))
                del history[:-window]
        # on the span round the fit (the builder's phase:fit)
        _tracing.add_attr("lbfgs_iterations", iters)
        if sink is not None:
            sink.save(
                "logistic",
                index + 1,
                [
                    np.asarray(leaf)
                    for leaf in jax.tree.leaves((params, opt_state))
                ]
                + [np.concatenate([np.asarray(l) for l in losses])],
                {
                    "iters": iters,
                    "max_iter": max_iter,
                    "l2": float(np.asarray(l2)),
                    "history": list(history),
                },
            )
    if passes:
        _tracing.add_attr("lbfgs_matrix_passes", passes)
    with _tracing.span("fit:enqueue"):
        joined = jnp.concatenate(losses) if len(losses) > 1 else losses[0]
    return params, joined


def _lr_standardize(X, mask):
    """Standardise a padded, row-sharded matrix on the device, ONE
    program (three passes): per-feature mean and
    scale of the valid rows (zero-variance features pinned to 1), and
    ``(X - mean) / scale`` with the padding rows zeroed. The recipe of
    every LR fit — solo, sharded and the sweep's members — so the paths
    cannot drift. The reductions cross the data axis; XLA inserts the
    psums.

    The mean is corrected by the second pass (the sum of the deviations
    from the first estimate), so a column far from zero keeps float32's
    precision relative to its spread; a constant column (min = max)
    takes its value as the mean exactly, and so reads scale 1."""
    weights = mask.astype(X.dtype)[:, None]
    valid = weights > 0
    count = weights.sum()
    lo = jnp.where(valid, X, jnp.inf).min(axis=0)
    hi = jnp.where(valid, X, -jnp.inf).max(axis=0)
    guess = jnp.where(lo == hi, lo, (X * weights).sum(axis=0) / count)
    deviation = (X - guess) * weights
    shift = deviation.sum(axis=0) / count
    var = (deviation ** 2).sum(axis=0) / count - shift ** 2
    std = jnp.sqrt(jnp.maximum(var, 0.0))
    mean = guess + shift
    scale = jnp.where(std > 0, std, 1.0)
    return ((X - mean) / scale) * weights, mean, scale


_standardize = jax.jit(_lr_standardize)


@jax.jit
def _forward(params, X, mean, scale):
    logits = (
        jnp.dot((X - mean) / scale, params["w"], precision=_PRECISION)
        + params["b"]
    )
    probs = jax.nn.softmax(logits)
    return jnp.argmax(logits, axis=1), probs


class LogisticRegressionModel(FittedModel):
    def __init__(self, params, mean, scale, mesh: Mesh):
        self.params = params
        self.mean = mean
        self.scale = scale
        self.mesh = mesh

    def _device_eval(self, X):
        X_dev, _, mask = prepare_xy(X, None, self.mesh)
        labels, probs = _forward(self.params, X_dev, self.mean, self.scale)
        return labels, probs, mask


class LogisticRegression:
    def __init__(
        self,
        max_iter: int = 100,
        reg_param: float = 0.0,
        mesh: Optional[Mesh] = None,
        tol: float = _LR_TOL,
    ):
        self.max_iter = max_iter
        self.reg_param = reg_param
        self.tol = tol  # MLlib's user-settable convergence tolerance
        self.mesh = resolve_mesh(mesh)

    def fit(self, X, y=None) -> LogisticRegressionModel:
        data = TrainingInput.of(X, y, self.mesh)
        matrix, labels = data.device()
        # Standardize for conditioning (MLlib standardizes internally
        # too), on the device; the scaler is part of the fitted model.
        # The device copy is the build's, shared with its other fits:
        # scaled into a new buffer, never donated. The span holds the
        # dispatch; the program's device seconds are its own
        # (jit__lr_standardize)
        with _tracing.span(
            "fit:standardize", rows=data.rows, features=data.features
        ):
            X_std, mean, scale = _standardize(matrix.data, matrix.mask)
        return self._fit_prepared(
            X_std, labels.data, matrix.mask, data.num_classes, mean, scale
        )

    def fit_sharded(
        self,
        X_dev: jax.Array,
        y_dev: jax.Array,
        mask: jax.Array,
        num_classes: int,
    ) -> LogisticRegressionModel:
        """Fit from already row-sharded device arrays — the per-host
        feeding entry: pair with ``parallel.shard_rows_local`` so on a
        multi-host mesh each host loads only its ``host_row_range`` row
        slice and NO process ever materializes the full dataset (the
        100M-row ingestion story; reference workers instead each read
        their Mongo partitions). Standardization happens on device from
        the shards (:func:`_lr_standardize`; the caller's arrays are left
        as they are); ``num_classes`` must be given since no host can
        scan all labels.
        """
        X_std, mean, scale = _standardize(X_dev, mask)
        return self._fit_prepared(X_std, y_dev, mask, num_classes, mean, scale)

    def _fit_prepared(
        self, X_dev, y_dev, mask, num_classes, mean, scale
    ) -> LogisticRegressionModel:
        # Tensor parallelism: the class dimension of W/b is sharded over
        # the mesh's model axis (init sharding propagates through the
        # whole L-BFGS scan), so X @ W partitions its output columns and
        # log_softmax's normalizer is the only model-axis collective.
        num_features = X_dev.shape[1]
        # Replicate when classes don't divide the axis (NamedSharding
        # needs even splits); the data axis still carries the rows. The
        # fallback is explicit: silent replication looked like tensor
        # parallelism without being it (VERDICT r2 weak #3).
        shardable = num_classes % model_size(self.mesh) == 0
        if not shardable and model_size(self.mesh) > 1:
            import warnings

            warnings.warn(
                f"LogisticRegression: {num_classes} classes do not divide "
                f"the model axis ({model_size(self.mesh)} devices); W/b "
                "replicate and the model axis adds no parallelism for "
                "this fit",
                stacklevel=3,
            )
        class_spec = P(None, MODEL_AXIS) if shardable else P()
        bias_spec = P(MODEL_AXIS) if shardable else P()
        params0 = {
            "w": jax.device_put(
                jnp.zeros((num_features, num_classes), jnp.float32),
                NamedSharding(self.mesh, class_spec),
            ),
            "b": jax.device_put(
                jnp.zeros((num_classes,), jnp.float32),
                NamedSharding(self.mesh, bias_spec),
            ),
        }
        params, _ = _fit(
            params0,
            X_dev,
            y_dev,
            mask.astype(jnp.float32),
            max_iter=self.max_iter,
            l2=jnp.float32(self.reg_param),
            tol=self.tol,
        )
        return LogisticRegressionModel(params, mean, scale, self.mesh)
