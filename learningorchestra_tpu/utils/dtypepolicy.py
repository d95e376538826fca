"""End-to-end feature dtype policy (``LO_DTYPE_POLICY``).

``f32`` (default) keeps the historical behavior: feature matrices ship
host→device and live in HBM as float32. ``bf16`` halves both — the
padded matrix is cast host-side before ``jax.device_put``
(parallel/sharding.py), so the H2D transfer AND the HBM-resident
working set drop 2×; the transfer is most of a cold build's boundary
cost. Parameters, reductions, and metrics
stay float32 (jnp type promotion lifts ``bf16 @ f32`` matmuls to f32
accumulation), so fits remain numerically anchored; the policy trades
feature-matrix mantissa bits for bandwidth, the same trade serving
stacks make for activations.

The policy is part of every device-cache key (core/devcache.py): an
entry prepared under one policy never serves another, exactly like the
mesh signature.

Read ONCE per process (like ``LO_SHAPE_BUCKETS`` /
``LO_PROGRAM_ROW_STEPS``): a per-request read could desynchronize SPMD
dispatch shapes across a multi-host mesh, so the knob is
process-lifetime constant and must be set identically on every host.
Stdlib+numpy only — the store server imports this transitively and must
never pay a jax import.
"""

from __future__ import annotations

import os

POLICIES = ("f32", "bf16")

_POLICY: list = []  # one-element cache: read once per process


def validate_policy(raw: str) -> str:
    value = raw.strip() or "f32"
    if value not in POLICIES:
        raise ValueError(
            f"LO_DTYPE_POLICY must be one of {'|'.join(POLICIES)}, "
            f"got {raw!r}"
        )
    return value


def dtype_policy() -> str:
    """The process's feature dtype policy string — also the token that
    rides device-cache keys."""
    if not _POLICY:
        _POLICY.append(
            # lo: allow[LO305] read-once accessor, validated in place
            validate_policy(os.environ.get("LO_DTYPE_POLICY", "f32"))
        )
    return _POLICY[0]


def validate_env() -> None:
    """Fail fast on a malformed ``LO_DTYPE_POLICY`` — deploy/run.sh's
    preflight calls this (uncached, so it always re-reads the env)."""
    validate_policy(os.environ.get("LO_DTYPE_POLICY", "f32"))
