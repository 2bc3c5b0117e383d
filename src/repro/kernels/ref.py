"""Pure-jnp oracles for the clustering kernels.

These are the reference semantics that the Pallas kernels in
``distance_assign.py`` / ``cluster_update.py`` must reproduce, and the
fallback implementation used on backends without Pallas support.

The assignment step is the paper's compute hot-spot (Section 1.2: the
``O(n·K·d)`` term). BWKM additionally needs the *second*-closest distance
for the misassignment function (Definition 3), so the oracle returns the
top-2 squared distances alongside the argmin.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = [
    "AssignUpdate",
    "MinSqDistUpdate",
    "PrunedAssignUpdate",
    "pairwise_sqdist",
    "assign_top2",
    "assign_update",
    "assign_update_pruned",
    "cluster_sums",
    "min_sqdist_update",
    "weighted_error",
]

_BIG = 3.0e38  # same "masked distance" sentinel the Pallas kernels use


class AssignUpdate(NamedTuple):
    """Everything one weighted Lloyd step needs from one data pass: the
    per-point top-2 assignment plus the per-cluster sufficient statistics.
    Produced in a single pass by the fused Pallas kernel; this oracle
    composes the two-pass reference semantics."""

    assign: jax.Array  # [n] i32
    d1: jax.Array  # [n] f32, squared distance to closest centroid
    d2: jax.Array  # [n] f32, squared distance to second closest
    sums: jax.Array  # [K, d] f32, Σ 1[assign==k]·w·x
    counts: jax.Array  # [K] f32, Σ 1[assign==k]·w
    err: jax.Array  # scalar f32, Σ w·d1 (the weighted error E^P)
    n_dist: jax.Array | None = None  # scalar f32: point-centroid distance
    # evaluations this pass REQUIRED (the paper's cost unit, Section 3).
    # Filled by the ops layer — identical across impls by construction, so
    # `FitResult.distances` doesn't depend on which kernel ran.


class PrunedAssignUpdate(NamedTuple):
    """One drift-bound-pruned weighted Lloyd pass (ADR 0004).

    The cluster statistics are FULL sums/counts under the composed
    assignment (argmin where ``active``, cached elsewhere), accumulated by
    the exact same one-hot contraction — in the same order — as the dense
    kernel, so pruning can never move the next centroids by even an ulp:
    skipped rows' contribution rides the cached assignment, only active
    rows pay the top-2 scan.

    ``d1``/``d2``/``err`` are defined ONLY where ``active`` was set — for
    skipped rows the caller owns tighter information (its drift-inflated
    bounds) and the kernel is free to leave garbage there (``err`` is the
    partial ``Σ_active w·d1``; the exact full error comes from the
    algebraic identity in ``core.lloyd.stats_error``).
    """

    assign: jax.Array  # [n] i32: argmin where active, cached elsewhere
    d1: jax.Array  # [n] f32, exact where active; garbage elsewhere
    d2: jax.Array  # [n] f32, exact where active; garbage elsewhere
    sums: jax.Array  # [K, d] f32, Σ 1[assign==k]·w·x (composed assignment)
    counts: jax.Array  # [K] f32, Σ 1[assign==k]·w
    err: jax.Array  # scalar f32, Σ_{active} w·d1 (partial error)
    n_dist: jax.Array | None = None  # scalar f32, filled by the ops layer


class MinSqDistUpdate(NamedTuple):
    """One k-means|| fold pass (ADR 0005): the running per-point minimum
    squared distance to the growing candidate set, updated with one batch of
    new candidates, plus the weighted cost ``φ = Σ w·min-d²`` of the updated
    state — everything one oversampling round needs from one data pass.
    Produced in a single HBM read of x by the Pallas kernel in
    ``min_sqdist_update.py``; this oracle is the two-line reference."""

    mind2: jax.Array  # [n] f32, updated running min squared distance
    cost: jax.Array  # scalar f32, Σ w·mind2 over the updated state
    n_dist: jax.Array | None = None  # scalar f32: distance evaluations the
    # pass required (active rows × valid candidates; the paper's cost unit).
    # Filled by the ops layer — identical across impls by construction.


def min_sqdist_update(
    x: jax.Array,
    w: jax.Array,
    cand: jax.Array,
    cvalid: jax.Array,
    mind2: jax.Array,
) -> MinSqDistUpdate:
    """Reference semantics for the k-means|| fold kernel.

    ``cand [L, d]`` is a fixed-capacity batch of new candidates with validity
    mask ``cvalid [L]`` (invalid rows are masked to the ``_BIG`` sentinel, so
    they can never win the min — the static-shape analogue of a ragged
    candidate list). ``mind2 [n]`` is the running min squared distance to all
    candidates folded so far; entries may be ``_BIG`` on the very first fold.
    Zero-weight rows still update their ``mind2`` but contribute nothing to
    the cost.
    """
    w = w.astype(jnp.float32)
    d2 = pairwise_sqdist(x, cand)  # [n, L]
    d2 = jnp.where(cvalid.astype(bool)[None, :], d2, _BIG)
    new = jnp.minimum(mind2.astype(jnp.float32), jnp.min(d2, axis=-1))
    cost = jnp.sum(w * new)
    return MinSqDistUpdate(new, cost)


def pairwise_sqdist(x: jax.Array, c: jax.Array) -> jax.Array:
    """Squared euclidean distances between rows of ``x [n,d]`` and ``c [K,d]``.

    Uses the MXU-friendly decomposition ``|x|^2 - 2 x.c + |c|^2`` with f32
    accumulation (this is exactly the decomposition the Pallas kernel tiles),
    at ``Precision.HIGHEST`` like the kernels: the TPU default rounds f32
    operands to bf16.
    """
    x = x.astype(jnp.float32)
    c = c.astype(jnp.float32)
    xn = jnp.sum(x * x, axis=-1, keepdims=True)  # [n, 1]
    cn = jnp.sum(c * c, axis=-1)  # [K]
    dots = jnp.matmul(x, c.T, precision=jax.lax.Precision.HIGHEST)
    d2 = xn - 2.0 * dots + cn[None, :]
    return jnp.maximum(d2, 0.0)  # clamp fp cancellation noise


def assign_top2(x: jax.Array, c: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Closest-centroid assignment plus top-2 squared distances.

    Returns ``(assign [n] int32, d1 [n] f32, d2 [n] f32)`` where ``d1`` is the
    squared distance to the closest centroid and ``d2`` to the second closest.
    For ``K == 1`` the second distance is ``+inf``.
    """
    d2all = pairwise_sqdist(x, c)
    assign = jnp.argmin(d2all, axis=-1).astype(jnp.int32)
    d1 = jnp.min(d2all, axis=-1)
    if c.shape[0] == 1:
        dsecond = jnp.full(x.shape[:1], jnp.inf, dtype=jnp.float32)
    else:
        masked = jnp.where(
            jax.nn.one_hot(assign, c.shape[0], dtype=bool), jnp.inf, d2all
        )
        dsecond = jnp.min(masked, axis=-1)
    return assign, d1, dsecond


def cluster_sums(
    x: jax.Array, w: jax.Array, assign: jax.Array, num_clusters: int
) -> tuple[jax.Array, jax.Array]:
    """Weighted per-cluster sums and counts.

    ``sums[k] = sum_i 1[assign_i == k] * w_i * x_i`` and
    ``counts[k] = sum_i 1[assign_i == k] * w_i``.
    Semantics match an on-the-fly ``onehot(assign)^T @ (w * x)`` matmul.
    """
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    wx = x * w[:, None]
    sums = jax.ops.segment_sum(wx, assign, num_segments=num_clusters)
    counts = jax.ops.segment_sum(w, assign, num_segments=num_clusters)
    return sums, counts


def assign_update(x: jax.Array, w: jax.Array, c: jax.Array) -> AssignUpdate:
    """Two-pass reference for the fused assign+accumulate kernel: assignment
    then weighted cluster statistics, over the SAME centroids — exactly the
    per-pass work of one weighted Lloyd step. Zero-weight rows still receive
    an assignment but contribute nothing to sums/counts/err."""
    assign, d1, d2 = assign_top2(x, c)
    sums, counts = cluster_sums(x, w, assign, c.shape[0])
    err = jnp.sum(w.astype(jnp.float32) * d1)
    return AssignUpdate(assign, d1, d2, sums, counts, err)


def assign_update_pruned(
    x: jax.Array,
    w: jax.Array,
    c: jax.Array,
    assign: jax.Array,
    active: jax.Array,
) -> PrunedAssignUpdate:
    """Reference semantics for the drift-bound-pruned pass (ADR 0004).

    ``assign [n] i32`` is the cached assignment from the previous iteration;
    ``active [n] bool`` marks rows whose bounds could not prove the
    assignment unchanged. Skipped rows keep their cached assignment; active
    rows re-run the full top-2 scan; the statistics run over ALL rows under
    the composed assignment — the identical ``cluster_sums`` accumulation
    the dense pass does. As a vectorized oracle this computes everything
    densely — the *semantics* (not the cost) are the contract the pruned
    Pallas kernel must reproduce.
    """
    w = w.astype(jnp.float32)
    active = active.astype(bool)
    a_new, d1, d2 = assign_top2(x, c)
    a = jnp.where(active, a_new, assign)
    sums, counts = cluster_sums(x, w, a, c.shape[0])
    err = jnp.sum(jnp.where(active, w * d1, 0.0))
    return PrunedAssignUpdate(a, d1, d2, sums, counts, err)


def weighted_error(
    x: jax.Array, w: jax.Array, c: jax.Array
) -> jax.Array:
    """Weighted K-means error ``E^P(C) = sum_i w_i * |x_i - c_{x_i}|^2`` (Sec 1.2.2.1)."""
    _, d1, _ = assign_top2(x, c)
    return jnp.sum(w.astype(jnp.float32) * d1)
