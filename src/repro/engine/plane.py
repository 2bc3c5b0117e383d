"""The ``DataPlane`` protocol — what an execution engine must provide.

A *data plane* owns the dataset in its native layout (resident array,
chunked stream, mesh shards) and exposes the small set of data-touching
primitives the shared drivers in :mod:`repro.engine.driver` are written
against. Everything algorithmic — stopping criteria, misassignment
sampling, the split plan, PRNG bookkeeping for the outer loop, distance
accounting — lives in the driver exactly once.

The primitives (ISSUE-10 nomenclature in parentheses):

  * ``build_partition`` (``fold_stats``) — build the initial spatial
    partition and fold every point's block statistics through it. Each
    plane keeps its own membership state: ``block_id`` in the partition
    (in-core), per-chunk host arrays (streaming), sharded rows (mesh).
  * ``route_round`` (``fold_stats``) — execute a resolved
    :class:`~repro.core.partition.SplitPlan`: repair memberships against
    the plan and re-tighten every block's statistics in one data pass.
  * ``ll_session`` (``fold_min_sqdist``) — a k-means|| seeding session;
    each round folds the pending candidate batch into the running min-d²
    state and draws the next batch. See :class:`LLSession`.
  * ``lloyd_session`` (``lloyd_round``) — a full-data pruned Lloyd
    session. The per-row bound state (assignment, upper, lower) is
    plane-owned by design: it lives in the ``while_loop`` carry in-core,
    in host arrays per chunk for streaming, and sharded alongside the
    points on a mesh — the driver never sees a per-row array.
  * ``run_health`` (``health()``) — the :class:`~repro.health.RunHealth`
    fault/degradation ledger the plane accumulates during the fit.

Invariants every plane must uphold (ADR 0010):

  * **PRNG ownership** — ``split_key`` consumes exactly the keys the
    plane's historical driver consumed (3-way split in-core/streaming,
    4-way with the sample key on the mesh), so fits are bit-identical to
    the pre-refactor engines.
  * **Associative statistics** — ``fold_stats`` results must equal the
    in-core fold up to summation order (sums/counts add, boxes min/max).
  * **Determinism under faults** — retries, quarantine, and
    drop-and-reweight must be deterministic functions of the data and the
    injected schedule (the fault-determinism pins rely on it).
"""

from __future__ import annotations

from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.partition import Partition, SplitPlan
from repro.health import RunHealth

__all__ = ["DataPlane", "LLSession", "LloydSession", "global_extent"]

_BIG = 3.0e38


def global_extent(part: Partition) -> float:
    """``‖max x − min x‖`` over the whole dataset, recovered from the
    accumulated block boxes — the out-of-core/sharded way to get the
    displacement-threshold scale without a dedicated data pass."""
    occ = (part.count > 0) & part.active
    lo = jnp.min(jnp.where(occ[:, None], part.lo, _BIG), axis=0)
    hi = jnp.max(jnp.where(occ[:, None], part.hi, -_BIG), axis=0)
    return obs.pull(jnp.linalg.norm(jnp.maximum(hi - lo, 0.0)))


@runtime_checkable
class DataPlane(Protocol):
    """Execution-plane interface consumed by :func:`repro.engine.driver.fit_plane`."""

    name: str
    run_health: RunHealth

    @property
    def n_points(self) -> int: ...

    @property
    def dim(self) -> int: ...

    def split_key(self, key: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Consume the plane's historical PRNG prefix: returns
        ``(carry_key, k_init, k_pp)``; extra engine keys (e.g. the mesh
        sample key) are stashed on the plane."""
        ...

    def build_partition(self, k_init: jax.Array, config: Any, p: dict) -> Partition:
        """Initial partition (paper Alg. 2) + first full-data stats fold."""
        ...

    def extent(self, part: Partition) -> float:
        """Dataset extent for the Thm-A.4 displacement threshold."""
        ...

    def route_round(self, part: Partition, plan: SplitPlan, round_index: int) -> Partition:
        """Execute a split round: route points against ``plan``, activate the
        new rows, re-tighten all block statistics (one data pass)."""
        ...

    def on_iteration(
        self, it: int, c: jax.Array, part: Partition, distances: float
    ) -> None:
        """Per-iteration hook, fired after Lloyd/misassignment and before the
        stop checks (the sharded plane checkpoints here)."""
        ...

    def make_result(self, **fields: Any) -> Any:
        """Assemble the plane's result type (``BWKMResult`` or subclass),
        attaching the plane's health ledger / stream accounting."""
        ...


class LLSession(Protocol):
    """One k-means|| seeding run over a plane (driver: ``plane_kmeans_parallel``).

    The driver calls ``seed()`` once, then per round ``begin_round`` →
    (the shared Bernoulli draw) → ``select``, then ``finish``. The session
    owns candidate storage, the min-d² state, and its historical RNG
    stream; ``begin_round`` folds any pending (not yet folded) candidate
    batch first so ``phi`` is the exact current cost when the driver draws.
    """

    l: int  # noqa: E741 — ℓ, the oversampling factor (Bahmani et al.)

    def seed(self) -> None: ...

    def begin_round(self, rnd: int) -> tuple[Any, Any, Any, float]:
        """Returns ``(u, w, mind2, phi)`` — per-point uniforms, weights, and
        min squared distances, plus the exact normaliser."""
        ...

    def select(self, rnd: int, u: Any, accept: Any) -> None: ...

    def finish(self, normalisers: tuple) -> dict: ...


class LloydSession(Protocol):
    """One full-data Lloyd run over a plane (driver: ``plane_lloyd``).

    ``seed`` runs the dense pass and returns the folded statistics plus the
    Σ w‖x‖² term of the algebraic error identity; ``step`` runs one pruned
    (or dense) tracking round against the new centroids. Per-row bound
    state stays inside the session between calls.
    """

    denom: float  # active-fraction denominator: max(k · n_points, 1)

    def seed(self, c: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, float]:
        """Returns ``(sums, counts, err, w2sum, n_dist)``."""
        ...

    def step(self, c_new: jax.Array, drift: jax.Array) -> tuple[jax.Array, jax.Array, float]:
        """Returns ``(sums, counts, n_dist)`` under the composed assignment."""
        ...
