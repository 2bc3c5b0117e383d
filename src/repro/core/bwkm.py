"""BWKM — Boundary Weighted K-means (paper Algorithm 5): in-core entry point.

The algorithm itself — weighted Lloyd over the current partition's
representatives alternating with ε-proportional boundary splitting, plus
the Section-2.4.2 stopping criteria — lives ONCE in
:func:`repro.engine.driver.fit_plane`; this module keeps the shared
config/result types and the resident-array entry point
(:func:`fit_incore` = the driver over :class:`repro.engine.incore.InCorePlane`).

Stopping criteria (paper Section 2.4.2):
  * ``boundary-empty``  — F = ∅: every block is well assigned; by Theorem 3
                           the weighted fixed point is a Lloyd fixed point on D.
  * ``distance-budget`` — the practical computational criterion.
  * ``displacement``    — ‖C − C'‖_∞ ≤ ε_w (Theorem A.4).
  * ``gap-bound``       — Theorem-2 bound below threshold.
  * ``capacity`` / ``max-iters`` — resource guards.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Any

import jax

from repro import obs
from repro.core import init_partition
from repro.core.partition import Partition
from repro.health import RunHealth

__all__ = ["BWKMConfig", "BWKMResult", "fit_incore", "seed_centroids"]


def seed_centroids(
    name: str, key: jax.Array, reps: jax.Array, w: jax.Array, k: int
) -> jax.Array:
    """Seed K centroids from a weighted point set via the named strategy in
    the ``repro.api.inits`` registry (imported lazily: the api layer imports
    the core drivers, not vice versa)."""
    from repro.api.inits import resolve_init

    strategy = resolve_init(name)
    if not strategy.supports_weights:
        warnings.warn(
            f"init strategy {strategy.name!r} ignores point weights; BWKM "
            "representatives are seeded as if unweighted",
            UserWarning,
            stacklevel=2,
        )
    return strategy.seed_centroids(key, reps, w, k)


@dataclasses.dataclass(frozen=True)
class BWKMConfig:
    """Knobs for Algorithm 5. ``m/m_prime/s/r`` default to the paper's values
    (Section 2.4.1) when left as ``None``."""

    k: int
    m: int | None = None
    m_prime: int | None = None
    s: int | None = None
    r: int = 5
    capacity: int | None = None  # max blocks; default 64·m
    max_iters: int = 30  # BWKM outer iterations
    lloyd_max_iters: int = 100
    lloyd_epsilon: float = 1e-4
    distance_budget: float | None = None
    displacement_epsilon: float | None = None  # Thm A.4's ε (on E^D scale)
    gap_bound_threshold: float | None = None  # Thm 2 stopping threshold
    init: str = "kmeans++"  # seeding strategy name (repro.api.inits registry)
    init_sample_size: int | None = None  # streaming first-pass sample rows;
    # None = engine default (in-core/distributed engines ignore it)
    prune: bool | None = None  # drift-bound pruned Lloyd (ADR 0004);
    # None = session default (REPRO_LLOYD_PRUNE, on unless set to 0)

    def resolve(self, n: int, d: int) -> dict[str, Any]:
        p = init_partition.default_params(n, self.k, d)
        m = self.m or p["m"]
        return {
            "m": m,
            "m_prime": self.m_prime or max(self.k + 1, m // 10),
            "s": self.s or p["s"],
            "r": self.r,
            "capacity": self.capacity or max(64 * m, 4 * self.k),
        }


@dataclasses.dataclass
class BWKMResult:
    centroids: jax.Array
    partition: Partition
    iterations: int
    distances: float  # total distance computations (paper's cost unit)
    weighted_errors: list[float]  # per outer iteration
    n_blocks: list[int]
    boundary_sizes: list[int]
    stop_reason: str
    trace: list[dict]  # per-iteration snapshots for the trade-off benchmark
    # fault/degradation ledger (DESIGN.md §5); None only on legacy paths —
    # the three engines always attach one, all-zero for a clean run
    health: RunHealth | None = None
    # the fit's ``repro.obs`` counters: {"host_syncs": int, "data_passes": int}
    counters: dict | None = None


def fit_incore(
    key: jax.Array,
    x: jax.Array,
    config: BWKMConfig,
    *,
    trace_centroids: bool = False,
) -> BWKMResult:
    """Run BWKM on ``x [n, d]``. Returns centroids and the audit trail.

    This is the in-core engine behind the ``repro.BWKM`` facade; call the
    facade unless you need driver-native access to the ``Partition``. The
    engine import is deferred — the engine package is layered ABOVE the
    core primitives (tools/check_layering.py), and this wrapper is the
    sanctioned upward reference.
    """
    from repro.engine import driver, incore

    with obs.fit_scope():  # the plane's finite-row pass belongs to the fit
        return driver.fit_plane(
            key, incore.InCorePlane(x), config, trace_centroids=trace_centroids
        )
