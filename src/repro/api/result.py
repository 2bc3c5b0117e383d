"""Unified fit-result schema for every execution engine (DESIGN.md §9).

Before the estimator facade, each entry point reported results in its own
shape: the in-core driver returned a ``BWKMResult``, the streaming driver a
``StreamBWKMResult`` (extra ``stream`` field), and the five baselines bare
``(centroids, distances)`` tuples. :class:`FitResult` is the one schema all
of them now share — the facade, the trade-off benchmark, and the tests can
consume any engine's output without knowing which engine produced it.

This module deliberately imports nothing from ``repro`` so that any layer
(core baselines included) can return a ``FitResult`` without import cycles;
conversion from driver-native results is duck-typed.
"""

from __future__ import annotations

import dataclasses
from typing import Any

__all__ = ["FitResult", "from_driver_result"]


@dataclasses.dataclass
class FitResult:
    """What every engine reports after ``fit``.

    ``metadata`` carries engine-specific extras (block counts, streaming
    pass statistics, the final ``Partition``, the ``health`` ledger, the
    fit's ``counters`` of host syncs and data passes, …) without widening
    the common schema; ``trace`` holds per-iteration snapshots when the caller
    asked for them (the paper's trade-off curves are plotted from it).
    """

    centroids: Any  # [K, d] jax.Array / np.ndarray
    distances: float  # total distance computations (the paper's cost unit)
    iterations: int
    stop_reason: str
    engine: str  # "incore" | "streaming" | "distributed" | "baseline:<name>"
    trace: list = dataclasses.field(default_factory=list)
    metadata: dict = dataclasses.field(default_factory=dict)

    @property
    def k(self) -> int:
        return int(self.centroids.shape[0])

    def schema(self) -> tuple[str, ...]:
        """Field names every engine agrees on (used by the contract tests)."""
        return tuple(f.name for f in dataclasses.fields(FitResult))


def from_driver_result(res: Any, engine: str) -> FitResult:
    """Convert a ``BWKMResult``-shaped driver result (duck-typed: the three
    BWKM drivers all share its fields) into the unified schema."""
    metadata = {
        "n_blocks": list(res.n_blocks),
        "boundary_sizes": list(res.boundary_sizes),
        "weighted_errors": list(res.weighted_errors),
        "partition": res.partition,
    }
    stream = getattr(res, "stream", None)
    if stream is not None:
        metadata["passes"] = stream.passes
        metadata["points_streamed"] = stream.points_streamed
        metadata["n_chunks"] = stream.n_chunks
        metadata["chunk_size"] = stream.chunk_size
    # RunHealth ledger (DESIGN.md §5) — duck-typed so this module keeps its
    # no-repro-imports guarantee; every engine attaches one (all-zero when
    # the run was clean).
    health = getattr(res, "health", None)
    if health is not None and hasattr(health, "as_dict"):
        metadata["health"] = health.as_dict()
    # the fit's host syncs and passes over all rows (``repro.obs``)
    counters = getattr(res, "counters", None)
    if counters is not None:
        metadata["counters"] = dict(counters)
    return FitResult(
        centroids=res.centroids,
        distances=float(res.distances),
        iterations=int(res.iterations),
        stop_reason=res.stop_reason,
        engine=engine,
        trace=list(res.trace),
        metadata=metadata,
    )
