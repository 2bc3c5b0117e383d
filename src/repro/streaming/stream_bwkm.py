"""Out-of-core streaming BWKM entry point (paper Algorithm 5; DESIGN.md §6).

:func:`fit_streaming` runs the SAME weighted Lloyd + ε-boundary-split loop
as ``core.bwkm.fit_incore`` — literally the same function,
:func:`repro.engine.driver.fit_plane` — over the chunked
:class:`repro.engine.streaming.StreamingPlane`: points arrive as fixed-size
chunks from a :class:`repro.data.ChunkSource`, and everything the algorithm
needs about them is folded into per-block sufficient statistics
``(Σx, |B|, min x, max x)`` (``core.partition.BlockStats``) chunk by chunk.

Memory budget per device: one padded chunk ``[chunk_size, d]`` (double
buffered → two) + the ``[M, d]`` block statistics + the ``[M, d]``/``[K, d]``
representative/centroid arrays. Host keeps 4 bytes/point of block
memberships (``int32``), the only full-length state — see
docs/adr/0001-streaming-ingestion.md for why that beats recomputing
memberships from boxes every pass.

Pass structure per outer iteration:
  * weighted Lloyd + misassignment run on the M-row representative set —
    no data pass at all;
  * a split round is ONE streaming pass: each chunk's memberships are
    repaired against the split plan (`route_split`) and its block
    statistics are re-accumulated in the same jitted program.

The chunk programs live in :mod:`repro.engine.streaming`; this module keeps
the entry points and the full-stream Lloyd/error evaluators.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from typing import NamedTuple

from repro.core import bwkm as core_bwkm
from repro.core import lloyd as lloyd_mod
from repro.data.chunks import ChunkSource, padded_device_chunks
from repro.engine import driver as engine_driver
from repro.engine import streaming as engine_streaming
from repro.engine.streaming import (  # noqa: F401  (re-exported: tests/benchmarks)
    StreamBWKMResult,
    StreamStats,
    StreamingPlane,
    _chunk_assign_stats,
    _routing_pass,
    _split_pass,
    _with_stats,
)
from repro.engine.plane import global_extent as _global_extent  # noqa: F401
from repro.kernels import ops

__all__ = [
    "StreamBWKMResult",
    "StreamStats",
    "StreamingLloydResult",
    "fit_streaming",
    "streaming_error",
    "streaming_lloyd",
    "streaming_lloyd_step",
]


def fit_streaming(
    key: jax.Array,
    source: ChunkSource,
    config: core_bwkm.BWKMConfig,
    *,
    trace_centroids: bool = False,
) -> StreamBWKMResult:
    """Algorithm 5 over a chunked stream — the shared engine driver over the
    streaming plane; only the dataset passes differ from in-core.

    This is the streaming engine behind the ``repro.BWKM`` facade. All
    knobs — including the first-pass sample size (``init_sample_size``) and
    the seeding strategy (``init``) — live on :class:`BWKMConfig`, so the
    facade needs no engine-specific kwargs.

    The returned ``partition.block_id`` is empty — full-length memberships
    are internal host state. ``result.stream`` records pass counts.
    """
    return engine_driver.fit_plane(
        key, StreamingPlane(source), config, trace_centroids=trace_centroids
    )


# ------------------------------------------------- full-stream evaluation
def streaming_lloyd_step(
    source: ChunkSource, c: jax.Array
) -> tuple[jax.Array, float]:
    """One exact Lloyd iteration over the full stream: ``(new_c, error)``.

    The out-of-core analogue of ``dist_bwkm.dist_assign_step`` — chunk
    statistics take the place of shard statistics (the two compose: on a
    mesh, each host streams its shard's chunks and the psum runs unchanged).
    """
    k, d = c.shape
    impl = ops.resolve_impl(None)  # resolve once per pass, outside jit
    sums = jnp.zeros((k, d), jnp.float32)
    counts = jnp.zeros((k,), jnp.float32)
    err = jnp.zeros((), jnp.float32)  # device-side: no per-chunk host sync
    for x_dev, nv in padded_device_chunks(source):
        s_, c_, e_ = _chunk_assign_stats(x_dev, nv, c, impl=impl)
        sums, counts, err = sums + s_, counts + c_, err + e_
    new_c = jnp.where(
        (counts > 0)[:, None], sums / jnp.maximum(counts, 1e-30)[:, None], c
    )
    return new_c, float(err)


def streaming_error(source: ChunkSource, c: jax.Array) -> float:
    """Exact K-means error E^D(C) (Eq. 1) computed in one streaming pass."""
    _, err = streaming_lloyd_step(source, c)
    return err


class StreamingLloydResult(NamedTuple):
    centroids: jax.Array  # [K, d]
    error: float  # exact weighted error at the final centroids
    iters: int  # Lloyd iterations executed (excludes the seeding pass)
    distances: float  # kernel-reported distance computations
    active_fractions: list[float]  # per-iteration fraction of rescanned rows


def streaming_lloyd(
    source: ChunkSource,
    c: jax.Array,
    *,
    max_iters: int = 50,
    epsilon: float = 1e-4,
    impl: str | None = None,
    prune: bool | None = None,
) -> StreamingLloydResult:
    """Full-stream Lloyd with drift-bound pruning carried ACROSS chunk folds.

    The shared :func:`repro.engine.driver.plane_lloyd` loop over the
    streaming session: the in-core pruned loop keeps (assignment, upper
    bound, lower bound) per row in the ``while_loop`` carry; out-of-core
    the same state lives on the host as one compact f32/i32 array per chunk
    (12 bytes/point) and is re-fed to the jitted chunk program each pass.
    Drift is computed once per iteration from the folded statistics, so
    after the first pass most chunks rescan only their boundary rows — the
    paper's distance-computation metric drops exactly as in-core, while the
    chunk pipeline (static shapes, one compiled program per pass) is
    unchanged.

    Stops on the Eq.-2 relative error change (the error is exact via the
    ``core.lloyd.stats_error`` identity). Returns kernel-reported distance
    counts and the per-iteration active fraction for the benchmarks.
    """
    sess = engine_streaming.StreamingLloydSession(
        source, c.shape[0],
        impl=ops.resolve_impl(impl), prune=lloyd_mod.resolve_prune(prune),
    )
    c, err, it, distances, active_fractions = engine_driver.plane_lloyd(
        sess, c, max_iters=max_iters, epsilon=epsilon
    )
    return StreamingLloydResult(
        centroids=c,
        error=err,
        iters=it,
        distances=distances,
        active_fractions=active_fractions,
    )
