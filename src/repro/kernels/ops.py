"""Public, jit-friendly entry points for the clustering kernels.

Each seam dispatches per backend: the Mosaic (TPU) kernels on a TPU, the
Triton-lowering kernels in ``gpu.py`` on a GPU, and the pure-jnp oracles
in ``ref.py`` elsewhere. ``impl="pallas"`` on a CPU host runs the Mosaic
kernels in interpret mode (the CPU CI container validates the kernel
bodies this way); ``impl="auto"`` resolves to ``"ref"`` there with a
once-per-process warning naming the fallback reason. GPU blockings come
from the measured autotune cache when one is available
(``kernels.autotune``, ADR 0008), the roofline heuristic otherwise.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from repro import _warnings
from repro.kernels import ref
from repro.kernels.ref import AssignUpdate, MinSqDistUpdate, PrunedAssignUpdate

__all__ = [
    "AssignUpdate",
    "MinSqDistUpdate",
    "PrunedAssignUpdate",
    "assign_top2",
    "assign_top2_chunk",
    "assign_update",
    "assign_update_chunk",
    "assign_update_pruned",
    "assign_update_pruned_chunk",
    "backend",
    "cluster_sums",
    "interpret_mode",
    "min_sqdist_update",
    "min_sqdist_update_chunk",
    "pairwise_sqdist_chunk",
    "pallas_available",
    "resolve_impl",
    "set_default_impl",
]

# "auto" | "pallas" | "ref". "auto" = pallas on TPU, ref elsewhere (the
# interpret-mode pallas path is exercised explicitly by tests/benchmarks:
# running every Lloyd iteration of the CPU test-suite through the Python
# interpreter loop would be needlessly slow).
_DEFAULT_IMPL = os.environ.get("REPRO_KERNEL_IMPL", "auto")


_VALID_IMPLS = ("auto", "pallas", "ref")

#: backends with a real Pallas lowering for the repo's kernels
_PALLAS_BACKENDS = ("tpu", "gpu")


def set_default_impl(impl: str) -> None:
    """Set the session default. Raises ``ValueError`` on anything outside
    ``"auto" | "pallas" | "ref"`` — a typo here must not silently corrupt
    every later dispatch (and ``assert`` would be stripped under ``-O``)."""
    global _DEFAULT_IMPL
    if impl not in _VALID_IMPLS:
        raise ValueError(
            f"impl must be one of {'|'.join(_VALID_IMPLS)}, got {impl!r}"
        )
    _DEFAULT_IMPL = impl


def backend() -> str:
    """The jax default backend, normalised to ``"tpu" | "gpu" | "cpu"``."""
    b = jax.default_backend()
    return "gpu" if b in ("cuda", "rocm") else b


def pallas_available() -> bool:
    """Whether the current backend has a real (non-interpret) Pallas lowering
    for the clustering kernels: Mosaic on TPU, Triton on GPU."""
    return backend() in _PALLAS_BACKENDS


def interpret_mode() -> bool:
    """Whether the Mosaic kernels run in interpret mode: everywhere but on a
    TPU. Every Mosaic seam below takes its ``interpret`` flag from here."""
    return backend() != "tpu"


def resolve_impl(impl: str | None) -> str:
    """Resolve ``impl``/the session default to a concrete ``"pallas"``/``"ref"``.

    Jitted callers that bake the kernel choice into a compiled program (e.g.
    ``core.lloyd.weighted_lloyd``) must resolve BEFORE entering jit and pass
    the result as a static argument — resolving inside the traced function
    would freeze whatever the session default was at first trace into the
    jit cache.

    ``"auto"`` resolves to ``"pallas"`` wherever a real lowering exists
    (TPU and GPU) and to ``"ref"`` elsewhere — warning once per process so
    a CUDA/TPU user who lands on the oracle path can tell, instead of
    silently benchmarking pure XLA.
    """
    impl = impl or _DEFAULT_IMPL
    if impl == "auto":
        if pallas_available():
            return "pallas"
        _warnings.warn_once(
            "kernel-impl-auto-fallback",
            f"impl='auto' resolved to the pure-JAX 'ref' oracle: backend "
            f"{jax.default_backend()!r} has no Pallas lowering for the "
            f"clustering kernels (supported: {', '.join(_PALLAS_BACKENDS)}). "
            "Set REPRO_KERNEL_IMPL=pallas to force the kernels in interpret "
            "mode.",
            category=RuntimeWarning,
            stacklevel=3,
        )
        return "ref"
    if impl not in ("pallas", "ref"):
        raise ValueError(
            f"impl must be one of {'|'.join(_VALID_IMPLS)}, got {impl!r}"
        )
    return impl


_resolve = resolve_impl  # internal alias, kept for existing call sites


def _gpu_blocking(seam: str, n: int, d: int, k: int, dtype) -> dict:
    """The (autotuned > analytic) GPU blocking for a seam — see autotune."""
    from repro.kernels import autotune

    return autotune.blocking(seam, n=n, d=d, k=k, dtype=dtype, backend="gpu")


def assign_top2(
    x: jax.Array, c: jax.Array, *, impl: str | None = None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused distance + argmin + top-2: ``(assign, d1, d2)``. See ref.assign_top2."""
    if _resolve(impl) == "pallas":
        if backend() == "gpu":
            from repro.kernels import gpu

            blk = _gpu_blocking(
                "assign_update", x.shape[0], x.shape[1], c.shape[0], x.dtype
            )
            return gpu.assign_top2_gpu(x, c, bn=blk["bn"], bk=blk["bk"])
        from repro.kernels import distance_assign

        interpret = interpret_mode()
        return distance_assign.assign_top2_pallas(x, c, interpret=interpret)
    return ref.assign_top2(x, c)


def assign_top2_chunk(
    x: jax.Array,
    c: jax.Array,
    *,
    chunk_size: int,
    impl: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Chunk-shaped ``assign_top2`` for streaming passes (DESIGN.md §6).

    Pads a ragged ``[n <= chunk_size, d]`` chunk to the static chunk shape
    before dispatching, so a whole out-of-core pass — including the tail
    chunk — reuses one compiled program (one Pallas kernel instantiation per
    pass, not one per distinct chunk length). Padding rows are sliced off the
    result; they cost ``(chunk_size − n)·K`` wasted distance lanes on the
    tail chunk only.
    """
    n, x = _pad_to_chunk(x, chunk_size)
    assign, d1, d2 = assign_top2(x, c, impl=impl)
    return assign[:n], d1[:n], d2[:n]


def _pad_to_chunk(x: jax.Array, chunk_size: int) -> tuple[int, jax.Array]:
    """The shared chunk-padding contract: zero-pad a ragged ``[n <= chunk_size,
    d]`` chunk to the static shape; callers slice the first ``n`` result rows
    off. One place to change if a Pallas variant needs different alignment."""
    n = x.shape[0]
    if n > chunk_size:
        raise ValueError(f"chunk of {n} rows exceeds chunk_size={chunk_size}")
    if n < chunk_size:
        x = jnp.pad(x, ((0, chunk_size - n), (0, 0)))
    return n, x


def pairwise_sqdist_chunk(
    x: jax.Array,
    c: jax.Array,
    *,
    chunk_size: int,
    impl: str | None = None,
) -> jax.Array:
    """Chunk-shaped full ``[n, K]`` squared-distance matrix (the facade's
    ``transform``). Same padding contract as :func:`assign_top2_chunk`: a
    ragged tail chunk is padded to the static shape so one compiled program
    serves the whole out-of-core pass, and padding rows are sliced off.

    Currently always the jnp oracle (``ref.pairwise_sqdist`` is already one
    MXU-friendly matmul); ``impl`` is accepted for parity with the other
    entry points so a Pallas variant can slot in without caller changes.
    """
    del impl
    n, x = _pad_to_chunk(x, chunk_size)
    return ref.pairwise_sqdist(x, c)[:n]


def cluster_sums(
    x: jax.Array,
    w: jax.Array,
    assign: jax.Array,
    num_clusters: int,
    *,
    impl: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Weighted per-cluster sums/counts. See ref.cluster_sums.

    On GPU the pallas path uses the oracle directly: the one-hot update is
    a single XLA segment-sum — already one fused GPU kernel — and the
    Mosaic accumulator kernel has no Triton lowering.
    """
    if _resolve(impl) == "pallas" and backend() != "gpu":
        from repro.kernels import cluster_update

        interpret = interpret_mode()
        return cluster_update.cluster_sums_pallas(
            x, w, assign, num_clusters, interpret=interpret
        )
    return ref.cluster_sums(x, w, assign, num_clusters)


def assign_update(
    x: jax.Array,
    w: jax.Array,
    c: jax.Array,
    *,
    impl: str | None = None,
) -> AssignUpdate:
    """One weighted Lloyd data pass: top-2 assignment + weighted cluster
    statistics + weighted error, all against the same centroids.

    This is THE shared hot path of all three engines (in-core Lloyd,
    streaming per-chunk fold, distributed per-shard body). On the Pallas
    path it runs as the single-pass fused kernel — x read from HBM once —
    whenever the ``[K, d]`` accumulator fits the kernel VMEM budget;
    otherwise it degrades to the two-pass composition (Pallas top-2 kernel +
    the XLA segment-sum update), which is also the ``ref`` semantics.
    Zero-weight rows are inert in sums/counts/err.

    ``n_dist`` on the result is the pass's distance-computation count in
    the paper's unit — ``active_points · K`` with ``active = w > 0`` — and
    is the same number for every ``impl`` (it accounts what the algorithm
    *requires*, so ``FitResult.distances`` can't drift with kernel choice).
    """
    out = _assign_update_impl(x, w, c, impl=_resolve(impl))
    return out._replace(n_dist=_dense_dist_count(w, c.shape[0]))


def _dense_dist_count(w: jax.Array, k: int) -> jax.Array:
    return jnp.sum((w > 0).astype(jnp.float32)) * k


def _assign_update_impl(
    x: jax.Array, w: jax.Array, c: jax.Array, *, impl: str
) -> AssignUpdate:
    if impl == "pallas":
        if backend() == "gpu":
            return _assign_update_gpu(x, w, c)
        from repro.kernels import distance_assign, fused_assign_update

        k, d = c.shape
        interpret = interpret_mode()
        if fused_assign_update.fused_supported(d, k):
            return AssignUpdate(
                *fused_assign_update.fused_assign_update_pallas(
                    x, w, c, interpret=interpret
                )
            )
        # Two-pass fallback (ADR 0003): the fused kernel's accumulator
        # budget is exceeded, so assignment runs the top-2 kernel alone and
        # the update runs as the standalone one-hot Pallas kernel — which
        # tolerates a [K, d] block up to the full 8 MB — degrading to the
        # XLA segment-sum only beyond that.
        assign, d1, d2 = distance_assign.assign_top2_pallas(
            x, c, interpret=interpret
        )
        sums, counts = _two_pass_cluster_sums(x, w, assign, k, interpret)
        err = jnp.sum(w.astype(jnp.float32) * d1)
        return AssignUpdate(assign, d1, d2, sums, counts, err)
    return ref.assign_update(x, w, c)


def _assign_update_gpu(x: jax.Array, w: jax.Array, c: jax.Array) -> AssignUpdate:
    """The GPU (Triton-lowering) dispatch of one dense Lloyd pass: the
    single-pass kernel while the per-program ``[K, d]`` statistics partial
    is affordable, else the top-2 kernel plus the XLA segment-sum (the GPU
    analogue of the TPU two-pass fallback)."""
    from repro.kernels import gpu

    k, d = c.shape
    blk = _gpu_blocking("assign_update", x.shape[0], d, k, x.dtype)
    if gpu.gpu_stats_supported(d, k):
        return AssignUpdate(
            *gpu.assign_update_gpu(x, w, c, bn=blk["bn"], bk=blk["bk"])
        )
    assign, d1, d2 = gpu.assign_top2_gpu(x, c, bn=blk["bn"], bk=blk["bk"])
    sums, counts = ref.cluster_sums(x, w, assign, k)
    err = jnp.sum(w.astype(jnp.float32) * d1)
    return AssignUpdate(assign, d1, d2, sums, counts, err)


def _two_pass_cluster_sums(x, w, assign, k, interpret):
    """The two-pass fallback's update stage, shared by the dense and pruned
    paths so their kernel selection can never diverge: the one-hot Pallas
    kernel while its [K, d] block fits its own 8 MB bound, XLA segment-sum
    beyond."""
    from repro.kernels import cluster_update

    d = x.shape[1]
    kp, dp = -(-k // 8) * 8, -(-d // 128) * 128
    if kp * dp * 4 <= 8 * 1024 * 1024:  # cluster_sums_pallas's own bound
        return cluster_update.cluster_sums_pallas(
            x, w, assign, k, interpret=interpret
        )
    return ref.cluster_sums(x, w, assign, k)


def min_sqdist_update(
    x: jax.Array,
    w: jax.Array,
    cand: jax.Array,
    cvalid: jax.Array,
    mind2: jax.Array,
    *,
    impl: str | None = None,
) -> MinSqDistUpdate:
    """One k-means|| fold pass: the running per-point min squared distance
    updated with a batch of new candidates, plus the weighted cost
    ``φ = Σ w·min-d²`` of the updated state (ADR 0005).

    This is the data pass every engine's k-means|| oversampling round runs
    (in-core over the representatives, streaming per chunk, distributed per
    shard). On the Pallas path the ``(n, L)`` distance matrix never exists —
    x is read from HBM once per round. Invalid candidate rows
    (``cvalid == 0``: the unfilled tail of a fixed-capacity batch) can never
    win the min; zero-weight rows are inert in the cost.

    ``n_dist`` on the result is the pass's distance-computation count in the
    paper's unit — ``active_points · valid_candidates`` — and is the same
    number for every ``impl``.
    """
    n_dist = (
        jnp.sum((w > 0).astype(jnp.float32))
        * jnp.sum((cvalid > 0).astype(jnp.float32))
    )
    if _resolve(impl) == "pallas":
        if backend() == "gpu":
            from repro.kernels import gpu

            blk = _gpu_blocking(
                "min_sqdist_update", x.shape[0], x.shape[1], cand.shape[0],
                x.dtype,
            )
            new, cost = gpu.min_sqdist_update_gpu(
                x, w, cand, cvalid, mind2, bn=blk["bn"], bl=blk["bl"]
            )
            return MinSqDistUpdate(new, cost, n_dist)
        from repro.kernels import min_sqdist_update as msu

        interpret = interpret_mode()
        new, cost = msu.min_sqdist_update_pallas(
            x, w, cand, cvalid, mind2, interpret=interpret
        )
        return MinSqDistUpdate(new, cost, n_dist)
    out = ref.min_sqdist_update(x, w, cand, cvalid, mind2)
    return out._replace(n_dist=n_dist)


def min_sqdist_update_chunk(
    x: jax.Array,
    w: jax.Array,
    cand: jax.Array,
    cvalid: jax.Array,
    mind2: jax.Array,
    *,
    chunk_size: int,
    impl: str | None = None,
) -> MinSqDistUpdate:
    """Chunk-shaped :func:`min_sqdist_update` for streaming k-means|| passes.

    Padding contract of :func:`assign_update_chunk`: a ragged tail chunk is
    padded to the static shape, padding rows carry weight 0 (inert in the
    cost) and min-d² 0, and the per-row output is sliced back to ``n``.
    """
    n, x = _pad_to_chunk(x, chunk_size)
    pad = chunk_size - n
    w = jnp.pad(w.astype(jnp.float32), (0, pad))
    mind2 = jnp.pad(mind2.astype(jnp.float32), (0, pad))
    out = min_sqdist_update(x, w, cand, cvalid, mind2, impl=impl)
    return out._replace(mind2=out.mind2[:n])


def assign_update_pruned(
    x: jax.Array,
    w: jax.Array,
    c: jax.Array,
    assign: jax.Array,
    active: jax.Array,
    *,
    impl: str | None = None,
) -> PrunedAssignUpdate:
    """One drift-bound-pruned weighted Lloyd pass (ADR 0004).

    ``assign`` is the cached assignment, ``active`` the mask of rows whose
    bounds could not prove it unchanged. Statistics are FULL sums/counts
    under the composed assignment, produced by the same accumulation (same
    order) as :func:`assign_update` — pruned centroids are bit-identical to
    dense ones whenever the assignments agree. ``d1``/``d2``/``err`` are
    defined only where active.

    ``n_dist`` charges ``K`` distance evaluations per *active* row with
    ``w > 0`` — the count a faithful row-level implementation needs, and
    (deliberately) the same number for every ``impl``: the ref oracle is
    vectorized-dense and the Pallas kernel skips at row-block granularity,
    but the algorithmic cost the paper reports is per-row.
    """
    n_dist = (
        jnp.sum((active.astype(bool) & (w > 0)).astype(jnp.float32)) * c.shape[0]
    )
    if _resolve(impl) == "pallas":
        k, d = c.shape
        if backend() == "gpu":
            from repro.kernels import gpu

            blk = _gpu_blocking(
                "assign_update_pruned", x.shape[0], d, k, x.dtype
            )
            if gpu.gpu_stats_supported(d, k):
                out = PrunedAssignUpdate(
                    *gpu.assign_update_pruned_gpu(
                        x, w, c, assign, active, bn=blk["bn"], bk=blk["bk"]
                    )
                )
                return out._replace(n_dist=n_dist)
            # GPU two-pass: dense top-2 kernel + XLA segment-sum under the
            # composed assignment
            a_new, d1, d2 = gpu.assign_top2_gpu(x, c, bn=blk["bn"], bk=blk["bk"])
            w32 = w.astype(jnp.float32)
            a = jnp.where(active.astype(bool), a_new, assign)
            sums, counts = ref.cluster_sums(x, w, a, k)
            err = jnp.sum(jnp.where(active.astype(bool), w32 * d1, 0.0))
            return PrunedAssignUpdate(a, d1, d2, sums, counts, err, n_dist)
        from repro.kernels import fused_assign_update

        interpret = interpret_mode()
        if fused_assign_update.fused_supported(d, k):
            out = PrunedAssignUpdate(
                *fused_assign_update.fused_assign_update_pruned_pallas(
                    x, w, c, assign, active, interpret=interpret
                )
            )
            return out._replace(n_dist=n_dist)
        # Two-pass fallback: dense Pallas top-2 for the assignment, full
        # statistics under the composed assignment through the SAME update
        # dispatch as the dense fallback (shared helper — the two paths'
        # kernel selection cannot diverge).
        from repro.kernels import distance_assign

        a_new, d1, d2 = distance_assign.assign_top2_pallas(
            x, c, interpret=interpret
        )
        w32 = w.astype(jnp.float32)
        a = jnp.where(active.astype(bool), a_new, assign)
        sums, counts = _two_pass_cluster_sums(x, w, a, k, interpret)
        err = jnp.sum(jnp.where(active.astype(bool), w32 * d1, 0.0))
        return PrunedAssignUpdate(a, d1, d2, sums, counts, err, n_dist)
    out = ref.assign_update_pruned(x, w, c, assign, active)
    return out._replace(n_dist=n_dist)


def assign_update_pruned_chunk(
    x: jax.Array,
    w: jax.Array,
    c: jax.Array,
    assign: jax.Array,
    active: jax.Array,
    *,
    chunk_size: int,
    impl: str | None = None,
) -> PrunedAssignUpdate:
    """Chunk-shaped :func:`assign_update_pruned` for streaming passes.

    Padding contract of :func:`assign_update_chunk` plus: padding rows are
    never active and carry weight 0 and cached id 0, so they are inert in
    the statistics deltas and the per-row outputs slice back to ``n``.
    """
    n, x = _pad_to_chunk(x, chunk_size)
    pad = chunk_size - n
    w = jnp.pad(w.astype(jnp.float32), (0, pad))
    assign = jnp.pad(assign.astype(jnp.int32), (0, pad))
    active = jnp.pad(active.astype(bool), (0, pad))
    out = assign_update_pruned(x, w, c, assign, active, impl=impl)
    return out._replace(assign=out.assign[:n], d1=out.d1[:n], d2=out.d2[:n])


def assign_update_chunk(
    x: jax.Array,
    w: jax.Array,
    c: jax.Array,
    *,
    chunk_size: int,
    impl: str | None = None,
) -> AssignUpdate:
    """Chunk-shaped :func:`assign_update` for streaming passes.

    Same padding contract as :func:`assign_top2_chunk`, with the addition
    that padding rows enter the kernel with weight 0 — so the accumulated
    sums/counts/err are EXACTLY those of the ``n`` real rows (no phantom
    points from ``_pad_to_chunk``; pinned by the padding regression test in
    tests/test_kernels_properties.py). Per-row outputs are sliced to ``n``.
    """
    n, x = _pad_to_chunk(x, chunk_size)
    w = jnp.pad(w.astype(jnp.float32), (0, chunk_size - n))
    out = assign_update(x, w, c, impl=impl)
    return out._replace(assign=out.assign[:n], d1=out.d1[:n], d2=out.d2[:n])
