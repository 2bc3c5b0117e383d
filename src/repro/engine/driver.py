"""The shared drivers: BWKM, k-means|| seeding, full-data Lloyd (ADR 0010).

Each loop in this module is the ONLY copy in the tree — the in-core,
streaming, and distributed engines are :mod:`repro.engine.plane`
implementations plus thin entry-point wrappers. Anything algorithmic that
was once hand-synchronized across ``core/bwkm.py`` /
``streaming/stream_bwkm.py`` / ``distributed/dist_bwkm.py`` lives here:

  * :func:`fit_plane`        — paper Algorithm 5: weighted Lloyd over the
    partition representatives + ε-proportional boundary splitting, with
    the Section-2.4.2 stopping criteria.
  * :func:`plane_kmeans_parallel` — the Bahmani et al. (2012) oversampling
    loop; the Bernoulli acceptance draw has exactly one call site
    (:func:`ll_bernoulli`), whatever plane executes the folds.
  * :func:`plane_lloyd`      — drift-bound pruned Lloyd over the full
    dataset (ADR 0004), bound state plane-owned.

Cross-engine agreement is therefore by construction: the engines can only
differ in how a data pass is executed (summation order, psum vs chunk
fold), never in what the algorithm does.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import bounds, bwkm as core_bwkm, lloyd as lloyd_mod
from repro.core import misassignment as mis
from repro.core import partition as part_mod

__all__ = [
    "fit_plane",
    "ll_bernoulli",
    "plane_kmeans_parallel",
    "plane_lloyd",
    "resolve_ll_params",
]


# ------------------------------------------------------- BWKM (Algorithm 5)
def fit_plane(
    key: jax.Array,
    plane: Any,
    config: "core_bwkm.BWKMConfig",
    *,
    trace_centroids: bool = False,
):
    """Run BWKM over ``plane``. Returns the plane's result type.

    Stopping criteria (paper Section 2.4.2) in evaluation order:
    boundary-empty, distance-budget, displacement (Thm A.4), gap-bound
    (Thm 2), capacity, max-iters. All planes honour all six — the sharded
    plane's displacement/gap thresholds derive the dataset extent from the
    accumulated block boxes, so no extra data pass is needed.
    """
    with obs.fit_scope() as fit:
        return _fit_plane(key, plane, config, trace_centroids, fit.counts)


def _fit_plane(key, plane, config, trace_centroids: bool, counters: dict):
    n, d = plane.n_points, plane.dim
    p = config.resolve(n, d)
    k = config.k

    key, k_init, k_pp = plane.split_key(key)
    with obs.span("bwkm.init"):
        part = plane.build_partition(k_init, config, p)
    # Init cost (Alg 2): r·s·(K-means++ over ≤m reps) + routing; we charge the
    # dominant distance term r · s_rounds · m · K the paper bounds in Thm A.3.
    distances = float(p["r"] * p["s"] * k + p["m"] * k)

    with obs.span("bwkm.seed"):
        reps, w = part_mod.representatives(part)
        c = core_bwkm.seed_centroids(config.init, k_pp, reps, w, k)
        distances += float(obs.pull(part.n_blocks, int)) * k  # seeding distance cost

    weighted_errors: list[float] = []
    n_blocks: list[int] = []
    boundary_sizes: list[int] = []
    trace: list[dict] = []
    stop_reason = "max-iters"

    displacement_eps_w = None
    if config.displacement_epsilon is not None:
        displacement_eps_w = bounds.displacement_threshold(
            plane.extent(part), n, config.displacement_epsilon
        )

    it = 0
    for it in range(1, config.max_iters + 1):
        with obs.span("bwkm.round", round=it):
            with obs.span("bwkm.lloyd", round=it):
                res = lloyd_mod.weighted_lloyd(
                    reps, w, c,
                    max_iters=config.lloyd_max_iters, epsilon=config.lloyd_epsilon,
                    prune=config.prune,
                )
                c = res.centroids
                distances += obs.pull(res.distances)
                weighted_errors.append(obs.pull(res.error))
                n_blocks.append(obs.pull(part.n_blocks, int))

            with obs.span("bwkm.boundary", round=it):
                eps = mis.misassignment(part, res.d1, res.d2)
                f_size = obs.pull(jnp.sum(eps > 0), int)
                boundary_sizes.append(f_size)
            if trace_centroids:
                trace.append(
                    {
                        "iteration": it,
                        "distances": distances,
                        "centroids": obs.pull(c, jax.device_get),
                        "n_blocks": obs.pull(part.n_blocks, int),
                        "boundary": f_size,
                    }
                )

            # Per-iteration hook BEFORE the stop checks: the sharded plane
            # checkpoints here, so a restart resumes even from the final round.
            plane.on_iteration(it, c, part, distances)

            # --- stopping criteria (Section 2.4.2) ---
            with obs.span("bwkm.stop", round=it):
                if f_size == 0:
                    stop_reason = "boundary-empty"  # Theorem 3 applies
                    break
                if (
                    config.distance_budget is not None
                    and distances >= config.distance_budget
                ):
                    stop_reason = "distance-budget"
                    break
                if (
                    displacement_eps_w is not None
                    and it > 1
                    and obs.pull(res.max_shift) <= displacement_eps_w
                ):
                    stop_reason = "displacement"
                    break
                if config.gap_bound_threshold is not None:
                    gap = obs.pull(bounds.thm2_gap_bound(part, eps, res.d1))
                    if gap <= config.gap_bound_threshold:
                        stop_reason = "gap-bound"
                        break
                free_rows = p["capacity"] - obs.pull(part.n_blocks, int)
                if free_rows <= 0:
                    stop_reason = "capacity"
                    break

            # --- Step 3: sample |F| blocks ∝ ε with replacement, split, retighten.
            # The split plan is resolved HERE, once, for every plane — the only
            # split_plan call site in the engines.
            with obs.span("bwkm.split", round=it):
                key, k_cut = jax.random.split(key)
                chosen = mis.sample_boundary(k_cut, eps, min(f_size, free_rows))
                plan = part_mod.split_plan(part, chosen)
            with obs.span("bwkm.route", round=it):
                part = plane.route_round(part, plan, it)
            with obs.span("bwkm.reps", round=it):
                reps, w = part_mod.representatives(part)

    with obs.span("bwkm.result"):
        return plane.make_result(
            centroids=c,
            partition=part,
            iterations=it,
            distances=distances,
            weighted_errors=weighted_errors,
            n_blocks=n_blocks,
            boundary_sizes=boundary_sizes,
            stop_reason=stop_reason,
            trace=trace,
            counters=counters,
        )


# --------------------------------------------------- k-means|| (Bahmani 2012)
def resolve_ll_params(
    k: int, oversampling: int | None, rounds: int | None
) -> tuple[int, int, int]:
    """Shared parameter resolution/validation: ``(ℓ, rounds, cap_round)``.

    ``cap_round`` is the static per-round candidate capacity (``≈ 2ℓ``,
    rounded up to a lane multiple): the Bernoulli draw count is random, so
    each round's accepted rows pack into a fixed batch with a validity
    mask; overflow is a tail event (E[draws] ≤ ℓ) and truncates in
    acceptance-priority order.
    """
    from repro.core import kmeans_ll as core_ll

    l = (  # noqa: E741 — ℓ is the paper's symbol
        int(oversampling) if oversampling is not None
        else core_ll.default_oversampling(k)
    )
    r = int(rounds) if rounds is not None else 5
    if l < 1 or r < 1:
        raise ValueError(f"oversampling and rounds must be >= 1, got {l}, {r}")
    cap_round = max(8, -(-2 * l // 8) * 8)
    return l, r, cap_round


def ll_bernoulli(u, w, mind2, l, phi):  # noqa: E741
    """THE k-means|| oversampling draw: accept each point independently with
    probability ``min(1, ℓ·w·d²(x,C)/φ)``. This is the algorithm's single
    Bernoulli-selection call site — every plane's round funnels through it
    (jnp ops accept device arrays and host numpy alike, bit-identically in
    f32), so the engines cannot drift apart in selection semantics.
    """
    u = jnp.asarray(u)
    w = jnp.asarray(w)
    p = jnp.minimum(1.0, l * w * jnp.asarray(mind2) / jnp.maximum(phi, 1e-30))
    return (u < p) & (w > 0)


def plane_kmeans_parallel(sess: Any, *, rounds: int) -> dict:
    """The oversampling loop, once, over an :class:`~repro.engine.plane.LLSession`.

    Round structure (uniform across planes): fold any pending candidate
    batch so ``φ`` is the EXACT current normaliser, draw this round's
    Bernoulli acceptances, pack the accepted rows as the next pending
    batch. The session owns its historical RNG stream and candidate
    storage; ``finish`` runs the weighting pass + weighted K-means++
    reduction (folding the final pending batch first where the plane's
    pass accounting historically did so).
    """
    sess.seed()
    normalisers: list[float] = []
    for rnd in range(1, rounds + 1):
        u, w, mind2, phi = sess.begin_round(rnd)
        normalisers.append(obs.pull(phi))
        accept = ll_bernoulli(u, w, mind2, sess.l, phi)
        sess.select(rnd, u, accept)
    return sess.finish(tuple(normalisers))


# ------------------------------------------- full-data pruned Lloyd (ADR 0004)
def plane_lloyd(
    sess: Any,
    c: jax.Array,
    *,
    max_iters: int = 50,
    epsilon: float = 1e-4,
) -> tuple[jax.Array, float, int, float, list[float]]:
    """Full-dataset Lloyd with drift-bound pruning, once, over a
    :class:`~repro.engine.plane.LloydSession`.

    Returns ``(centroids, error, iters, distances, active_fractions)``.
    The error is exact via the ``core.lloyd.stats_error`` algebraic
    identity; the stop rule is the Eq.-2 relative error change. Per-row
    bound state never crosses the session boundary.
    """
    sums, counts, err, w2sum, n_dist = sess.seed(c)
    distances = float(n_dist)
    prev_err = jnp.inf
    active_fractions: list[float] = []
    it = 0
    while it < max_iters and abs(float(prev_err) - float(err)) > (
        epsilon * max(float(err), 1e-30)
    ):
        c_new = lloyd_mod._next_centroids(sums, counts, c)
        drift = jnp.linalg.norm(c_new - c, axis=-1)
        sums, counts, n_dist = sess.step(c_new, drift)
        c = c_new
        prev_err, err = err, lloyd_mod.stats_error(w2sum, c_new, sums, counts)
        distances += float(n_dist)
        active_fractions.append(float(n_dist) / sess.denom)
        it += 1

    return c, float(err), it, distances, active_fractions
