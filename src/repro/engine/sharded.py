"""Sharded data plane: the mesh implementation of ``DataPlane``.

Layout (docs/DESIGN.md §3, fault tolerance §5):
  * points      ``x [n, d]``   — rows over ``(pod, data)``, features
                                  optionally over ``model`` (distances
                                  decompose additively over d → one psum).
  * block stats ``[M, ·]``     — partial per shard, ``psum`` over the data
                                  axes; exact, since sums/counts/min/max are
                                  associative-commutative.
  * representatives / centroids — tiny (M ≤ thousands): computed on the
                                  mesh's first device (:func:`_to_lead`)
                                  and broadcast to every shard when a data
                                  pass needs them (:func:`_replicate`). A
                                  Mosaic kernel cannot be partitioned over
                                  replicated operands, so the driver's
                                  kernel calls on this state (weighted
                                  Lloyd, seeding) must see one device.

Points never leave their shard; per-iteration traffic is O(M·d + M·K)
statistics. The outer loop is :func:`repro.engine.driver.fit_plane` — this
module only supplies the mesh dialect of the data passes.

Fault tolerance: the driver state (centroids, block boxes, iteration,
distance budget) is checkpointed via ``train.checkpoint`` every round;
``block_id`` is *not* checkpointed — it is recomputed from the block boxes
in O(n·log M) on restart (cheaper than storing n int32s, and correct on any
mesh shape → elastic restart).
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import bwkm as core_bwkm
from repro.core import init_partition, kmeanspp
from repro.core import kmeans_ll as core_ll
from repro.core import lloyd as lloyd_mod
from repro.core import partition as part_mod
from repro.core.partition import Partition, SplitPlan
from repro.distributed import sharding as sh
from repro.engine.plane import global_extent
from repro.health import RunHealth
from repro.kernels import ops

__all__ = [
    "DistLloydResult",
    "ShardLossError",
    "ShardedLLSession",
    "ShardedLloydSession",
    "ShardedPlane",
    "dist_assign_step",
    "dist_recompute_stats",
    "dist_route_points",
    "n_data_shards",
    "shard_points",
]

_BIG = 3.0e38


class ShardLossError(RuntimeError):
    """Shard-stat losses in one round exceeded ``max_shard_loss_frac`` —
    drop-and-reweight would no longer be a defensible approximation, so the
    round aborts instead of silently fitting a sliver of the data."""


def _data_axes():
    return sh.batch_axes()


def n_data_shards() -> int:
    """Number of data-parallel shards on the current mesh (1 when unmeshed)."""
    return math.prod(sh.axis_size(a) for a in sh.batch_axes()) or 1


def _to_lead(*arrays):
    """Move replicated shard_map outputs to the mesh's first device."""
    mesh = sh.current_mesh()
    if mesh is None:
        return arrays
    return jax.device_put(arrays, mesh.devices.flat[0])


def _replicate(*arrays):
    """Broadcast small lead-device state to every device of the mesh."""
    mesh = sh.current_mesh()
    if mesh is None:
        return arrays
    return jax.device_put(arrays, NamedSharding(mesh, P()))


def shard_points(x: jax.Array) -> jax.Array:
    """Place the dataset: rows over (pod, data), features over model."""
    mesh = sh.current_mesh()
    if mesh is None:
        return x
    return jax.device_put(
        x, NamedSharding(mesh, sh.logical_to_spec(("batch", "tensor"), x.shape))
    )


# ------------------------------------------------------------- shard_map ops
def _stats_body(x_loc, bid_loc, alive_loc, *, m):
    """Local ``partition.block_stats`` + cross-shard combine. The psum/pmin/
    pmax quartet is exactly ``combine_block_stats`` folded over the data
    axes — the same associative statistics the streaming plane folds over
    chunks (docs/DESIGN.md §6.4).

    Fault tolerance (DESIGN.md §5): rows with ``alive == 0`` (a shard whose
    stats are declared lost for this round) are routed to the scratch
    segment, and a shard whose local stats come back non-finite (a NaN row
    poisoned its fold) zeroes its whole contribution before the psum — both
    read as "that shard's BlockStats are missing", and the driver reweights
    the surviving mass. The replicated ``ok_shards`` count tells the driver
    how many shards actually contributed finite stats.
    """
    st = part_mod.block_stats(x_loc, bid_loc, m, valid=alive_loc > 0)
    ok = jnp.all(jnp.isfinite(st.psum)) & jnp.all(jnp.isfinite(st.count))
    psum_l = jnp.where(ok, st.psum, 0.0)
    count_l = jnp.where(ok, st.count, 0.0)
    lo_l = jnp.where(ok, st.lo, _BIG)
    hi_l = jnp.where(ok, st.hi, -_BIG)
    axes = _data_axes()
    psum_ = jax.lax.psum(psum_l, axes)
    count = jax.lax.psum(count_l, axes)
    lo = jax.lax.pmin(lo_l, axes)
    hi = jax.lax.pmax(hi_l, axes)
    ok_shards = jax.lax.psum(ok.astype(jnp.float32), axes)
    empty = count <= 0
    lo = jnp.where(empty[:, None], _BIG, lo)
    hi = jnp.where(empty[:, None], -_BIG, hi)
    return psum_, count, lo, hi, ok_shards


def _recompute_stats_ok(
    part: Partition,
    x: jax.Array,
    bid: jax.Array,
    alive_rows: jax.Array | None = None,
) -> tuple[Partition, int]:
    """:func:`dist_recompute_stats` plus the number of shards whose local
    stats survived finite (the drop-and-reweight driver needs it; plain
    callers don't)."""
    mesh = sh.current_mesh()
    m = part.capacity
    n = x.shape[0]
    if mesh is None:
        valid = (alive_rows > 0) if alive_rows is not None else None
        st = part_mod.block_stats(x, bid, m, valid=valid)
        ok = bool(jnp.all(jnp.isfinite(st.psum)) & jnp.all(jnp.isfinite(st.count)))
        if not ok:
            st = st._replace(psum=jnp.zeros_like(st.psum),
                             count=jnp.zeros_like(st.count),
                             lo=jnp.full_like(st.lo, _BIG),
                             hi=jnp.full_like(st.hi, -_BIG))
        return (
            part._replace(psum=st.psum, count=st.count, lo=st.lo, hi=st.hi,
                          block_id=bid),
            int(ok),
        )
    d = x.shape[1]
    row_spec = sh.logical_to_spec(("batch", "tensor"), (n, d))
    bid_spec = sh.logical_to_spec(("batch",), (n,))
    if alive_rows is None:
        alive_rows = jnp.ones(n, jnp.float32)
    fn = jax.shard_map(
        partial(_stats_body, m=m),
        mesh=mesh,
        in_specs=(row_spec, bid_spec, bid_spec),
        out_specs=(
            P(None, row_spec[1]), P(None), P(None, row_spec[1]),
            P(None, row_spec[1]), P(),
        ),
        check_vma=False,
    )
    psum_, count, lo, hi, ok_shards = _to_lead(
        *fn(x, bid, jnp.asarray(alive_rows, jnp.float32))
    )
    part = part._replace(psum=psum_, count=count, lo=lo, hi=hi, block_id=bid)
    return part, int(ok_shards)


def dist_recompute_stats(
    part: Partition,
    x: jax.Array,
    bid: jax.Array,
    alive_rows: jax.Array | None = None,
) -> Partition:
    """psum-combined (Σx, count, lo, hi) over sharded points. ``alive_rows``
    (f32 0/1 per row, sharded like ``bid``) drops rows from the fold — the
    row-level encoding of "this shard's stats are lost this round"."""
    part, _ = _recompute_stats_ok(part, x, bid, alive_rows)
    return part


def _route_body(x_loc, bid_loc, fits, axis, mid, right_row):
    plan = part_mod.SplitPlan(fits, axis, mid, right_row, jnp.sum(fits))
    return part_mod.route_split(x_loc, bid_loc, plan)


def dist_route_points(
    x: jax.Array, bid: jax.Array, fits, axis, mid, right_row
) -> jax.Array:
    """Repair local block ids after a split round — ``partition.route_split``
    applied per shard (a local plan lookup and compare, no communication).

    Feature sharding caveat: the split coordinate lives on one model shard,
    so the rows are gathered over ``model`` before routing (the plan is
    replicated).
    """
    mesh = sh.current_mesh()
    if mesh is None:
        return _route_body(x, bid, fits, axis, mid, right_row)
    n, d = x.shape
    row_spec = sh.logical_to_spec(("batch", None), (n, d))  # gather features
    bid_spec = sh.logical_to_spec(("batch",), (n,))
    fn = jax.shard_map(
        _route_body,
        mesh=mesh,
        in_specs=(row_spec, bid_spec, P(None), P(None), P(None), P(None)),
        out_specs=bid_spec,
        check_vma=False,
    )
    return fn(x, bid, *_replicate(fits, axis, mid, right_row))


def _assign_body(x_loc, c, w_loc, *, impl):
    """One full-dataset assignment + partial cluster stats (for the
    distributed Lloyd baseline / final refinement). The per-shard body is
    the same fused ``kernels.ops.assign_update`` pass the in-core Lloyd and
    the streaming chunk fold run; the psum quartet is the cross-shard
    combine."""
    fu = ops.assign_update(x_loc, w_loc, c, impl=impl)
    axes = _data_axes()
    return (
        jax.lax.psum(fu.sums, axes),
        jax.lax.psum(fu.counts, axes),
        jax.lax.psum(fu.err, axes),
        fu.assign,
    )


def dist_assign_step(x: jax.Array, c: jax.Array, w: jax.Array | None = None):
    """Distributed Lloyd iteration over the full dataset (the scalable
    baseline the paper compares against): returns (new_c, error)."""
    mesh = sh.current_mesh()
    n, d = x.shape
    impl = ops.resolve_impl(None)
    w = jnp.ones(n, jnp.float32) if w is None else w
    if mesh is None:
        sums, counts, err, _ = _assign_body(x, c, w, impl=impl)
    else:
        row_spec = sh.logical_to_spec(("batch", None), (n, d))
        fn = jax.shard_map(
            partial(_assign_body, impl=impl),
            mesh=mesh,
            in_specs=(row_spec, P(None, None), sh.logical_to_spec(("batch",), (n,))),
            out_specs=(P(None, None), P(None), P(), sh.logical_to_spec(("batch",), (n,))),
            check_vma=False,
        )
        sums, counts, err, _ = _to_lead(*fn(x, *_replicate(c), w))
        c = _to_lead(c)[0]
    new_c = jnp.where(
        (counts > 0)[:, None], sums / jnp.maximum(counts, 1e-30)[:, None], c
    )
    return new_c, err


# ---------------------------------------- pruned distributed Lloyd (ADR 0004)
def _dense_full_body(x_loc, c, w_loc, *, impl):
    """Seeding pass for the sharded Lloyd session: the fused dense pass plus
    the per-shard bound state (sqrt of the exact top-2) and the Σ w‖x‖² term
    of the algebraic error identity. Stats/err/w2/n_dist psum; per-row state
    stays shard-local."""
    fu = ops.assign_update(x_loc, w_loc, c, impl=impl)
    axes = _data_axes()
    w2 = jnp.sum(w_loc * jnp.sum(x_loc.astype(jnp.float32) ** 2, axis=-1))
    return (
        jax.lax.psum(fu.sums, axes),
        jax.lax.psum(fu.counts, axes),
        jax.lax.psum(fu.err, axes),
        jax.lax.psum(fu.n_dist, axes),
        jax.lax.psum(w2, axes),
        fu.assign,
        jnp.sqrt(jnp.maximum(fu.d1, 0.0)),
        jnp.sqrt(jnp.maximum(fu.d2, 0.0)),
    )


def _pruned_body(x_loc, c_new, w_loc, a_loc, ub_loc, lb_loc, drift, *, impl):
    """One pruned Lloyd iteration per shard: the drift vector arrives
    replicated (it derives from the psum'd statistics, so every shard
    computes the identical centroids and drift), bounds update locally,
    only unsettled rows rescan, and the composed-assignment statistics
    psum back — points never leave their shard, per-iteration traffic stays
    O(K·d)."""
    ub, lb = lloyd_mod.drift_bound_update(ub_loc, lb_loc, a_loc, drift)
    active = ub >= lb
    fu = ops.assign_update_pruned(x_loc, w_loc, c_new, a_loc, active, impl=impl)
    ub = jnp.where(active, jnp.sqrt(jnp.maximum(fu.d1, 0.0)), ub)
    lb = jnp.where(active, jnp.sqrt(jnp.maximum(fu.d2, 0.0)), lb)
    axes = _data_axes()
    return (
        jax.lax.psum(fu.sums, axes),
        jax.lax.psum(fu.counts, axes),
        jax.lax.psum(fu.n_dist, axes),
        fu.assign,
        ub,
        lb,
    )


class DistLloydResult(NamedTuple):
    centroids: jax.Array  # [K, d] replicated
    error: float  # exact weighted error at the final centroids
    iters: int
    distances: float  # kernel-reported, summed over shards


class ShardedLloydSession:
    """Full-dataset Lloyd over mesh-sharded points, bound state sharded.

    The mesh analogue of ``core.lloyd.weighted_lloyd``'s pruned loop: the
    per-row (assignment, upper, lower) bound state lives sharded alongside
    the points across iterations, the drift vector is replicated for free
    (centroids are computed from psum'd statistics), and each iteration
    psums the composed-assignment statistics plus the kernel-reported
    distance count. ``prune=False`` degrades to iterated dense assignment.
    """

    def __init__(self, x, w, *, k, impl, prune: bool):
        self.x = x
        self.k = k
        self.prune = prune
        self.denom = max(k * int(x.shape[0]), 1)
        n, d = x.shape
        self.w = jnp.ones(n, jnp.float32) if w is None else w.astype(jnp.float32)
        mesh = sh.current_mesh()
        row_spec = sh.logical_to_spec(("batch", None), (n, d))
        vec_spec = sh.logical_to_spec(("batch",), (n,))
        if mesh is None:
            self._seed = partial(_dense_full_body, impl=impl)
            self._step = partial(_pruned_body, impl=impl)
            self._dense_step = partial(_assign_body, impl=impl)
        else:
            self._seed = jax.shard_map(
                partial(_dense_full_body, impl=impl),
                mesh=mesh,
                in_specs=(row_spec, P(None, None), vec_spec),
                out_specs=(P(None, None), P(None), P(), P(), P(),
                           vec_spec, vec_spec, vec_spec),
                check_vma=False,
            )
            self._step = jax.shard_map(
                partial(_pruned_body, impl=impl),
                mesh=mesh,
                in_specs=(row_spec, P(None, None), vec_spec, vec_spec, vec_spec,
                          vec_spec, P(None)),
                out_specs=(P(None, None), P(None), P(), vec_spec, vec_spec,
                           vec_spec),
                check_vma=False,
            )
            self._dense_step = jax.shard_map(
                partial(_assign_body, impl=impl),
                mesh=mesh,
                in_specs=(row_spec, P(None, None), vec_spec),
                out_specs=(P(None, None), P(None), P(), vec_spec),
                check_vma=False,
            )

    def seed(self, c):
        sums, counts, err, n_dist, w2sum, self.assign, self.ub, self.lb = (
            self._seed(self.x, *_replicate(c), self.w)
        )
        sums, counts, err, w2sum = _to_lead(sums, counts, err, w2sum)
        return sums, counts, err, w2sum, float(n_dist)

    def step(self, c_new, drift):
        c_new, drift = _replicate(c_new, drift)
        if self.prune:
            sums, counts, n_dist, self.assign, self.ub, self.lb = self._step(
                self.x, c_new, self.w, self.assign, self.ub, self.lb, drift
            )
            return *_to_lead(sums, counts), float(n_dist)
        sums, counts, _, self.assign = self._dense_step(self.x, c_new, self.w)
        n_dist = jnp.sum((self.w > 0).astype(jnp.float32)) * self.k
        return *_to_lead(sums, counts), float(n_dist)


# ------------------------------------------------------- k-means|| session
def _ll_fold_body(x_loc, w_loc, m_loc, cand, cvalid, *, impl):
    """Per-shard k-means|| fold: the same ``min_sqdist_update`` pass the
    in-core session runs, with cost and distance count psum'd over the data
    axes. min-d² stays shard-local."""
    out = ops.min_sqdist_update(x_loc, w_loc, cand, cvalid, m_loc, impl=impl)
    axes = sh.batch_axes()
    return (
        out.mind2,
        jax.lax.psum(out.cost, axes),
        jax.lax.psum(out.n_dist, axes),
    )


def _ll_weight_body(x_loc, w_loc, cand, *, impl):
    """Candidate-weighting pass: per-shard nearest-candidate statistics,
    psum'd counts — the weights the final K-means++ reduction consumes."""
    au = ops.assign_update(x_loc, w_loc, cand, impl=impl)
    return jax.lax.psum(au.counts, sh.batch_axes())


class ShardedLLSession:
    """Mesh k-means|| session (ADR 0005; DESIGN §12).

    The per-point min-d² state lives sharded alongside the points across
    rounds; each round's fold runs the ``min_sqdist_update`` kernel per
    shard inside a ``shard_map`` with the cost ``φ`` psum'd over the data
    axes, and the round's candidate batch — a top-k over the global
    Bernoulli draws — is gathered to every shard (O(ℓ·d) bytes/round;
    points never leave their shard). Draws and the final weighted K-means++
    reduction run on replicated values, so every shard computes identical
    candidates and seeds by construction. Keys match the in-core session
    (``split(key, rounds + 2)``), so an unmeshed run is bit-identical.
    """

    def __init__(self, key, x, w, *, k, l, rounds, cap_round, impl, mesh):  # noqa: E741
        self.x = x
        self.w = w.astype(jnp.float32)
        self.k, self.l, self.rounds, self.cap_round = k, l, rounds, cap_round
        self.keys = jax.random.split(key, rounds + 2)
        self.n, self.d = x.shape
        cap_total = 1 + rounds * cap_round
        self.cand = jnp.full((cap_total, self.d), core_ll._FAR, x.dtype)
        self.cvalid = jnp.zeros((cap_total,), jnp.float32).at[0].set(1.0)
        self.pending = None
        row_spec = sh.logical_to_spec(("batch", None), (self.n, self.d))
        vec_spec = sh.logical_to_spec(("batch",), (self.n,))
        self._fold = jax.shard_map(
            partial(_ll_fold_body, impl=impl),
            mesh=mesh,
            in_specs=(row_spec, vec_spec, vec_spec, P(None, None), P(None)),
            out_specs=(vec_spec, P(), P()),
            check_vma=False,
        )
        self._weigh = jax.shard_map(
            partial(_ll_weight_body, impl=impl),
            mesh=mesh,
            in_specs=(row_spec, vec_spec, P(None, None)),
            out_specs=P(None),
            check_vma=False,
        )

    def seed(self) -> None:
        logw = jnp.where(
            self.w > 0, jnp.log(jnp.maximum(self.w, 1e-30)), -jnp.inf
        )
        self.cand = self.cand.at[0].set(
            self.x[jax.random.categorical(self.keys[0], logw)]
        )
        mind2 = jnp.full((self.n,), _BIG, jnp.float32)
        self.mind2, self.phi, _ = self._fold(
            self.x, self.w, mind2, self.cand[:1], self.cvalid[:1]
        )

    def begin_round(self, rnd: int):
        if self.pending is not None:
            newc, newv = self.pending
            self.mind2, self.phi, _ = self._fold(
                self.x, self.w, self.mind2, newc, newv
            )
            self.pending = None
        u = jax.random.uniform(self.keys[rnd], (self.n,))
        return u, self.w, self.mind2, self.phi

    def select(self, rnd: int, u, accept) -> None:
        # replicated Bernoulli draw + global top-k: every shard computes the
        # identical candidate batch, gathered to all shards by x[idx]
        neg, idx = jax.lax.top_k(
            -jnp.where(accept, u, jnp.inf), self.cap_round
        )
        newv = jnp.isfinite(neg).astype(jnp.float32)
        newc = jnp.where(newv[:, None] > 0, self.x[idx], core_ll._FAR)
        start = 1 + (rnd - 1) * self.cap_round
        self.cand = self.cand.at[start : start + self.cap_round].set(newc)
        self.cvalid = self.cvalid.at[start : start + self.cap_round].set(newv)
        self.pending = (newc, newv)

    def finish(self, normalisers: tuple) -> dict:
        if self.pending is not None:
            newc, newv = self.pending
            self.mind2, self.phi, _ = self._fold(
                self.x, self.w, self.mind2, newc, newv
            )
            self.pending = None
        counts = self._weigh(self.x, self.w, self.cand)
        c = kmeanspp.weighted_kmeanspp(self.keys[-1], self.cand, counts, self.k)
        return {
            "centroids": c,
            "n_candidates": jnp.sum(self.cvalid),
            "distances": 0.0,  # mesh path reports no host-side count
            "passes": self.rounds + 2,
            "normalisers": normalisers,
        }


# ------------------------------------------------------------------ plane
def _route_into_boxes(x: jax.Array, part: Partition) -> jax.Array:
    """The shared ``core.partition.route_into_boxes`` clipped-L∞ rule, run
    sharded: each shard routes its local rows against the replicated boxes."""
    mesh = sh.current_mesh()
    # live rows are the dense prefix [0, n_blocks) (as in the streaming
    # routing pass): route against it, not the 64·m capacity
    m_live = min(part.capacity, max(128, -(-int(part.n_blocks) // 128) * 128))
    boxes = (part.lo[:m_live], part.hi[:m_live], part.active[:m_live])
    if mesh is None:
        return part_mod.route_into_boxes(x, *boxes)
    n, d = x.shape
    row_spec = sh.logical_to_spec(("batch", None), (n, d))
    return jax.shard_map(
        part_mod.route_into_boxes, mesh=mesh,
        in_specs=(row_spec, P(None, None), P(None, None), P(None)),
        out_specs=sh.logical_to_spec(("batch",), (n,)), check_vma=False,
    )(x, *_replicate(*boxes))


def _alive_mask_for(
    n: int, n_shards: int, lost: Sequence[int]
) -> jax.Array | None:
    """f32 row mask zeroing the contiguous row blocks of the lost shards
    (``shard_points`` places rows contiguously over the data axes)."""
    if not lost:
        return None
    # Same geometry as repro.testing.faults.shard_loss_rows_mask, inlined so
    # the production driver does not import the test harness.
    if n % n_shards != 0:
        raise ValueError(f"n={n} not divisible by n_shards={n_shards}")
    import numpy as np

    mask = np.ones(n, np.float32)
    per = n // n_shards
    for s in lost:
        if not 0 <= int(s) < n_shards:
            raise ValueError(f"shard {s} out of range [0, {n_shards})")
        mask[int(s) * per : (int(s) + 1) * per] = 0.0
    return jnp.asarray(mask)


def _apply_shard_loss(
    part: Partition,
    *,
    n: int,
    n_ok: int,
    n_shards: int,
    n_injected: int,
    health: RunHealth,
    max_shard_loss_frac: float,
    round_index: int,
) -> Partition:
    """Round-level drop-and-reweight (DESIGN.md §5): if the recomputed stats
    are missing mass (injected shard loss, or shards whose local stats went
    non-finite), scale ``psum``/``count`` of the survivors by ``n / Σcount``
    so total mass is restored. The uniform scale leaves every representative
    mean ``psum/count`` and all weight *ratios* unchanged — weighted Lloyd's
    fixed points on the surviving blocks are invariant — while keeping the
    reported weighted errors on the same scale as a lossless run. Aborts
    with :class:`ShardLossError` when the lost fraction exceeds
    ``max_shard_loss_frac``.
    """
    total = float(jnp.sum(part.count))
    lost_frac = max(0.0, 1.0 - total / float(n))
    n_lost = n_injected + max(0, n_shards - n_ok - n_injected)
    if n_lost == 0 and lost_frac <= 1e-6:
        return part
    if lost_frac > max_shard_loss_frac:
        raise ShardLossError(
            f"round {round_index}: lost {lost_frac:.1%} of the data mass "
            f"({n_lost} of {n_shards} shards) — exceeds "
            f"max_shard_loss_frac={max_shard_loss_frac:.1%}; aborting rather "
            "than fitting the remnant"
        )
    scale = float(n) / max(total, 1e-30)
    part = part._replace(psum=part.psum * scale, count=part.count * scale)
    health.lost_shards += n_lost
    health.degraded_rounds += 1
    health.lost_mass_frac = max(health.lost_mass_frac, lost_frac)
    return part


class ShardedPlane:
    """Mesh-sharded execution plane (``engine="distributed"``).

    ``x`` should be placed with :func:`shard_points` (the ``repro.BWKM``
    facade does it). Representatives/centroids are computed replicated from
    psum'd statistics, so the trajectory is the single-host one up to psum
    summation order.

    Fault injection: ``shard_faults`` maps a stats round (0 = the initial
    routing round, ``i`` = the split round of outer iteration ``i``) to data
    shard indices whose ``BlockStats`` are lost that round. Survivors are
    mass-reweighted (``Σw`` correction, DESIGN.md §5) and the round
    continues; :class:`ShardLossError` aborts the fit when a round loses
    more than ``max_shard_loss_frac`` of the data mass. The result's
    ``health`` ledger records shards lost and degraded rounds.
    """

    name = "distributed"

    def __init__(
        self,
        x: jax.Array,
        *,
        checkpoint_dir: str | None = None,
        shard_faults: "dict[int, Sequence[int]] | None" = None,
        max_shard_loss_frac: float = 0.5,
    ):
        self.x = x
        self.checkpoint_dir = checkpoint_dir
        self.faults = {int(r): tuple(s) for r, s in (shard_faults or {}).items()}
        self.max_shard_loss_frac = max_shard_loss_frac
        self.run_health = RunHealth()
        self.n_shards = n_data_shards()
        self.bid: jax.Array | None = None

    @property
    def n_points(self) -> int:
        return int(self.x.shape[0])

    @property
    def dim(self) -> int:
        return int(self.x.shape[1])

    def split_key(self, key):
        # Historical 4-way split: the extra key draws the init sample.
        key, k_init, k_pp, self._k_s = jax.random.split(key, 4)
        return key, k_init, k_pp

    def _stats_round(self, part_in, bid_in, round_index):
        lost = self.faults.get(round_index, ())
        alive = _alive_mask_for(self.n_points, self.n_shards, lost)
        part_out, n_ok = _recompute_stats_ok(part_in, self.x, bid_in, alive)
        return _apply_shard_loss(
            part_out, n=self.n_points, n_ok=n_ok, n_shards=self.n_shards,
            n_injected=len(lost), health=self.run_health,
            max_shard_loss_frac=self.max_shard_loss_frac,
            round_index=round_index,
        )

    def build_partition(self, k_init, config, p) -> Partition:
        # Algorithm 2 on a host-gathered SAMPLE (the paper's init only ever
        # touches O(r·s) points; gathering the sample is O(s·d), not O(n·d)),
        # then broadcast boxes + distributed re-route.
        n = self.n_points
        k = config.k
        s_init = min(n, max(p["s"] * p["r"] * 4, 4 * p["m"]))
        idx = jax.random.choice(self._k_s, n, shape=(s_init,), replace=False)
        x_sample = jax.device_get(self.x[jnp.sort(idx)])  # gather once, small
        sample_part = init_partition.build_initial_partition(
            k_init, jnp.asarray(x_sample), k,
            m=p["m"], m_prime=p["m_prime"], s=min(p["s"], s_init), r=p["r"],
            capacity=p["capacity"],
        )
        # route the full dataset through the sample-built boxes: nearest box
        # by containment (boxes partition the sample's bounding box; clip)
        self.bid = _route_into_boxes(self.x, sample_part)
        return self._stats_round(sample_part, self.bid, 0)

    def extent(self, part: Partition) -> float:
        # Box-derived: the displacement threshold needs only the global
        # bounding box, already accumulated in the block stats.
        return global_extent(part)

    def route_round(self, part: Partition, plan: SplitPlan, round_index: int) -> Partition:
        new_bid = dist_route_points(
            self.x, self.bid, plan.fits, plan.axis, plan.mid, plan.right_row
        )
        part = part_mod.apply_split_plan(part, plan)
        self.bid = new_bid
        return self._stats_round(part, new_bid, round_index)

    def on_iteration(self, it, c, part, distances) -> None:
        if self.checkpoint_dir is None:
            return
        from repro.train import checkpoint as ckpt

        ckpt.save(
            self.checkpoint_dir, it,
            {"centroids": c, "boxes": {"lo": part.lo, "hi": part.hi,
                                       "active": part.active,
                                       "n_blocks": part.n_blocks}},
            extra={"distances": distances, "iteration": it,
                   "health": self.run_health.as_dict()},
        )

    def make_result(self, **fields) -> core_bwkm.BWKMResult:
        return core_bwkm.BWKMResult(health=self.run_health, **fields)
