"""Dataset partitions induced by spatial partitions (paper Definition 1).

A partition lives in *fixed-capacity* arrays so every BWKM step is a static
XLA program: ``max_blocks`` rows, a per-row active mask, and a per-point
``block_id``. Splits consume preallocated rows (parent row becomes the left
child, a fresh row the right child) and point routing is repaired in one
compiled pass (one packed plan lookup per row, a compare against the split
plane) — no tree traversal.

Blocks are recorded by their *tight bounding boxes* (the paper recomputes the
smallest bounding box of every subset when updating the partition in Step 3 of
Algorithm 5, because the misassignment criterion is sharper on tight boxes).
Splitting a tight box at the midpoint of its longest side is a valid
refinement of the spatial partition: member points always lie inside the
tight box.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs

__all__ = [
    "Partition",
    "BlockStats",
    "SplitPlan",
    "create_partition",
    "block_stats",
    "decay_stats",
    "empty_block_stats",
    "combine_block_stats",
    "recompute_stats",
    "route_into_boxes",
    "split_plan",
    "route_split",
    "apply_split_plan",
    "split_blocks",
    "split_blocks_virtual",
    "representatives",
    "diagonals",
]

_BIG = 3.0e38  # sentinel for min/max reductions (f32-safe, < inf to dodge nan arith)


class Partition(NamedTuple):
    """Fixed-capacity dataset partition state (a JAX pytree).

    Attributes:
      lo, hi:    ``[M, d]`` tight bounding box per block (lo > hi for empty).
      psum:      ``[M, d]`` sum of member points.
      count:     ``[M]`` number of member points (f32; these are the weights).
      active:    ``[M]`` bool, whether the row is a live block.
      block_id:  ``[n]`` int32, block membership of every point.
      n_blocks:  scalar int32, number of live rows (rows ``[0, n_blocks)``).
    """

    lo: jax.Array
    hi: jax.Array
    psum: jax.Array
    count: jax.Array
    active: jax.Array
    block_id: jax.Array
    n_blocks: jax.Array

    @property
    def capacity(self) -> int:
        return self.lo.shape[0]

    @property
    def dim(self) -> int:
        return self.lo.shape[1]


def representatives(part: Partition) -> tuple[jax.Array, jax.Array]:
    """Per-block centers of mass and weights ``(reps [M,d], w [M])``.

    Empty/inactive rows get weight 0 and a representative parked at the
    origin; every consumer must mask by ``w > 0``.
    """
    safe = jnp.maximum(part.count, 1.0)
    occupied = (part.count > 0) & part.active
    reps = jnp.where(occupied[:, None], part.psum / safe[:, None], 0.0)
    w = jnp.where(occupied, part.count, 0.0)
    return reps, w


def diagonals(part: Partition) -> jax.Array:
    """Length of the tight bounding-box diagonal per block, ``[M]`` (0 if empty)."""
    ext = jnp.maximum(part.hi - part.lo, 0.0)
    occupied = (part.count > 0) & part.active
    return jnp.where(occupied, jnp.linalg.norm(ext, axis=-1), 0.0)


class BlockStats(NamedTuple):
    """Per-block sufficient statistics ``(Σx, |B|, min x, max x)`` — everything
    BWKM needs about a block (representative = psum/count, diagonal from
    lo/hi). Sums/counts add and min/max combine associatively, so stats are
    accumulated chunk-by-chunk (streaming), shard-by-shard (mesh psum), or in
    one pass (in-core) with identical results up to summation order."""

    psum: jax.Array  # [M, d]
    count: jax.Array  # [M]
    lo: jax.Array  # [M, d] (lo > hi marks an empty row)
    hi: jax.Array  # [M, d]


def block_stats(
    x: jax.Array, bid: jax.Array, m: int, valid: jax.Array | None = None
) -> BlockStats:
    """``O(n·d)`` segment reductions of points into ``m`` block rows — the cost
    the paper assigns to the partition-update step (Section 2.3.1).

    ``valid`` masks padding rows (streaming chunks are padded to a static
    shape); masked points land in a scratch segment that is dropped.
    """
    if valid is not None:
        bid = jnp.where(valid, bid, m)  # scratch segment m, sliced away below
    seg = m + 1 if valid is not None else m
    ones = jnp.ones(x.shape[0], jnp.float32)
    psum = jax.ops.segment_sum(x, bid, num_segments=seg)[:m]
    count = jax.ops.segment_sum(ones, bid, num_segments=seg)[:m]
    lo = jax.ops.segment_min(x, bid, num_segments=seg)[:m]
    hi = jax.ops.segment_max(x, bid, num_segments=seg)[:m]
    empty = count <= 0
    lo = jnp.where(empty[:, None], _BIG, lo)
    hi = jnp.where(empty[:, None], -_BIG, hi)
    return BlockStats(psum, count, lo, hi)


def empty_block_stats(m: int, d: int) -> BlockStats:
    """The identity element of ``combine_block_stats``."""
    return BlockStats(
        psum=jnp.zeros((m, d), jnp.float32),
        count=jnp.zeros((m,), jnp.float32),
        lo=jnp.full((m, d), _BIG, jnp.float32),
        hi=jnp.full((m, d), -_BIG, jnp.float32),
    )


def combine_block_stats(a: BlockStats, b: BlockStats) -> BlockStats:
    """Merge two partial statistics (associative + commutative; the empty-row
    sentinels ±_BIG are absorbing for min/max, so no masking is needed)."""
    return BlockStats(
        psum=a.psum + b.psum,
        count=a.count + b.count,
        lo=jnp.minimum(a.lo, b.lo),
        hi=jnp.maximum(a.hi, b.hi),
    )


def decay_stats(part: Partition, gamma: float | jax.Array) -> Partition:
    """Exponential forgetting of block mass (the online service's merge rule,
    DESIGN.md §13): sums and counts scale by ``gamma`` so old stream batches
    fade at a configurable half-life, while the boxes stay — they are
    geometric routing state, and shrinking them without a data pass would
    break the tight-box containment invariant for the mass that remains."""
    return part._replace(psum=part.psum * gamma, count=part.count * gamma)


#: f32 elements of one routing tile ``[rows, M]``: bounds the distance
#: matrix of :func:`route_into_boxes` whatever the number of points
_ROUTE_TILE_ELEMS = 1 << 22


def route_into_boxes(
    x: jax.Array, lo: jax.Array, hi: jax.Array, active: jax.Array
) -> jax.Array:
    """Assign every point to the box with the smallest *clipped L∞* distance:
    containment for points inside some box, nearest box for out-of-sample
    tails. ``O(n·M)`` elementwise — the one routing rule shared by the
    streaming pass (`engine.streaming._box_route_stats`), the sharded plane
    (`engine.sharded._route_into_boxes`), and the online service's
    mini-batch merge (`service.session`). Rows are routed in tiles of
    ``_ROUTE_TILE_ELEMS // M`` so the ``[n, M]`` matrix never exists whole."""
    lo_ = jnp.where(active[:, None], lo, _BIG)
    hi_ = jnp.where(active[:, None], hi, -_BIG)

    def nearest(xb):
        below = jnp.maximum(lo_[None] - xb[:, None, :], 0.0)
        above = jnp.maximum(xb[:, None, :] - hi_[None], 0.0)
        dist = jnp.max(below + above, axis=-1)  # [rows, M] clipped L∞
        return jnp.argmin(dist, axis=-1).astype(jnp.int32)

    rows = max(8, _ROUTE_TILE_ELEMS // max(lo.shape[0], 1))
    if x.shape[0] <= rows:
        return nearest(x)
    return jax.lax.map(lambda xr: nearest(xr[None])[0], x, batch_size=rows)


def recompute_stats(part: Partition, x: jax.Array) -> Partition:
    """Recompute (psum, count, lo, hi) for all rows from point memberships:
    one pass over ``x``, counted as such (``repro.obs``). Call it eagerly."""
    st = block_stats(x, part.block_id, part.capacity)
    obs.data_pass()
    return part._replace(psum=st.psum, count=st.count, lo=st.lo, hi=st.hi)


def create_partition(x: jax.Array, capacity: int) -> Partition:
    """The trivial one-block partition: the smallest bounding box of ``D``."""
    n, d = x.shape
    part = Partition(
        lo=jnp.full((capacity, d), _BIG, jnp.float32),
        hi=jnp.full((capacity, d), -_BIG, jnp.float32),
        psum=jnp.zeros((capacity, d), jnp.float32),
        count=jnp.zeros((capacity,), jnp.float32),
        active=jnp.zeros((capacity,), bool).at[0].set(True),
        block_id=jnp.zeros((n,), jnp.int32),
        n_blocks=jnp.asarray(1, jnp.int32),
    )
    return recompute_stats(part, x)


class SplitPlan(NamedTuple):
    """A resolved split round: which rows split (``fits``), along which
    coordinate (``axis``) at which midpoint (``mid``), and the row index of
    each right child (``right_row``). The plan is O(M) data — the in-core,
    distributed, and streaming drivers all compute it once per round and then
    route points against it (all at once, per shard, or per chunk)."""

    fits: jax.Array  # [M] bool
    axis: jax.Array  # [M] int32
    mid: jax.Array  # [M] f32
    right_row: jax.Array  # [M] int32
    n_new: jax.Array  # scalar int32


def split_plan(part: Partition, chosen: jax.Array) -> SplitPlan:
    """Resolve ``chosen`` (bool mask ``[M]``) into a :class:`SplitPlan`: each
    block splits at the midpoint of its longest side (paper Section 2.3:
    "divided in the middle point of its largest side ... replaced ... to
    produce the new thinner spatial partition").

    Blocks whose right child would exceed capacity are silently not split
    (callers bound ``sum(chosen)`` against free rows; this is the safety net).
    """
    m = part.capacity
    chosen = chosen & part.active & (part.count > 1)  # singleton blocks can't split

    # Allocate rows for right children: rank via cumsum over chosen.
    rank = jnp.cumsum(chosen.astype(jnp.int32)) - 1
    right_row = part.n_blocks + rank  # [M]
    fits = chosen & (right_row < m)
    right_row = jnp.where(fits, right_row, 0).astype(jnp.int32)

    ext = jnp.maximum(part.hi - part.lo, 0.0)
    axis = jnp.argmax(ext, axis=-1).astype(jnp.int32)  # [M]
    mid = 0.5 * (
        jnp.take_along_axis(part.lo, axis[:, None], axis=1)[:, 0]
        + jnp.take_along_axis(part.hi, axis[:, None], axis=1)[:, 0]
    )  # [M]
    return SplitPlan(fits, axis, mid, right_row, jnp.sum(fits.astype(jnp.int32)))


@jax.jit
def route_split(x: jax.Array, bid: jax.Array, plan: SplitPlan) -> jax.Array:
    """Repair point memberships after a split round: a member of a split block
    goes right iff ``x[axis] > mid``. One compiled pass over the rows, with no
    tree traversal; works on any subset of the dataset (shard, chunk).

    The plan is looked up once per row, from one packed ``[M, 3]`` int32
    table (split axis, or -1 for a block that does not split; ``mid``'s
    bits; right child row). The split coordinate is picked densely, by a
    compare-and-select over the row's ``d`` columns, so ``x`` is read once in
    its own layout and never gathered per row. The pick is a max against
    ``-inf``, which passes the chosen value through unchanged, so the routing
    is bit for bit that of a per-row gather of ``x[axis]``.
    """
    table = jnp.stack(
        [
            jnp.where(plan.fits, plan.axis, -1),
            jax.lax.bitcast_convert_type(plan.mid, jnp.int32),
            plan.right_row,
        ],
        axis=1,
    )
    row = table[bid]  # [n, 3]: the one per-row lookup
    p_axis, p_right = row[:, 0], row[:, 2]
    p_mid = jax.lax.bitcast_convert_type(row[:, 1], jnp.float32)
    on_axis = jnp.arange(x.shape[1]) == p_axis[:, None]  # all false when -1
    p_val = jnp.max(jnp.where(on_axis, x, -jnp.inf), axis=1)
    return jnp.where((p_axis >= 0) & (p_val > p_mid), p_right, bid)


def apply_split_plan(part: Partition, plan: SplitPlan) -> Partition:
    """Activate the right-child rows of ``plan`` (stats are stale until the
    caller recomputes them from routed memberships)."""
    m = part.capacity
    mrange = jnp.arange(m)
    active = part.active | (
        (mrange >= part.n_blocks) & (mrange < part.n_blocks + plan.n_new)
    )
    return part._replace(active=active, n_blocks=part.n_blocks + plan.n_new)


def split_blocks(part: Partition, x: jax.Array, chosen: jax.Array) -> Partition:
    """In-core split round: plan, route every point, re-tighten all boxes."""
    plan = split_plan(part, chosen)
    new_bid = route_split(x, part.block_id, plan)
    obs.data_pass()
    out = apply_split_plan(part._replace(block_id=new_bid), plan)
    return recompute_stats(out, x)


def split_blocks_virtual(part: Partition, plan: SplitPlan) -> Partition:
    """Execute a split round WITHOUT any data pass — the online service path
    (DESIGN.md §13), where member points are long gone downstream.

    Each child takes the parent's box clipped at the split plane (so future
    stream batches route into both sides), and the parent's accumulated
    statistics go wholly to the child containing the parent's representative
    — the other child starts with zero mass and fills from subsequent
    batches. The inherited stats over-claim the representative's side by the
    parent's cross-plane mass; under stat decay that bias washes out at the
    forgetting half-life, and the misassignment criterion only ever reads the
    boxes (which are exact), so drift detection stays sound.

    Deterministic and batch-free: resumed sessions replay it bit-identically
    from checkpointed state.
    """
    m, d = part.capacity, part.dim
    fits = plan.fits
    onehot = jax.nn.one_hot(plan.axis, d, dtype=bool)  # [M, d]
    mid_col = plan.mid[:, None]

    # Geometric child boxes: parent box clipped at the split plane. mid lies
    # inside [lo, hi] along the split axis by construction, so both are valid.
    hi_left = jnp.where(fits[:, None] & onehot, jnp.minimum(part.hi, mid_col), part.hi)
    lo_right = jnp.where(onehot, jnp.maximum(part.lo, mid_col), part.lo)

    # The representative's side inherits the parent's mass.
    safe = jnp.maximum(part.count, 1.0)
    rep_ax = jnp.take_along_axis(part.psum / safe[:, None], plan.axis[:, None], axis=1)[
        :, 0
    ]
    rep_right = fits & (rep_ax > plan.mid)

    psum_left = jnp.where(rep_right[:, None], 0.0, part.psum)
    count_left = jnp.where(rep_right, 0.0, part.count)
    psum_right = jnp.where(rep_right[:, None], part.psum, 0.0)
    count_right = jnp.where(rep_right, part.count, 0.0)

    # Scatter the right children into their allocated rows; non-splitting
    # rows target index m and are dropped.
    idx = jnp.where(fits, plan.right_row, m)
    out = part._replace(
        lo=part.lo.at[idx].set(lo_right, mode="drop"),
        hi=hi_left.at[idx].set(part.hi, mode="drop"),
        psum=psum_left.at[idx].set(psum_right, mode="drop"),
        count=count_left.at[idx].set(count_right, mode="drop"),
    )
    return apply_split_plan(out, plan)
