"""Fused single-pass assign + accumulate Pallas TPU kernel (ADR 0003).

One Lloyd/BWKM step needs, per weighted point: the top-2 nearest centroids
(assignment + the misassignment gap, Definition 3) AND the weighted
per-cluster sufficient statistics ``(Σ w·x, Σ w)`` plus the weighted error
``Σ w·d1``. The pre-fusion pipeline ran these as two kernels —
``distance_assign`` then ``cluster_update`` — reading every x block from
HBM twice per iteration. On accelerators that HBM traffic, not the paper's
distance-computation count, is the binding cost of the step; this kernel
restructures the data movement so each x block is read ONCE:

  grid = (n/bn, K/bk), K innermost. Per (i, j) step the ``[bn, dp]`` x tile
  and one ``[bk, dp]`` centroid tile produce a ``[bn, bk]`` distance tile on
  the MXU (``‖x−c‖² = ‖x‖² − 2·x·c + ‖c‖²``), merged into the row block's
  running online top-2 (the flash-attention trick applied to order
  statistics). On the LAST centroid tile (j == K/bk − 1) the assignment for
  the row block is final, so the same invocation — while the x tile is
  still resident in VMEM — builds the ``[bn, K]`` weighted one-hot
  in-registers and contracts it on the MXU into the ``[K, d]``/``[K, 1]``
  accumulators that persist in VMEM across the whole grid. The ``(n, K)``
  distance matrix and the intermediate assignment round-trip to HBM are
  both eliminated.

Block sizes come from ``roofline.analysis.assign_update_blocking``: the
``[K, d]`` accumulator is pinned first, the rest of the kernel VMEM budget
goes to ``bn``. When the accumulator does not fit (``fused_ok=False``),
``kernels.ops.assign_update`` selects the two-pass path instead — see the
ADR for the trade-off.

Precision: every MXU contraction here (and in the other Mosaic kernels)
runs at ``Precision.HIGHEST``. At the TPU default, f32 operands are rounded
to bf16; on the SUSY profile (‖x‖² ≈ 1.8e3) that moved the summed error by
1.6e-3 relative and the assignment of 3% of the rows a float64 reference
assigns unambiguously, measured on a v5e chip.

Padding contract: padded rows (n → multiple of bn, and streaming chunk
padding) MUST carry weight 0 — they still get a (garbage, sliced-off)
assignment, but contribute exactly nothing to sums/counts/err. Padded
centroid columns are masked to ``_BIG`` before the top-2, identically to
``distance_assign``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.roofline import analysis

__all__ = [
    "fused_assign_update_pallas",
    "fused_assign_update_pruned_pallas",
    "fused_supported",
]

_BIG = 3.0e38  # python float: pallas kernels must not capture traced constants


def fused_supported(d: int, k: int) -> bool:
    """Whether the ``[K, d]`` accumulator fits the kernel VMEM budget (the
    accumulator is always f32, so this does not depend on the input dtype)."""
    return bool(analysis.assign_update_blocking(d, k)["fused_ok"])


def _kernel(
    x_ref,
    w_ref,
    c_ref,
    assign_ref,
    d1_ref,
    d2_ref,
    sums_ref,
    counts_ref,
    err_ref,
    *,
    k_actual: int,
    bk: int,
    nk: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init_row_block():
        assign_ref[...] = jnp.zeros_like(assign_ref)
        d1_ref[...] = jnp.full_like(d1_ref, _BIG)
        d2_ref[...] = jnp.full_like(d2_ref, _BIG)

    @pl.when((i == 0) & (j == 0))
    def _init_accumulators():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        err_ref[...] = jnp.zeros_like(err_ref)

    xb = x_ref[...].astype(jnp.float32)  # [bn, dp]
    cb = c_ref[...].astype(jnp.float32)  # [bk, dp]
    xn = jnp.sum(xb * xb, axis=-1, keepdims=True)  # [bn, 1]
    cn = jnp.sum(cb * cb, axis=-1)  # [bk]
    dots = jax.lax.dot_general(
        xb, cb, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # [bn, bk] on the MXU
    dist = jnp.maximum(xn - 2.0 * dots + cn[None, :], 0.0)

    # Mask padded centroid columns (global column id >= K).
    col = j * bk + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    dist = jnp.where(col < k_actual, dist, _BIG)

    # Tile-local top-2; ties resolve to the smallest column id (argmin order).
    m1 = jnp.min(dist, axis=1, keepdims=True)  # [bn, 1]
    a1 = jnp.min(jnp.where(dist == m1, col, jnp.int32(2**30)), axis=1, keepdims=True)
    dist_wo = jnp.where(col == a1, _BIG, dist)
    m2 = jnp.min(dist_wo, axis=1, keepdims=True)

    # Merge into the running top-2 (associative order-statistics merge).
    r1, r2, ra = d1_ref[...], d2_ref[...], assign_ref[...]
    d1_ref[...] = jnp.minimum(r1, m1)
    d2_ref[...] = jnp.minimum(jnp.maximum(r1, m1), jnp.minimum(r2, m2))
    assign_ref[...] = jnp.where(m1 < r1, a1, ra)

    @pl.when(j == nk - 1)
    def _accumulate_block_stats():
        # Assignment for this row block is final; fold its sufficient
        # statistics while the x tile is still in VMEM — this is the fusion.
        wb = w_ref[...].astype(jnp.float32)  # [bn, 1]; padded rows carry 0
        kp = sums_ref.shape[0]
        onehot = (
            assign_ref[...]
            == jax.lax.broadcasted_iota(jnp.int32, (xb.shape[0], kp), 1)
        ).astype(jnp.float32) * wb  # [bn, kp] weighted one-hot, in-registers
        sums_ref[...] += jax.lax.dot_general(
            onehot, xb, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
        )  # [kp, dp] via MXU
        counts_ref[...] += jnp.sum(onehot, axis=0, keepdims=True).T  # [kp, 1]
        # (1, 1) vector accumulate: Mosaic cannot store a scalar into VMEM
        err_ref[...] += jnp.sum(wb * d1_ref[...], keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bk"))
def fused_assign_update_pallas(
    x: jax.Array,
    w: jax.Array,
    c: jax.Array,
    *,
    interpret: bool = False,
    bn: int | None = None,
    bk: int = 128,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-pass ``ref.assign_update``: ``(assign, d1, d2, sums, counts, err)``.

    ``x [n, d]`` points, ``w [n]`` nonnegative weights, ``c [K, d]``
    centroids. Padded/invalid rows must be encoded as ``w == 0``.
    """
    n, d = x.shape
    k = c.shape[0]

    blk = analysis.assign_update_blocking(
        d, k, bn=bn, bk=bk, dtype_bytes=x.dtype.itemsize
    )
    if not blk["fused_ok"]:
        raise ValueError(
            f"[K={k}, d={d}] accumulator exceeds the kernel VMEM budget; "
            "use the two-pass path (ops.assign_update falls back automatically)"
        )
    bn, dp, kp_acc, kp_dist = blk["bn"], blk["dp"], blk["kp_acc"], blk["kp_dist"]
    np_ = pl.cdiv(n, bn) * bn
    nk = kp_dist // bk

    xpad = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    wpad = jnp.pad(w.astype(jnp.float32), (0, np_ - n))[:, None]  # pad rows -> w=0
    cpad = jnp.pad(c, ((0, kp_dist - k), (0, dp - d)))

    grid = (np_ // bn, nk)
    assign, d1, d2, sums, counts, err = pl.pallas_call(
        functools.partial(_kernel, k_actual=k, bk=bk, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, dp), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((kp_acc, dp), lambda i, j: (0, 0)),
            pl.BlockSpec((kp_acc, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((kp_acc, dp), jnp.float32),
            jax.ShapeDtypeStruct((kp_acc, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # both dims carry VMEM state across steps (row top-2 over j, the
            # cluster accumulators over i and j) — neither is parallel
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(xpad, wpad, cpad)

    inf = jnp.float32(jnp.inf)
    d1 = d1[:n, 0]
    d2 = d2[:n, 0]
    d2 = jnp.where(d2 >= _BIG, inf, d2)  # K == 1: no second centroid
    return assign[:n, 0], d1, d2, sums[:k, :d], counts[:k, 0], err[0, 0]


def _pruned_kernel(
    flags_ref,
    x_ref,
    w_ref,
    cached_ref,
    act_ref,
    c_ref,
    assign_ref,
    d1_ref,
    d2_ref,
    sums_ref,
    counts_ref,
    err_ref,
    *,
    k_actual: int,
    bk: int,
    nk: int,
):
    """Drift-bound-pruned variant of ``_kernel`` (ADR 0004).

    ``flags_ref [n_blocks]`` (scalar-prefetched into SMEM) holds the
    precomputed any-active flag of every row block, ``cached_ref [bn, 1]``
    the previous assignment and ``act_ref [bn, 1]`` the per-row active
    mask. A fully skipped block runs NO distance work — its rows keep the cached assignment — but every block
    still folds its weighted one-hot statistics contraction with the
    composed assignment, in the identical order the dense kernel uses, so
    the accumulated sums/counts (and hence the next centroids) are
    bit-identical to a dense pass whenever the assignments agree. Pruning
    therefore cuts the distance FLOPs (the paper's cost metric), not the
    HBM traffic: x is read once per iteration either way (see
    ``analysis.assign_update_pruned_cost``).
    """
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init_row_block():
        assign_ref[...] = cached_ref[...]
        d1_ref[...] = jnp.full_like(d1_ref, _BIG)
        d2_ref[...] = jnp.full_like(d2_ref, _BIG)

    @pl.when((i == 0) & (j == 0))
    def _init_accumulators():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)
        err_ref[...] = jnp.zeros_like(err_ref)

    blk_active = flags_ref[i] > 0
    xb = x_ref[...].astype(jnp.float32)  # [bn, dp]

    @pl.when(blk_active)
    def _distance_tile():
        # Identical to the dense kernel's top-2 merge; rows in an active
        # block that are themselves inactive get a recomputed argmin too
        # (bound soundness guarantees it equals the cache), and the final
        # compose below masks them back anyway.
        cb = c_ref[...].astype(jnp.float32)  # [bk, dp]
        xn = jnp.sum(xb * xb, axis=-1, keepdims=True)
        cn = jnp.sum(cb * cb, axis=-1)
        dots = jax.lax.dot_general(
            xb, cb, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
        )
        dist = jnp.maximum(xn - 2.0 * dots + cn[None, :], 0.0)
        col = j * bk + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
        dist = jnp.where(col < k_actual, dist, _BIG)
        m1 = jnp.min(dist, axis=1, keepdims=True)
        a1 = jnp.min(
            jnp.where(dist == m1, col, jnp.int32(2**30)), axis=1, keepdims=True
        )
        dist_wo = jnp.where(col == a1, _BIG, dist)
        m2 = jnp.min(dist_wo, axis=1, keepdims=True)
        r1, r2, ra = d1_ref[...], d2_ref[...], assign_ref[...]
        # j == 0 overwrites the cached-assignment init with the first tile's
        # argmin so stale cache ids can never win the merge on active rows.
        first = j == 0
        d1_ref[...] = jnp.minimum(r1, m1)
        d2_ref[...] = jnp.minimum(jnp.maximum(r1, m1), jnp.minimum(r2, m2))
        assign_ref[...] = jnp.where(first | (m1 < r1), a1, ra)

    @pl.when(j == nk - 1)
    def _accumulate_block_stats():
        act = act_ref[...] > 0  # [bn, 1]
        final = jnp.where(act, assign_ref[...], cached_ref[...])
        assign_ref[...] = final
        wb = w_ref[...].astype(jnp.float32)  # [bn, 1]
        kp = sums_ref.shape[0]
        onehot = (
            final == jax.lax.broadcasted_iota(jnp.int32, (xb.shape[0], kp), 1)
        ).astype(jnp.float32) * wb  # [bn, kp] weighted one-hot, in-registers
        sums_ref[...] += jax.lax.dot_general(
            onehot, xb, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
        )  # [kp, dp] via MXU — identical contraction to the dense kernel
        counts_ref[...] += jnp.sum(onehot, axis=0, keepdims=True).T
        err_ref[...] += jnp.sum(
            jnp.where(act, wb * d1_ref[...], 0.0), keepdims=True
        )


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bk"))
def fused_assign_update_pruned_pallas(
    x: jax.Array,
    w: jax.Array,
    c: jax.Array,
    assign: jax.Array,
    active: jax.Array,
    *,
    interpret: bool = False,
    bn: int | None = None,
    bk: int = 128,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-pass ``ref.assign_update_pruned``:
    ``(assign, d1, d2, dsums, dcounts, err)``.

    ``assign [n] i32`` cached assignments, ``active [n]`` bool/int mask of
    rows whose drift bounds could not prove the assignment unchanged.
    ``d1``/``d2``/``err`` are defined only where active (see the ref
    oracle); sums/counts are FULL statistics under the composed assignment,
    accumulated in the dense kernel's order — see the kernel docstring.
    """
    n, d = x.shape
    k = c.shape[0]

    blk = analysis.assign_update_blocking(
        d, k, bn=bn, bk=bk, dtype_bytes=x.dtype.itemsize
    )
    if not blk["fused_ok"]:
        raise ValueError(
            f"[K={k}, d={d}] accumulator exceeds the kernel VMEM budget; "
            "use the two-pass path (ops.assign_update_pruned falls back "
            "automatically)"
        )
    bn, dp, kp_acc, kp_dist = blk["bn"], blk["dp"], blk["kp_acc"], blk["kp_dist"]
    np_ = pl.cdiv(n, bn) * bn
    nk = kp_dist // bk

    xpad = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    wpad = jnp.pad(w.astype(jnp.float32), (0, np_ - n))[:, None]
    apad = jnp.pad(assign.astype(jnp.int32), (0, np_ - n))[:, None]
    # padding rows are never active: they keep cached id 0 with weight 0
    actpad = jnp.pad(active.astype(jnp.int32), (0, np_ - n))[:, None]
    # any-active per row block, scalar-prefetched into SMEM
    flags = jnp.max(actpad.reshape(np_ // bn, bn), axis=1)
    cpad = jnp.pad(c, ((0, kp_dist - k), (0, dp - d)))

    # with scalar prefetch every index map also receives the flags ref
    row = pl.BlockSpec((bn, 1), lambda i, j, f: (i, 0))
    assign_o, d1, d2, sums, counts, err = pl.pallas_call(
        functools.partial(_pruned_kernel, k_actual=k, bk=bk, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(np_ // bn, nk),
            in_specs=[
                pl.BlockSpec((bn, dp), lambda i, j, f: (i, 0)),
                row,
                row,
                row,
                pl.BlockSpec((bk, dp), lambda i, j, f: (j, 0)),
            ],
            out_specs=[
                row,
                row,
                row,
                pl.BlockSpec((kp_acc, dp), lambda i, j, f: (0, 0)),
                pl.BlockSpec((kp_acc, 1), lambda i, j, f: (0, 0)),
                pl.BlockSpec((1, 1), lambda i, j, f: (0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((kp_acc, dp), jnp.float32),
            jax.ShapeDtypeStruct((kp_acc, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(flags, xpad, wpad, apad, actpad, cpad)

    inf = jnp.float32(jnp.inf)
    d1 = d1[:n, 0]
    d2 = d2[:n, 0]
    d2 = jnp.where(d2 >= _BIG, inf, d2)  # K == 1 / skipped rows: no second
    return assign_o[:n, 0], d1, d2, sums[:k, :d], counts[:k, 0], err[0, 0]
