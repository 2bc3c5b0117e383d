"""Pallas GPU (Triton-lowering) kernels for the three clustering seams.

The TPU kernels (``fused_assign_update.py``, ``min_sqdist_update.py``) rely
on Mosaic's *sequential* grid: VMEM accumulators persist across grid steps
(``dimension_semantics=("arbitrary", ...)``), so one ``[K, d]`` sums block
is folded by every row block in turn. The Triton lowering has no such
guarantee — each program in the grid is an independent CTA that may run
concurrently on any SM — so the same seams are restructured here for a
*parallel* grid:

  grid = (n/bn,): one program per ``[bn, dp]`` row block. The full padded
  candidate/centroid array is one BlockSpec operand; the program loops over
  ``[bk, dp]`` tiles of it with dynamic slices (``pl.ds``), merging the
  running top-2 (or min-d²) in loop carry — registers, not memory. Cluster
  statistics cannot be accumulated across programs without atomics (float
  atomics are non-deterministic), so each program writes a per-block
  ``[K, d]`` partial that an XLA reduction sums outside the kernel — the
  deterministic split-K idiom. Labels are bit-equal to the ref oracle by
  construction (same argmin tie-break: smallest centroid id); statistics
  agree to f32 reduction tolerance.

Mixed precision: x/centroid tiles are loaded at their input dtype (bf16
tiles are half the HBM traffic and shared-memory footprint of f32) and
cast to f32 *inside* the kernel; distances, top-2 state and statistics all
accumulate in f32 (ADR 0008).

Block sizes come from ``roofline.analysis.*_blocking(backend="gpu")`` —
power-of-two dims (``tl.arange`` requires them) under an SM shared-memory
budget — or, in production, from the measured autotune cache
(``kernels.autotune``; the ops layer passes the tuned ``bn``/``bk`` in).

Everything here runs under ``interpret=True`` on any backend (the CI
smoke path) and lowers through Triton on a real GPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.roofline import analysis

__all__ = [
    "assign_top2_gpu",
    "assign_update_gpu",
    "assign_update_pruned_gpu",
    "gpu_compiler_params",
    "gpu_stats_supported",
    "min_sqdist_update_gpu",
]

_BIG = 3.0e38  # python float: pallas kernels must not capture traced constants


def gpu_stats_supported(d: int, k: int) -> bool:
    """Whether the per-program ``[K, d]`` statistics partial is small enough
    for the single-pass GPU kernel (beyond it, ops composes the top-2 kernel
    with the XLA segment-sum — the GPU analogue of the TPU two-pass path)."""
    return bool(analysis.assign_update_blocking(d, k, backend="gpu")["fused_ok"])


def gpu_compiler_params(bn: int, bk: int):
    """``TritonCompilerParams`` sized to the tile: wide tiles get more warps.

    Kept separate (and only attached when NOT interpreting) so the interpret
    path never depends on the Triton plugin being importable.
    """
    from jax.experimental.pallas import triton as plgpu

    num_warps = 8 if bn * bk >= 64 * 128 else 4
    return plgpu.TritonCompilerParams(num_warps=num_warps, num_stages=2)


def _top2_loop(x_ref, c_ref, *, k_actual: int, bk: int, nk):
    """The shared inner loop: fold ``[bk, dp]`` centroid tiles into the row
    block's running ``(d1, d2, argmin)`` carry. ``nk`` may be a traced trip
    count (the pruned kernel passes 0 for fully-skipped blocks). Ties
    resolve to the smallest centroid id — the ref oracle's argmin order —
    which is what makes labels bit-equal across impls.
    """
    xb = x_ref[...].astype(jnp.float32)  # [bn, dp]
    bn = xb.shape[0]
    xn = jnp.sum(xb * xb, axis=-1, keepdims=True)  # [bn, 1]

    def body(j, carry):
        d1, d2, a1 = carry
        cb = c_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)  # [bk, dp]
        cn = jnp.sum(cb * cb, axis=-1)  # [bk]
        dots = jax.lax.dot_general(
            xb, cb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bn, bk] tensor-core matmul
        dist = jnp.maximum(xn - 2.0 * dots + cn[None, :], 0.0)
        col = j * bk + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
        dist = jnp.where(col < k_actual, dist, _BIG)
        m1 = jnp.min(dist, axis=1, keepdims=True)
        t1 = jnp.min(
            jnp.where(dist == m1, col, jnp.int32(2**30)), axis=1, keepdims=True
        )
        m2 = jnp.min(jnp.where(col == t1, _BIG, dist), axis=1, keepdims=True)
        return (
            jnp.minimum(d1, m1),
            jnp.minimum(jnp.maximum(d1, m1), jnp.minimum(d2, m2)),
            jnp.where(m1 < d1, t1, a1),
        )

    init = (
        jnp.full((bn, 1), _BIG, jnp.float32),
        jnp.full((bn, 1), _BIG, jnp.float32),
        jnp.zeros((bn, 1), jnp.int32),
    )
    d1, d2, a1 = jax.lax.fori_loop(0, nk, body, init)
    return xb, d1, d2, a1


def _store_stat_partials(
    xb, wb, a1, d1, sums_ref, counts_ref, err_ref, *, bk: int, nk: int
):
    """Write this program's ``[K, d]`` statistics partial tile by tile, so
    the in-flight one-hot never exceeds ``[bn, bk]`` registers."""
    bn = xb.shape[0]

    def stats_body(j, _):
        col = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bn, bk), 1)
        onehot = (a1 == col).astype(jnp.float32) * wb  # [bn, bk]
        part = jax.lax.dot_general(
            onehot, xb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bk, dp]
        sums_ref[pl.ds(0, 1), pl.ds(j * bk, bk), :] = part[None]
        counts_ref[pl.ds(0, 1), pl.ds(j * bk, bk)] = jnp.sum(onehot, axis=0)[None]
        return 0

    jax.lax.fori_loop(0, nk, stats_body, 0)
    err_ref[0, 0] = jnp.sum(wb * d1)


def _assign_update_kernel(
    x_ref, w_ref, c_ref,
    assign_ref, d1_ref, d2_ref, sums_ref, counts_ref, err_ref,
    *, k_actual: int, bk: int, nk: int,
):
    xb, d1, d2, a1 = _top2_loop(x_ref, c_ref, k_actual=k_actual, bk=bk, nk=nk)
    assign_ref[...] = a1
    d1_ref[...] = d1
    d2_ref[...] = d2
    wb = w_ref[...].astype(jnp.float32)  # [bn, 1]; padded rows carry 0
    _store_stat_partials(
        xb, wb, a1, d1, sums_ref, counts_ref, err_ref, bk=bk, nk=nk
    )


def _assign_update_pruned_kernel(
    x_ref, w_ref, cached_ref, act_ref, flag_ref, c_ref,
    assign_ref, d1_ref, d2_ref, sums_ref, counts_ref, err_ref,
    *, k_actual: int, bk: int, nk: int,
):
    """Drift-bound-pruned variant (ADR 0004): a fully-skipped row block runs
    the top-2 fold with a ZERO trip count — no distance work, carry stays at
    the init and every row keeps its cached assignment — but still writes
    its statistics partial under the composed assignment, so the reduced
    sums/counts match the dense kernel whenever the assignments agree."""
    act = act_ref[...] > 0  # [bn, 1]
    blk_active = flag_ref[0, 0] > 0
    xb, d1, d2, a1 = _top2_loop(
        x_ref, c_ref, k_actual=k_actual, bk=bk,
        nk=jnp.where(blk_active, nk, 0),
    )
    final = jnp.where(act, a1, cached_ref[...])
    assign_ref[...] = final
    d1_ref[...] = d1  # garbage (_BIG) where skipped — the documented contract
    d2_ref[...] = d2
    wb = w_ref[...].astype(jnp.float32)
    err_d1 = jnp.where(act, d1, 0.0)
    _store_stat_partials(
        xb, wb, final, err_d1, sums_ref, counts_ref, err_ref, bk=bk, nk=nk
    )


def _min_sqdist_kernel(
    x_ref, w_ref, m_ref, c_ref, v_ref,
    out_ref, cost_ref,
    *, bl: int, nl: int,
):
    xb = x_ref[...].astype(jnp.float32)  # [bn, dp]
    xn = jnp.sum(xb * xb, axis=-1, keepdims=True)

    def body(j, mind2):
        cb = c_ref[pl.ds(j * bl, bl), :].astype(jnp.float32)
        vb = v_ref[:, pl.ds(j * bl, bl)]  # [1, bl]
        cn = jnp.sum(cb * cb, axis=-1)
        dots = jax.lax.dot_general(
            xb, cb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        dist = jnp.maximum(xn - 2.0 * dots + cn[None, :], 0.0)
        dist = jnp.where(vb > 0, dist, _BIG)  # invalid candidates can't win
        return jnp.minimum(mind2, jnp.min(dist, axis=1, keepdims=True))

    mind2 = jax.lax.fori_loop(0, nl, body, m_ref[...])
    out_ref[...] = mind2
    wb = w_ref[...].astype(jnp.float32)  # padded rows carry 0
    cost_ref[0, 0] = jnp.sum(wb * mind2)


def _pad_rows(a, np_):
    return jnp.pad(a, ((0, np_ - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bk"))
def assign_update_gpu(
    x: jax.Array,
    w: jax.Array,
    c: jax.Array,
    *,
    interpret: bool = False,
    bn: int | None = None,
    bk: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-pass ``ref.assign_update`` on the parallel grid:
    ``(assign, d1, d2, sums, counts, err)``. Padded rows must carry w == 0."""
    n, d = x.shape
    k = c.shape[0]
    blk = analysis.assign_update_blocking(
        d, k, bn=bn, bk=bk, dtype_bytes=x.dtype.itemsize, backend="gpu"
    )
    bn, bk, dp, kp = blk["bn"], blk["bk"], blk["dp"], blk["kp_acc"]
    nk = kp // bk
    np_ = pl.cdiv(n, bn) * bn
    nb = np_ // bn

    xpad = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    wpad = _pad_rows(w.astype(jnp.float32), np_)[:, None]
    cpad = jnp.pad(c, ((0, kp - k), (0, dp - d)))

    kwargs = {} if interpret else {"compiler_params": gpu_compiler_params(bn, bk)}
    assign, d1, d2, sums_p, counts_p, err_p = pl.pallas_call(
        functools.partial(_assign_update_kernel, k_actual=k, bk=bk, nk=nk),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((kp, dp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, kp, dp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, kp), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, kp, dp), jnp.float32),
            jax.ShapeDtypeStruct((nb, kp), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(xpad, wpad, cpad)

    inf = jnp.float32(jnp.inf)
    d1 = d1[:n, 0]
    d2 = jnp.where(d2[:n, 0] >= _BIG, inf, d2[:n, 0])  # K == 1: no second
    sums = jnp.sum(sums_p, axis=0)[:k, :d]
    counts = jnp.sum(counts_p, axis=0)[:k]
    return assign[:n, 0], d1, d2, sums, counts, jnp.sum(err_p)


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bk"))
def assign_update_pruned_gpu(
    x: jax.Array,
    w: jax.Array,
    c: jax.Array,
    assign: jax.Array,
    active: jax.Array,
    *,
    interpret: bool = False,
    bn: int | None = None,
    bk: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Single-pass ``ref.assign_update_pruned`` on the parallel grid.
    Semantics of ``fused_assign_update_pruned_pallas`` (ADR 0004)."""
    n, d = x.shape
    k = c.shape[0]
    blk = analysis.assign_update_blocking(
        d, k, bn=bn, bk=bk, dtype_bytes=x.dtype.itemsize, backend="gpu"
    )
    bn, bk, dp, kp = blk["bn"], blk["bk"], blk["dp"], blk["kp_acc"]
    nk = kp // bk
    np_ = pl.cdiv(n, bn) * bn
    nb = np_ // bn

    xpad = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    wpad = _pad_rows(w.astype(jnp.float32), np_)[:, None]
    apad = _pad_rows(assign.astype(jnp.int32), np_)[:, None]
    # padding rows are never active: cached id 0 with weight 0
    actpad = _pad_rows(active.astype(jnp.int32), np_)[:, None]
    flags = jnp.max(actpad.reshape(nb, bn), axis=1, keepdims=True).astype(
        jnp.int32
    )  # [nb, 1] any-active per row block
    cpad = jnp.pad(c, ((0, kp - k), (0, dp - d)))

    kwargs = {} if interpret else {"compiler_params": gpu_compiler_params(bn, bk)}
    assign_o, d1, d2, sums_p, counts_p, err_p = pl.pallas_call(
        functools.partial(_assign_update_pruned_kernel, k_actual=k, bk=bk, nk=nk),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
            pl.BlockSpec((kp, dp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, kp, dp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, kp), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, kp, dp), jnp.float32),
            jax.ShapeDtypeStruct((nb, kp), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(xpad, wpad, apad, actpad, flags, cpad)

    inf = jnp.float32(jnp.inf)
    d1 = d1[:n, 0]
    d2 = jnp.where(d2[:n, 0] >= _BIG, inf, d2[:n, 0])
    sums = jnp.sum(sums_p, axis=0)[:k, :d]
    counts = jnp.sum(counts_p, axis=0)[:k]
    return assign_o[:n, 0], d1, d2, sums, counts, jnp.sum(err_p)


def _assign_top2_kernel(x_ref, c_ref, assign_ref, d1_ref, d2_ref, *, k_actual, bk, nk):
    _, d1, d2, a1 = _top2_loop(x_ref, c_ref, k_actual=k_actual, bk=bk, nk=nk)
    assign_ref[...] = a1
    d1_ref[...] = d1
    d2_ref[...] = d2


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bk"))
def assign_top2_gpu(
    x: jax.Array,
    c: jax.Array,
    *,
    interpret: bool = False,
    bn: int | None = None,
    bk: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``ref.assign_top2`` on the parallel grid: ``(assign, d1, d2)`` — the
    assignment leg of the GPU two-pass path when the statistics partial is
    too large for :func:`assign_update_gpu` (``gpu_stats_supported``)."""
    n, d = x.shape
    k = c.shape[0]
    blk = analysis.assign_update_blocking(
        d, k, bn=bn, bk=bk, dtype_bytes=x.dtype.itemsize, backend="gpu"
    )
    bn, bk, dp, kp = blk["bn"], blk["bk"], blk["dp"], blk["kp_dist"]
    nk = kp // bk
    np_ = pl.cdiv(n, bn) * bn

    xpad = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    cpad = jnp.pad(c, ((0, kp - k), (0, dp - d)))

    kwargs = {} if interpret else {"compiler_params": gpu_compiler_params(bn, bk)}
    assign, d1, d2 = pl.pallas_call(
        functools.partial(_assign_top2_kernel, k_actual=k, bk=bk, nk=nk),
        grid=(np_ // bn,),
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i: (i, 0)),
            pl.BlockSpec((kp, dp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(xpad, cpad)

    inf = jnp.float32(jnp.inf)
    d2 = jnp.where(d2[:n, 0] >= _BIG, inf, d2[:n, 0])
    return assign[:n, 0], d1[:n, 0], d2


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bl"))
def min_sqdist_update_gpu(
    x: jax.Array,
    w: jax.Array,
    cand: jax.Array,
    cvalid: jax.Array,
    mind2: jax.Array,
    *,
    interpret: bool = False,
    bn: int | None = None,
    bl: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Single-pass ``ref.min_sqdist_update`` on the parallel grid:
    ``(mind2, cost)``. Semantics of ``min_sqdist_update_pallas`` (ADR 0005)."""
    n, d = x.shape
    l = cand.shape[0]
    blk = analysis.min_sqdist_blocking(
        d, l, bn=bn, bl=bl, dtype_bytes=x.dtype.itemsize, backend="gpu"
    )
    bn, bl, dp, lp = blk["bn"], blk["bl"], blk["dp"], blk["lp"]
    nl = lp // bl
    np_ = pl.cdiv(n, bn) * bn
    nb = np_ // bn

    xpad = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    wpad = _pad_rows(w.astype(jnp.float32), np_)[:, None]
    mpad = _pad_rows(mind2.astype(jnp.float32), np_)[:, None]
    cpad = jnp.pad(cand, ((0, lp - l), (0, dp - d)))
    vpad = jnp.pad(cvalid.astype(jnp.float32), (0, lp - l))[None, :]

    kwargs = {} if interpret else {"compiler_params": gpu_compiler_params(bn, bl)}
    out, cost_p = pl.pallas_call(
        functools.partial(_min_sqdist_kernel, bl=bl, nl=nl),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((lp, dp), lambda i: (0, 0)),
            pl.BlockSpec((1, lp), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(xpad, wpad, mpad, cpad, vpad)

    return out[:n, 0], jnp.sum(cost_p)
