"""Fused distance + argmin + top-2 Pallas TPU kernel.

The K-means assignment step is the paper's compute hot-spot
(``O(n·K·d)``, Section 1.2). On TPU we decompose
``‖x−c‖² = ‖x‖² − 2·x·c + ‖c‖²`` so the dominant term is an ``[bn,d]×[d,bk]``
MXU matmul, and we keep an **online top-2** (closest and second-closest
squared distance) plus the argmin across centroid tiles — BWKM's
misassignment function (Definition 3) needs exactly the top-2 gap, so the
boundary test costs nothing extra. The n×K distance matrix never leaves
VMEM: HBM traffic is ``n·d + K·d`` reads and ``3·n`` writes instead of
``n·K`` intermediate.

Blocking:
  grid = (n/bn, K/bk); the K axis is the innermost (reduction) dimension so
  the per-row running (d1, d2, assign) blocks stay resident in VMEM across
  centroid tiles. The full feature dimension d (padded to the 128-lane
  boundary) is kept in VMEM per tile: clustering dims in this framework are
  ≤ 8192 (LM activations), so an x-tile is ≤ bn·d·4B ≤ 4 MB.

The merge of two (best, second) pairs is
  best' = min(b1, b2);  second' = min(max(b1, b2), s1, s2)
which is associative — the same online-reduction trick as flash attention's
running max/sum, applied to order statistics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["assign_top2_pallas"]

_BIG = 3.0e38  # python float: pallas kernels must not capture traced constants


def _kernel(x_ref, c_ref, assign_ref, d1_ref, d2_ref, *, k_actual: int, bk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        assign_ref[...] = jnp.zeros_like(assign_ref)
        d1_ref[...] = jnp.full_like(d1_ref, _BIG)
        d2_ref[...] = jnp.full_like(d2_ref, _BIG)

    xb = x_ref[...].astype(jnp.float32)  # [bn, d]
    cb = c_ref[...].astype(jnp.float32)  # [bk, d]
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

    # Tile-local top-2. Ties resolve to the smallest column id, matching
    # jnp.argmin; duplicate centroids correctly give second == best.
    m1 = jnp.min(dist, axis=1, keepdims=True)  # [bn, 1]
    a1 = jnp.min(jnp.where(dist == m1, col, jnp.int32(2**30)), axis=1, keepdims=True)
    dist_wo = jnp.where(col == a1, _BIG, dist)
    m2 = jnp.min(dist_wo, axis=1, keepdims=True)

    r1, r2, ra = d1_ref[...], d2_ref[...], assign_ref[...]
    best = jnp.minimum(r1, m1)
    second = jnp.minimum(jnp.maximum(r1, m1), jnp.minimum(r2, m2))
    assign = jnp.where(m1 < r1, a1, ra)

    d1_ref[...] = best
    d2_ref[...] = second
    assign_ref[...] = assign


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bk"))
def assign_top2_pallas(
    x: jax.Array,
    c: jax.Array,
    *,
    interpret: bool = False,
    bn: int | None = None,
    bk: int = 128,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Pallas-accelerated ``ref.assign_top2``: returns ``(assign, d1, d2)``."""
    n, d = x.shape
    k = c.shape[0]

    dp = pl.cdiv(d, 128) * 128
    if bn is None:
        # keep the x tile around <= 2 MB of f32 in VMEM
        bn = max(8, min(512, (2 * 1024 * 1024 // (4 * dp)) // 8 * 8))
    np_ = pl.cdiv(n, bn) * bn
    kp = pl.cdiv(k, bk) * bk

    xpad = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    cpad = jnp.pad(c, ((0, kp - k), (0, dp - d)))

    grid = (np_ // bn, kp // bk)
    assign, d1, d2 = pl.pallas_call(
        functools.partial(_kernel, k_actual=k, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bk, dp), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.int32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xpad, cpad)

    inf = jnp.float32(jnp.inf)
    d1 = d1[:n, 0]
    d2 = d2[:n, 0]
    d2 = jnp.where(d2 >= _BIG, inf, d2)  # K == 1: no second centroid
    return assign[:n, 0], d1, d2
