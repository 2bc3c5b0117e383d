"""Pallas TPU kernel for the k-means|| fold pass (ADR 0005).

Each oversampling round of k-means|| (Bahmani et al. 2012) needs, per point:
the minimum squared distance to the candidate set grown so far — updated
with the round's new candidates — and the global weighted cost
``φ = Σ w·min-d²`` that normalises the next round's Bernoulli draws. The
naive composition (``pairwise_sqdist`` then ``min`` then a separate cost
reduction) materialises an ``[n, L]`` distance matrix and reads x from HBM
once per stage; this kernel restructures the round so x is read ONCE:

  grid = (n/bn, L/bl), L innermost. Per (i, j) step the ``[bn, dp]`` x tile
  and one ``[bl, dp]`` candidate tile produce a ``[bn, bl]`` distance tile
  on the MXU (``‖x−c‖² = ‖x‖² − 2·x·c + ‖c‖²``), whose row-min folds into
  the row block's running min-d² held in VMEM across the candidate tiles.
  On the LAST candidate tile the min is final, so the same invocation —
  while the updated state is still resident — accumulates the row block's
  weighted cost partial sum into the scalar ``φ`` accumulator. The
  ``(n, L)`` distance matrix never exists.

Block sizes come from ``roofline.analysis.min_sqdist_blocking``: with no
``[K, d]``-sized accumulator to pin (unlike the fused assign+update
kernel), nearly the whole kernel VMEM budget goes to the x tile.

Masking contract: invalid candidate rows arrive flagged by ``cvalid``
(shaped ``[1, L]`` so the mask broadcasts over lanes without a transpose)
and are masked to ``_BIG`` before the min — identically to the ref oracle.
Padded x rows must carry weight 0: their min-d² is garbage that callers
slice off, and the cost ignores them by the zero-weight contract.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.roofline import analysis

__all__ = ["min_sqdist_update_pallas"]

_BIG = 3.0e38  # python float: pallas kernels must not capture traced constants


def _kernel(
    x_ref,
    w_ref,
    m_ref,
    c_ref,
    v_ref,
    out_ref,
    cost_ref,
    *,
    nl: int,
):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init_row_block():
        out_ref[...] = m_ref[...]

    @pl.when((i == 0) & (j == 0))
    def _init_cost():
        cost_ref[...] = jnp.zeros_like(cost_ref)

    xb = x_ref[...].astype(jnp.float32)  # [bn, dp]
    cb = c_ref[...].astype(jnp.float32)  # [bl, dp]
    xn = jnp.sum(xb * xb, axis=-1, keepdims=True)  # [bn, 1]
    cn = jnp.sum(cb * cb, axis=-1)  # [bl]
    dots = jax.lax.dot_general(
        xb, cb, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # [bn, bl] on the MXU
    dist = jnp.maximum(xn - 2.0 * dots + cn[None, :], 0.0)
    dist = jnp.where(v_ref[...] > 0, dist, _BIG)  # [1, bl] mask broadcast

    out_ref[...] = jnp.minimum(
        out_ref[...], jnp.min(dist, axis=1, keepdims=True)
    )

    @pl.when(j == nl - 1)
    def _accumulate_cost():
        # The row block's min-d² is final; fold its weighted cost while the
        # state is still in VMEM — this is the fusion.
        wb = w_ref[...].astype(jnp.float32)  # [bn, 1]; padded rows carry 0
        # (1, 1) vector accumulate: Mosaic cannot store a scalar into VMEM
        cost_ref[...] += jnp.sum(wb * out_ref[...], keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", "bn", "bl"))
def min_sqdist_update_pallas(
    x: jax.Array,
    w: jax.Array,
    cand: jax.Array,
    cvalid: jax.Array,
    mind2: jax.Array,
    *,
    interpret: bool = False,
    bn: int | None = None,
    bl: int = 128,
) -> tuple[jax.Array, jax.Array]:
    """Single-pass ``ref.min_sqdist_update``: ``(mind2, cost)``.

    ``x [n, d]`` points, ``w [n]`` nonnegative weights, ``cand [L, d]`` new
    candidates with validity mask ``cvalid [L]``, ``mind2 [n]`` the running
    state (may be ``_BIG`` on the first fold). Padded/invalid x rows must be
    encoded as ``w == 0``.
    """
    n, d = x.shape
    l = cand.shape[0]

    blk = analysis.min_sqdist_blocking(
        d, l, bn=bn, bl=bl, dtype_bytes=x.dtype.itemsize
    )
    bn, dp, lp = blk["bn"], blk["dp"], blk["lp"]
    np_ = pl.cdiv(n, bn) * bn
    nl = lp // bl

    xpad = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    wpad = jnp.pad(w.astype(jnp.float32), (0, np_ - n))[:, None]
    mpad = jnp.pad(mind2.astype(jnp.float32), (0, np_ - n))[:, None]
    cpad = jnp.pad(cand, ((0, lp - l), (0, dp - d)))
    # padded candidate rows are invalid; [1, L] layout keeps the in-kernel
    # mask a lane-wise broadcast instead of a sublane transpose
    vpad = jnp.pad(cvalid.astype(jnp.float32), (0, lp - l))[None, :]

    grid = (np_ // bn, nl)
    out, cost = pl.pallas_call(
        functools.partial(_kernel, nl=nl),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bl, dp), lambda i, j: (j, 0)),
            pl.BlockSpec((1, bl), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((np_, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            # the row min-d² is carried across j and the cost accumulator
            # across i and j — neither grid dimension is parallel
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(xpad, wpad, mpad, cpad, vpad)

    return out[:n, 0], cost[0, 0]
