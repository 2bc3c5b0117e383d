"""The benchmark's plain reference, independent of the program under test.

Distances over many rows are ``jax.numpy`` float32 with the MXU at
``Precision.HIGHEST``, computed in row blocks so that any row count fits;
the same functions at a lower precision are the control that ``correct``
must reject. Block statistics and small per-block arithmetic run in
float64 NumPy on the host. Nothing here imports the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
#: rows per reference block: [BLOCK, d] x [K, d] stays small at any K here
BLOCK = 1 << 15
#: steps of the control's Lloyd: past where a fit's Lloyd has settled
LLOYD_STEPS = 50


def sqdist(x: jax.Array, c: jax.Array, precision) -> jax.Array:
    """Squared distances ``[rows, K]`` by the expansion the program uses."""
    xc = jnp.dot(x, c.T, precision=precision)
    xn = jnp.sum(x * x, axis=1, keepdims=True)
    cn = jnp.sum(c * c, axis=1)[None, :]
    return jnp.maximum(xn - 2.0 * xc + cn, 0.0)


def _map_rows(f, x, block):
    """``f`` over row blocks of ``x``; each block's outputs, whose leading
    axis may be of any length, concatenated along it."""
    n = x.shape[0]
    full = n // block
    outs = []
    if full:
        stacked = lax.map(
            lambda i: f(lax.dynamic_slice_in_dim(x, i * block, block)),
            jnp.arange(full),
        )
        outs.append(jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), stacked))
    if n % block:
        outs.append(f(x[full * block:]))
    return jax.tree.map(lambda *parts: jnp.concatenate(parts), *outs)


@partial(jax.jit, static_argnames=("block",))
def error_blocks(x, c, *, block=BLOCK):
    """E^D(C) as float32 partial sums, one per row block (sum on the host)."""
    def f(xb):
        return jnp.sum(jnp.min(sqdist(xb, c, HIGHEST), axis=1))[None]
    return _map_rows(f, x, block)


@partial(jax.jit, static_argnames=("block",))
def moment_blocks(x, *, block=BLOCK):
    """Per-block row counts, column sums and sums of squares about zero."""
    def f(xb):
        return jnp.sum(xb, axis=0)[None], jnp.sum(xb * xb, axis=0)[None]
    return _map_rows(f, x, block)


def error(x: jax.Array, c) -> float:
    """E^D(C) = Σ_x min_k ‖x − c_k‖² over all rows of ``x``."""
    return float(np.sum(np.asarray(error_blocks(x, jnp.asarray(c, jnp.float32)),
                                   np.float64)))


def total_sum_of_squares(x: jax.Array) -> float:
    """Σ ‖x − mean‖² over all rows: the error of one centroid at the mean."""
    s, q = (np.asarray(a, np.float64) for a in moment_blocks(x))
    n = x.shape[0]
    mean = s.sum(0) / n
    return float(q.sum() - n * np.dot(mean, mean))


# ----------------------------------------------------------- block statistics
def block_stats_f64(x_host: np.ndarray, block_id: np.ndarray, m: int):
    """``(psum, count, lo, hi)`` of rows per block id, float64 on the host.

    Empty rows get count 0 and lo = +inf, hi = -inf.
    """
    bid = np.asarray(block_id, np.int64)
    count = np.bincount(bid, minlength=m)[:m].astype(np.float64)
    psum = np.stack(
        [np.bincount(bid, weights=x_host[:, j], minlength=m)[:m]
         for j in range(x_host.shape[1])], axis=1)
    order = np.argsort(bid, kind="stable")
    sb = bid[order]
    starts = np.flatnonzero(np.r_[True, sb[1:] != sb[:-1]])
    ids = sb[starts]
    xs = x_host[order].astype(np.float64)
    lo = np.full((m, x_host.shape[1]), np.inf)
    hi = np.full((m, x_host.shape[1]), -np.inf)
    keep = ids < m
    lo[ids[keep]] = np.minimum.reduceat(xs, starts, axis=0)[keep]
    hi[ids[keep]] = np.maximum.reduceat(xs, starts, axis=0)[keep]
    return psum, count, lo, hi


@partial(jax.jit, static_argnames=("m",))
def block_stats_low(x, block_id, *, m):
    """The control's ``(psum, count, lo, hi)``: the same reductions carried
    out in bfloat16, the precision below the configuration's float32."""
    xb = x.astype(jnp.bfloat16)
    ones = jnp.ones(x.shape[0], jnp.bfloat16)
    return (jax.ops.segment_sum(xb, block_id, num_segments=m),
            jax.ops.segment_sum(ones, block_id, num_segments=m),
            jax.ops.segment_min(xb, block_id, num_segments=m),
            jax.ops.segment_max(xb, block_id, num_segments=m))


def weighted_error_f64(reps, w, c) -> float:
    """Σ w · min_k ‖rep − c_k‖² over the representatives, in float64."""
    reps = np.asarray(reps, np.float64)
    c = np.asarray(c, np.float64)
    d = ((reps[:, None, :] - c[None]) ** 2).sum(-1)
    return float(np.sum(np.asarray(w, np.float64) * d.min(1)))


@partial(jax.jit, static_argnames=("precision",))
def _weighted_error_f32(reps, w, c, *, precision):
    return jnp.sum(w * jnp.min(sqdist(reps, c, precision), axis=1))


def weighted_error_f32(reps, w, c, *, precision) -> float:
    """Σ w · min_k ‖rep − c_k‖² in float32, the distances' product at
    ``precision``: the control's report of its weighted error."""
    return float(_weighted_error_f32(reps, w, c, precision=precision))


@partial(jax.jit, static_argnames=("precision", "steps"))
def weighted_lloyd(reps, w, c, *, precision, steps=LLOYD_STEPS):
    """``steps`` weighted Lloyd steps from ``c`` over ``(reps, w)`` in float32
    with the distances' product at ``precision``: the control's Lloyd."""
    def step(_, c):
        lab = jnp.argmin(sqdist(reps, c, precision), axis=1)
        mass = jax.ops.segment_sum(w, lab, num_segments=c.shape[0])
        sums = jax.ops.segment_sum(w[:, None] * reps, lab, num_segments=c.shape[0])
        return jnp.where(mass[:, None] > 0, sums / jnp.maximum(mass, 1e-30)[:, None], c)
    return lax.fori_loop(0, steps, step, c)
