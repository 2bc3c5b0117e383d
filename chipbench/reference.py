"""The benchmark's plain reference, independent of the program under test.

Distances over many rows are ``jax.numpy`` float32 with the MXU at
``Precision.HIGHEST``, computed in row blocks of at most ``ELEMENTS``
distances, so that no array grows with rows × K at any row count or K;
the same functions at a lower precision are the control that ``correct``
must reject. Block counts, sums and boxes and the exact weighted error run
in NumPy on the host, in float64 where they sum, the weighted error in
row blocks too. Nothing here imports the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
#: rows per reference block at most
BLOCK = 1 << 15
#: distances per reference block at most: [rows, K] float32 stays at 32 MiB
ELEMENTS = 1 << 23
#: nearest centroids by the float32 distance whose float64 distance is taken
CANDIDATES = 8
#: representatives per float64 block on the host, one candidate at a time
HOST_ROWS = 4096
#: steps of the control's Lloyd: past where a fit's Lloyd has settled
LLOYD_STEPS = 50


def rows_for(k: int) -> int:
    """Rows per block against ``k`` centroids: a power of two, at most
    ``BLOCK``, with at most ``ELEMENTS`` distances a block."""
    return max(8, min(BLOCK, 1 << max(0, (ELEMENTS // k).bit_length() - 1)))


def sqdist(x: jax.Array, c: jax.Array, precision) -> jax.Array:
    """Squared distances ``[rows, K]`` by the expansion the program uses."""
    xc = jnp.dot(x, c.T, precision=precision)
    xn = jnp.sum(x * x, axis=1, keepdims=True)
    cn = jnp.sum(c * c, axis=1)[None, :]
    return jnp.maximum(xn - 2.0 * xc + cn, 0.0)


def _map_rows(f, xs, block):
    """``f`` over row blocks of ``xs``, an array or a tuple of arrays with
    the same number of rows; each block's outputs, whose leading axis may
    be of any length, concatenated along it."""
    n = jax.tree.leaves(xs)[0].shape[0]
    full = n // block
    outs = []
    if full:
        stacked = lax.map(
            lambda i: f(jax.tree.map(
                lambda a: lax.dynamic_slice_in_dim(a, i * block, block), xs)),
            jnp.arange(full),
        )
        outs.append(jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), stacked))
    if n % block:
        outs.append(f(jax.tree.map(lambda a: a[full * block:], xs)))
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
    c = jnp.asarray(c, jnp.float32)
    return float(np.sum(np.asarray(error_blocks(x, c, block=rows_for(c.shape[0])),
                                   np.float64)))


def total_sum_of_squares(x: jax.Array) -> float:
    """Σ ‖x − mean‖² over all rows: the error of one centroid at the mean."""
    s, q = (np.asarray(a, np.float64) for a in moment_blocks(x))
    n = x.shape[0]
    mean = s.sum(0) / n
    return float(q.sum() - n * np.dot(mean, mean))


# ----------------------------------------------------------- block statistics
def block_stats(x: jax.Array, block_id, m: int):
    """``(psum, count, lo, hi)`` of the rows of each block id ``< m``, on
    the host.

    Counts and coordinate sums are float64, summed in row order; boxes are
    the float32 minima and maxima of each block's rows, taken over the
    rows sorted by block. Every reduction runs over one contiguous column
    at a time. Empty blocks get count 0 and lo = +inf, hi = -inf. Ids out
    of range are counted in no block.
    """
    bid = np.asarray(block_id, np.int64)
    bid = np.where((bid >= 0) & (bid < m), bid, m)
    rows = np.bincount(bid, minlength=m + 1)[:m]
    # one contiguous row a coordinate: x is column-major on the chip
    xt = np.asarray(jnp.transpose(x))
    psum = np.stack([np.bincount(bid, weights=col.astype(np.float64), minlength=m + 1)[:m]
                     for col in xt])
    # rows out of range sort last and fall in no segment
    order = np.argsort(bid, kind="stable")[:rows.sum()]
    ids = np.flatnonzero(rows)
    starts = (np.cumsum(rows) - rows)[ids]
    lo = np.full((xt.shape[0], m), np.inf, np.float32)
    hi = np.full((xt.shape[0], m), -np.inf, np.float32)
    if ids.size:
        for j, col in enumerate(xt):
            sorted_col = col[order]
            lo[j, ids] = np.minimum.reduceat(sorted_col, starts)
            hi[j, ids] = np.maximum.reduceat(sorted_col, starts)
    # [m, d] views of the coordinate-major arrays
    return psum.T, rows.astype(np.float64), lo.T, hi.T


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


@partial(jax.jit, static_argnames=("j", "block"))
def nearest_candidates(reps, c, *, j, block):
    """``[M, j]``: the indices of each representative's ``j`` nearest
    centroids by the float32 distance at ``HIGHEST``, in row blocks."""
    return _map_rows(lambda rb: lax.top_k(-sqdist(rb, c, HIGHEST), j)[1], reps, block)


def weighted_error_f64(reps, w, c) -> float:
    """Σ w · min_k ‖rep − c_k‖² over the representatives, in float64.

    The float32 distances rank the centroids, and the float64 distance is
    taken to each representative's ``CANDIDATES`` nearest by them, so no
    array grows with M × K. That is the exact minimum unless the true
    nearest centroid ranks past ``CANDIDATES``, which takes that many
    centroids within the float32 distances' error of it; even then the
    minimum taken is off by less than that error (about 1e-3 against sums
    of about 1e8 in the fit cells). Both sides are shifted by the
    centroids' mean before the cast to float32, which keeps the ranking
    sharp on data far from the origin; the float64 distances are taken
    unshifted.
    """
    reps = np.asarray(reps, np.float64)
    c = np.asarray(c, np.float64)
    shift = c.mean(0)
    cand = nearest_candidates(jnp.asarray(reps - shift, jnp.float32),
                                 jnp.asarray(c - shift, jnp.float32),
                                 j=min(CANDIDATES, c.shape[0]), block=rows_for(c.shape[0]))
    return weighted_min_f64(reps, np.asarray(w, np.float64), c, np.asarray(cand))


def weighted_min_f64(reps, w, c, cand) -> float:
    """Σ w · min over the centroids that ``cand``'s row names of
    ‖rep − c‖², float64, in blocks of ``HOST_ROWS`` representatives."""
    total = 0.0
    for s in range(0, reps.shape[0], HOST_ROWS):
        r, ci = reps[s:s + HOST_ROWS], cand[s:s + HOST_ROWS]
        d = np.full(r.shape[0], np.inf)
        for j in range(ci.shape[1]):
            diff = r - c[ci[:, j]]
            np.minimum(d, np.einsum("ij,ij->i", diff, diff), out=d)
        total += float(np.dot(w[s:s + HOST_ROWS], d))
    return total


@partial(jax.jit, static_argnames=("precision", "block"))
def _weighted_error_f32(reps, w, c, *, precision, block):
    def f(rw):
        rb, wb = rw
        return jnp.sum(wb * jnp.min(sqdist(rb, c, precision), axis=1))[None]
    return jnp.sum(_map_rows(f, (reps, w), block))


def weighted_error_f32(reps, w, c, *, precision) -> float:
    """Σ w · min_k ‖rep − c_k‖² in float32, the distances' product at
    ``precision``, in row blocks: the control's report of its weighted
    error."""
    return float(_weighted_error_f32(reps, w, c, precision=precision,
                                     block=rows_for(c.shape[0])))


@partial(jax.jit, static_argnames=("precision", "steps", "block"))
def _weighted_lloyd(reps, w, c, *, precision, steps, block):
    def step(_, c):
        lab = _map_rows(lambda rb: jnp.argmin(sqdist(rb, c, precision), axis=1), reps, block)
        mass = jax.ops.segment_sum(w, lab, num_segments=c.shape[0])
        sums = jax.ops.segment_sum(w[:, None] * reps, lab, num_segments=c.shape[0])
        return jnp.where(mass[:, None] > 0, sums / jnp.maximum(mass, 1e-30)[:, None], c)
    return lax.fori_loop(0, steps, step, c)


def weighted_lloyd(reps, w, c, *, precision, steps=LLOYD_STEPS):
    """``steps`` weighted Lloyd steps from ``c`` over ``(reps, w)`` in float32
    with the distances' product at ``precision``: the control's Lloyd.
    Labels are taken in row blocks, so no array grows with M × K."""
    return _weighted_lloyd(reps, w, c, precision=precision, steps=steps,
                           block=rows_for(c.shape[0]))
