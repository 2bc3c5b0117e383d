"""The plain reference against float64 NumPy, at row counts that span
several of its row blocks and a ragged tail; the bounded weighted error
against the whole broadcast, and the blocked control against the whole
form.

    python -m pytest chipbench/tests/test_reference.py
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference


@pytest.mark.parametrize("n", [reference.BLOCK - 5, 2 * reference.BLOCK + 123])
def test_error_and_total_sum_of_squares_match_float64(n):
    rng = np.random.default_rng(n)
    x = (rng.standard_normal((n, 5)) * 3.0 + 1.5).astype(np.float32)
    c = x[rng.choice(n, 7, replace=False)]
    x64 = x.astype(np.float64)
    d = ((x64[:, None, :] - c.astype(np.float64)[None]) ** 2).sum(-1)
    want_error = d.min(1).sum()
    want_tss = ((x64 - x64.mean(0)) ** 2).sum()
    assert reference.error(x, c) == pytest.approx(want_error, rel=1e-5)
    assert reference.total_sum_of_squares(x) == pytest.approx(want_tss, rel=1e-5)


def _broadcast_f64(reps, w, c):
    """The weighted error by the whole [M, K, d] float64 broadcast."""
    d = ((reps[:, None, :] - c[None]) ** 2).sum(-1)
    return float(np.sum(w * d.min(1)))


def _near_ties(rng):
    """Two groups of representatives. About one point, within 1e-6: 6
    centroids at one distance from it to 1e-9, closer than float32 tells
    apart. At another point exactly: 16 centroids at exactly one distance.
    Other centroids lie farther from both."""
    d = 8
    r = 7.0
    base_a = rng.standard_normal(d) * 50.0
    base_b = base_a + 40 * r
    u = rng.standard_normal((6, d))
    u /= np.linalg.norm(u, axis=1, keepdims=True)
    ring = base_a + u * r * (1.0 + 1e-9 * rng.standard_normal((6, 1)))
    axes = base_b + np.concatenate([np.eye(d), -np.eye(d)]) * r
    far = base_a + rng.standard_normal((30, d)) * 3 * r + 10 * r
    reps = np.concatenate([base_a + rng.standard_normal((300, d)) * 1e-6,
                           np.repeat(base_b[None], 50, axis=0)])
    c = np.concatenate([ring, axes, far])
    return reps, c[rng.permutation(len(c))]


@pytest.mark.parametrize("case", ["plain", "far_from_origin", "near_ties", "k_below_candidates"])
def test_weighted_error_f64_equals_the_broadcast(case):
    rng = np.random.default_rng(len(case))
    if case == "near_ties":
        reps, c = _near_ties(rng)
    else:
        k = 5 if case == "k_below_candidates" else 64
        offset = 1e4 if case == "far_from_origin" else 0.0
        c = rng.standard_normal((k, 6)) * 4.0 + offset
        reps = c[rng.integers(k, size=3000)] + rng.standard_normal((3000, 6)) * 2.0
    w = rng.integers(1, 500, size=len(reps)).astype(np.float64)
    want = _broadcast_f64(reps, w, c)
    assert reference.weighted_error_f64(reps, w, c) == pytest.approx(want, rel=1e-12, abs=0)


def test_weighted_min_f64_host_memory_is_bounded():
    import tracemalloc

    m, k, d = 50_000, 4096, 128
    rng = np.random.default_rng(3)
    reps = rng.standard_normal((m, d))
    w = np.ones(m)
    c = rng.standard_normal((k, d))
    cand = rng.integers(k, size=(m, reference.CANDIDATES))
    tracemalloc.start()
    try:
        reference.weighted_min_f64(reps, w, c, cand)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    # the [M, K, d] broadcast would take m * k * d * 8 bytes: 210 GB
    assert peak < 512 * 2**20, peak


def _lloyd_unblocked(reps, w, c, precision, steps):
    def step(_, c):
        lab = jnp.argmin(reference.sqdist(reps, c, precision), axis=1)
        mass = jax.ops.segment_sum(w, lab, num_segments=c.shape[0])
        sums = jax.ops.segment_sum(w[:, None] * reps, lab, num_segments=c.shape[0])
        return jnp.where(mass[:, None] > 0, sums / jnp.maximum(mass, 1e-30)[:, None], c)
    return jax.lax.fori_loop(0, steps, step, c)


def test_blocked_control_matches_the_whole_form(monkeypatch):
    k = 16
    # 64 rows a block: 2,000 representatives take 31 blocks and a tail
    monkeypatch.setattr(reference, "ELEMENTS", 64 * k)
    assert reference.rows_for(k) == 64
    rng = np.random.default_rng(11)
    c0 = rng.standard_normal((k, 5)).astype(np.float32) * 6.0
    reps = jnp.asarray(c0[rng.integers(k, size=2000)]
                       + rng.standard_normal((2000, 5)).astype(np.float32))
    w = jnp.asarray(rng.integers(1, 300, size=2000).astype(np.float32))
    c0 = jnp.asarray(c0)
    high = jax.lax.Precision.HIGH
    got = reference.weighted_lloyd(reps, w, c0, precision=high, steps=7)
    want = _lloyd_unblocked(reps, w, c0, high, 7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    e_got = reference.weighted_error_f32(reps, w, want, precision=high)
    e_want = float(jnp.sum(w * jnp.min(reference.sqdist(reps, want, high), axis=1)))
    assert e_got == pytest.approx(e_want, rel=1e-5)


@pytest.mark.parametrize("k, rows", [(9, reference.BLOCK), (27, reference.BLOCK),
                                     (4096, 2048), (1 << 23, 8)])
def test_rows_for_keeps_a_block_at_most_elements(k, rows):
    assert reference.rows_for(k) == rows
    assert reference.rows_for(k) * k <= max(reference.ELEMENTS, 8 * k)


#: a SIFT1M coarse quantizer at its partition's capacity: 64 x 10 sqrt(K d)
M_CAP, K_BIG, D_BIG = 463_424, 4096, 128


def _big(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


@pytest.mark.parametrize("part", ["candidates", "control_lloyd", "control_error", "error"])
def test_device_parts_compile_bounded_at_k4096(part):
    """Compiled only: the [M, K] distances at capacity would take 7.6 GB."""
    block = reference.rows_for(K_BIG)
    low = jax.lax.Precision.HIGH
    fn, args = {
        "candidates": (lambda r, c: reference.nearest_candidates(r, c, j=8, block=block),
                       (_big(M_CAP, D_BIG), _big(K_BIG, D_BIG))),
        "control_lloyd": (lambda r, w, c: reference._weighted_lloyd(
            r, w, c, precision=low, steps=reference.LLOYD_STEPS, block=block),
            (_big(M_CAP, D_BIG), _big(M_CAP), _big(K_BIG, D_BIG))),
        "control_error": (lambda r, w, c: reference._weighted_error_f32(
            r, w, c, precision=low, block=block),
            (_big(M_CAP, D_BIG), _big(M_CAP), _big(K_BIG, D_BIG))),
        "error": (lambda x, c: reference.error_blocks(x, c, block=block),
                  (_big(1_000_000, D_BIG), _big(K_BIG, D_BIG))),
    }[part]
    temp = jax.jit(fn).lower(*args).compile().memory_analysis().temp_size_in_bytes
    assert temp < 2**30, temp


def _block_stats_by_rows(x, bid, m):
    """``(psum, count, lo, hi)`` row by row, float64."""
    d = x.shape[1]
    psum, count = np.zeros((m, d)), np.zeros(m)
    lo, hi = np.full((m, d), np.inf), np.full((m, d), -np.inf)
    for row, b in zip(x.astype(np.float64), bid):
        if 0 <= b < m:
            psum[b] += row
            count[b] += 1
            lo[b] = np.minimum(lo[b], row)
            hi[b] = np.maximum(hi[b], row)
    return psum, count, lo, hi


def test_block_stats_match_a_row_by_row_sum():
    rng = np.random.default_rng(5)
    n, d, m = 3000, 7, 200
    x = (rng.standard_normal((n, d)) * 30.0 + 7.0).astype(np.float32)
    # blocks 150.. are empty; a few ids fall outside [0, m)
    bid = rng.integers(0, 150, size=n).astype(np.int32)
    bid[:5] = [-1, m, m + 3, -7, 2**30]
    got = reference.block_stats(jnp.asarray(x), jnp.asarray(bid), m)
    want = _block_stats_by_rows(x, bid, m)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-13, atol=1e-10)
    np.testing.assert_array_equal(got[2], want[2].astype(np.float32))
    np.testing.assert_array_equal(got[3], want[3].astype(np.float32))
    assert got[2].dtype == got[3].dtype == np.float32
