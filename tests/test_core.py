"""Unit tests: weighted Lloyd, seeding, misassignment mechanics, BWKM driver,
baselines."""

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import baselines, bwkm, metrics, misassignment as mis, partition as pm
from repro.core.kmeanspp import afkmc2, forgy, kmeanspp, weighted_kmeanspp
from repro.core.lloyd import lloyd, weighted_lloyd
from repro.kernels import ref

from helpers import error_f64, gmm, weighted_error_f64


# ---------------------------------------------------------------- seeding
def test_forgy_selects_rows():
    x = gmm(jax.random.PRNGKey(0), 100, 3, 4)
    c = forgy(jax.random.PRNGKey(1), x, 5)
    xs = np.asarray(x)
    for row in np.asarray(c):
        assert (np.abs(xs - row).sum(1) < 1e-6).any()


def test_weighted_kmeanspp_ignores_zero_weight():
    key = jax.random.PRNGKey(2)
    x = jnp.concatenate([jnp.zeros((10, 2)), 100.0 + jnp.zeros((10, 2))])
    w = jnp.concatenate([jnp.ones(10), jnp.zeros(10)])
    for seed in range(5):
        c = weighted_kmeanspp(jax.random.PRNGKey(seed), x, w, 3)
        assert bool(jnp.all(c < 50.0)), "picked a zero-weight point"


def test_kmeanspp_spreads_seeds():
    """On well-separated clusters, KM++ should hit every cluster most times."""
    x = gmm(jax.random.PRNGKey(3), 3000, 2, 5, spread=30.0, noise=0.3)
    hits = 0
    for seed in range(10):
        c = kmeanspp(jax.random.PRNGKey(seed), x, 5)
        a, _, _ = ref.assign_top2(x, c)
        hits += int(len(np.unique(np.asarray(a))) == 5)
    assert hits >= 8


def test_afkmc2_selects_rows():
    x = gmm(jax.random.PRNGKey(4), 500, 3, 4)
    c = afkmc2(jax.random.PRNGKey(5), x, 4, chain_length=50)
    xs = np.asarray(x)
    for row in np.asarray(c):
        assert (np.abs(xs - row).sum(1) < 1e-6).any()


def test_forgy_never_seeds_zero_weight_padding_rows():
    """ISSUE 5 regression: on a padded partition with fewer positive-weight
    rows than K, the Gumbel top-k used to run out of finite scores and hand
    back padding rows as seeds. It must duplicate valid rows instead."""
    rng = np.random.RandomState(0)
    reps = np.zeros((64, 3), np.float32)  # mostly padding, like a Partition
    reps[:3] = rng.normal(size=(3, 3)).astype(np.float32) + 40.0
    w = np.zeros((64,), np.float32)
    w[:3] = 2.0
    c = forgy(jax.random.PRNGKey(0), jnp.asarray(reps), 5, w=jnp.asarray(w))
    norms = np.linalg.norm(np.asarray(c), axis=1)
    assert norms.min() > 1.0, f"padding row seeded: {norms}"
    # every seed is one of the three valid rows
    for row in np.asarray(c):
        assert (np.abs(reps[:3] - row).sum(1) < 1e-6).any()
    # same contract under tracing (the registry path is eager, but forgy is
    # documented jit-compatible)
    cj = jax.jit(lambda k, x, w: forgy(k, x, 5, w=w))(
        jax.random.PRNGKey(0), jnp.asarray(reps), jnp.asarray(w)
    )
    assert np.linalg.norm(np.asarray(cj), axis=1).min() > 1.0
    # and no positive weight at all is an error, not silent garbage
    with pytest.raises(ValueError, match="positive weight"):
        forgy(jax.random.PRNGKey(0), jnp.asarray(reps), 5, w=jnp.zeros(64))


def test_forgy_weighted_dense_unchanged():
    """The fallback must not disturb the well-posed case: with >= K
    positive-weight rows all seeds are distinct data rows."""
    x = gmm(jax.random.PRNGKey(30), 100, 3, 4)
    w = jnp.ones(100)
    c = np.asarray(forgy(jax.random.PRNGKey(1), x, 5, w=w))
    assert len(np.unique(c, axis=0)) == 5
    xs = np.asarray(x)
    for row in c:
        assert (np.abs(xs - row).sum(1) < 1e-6).any()


def _jaxpr_eqns_with_shape(jaxpr, shape, acc=None):
    """All (primitive-name, out-shape) eqns producing ``shape``, recursing
    into call/scan/pjit sub-jaxprs."""
    acc = [] if acc is None else acc
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            if tuple(getattr(getattr(v, "aval", None), "shape", ())) == shape:
                acc.append(eqn.primitive.name)
        for param in eqn.params.values():
            sub = param if isinstance(param, (tuple, list)) else [param]
            for p in sub:
                if isinstance(p, jax.extend.core.ClosedJaxpr):
                    _jaxpr_eqns_with_shape(p.jaxpr, shape, acc)
                elif isinstance(p, jax.extend.core.Jaxpr):
                    _jaxpr_eqns_with_shape(p, shape, acc)
    return acc


def test_afkmc2_proposals_are_o_n_memory_and_bit_identical():
    """ISSUE 5 regression: proposal sampling used to materialise an
    ``[chain_length, n]`` logits matrix (``logq[None, :].repeat(...)``)
    before ``categorical``. The batch must come from ``shape=`` instead —
    no reshape/broadcast/concat may build an [m, n] logits operand — and
    the draws must be bit-identical to the old expression (categorical
    broadcasts internally), so fixed seeds keep their centroids."""
    n, m, k = 500, 64, 4
    x = gmm(jax.random.PRNGKey(31), n, 3, k)

    jaxpr = jax.make_jaxpr(lambda key: afkmc2(key, x, k, chain_length=m))(
        jax.random.PRNGKey(0)
    )
    material = [
        p
        for p in _jaxpr_eqns_with_shape(jaxpr.jaxpr, (m, n))
        if p in ("reshape", "concatenate")
    ]
    assert not material, f"[chain_length, n] logits materialised via {material}"

    # seed compatibility: the new batched draw is the old draw, bit for bit
    logq = jnp.log(jnp.ones(n) / n)
    kidx = jax.random.PRNGKey(7)
    old = jax.random.categorical(kidx, logq[None, :].repeat(m, 0))
    new = jax.random.categorical(kidx, logq, shape=(m,))
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))

    # fixed key end-to-end determinism
    c1 = afkmc2(jax.random.PRNGKey(9), x, k, chain_length=m)
    c2 = afkmc2(jax.random.PRNGKey(9), x, k, chain_length=m)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


# ---------------------------------------------------------------- lloyd
def test_weighted_lloyd_monotone_weighted_error():
    key = jax.random.PRNGKey(6)
    x = gmm(key, 500, 4, 3)
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (500,))) + 0.1
    c0 = forgy(jax.random.PRNGKey(8), x, 3)
    errs = []
    c = c0
    for _ in range(6):
        res = weighted_lloyd(x, w, c, max_iters=1, epsilon=0.0)
        errs.append(weighted_error_f64(x, w, res.centroids))
        c = res.centroids
    assert all(e2 <= e1 * (1 + 1e-9) for e1, e2 in zip(errs, errs[1:])), errs


def test_lloyd_top2_consistency():
    x = gmm(jax.random.PRNGKey(9), 300, 3, 4)
    c0 = kmeanspp(jax.random.PRNGKey(10), x, 4)
    res = lloyd(x, c0, max_iters=10)
    assert bool(jnp.all(res.d1 <= res.d2 + 1e-6))
    d2ref = ref.pairwise_sqdist(x, res.centroids)
    np.testing.assert_array_equal(np.asarray(res.assign), np.asarray(d2ref).argmin(1))


def test_lloyd_empty_cluster_keeps_centroid():
    x = jnp.asarray([[0.0, 0.0], [1.0, 0.0], [0.0, 1.0]], jnp.float32)
    far = jnp.asarray([[100.0, 100.0]], jnp.float32)
    c0 = jnp.concatenate([x[:1], far])
    res = lloyd(x, c0, max_iters=3)
    np.testing.assert_allclose(np.asarray(res.centroids[1]), [100.0, 100.0])


def test_lloyd_counts_distances():
    """Kernel-reported counts (ISSUE 4 satellite): the dense path charges
    exactly active_rows·K per pass; the pruned path charges only rescanned
    rows plus the seeding and finishing passes — never more than dense + one
    pass, and strictly less once the bounds start settling rows."""
    x = gmm(jax.random.PRNGKey(11), 200, 2, 3)
    c0 = forgy(jax.random.PRNGKey(12), x, 3)
    res = lloyd(x, c0, max_iters=5, epsilon=0.0, prune=False)
    expected = 200 * 3 * (int(res.iters) + 1)  # +1 for the initial assignment
    assert float(res.distances) == expected

    pruned = lloyd(x, c0, max_iters=5, epsilon=0.0, prune=True)
    assert int(pruned.iters) == int(res.iters)
    # seeding + per-iteration active + finishing: bounded by dense + 1 pass
    assert float(pruned.distances) <= expected + 200 * 3
    assert float(pruned.distances) >= 2 * 200 * 3  # seed + finish at least

    # zero-weight rows are never charged, pruned or dense
    w = jnp.ones(200).at[:50].set(0.0)
    r = weighted_lloyd(x, w, c0, max_iters=1, epsilon=0.0, prune=False)
    assert float(r.distances) == 150 * 3 * (int(r.iters) + 1)


def test_weighted_lloyd_pruned_equals_dense():
    """ADR 0004 acceptance: pruning changes cost, never results — identical
    assignments/centroids/error on both kernel impls, with a real saving."""
    x = gmm(jax.random.PRNGKey(40), 4000, 5, 6, spread=20.0, noise=1.0)
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(41), (4000,))) + 0.1
    c0 = forgy(jax.random.PRNGKey(42), x, 6)
    for impl in ("ref", "pallas"):
        dn = weighted_lloyd(x, w, c0, max_iters=40, impl=impl, prune=False)
        pr = weighted_lloyd(x, w, c0, max_iters=40, impl=impl, prune=True)
        assert int(dn.iters) == int(pr.iters)
        np.testing.assert_array_equal(np.asarray(dn.assign), np.asarray(pr.assign))
        np.testing.assert_allclose(
            np.asarray(dn.centroids), np.asarray(pr.centroids), rtol=0, atol=1e-5
        )
        np.testing.assert_allclose(float(dn.error), float(pr.error), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(dn.d1), np.asarray(pr.d1),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dn.d2), np.asarray(pr.d2),
                                   rtol=1e-6, atol=1e-6)
        if int(dn.iters) >= 3:
            assert float(pr.distances) < float(dn.distances)


def test_drift_bound_soundness():
    """The maintained bounds stay valid: after a drift update, ub ≥ the true
    own-centroid distance and lb ≤ the true second-closest distance — so a
    skipped row's argmin provably cannot have changed (DESIGN.md §11)."""
    from repro.core.lloyd import drift_bound_update
    from repro.kernels import ref as kref

    for seed in range(5):
        key = jax.random.PRNGKey(seed)
        kx, kc, kd = jax.random.split(key, 3)
        x = jax.random.normal(kx, (300, 4)) * 5
        c = jax.random.normal(kc, (8, 4)) * 5
        a, d1, d2 = kref.assign_top2(x, c)
        ub = jnp.sqrt(d1)
        lb = jnp.sqrt(d2)
        c_new = c + 0.3 * jax.random.normal(kd, c.shape)
        drift = jnp.linalg.norm(c_new - c, axis=-1)
        ub2, lb2 = drift_bound_update(ub, lb, a, drift)
        dd = np.sqrt(np.asarray(kref.pairwise_sqdist(x, c_new)))
        own = dd[np.arange(300), np.asarray(a)]
        others = np.where(
            np.arange(8)[None] == np.asarray(a)[:, None], np.inf, dd
        ).min(axis=1)
        assert (np.asarray(ub2) >= own - 1e-5).all()
        assert (np.asarray(lb2) <= others + 1e-5).all()


def test_stats_error_identity_matches_rowwise():
    """stats_error ≡ Σ w·d1 (f64 oracle) under any assignment's stats."""
    from repro.core.lloyd import stats_error
    from repro.kernels import ref as kref

    x = gmm(jax.random.PRNGKey(43), 1000, 3, 4)
    w = jnp.abs(jax.random.normal(jax.random.PRNGKey(44), (1000,))) + 0.2
    c = forgy(jax.random.PRNGKey(45), x, 4)
    fu = kref.assign_update(x, w, c)
    w2 = jnp.sum(w * jnp.sum(x.astype(jnp.float32) ** 2, axis=-1))
    e_alg = float(stats_error(w2, c, fu.sums, fu.counts))
    e_row = weighted_error_f64(x, w, c)
    np.testing.assert_allclose(e_alg, e_row, rtol=5e-5)


# ---------------------------------------------------------------- misassignment
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_misassignment_matches_definition(seed):
    key = jax.random.PRNGKey(seed)
    x = gmm(key, 300, 3, 4)
    part = pm.create_partition(x, capacity=64)
    for i in range(3):
        part = pm.split_blocks(part, x, part.active)
    reps, w = pm.representatives(part)
    c = jax.random.normal(jax.random.PRNGKey(seed ^ 1), (4, 3)) * 6
    _, d1, d2 = ref.assign_top2(reps, c)
    eps = np.asarray(mis.misassignment(part, d1, d2))
    # recompute in f64
    reps64 = np.asarray(reps, np.float64)
    c64 = np.asarray(c, np.float64)
    lb = np.asarray(pm.diagonals(part), np.float64)
    dist = np.sqrt(((reps64[:, None] - c64[None]) ** 2).sum(-1))
    dist.sort(axis=1)
    delta = dist[:, 1] - dist[:, 0]
    occupied = np.asarray(part.count) > 0
    expect = np.where(occupied, np.maximum(0.0, 2 * lb - delta), 0.0)
    np.testing.assert_allclose(eps, expect, rtol=2e-3, atol=2e-3)


def test_sample_boundary_only_positive_eps():
    eps = jnp.asarray([0.0, 1.0, 0.0, 2.0, 0.0])
    for seed in range(10):
        chosen = mis.sample_boundary(jax.random.PRNGKey(seed), eps, 2)
        assert not bool(chosen[0] | chosen[2] | chosen[4])


def test_sample_boundary_empty_eps_selects_nothing():
    eps = jnp.zeros(8)
    chosen = mis.sample_boundary(jax.random.PRNGKey(0), eps, 4)
    assert not bool(jnp.any(chosen))


# ---------------------------------------------------------------- BWKM driver
def test_bwkm_reaches_kmpp_quality_with_fewer_distances():
    x = gmm(jax.random.PRNGKey(20), 30000, 5, 9, spread=10.0)
    res = bwkm.fit_incore(jax.random.PRNGKey(21), x, bwkm.BWKMConfig(k=9, max_iters=25))
    pp = baselines.kmeanspp_kmeans(jax.random.PRNGKey(22), x, 9)
    c_pp, d_pp = pp.centroids, pp.distances
    e_b = error_f64(x, res.centroids)
    e_pp = error_f64(x, c_pp)
    rel = (e_b - e_pp) / e_pp
    assert rel < 0.05, f"BWKM rel error vs KM++ {rel:.3f}"
    assert res.distances < 0.2 * d_pp, (res.distances, d_pp)


def test_bwkm_distance_budget_stops():
    x = gmm(jax.random.PRNGKey(23), 5000, 3, 4)
    res = bwkm.fit_incore(
        jax.random.PRNGKey(24),
        x,
        bwkm.BWKMConfig(k=4, max_iters=50, distance_budget=20000.0),
    )
    assert res.stop_reason in ("distance-budget", "boundary-empty")


def test_bwkm_blocks_grow_monotonically():
    x = gmm(jax.random.PRNGKey(25), 8000, 4, 5)
    res = bwkm.fit_incore(jax.random.PRNGKey(26), x, bwkm.BWKMConfig(k=5, max_iters=10))
    assert all(b2 >= b1 for b1, b2 in zip(res.n_blocks, res.n_blocks[1:]))
    assert res.n_blocks[0] >= 5  # at least K blocks after init


def test_bwkm_trace_for_benchmark():
    x = gmm(jax.random.PRNGKey(27), 4000, 3, 3)
    res = bwkm.fit_incore(
        jax.random.PRNGKey(28), x, bwkm.BWKMConfig(k=3, max_iters=6),
        trace_centroids=True,
    )
    assert len(res.trace) == res.iterations
    dists = [t["distances"] for t in res.trace]
    assert all(d2 >= d1 for d1, d2 in zip(dists, dists[1:]))


# ---------------------------------------------------------------- baselines
@pytest.mark.parametrize(
    "fn,kwargs",
    [
        (baselines.forgy_kmeans, {}),
        (baselines.kmeanspp_kmeans, {}),
        (baselines.kmc2_kmeans, {"chain_length": 50}),
        (baselines.minibatch_kmeans, {"batch": 100, "iters": 100}),
        (baselines.grid_rpkm, {"max_level": 4}),
    ],
)
def test_baselines_return_finite_solutions(fn, kwargs):
    x = gmm(jax.random.PRNGKey(30), 3000, 4, 5)
    res = fn(jax.random.PRNGKey(31), x, 5, **kwargs)
    c, d = res.centroids, res.distances
    assert c.shape == (5, 4)
    assert np.isfinite(np.asarray(c)).all()
    assert d > 0
    assert np.isfinite(error_f64(x, c))


def test_relative_errors():
    rel = metrics.relative_errors({"a": 100.0, "b": 110.0, "c": 150.0})
    assert rel["a"] == 0.0
    np.testing.assert_allclose(rel["b"], 0.1)


def test_kmeans_error_batched_matches_f64():
    x = gmm(jax.random.PRNGKey(32), 5000, 6, 4)
    c = kmeanspp(jax.random.PRNGKey(33), x, 4)
    e = float(metrics.kmeans_error(x, c, batch=512))
    assert abs(e - error_f64(x, c)) / error_f64(x, c) < 1e-4
