"""Partition invariants: membership, tight boxes, refinement under splits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import partition as pm

from helpers import gmm


def _random_partition(key, x, rounds=4):
    part = pm.create_partition(x, capacity=256)
    for i in range(rounds):
        key, sub = jax.random.split(key)
        nb = int(part.n_blocks)
        chosen = jax.random.bernoulli(sub, 0.6, (part.capacity,)) & part.active
        part = pm.split_blocks(part, x, chosen)
    return part


def test_create_partition_single_block():
    x = gmm(jax.random.PRNGKey(0), 500, 3, 4)
    part = pm.create_partition(x, capacity=64)
    assert int(part.n_blocks) == 1
    assert bool(jnp.all(part.block_id == 0))
    np.testing.assert_allclose(part.lo[0], jnp.min(x, 0), rtol=1e-6)
    np.testing.assert_allclose(part.hi[0], jnp.max(x, 0), rtol=1e-6)
    assert float(part.count[0]) == 500.0


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_split_preserves_membership_and_counts(seed):
    x = gmm(jax.random.PRNGKey(seed), 2000, 5, 6)
    part = _random_partition(jax.random.PRNGKey(seed + 10), x)
    # counts sum to n
    assert float(jnp.sum(part.count)) == x.shape[0]
    # every point inside its block's tight box
    lo = part.lo[part.block_id]
    hi = part.hi[part.block_id]
    assert bool(jnp.all((x >= lo - 1e-5) & (x <= hi + 1e-5)))
    # active rows are exactly [0, n_blocks)
    nb = int(part.n_blocks)
    assert bool(jnp.all(part.active[:nb])) and not bool(jnp.any(part.active[nb:]))


def test_split_is_refinement():
    """Each post-split block's point set is a subset of one pre-split block."""
    x = gmm(jax.random.PRNGKey(3), 1000, 4, 5)
    part = pm.create_partition(x, capacity=64)
    part = pm.split_blocks(part, x, jnp.zeros(64, bool).at[0].set(True))
    before = np.asarray(part.block_id)
    chosen = jnp.zeros(64, bool).at[0].set(True).at[1].set(True)
    after_part = pm.split_blocks(part, x, chosen)
    after = np.asarray(after_part.block_id)
    for b_new in np.unique(after):
        parents = np.unique(before[after == b_new])
        assert parents.size == 1  # thinner partition (paper footnote 4)


def test_representatives_are_centers_of_mass():
    x = gmm(jax.random.PRNGKey(4), 1500, 3, 4)
    part = _random_partition(jax.random.PRNGKey(5), x)
    reps, w = pm.representatives(part)
    bid = np.asarray(part.block_id)
    xs = np.asarray(x, np.float64)
    for b in np.unique(bid):
        np.testing.assert_allclose(
            np.asarray(reps)[b], xs[bid == b].mean(0), rtol=2e-4, atol=2e-5
        )
        assert float(w[b]) == (bid == b).sum()


def test_singleton_blocks_never_split():
    x = jnp.asarray(np.random.RandomState(0).randn(4, 2), jnp.float32)
    part = pm.create_partition(x, capacity=32)
    for _ in range(8):  # split everything until only singletons remain
        part = pm.split_blocks(part, x, part.active)
    assert int(part.n_blocks) == 4
    assert float(jnp.max(part.count)) == 1.0
    nb_before = int(part.n_blocks)
    part2 = pm.split_blocks(part, x, part.active)
    assert int(part2.n_blocks) == nb_before


def test_capacity_respected():
    x = gmm(jax.random.PRNGKey(6), 512, 2, 3)
    part = pm.create_partition(x, capacity=8)
    for _ in range(6):
        part = pm.split_blocks(part, x, part.active)
    assert int(part.n_blocks) <= 8


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(20, 200),
    d=st.integers(1, 6),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_split_axis_separates(n, d, seed):
    """After a split, left-child points are <= mid and right-child > mid on
    the split axis; both children are inside the parent box."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d), jnp.float32) * 5
    part = pm.create_partition(x, capacity=16)
    lo0, hi0 = np.asarray(part.lo[0]), np.asarray(part.hi[0])
    axis = int(np.argmax(hi0 - lo0))
    mid = 0.5 * (lo0[axis] + hi0[axis])
    part = pm.split_blocks(part, x, jnp.zeros(16, bool).at[0].set(True))
    bid = np.asarray(part.block_id)
    xs = np.asarray(x)
    if int(part.n_blocks) == 2:
        assert (xs[bid == 0][:, axis] <= mid + 1e-6).all()
        assert (xs[bid == 1][:, axis] > mid - 1e-6).all()


@pytest.mark.parametrize("n", [96, 101])
def test_route_into_boxes_in_tiles_matches_one_pass(monkeypatch, n):
    """Routing in row tiles (the path every large dataset takes) gives the
    labels of the one-pass ``[n, M]`` rule, ragged last tile included."""
    x = gmm(jax.random.PRNGKey(5), n, 3, 4)
    part = _random_partition(jax.random.PRNGKey(6), x)
    whole = pm.route_into_boxes(x, part.lo, part.hi, part.active)
    monkeypatch.setattr(pm, "_ROUTE_TILE_ELEMS", 16 * part.capacity)
    tiled = pm.route_into_boxes(x, part.lo, part.hi, part.active)
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(whole))
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(part.block_id))


# ------------------------------------------------- split-round routing
def _route_split_by_gather(x, bid, plan):
    """The per-row gather formulation ``route_split`` replaced: four scalar
    plan lookups and a gather of ``x[axis]`` per row."""
    p_axis = plan.axis[bid]
    p_val = jnp.take_along_axis(x, p_axis[:, None], axis=1)[:, 0]
    goes_right = plan.fits[bid] & (p_val > plan.mid[bid])
    return jnp.where(goes_right, plan.right_row[bid], bid)


def _routing_case(d, n=1001, capacity=48):
    """A partition one split round away from full, and a plan whose rows
    sit on every edge: members exactly on ``mid`` (they go left), ``-0.0``
    and ``+0.0`` coordinates against a ``0.0`` plane, blocks that are not
    chosen, and chosen blocks whose right child would exceed capacity."""
    x = gmm(jax.random.PRNGKey(d), n, d, 5)
    part = pm.create_partition(x, capacity)
    grown = capacity - capacity // 4
    while int(part.n_blocks) < grown:
        splittable = part.active & (part.count > 1)
        first = jnp.cumsum(splittable) <= grown - part.n_blocks
        part = pm.split_blocks(part, x, splittable & first)
    chosen = part.active & (jnp.arange(capacity) % 5 != 0)
    plan = pm.split_plan(part, chosen)
    too_late = chosen & (part.count > 1) & ~plan.fits
    assert bool(jnp.any(too_late)) and bool(jnp.any(plan.fits))
    bid = part.block_id
    rows = np.arange(0, n, 7)
    col = np.asarray(plan.axis)[np.asarray(bid)[rows]]
    x = x.at[rows, col].set(plan.mid[bid[rows]])  # exactly on the plane
    zero_blocks = jnp.arange(capacity) % 3 == 0
    plan = plan._replace(mid=jnp.where(zero_blocks, 0.0, plan.mid))
    signed = np.arange(3, n, 11)
    col = np.asarray(plan.axis)[np.asarray(bid)[signed]]
    x = x.at[signed, col].set(jnp.where(signed % 2 == 0, -0.0, 0.0))
    x = x.at[signed[::3]].set(-0.0)  # whole rows of -0.0
    return x, bid, plan


@pytest.mark.parametrize("where", ["jit", "streaming"])
@pytest.mark.parametrize("d", [3, 19, 128])
def test_route_split_matches_the_gather_formulation_bit_for_bit(d, where):
    x, bid, plan = _routing_case(d)
    want = np.asarray(_route_split_by_gather(x, bid, plan))
    if where == "jit":
        got = jax.jit(pm.route_split)(x, bid, plan)
    else:
        from repro.engine.streaming import _split_route_stats

        n, cs = x.shape[0], 1024 + 128  # padded like a streaming chunk
        xp = jnp.zeros((cs, d), x.dtype).at[:n].set(x)
        bp = jnp.zeros((cs,), jnp.int32).at[:n].set(bid)
        got, _ = _split_route_stats(xp, bp, n, plan, m=plan.fits.shape[0])
        got = got[:n]
    np.testing.assert_array_equal(np.asarray(got), want)
    moved = want != np.asarray(bid)
    assert moved.any() and not moved.all()


def test_route_split_is_one_program_with_no_gather_into_x():
    n, d, m = 4096, 19, 256
    x = jax.ShapeDtypeStruct((n, d), jnp.float32)
    bid = jax.ShapeDtypeStruct((n,), jnp.int32)
    plan = pm.SplitPlan(
        jax.ShapeDtypeStruct((m,), jnp.bool_), jax.ShapeDtypeStruct((m,), jnp.int32),
        jax.ShapeDtypeStruct((m,), jnp.float32), jax.ShapeDtypeStruct((m,), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32),
    )
    text = pm.route_split.lower(x, bid, plan).as_text()
    ops = [line for line in text.splitlines() if "stablehlo.gather" in line
           or "stablehlo.transpose" in line]
    assert sum("stablehlo.gather" in line for line in ops) <= 1
    assert not [line for line in ops if f"tensor<{n}x{d}xf32>" in line]
    # an eager call is one dispatch: the whole round traces to one jit
    eqns = jax.make_jaxpr(pm.route_split)(x, bid, plan).eqns
    assert len(eqns) == 1 and eqns[0].params["name"] == "route_split"


def test_in_core_route_round_routes_in_one_call(monkeypatch):
    from repro.engine.incore import InCorePlane

    x = gmm(jax.random.PRNGKey(9), 600, 4, 3)
    plane = InCorePlane(x)
    part = pm.create_partition(plane.x, 32)
    plan = pm.split_plan(part, part.active)
    calls = []
    route = pm.route_split
    monkeypatch.setattr(pm, "route_split", lambda *a: calls.append(a) or route(*a))
    out = plane.route_round(part, plan, 1)
    assert len(calls) == 1
    ref = pm.split_blocks(part, x, part.active)
    np.testing.assert_array_equal(np.asarray(out.block_id), np.asarray(ref.block_id))
