"""Spans and per-fit counters of the BWKM host driver (``repro.obs``).

A small in-core default fit on the CPU is run and its
``metadata["counters"]`` checked against what actually happened:

* every device-to-host read is a call of one of ``ArrayImpl``'s conversion
  methods, so counting those calls counts the fit's host syncs;
* every pass over all ``n`` rows is a call of a full-data function with
  the ``[n, d]`` data, so counting those calls counts the data passes;
* under ``jax.profiler.trace`` the ``bwkm.*`` spans form the tree of
  PERF.md §3, and the fit's results are bit-identical with the profiler
  off.
"""

import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import array as jarray

import repro
from repro import obs
from repro.core import partition as part_mod

K = 4
SYNC_METHODS = ("__float__", "__int__", "__bool__", "__index__", "item", "__array__")


def _data(n=3200, d=3, seed=0):
    rng = np.random.default_rng(seed)
    centres = rng.normal(0.0, 6.0, (K, d))
    x = np.concatenate([rng.normal(c, 0.7, (n // K, d)) for c in centres])
    return jnp.asarray(x.astype(np.float32))


def _fit(x, engine="auto"):
    return repro.BWKM(k=K, engine=engine).fit(x, key=jax.random.PRNGKey(3)).result_


@pytest.fixture(scope="module")
def x():
    return _data()


@pytest.fixture(scope="module")
def warm(x):
    """One fit first, so later fits compile nothing."""
    return _fit(x)


def test_every_device_to_host_read_is_counted(x, warm, monkeypatch):
    calls = {"n": 0, "depth": 0}

    def counting(method):
        def wrapper(self, *args, **kwargs):
            calls["depth"] += 1
            try:
                if calls["depth"] == 1:
                    calls["n"] += 1
                return method(self, *args, **kwargs)
            finally:
                calls["depth"] -= 1
        return wrapper

    for name in SYNC_METHODS:
        monkeypatch.setattr(jarray.ArrayImpl, name, counting(getattr(jarray.ArrayImpl, name)))
    res = _fit(x)
    monkeypatch.undo()
    syncs = res.metadata["counters"]["host_syncs"]
    assert calls["n"] == syncs
    # at least the init's growth checks and five reads in every round
    assert syncs >= 5 * res.iterations


def test_every_pass_over_all_rows_is_counted(x, warm, monkeypatch):
    calls = {"n": 0}

    def counting(fn):
        def wrapper(*args, **kwargs):
            reads_all = any(
                isinstance(a, jax.Array) and not isinstance(a, jax.core.Tracer)
                and a.shape == x.shape
                for a in args
            )
            calls["n"] += reads_all
            return fn(*args, **kwargs)
        return wrapper

    monkeypatch.setattr(jnp, "isfinite", counting(jnp.isfinite))  # finite-row check
    for name in ("route_split", "recompute_stats", "route_into_boxes"):
        monkeypatch.setattr(part_mod, name, counting(getattr(part_mod, name)))
    res = _fit(x)
    monkeypatch.undo()
    passes = res.metadata["counters"]["data_passes"]
    assert calls["n"] == passes
    # the finite-row check, create_partition, and two per route round
    assert passes >= 2 + 2 * (res.iterations - 1)


def _bwkm_events(trace_dir):
    """``(name, start_ns, end_ns, ids)`` of the ``bwkm.*`` host spans."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name, _, suffix = ev.name.partition("#")
                if not name.startswith("bwkm."):
                    continue
                ids = {k: int(v) for k, v in ev.stats if k in ("fit", "round")}
                for item in filter(None, suffix.strip("#").split(",")):
                    k, _, v = item.partition("=")
                    ids[k] = int(v)
                out.append((name, int(ev.start_ns), int(ev.end_ns), ids))
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module")
def traced(x, warm, tmp_path_factory):
    trace_dir = tmp_path_factory.mktemp("obs_trace")
    with jax.profiler.trace(str(trace_dir)):
        res = _fit(x)
    return res, _bwkm_events(trace_dir)


def test_a_traced_fit_has_the_span_tree(traced):
    res, events = traced
    by_name = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e)
    (fit,) = by_name["bwkm.fit"]
    assert len(by_name["bwkm.init"]) == 1
    assert len(by_name["bwkm.plane"]) == len(by_name["bwkm.seed"]) == 1
    rounds = by_name["bwkm.round"]
    assert len(rounds) == res.iterations
    assert [r[3]["round"] for r in rounds] == list(range(1, res.iterations + 1))
    assert {e[3]["fit"] for e in events} == {fit[3]["fit"]}
    for name, s, e, _ in events:
        assert fit[1] <= s and e <= fit[2], name
    (init,) = by_name["bwkm.init"]
    for name in ("bwkm.init.start", "bwkm.init.grow"):
        for _, s, e, _ in by_name.get(name, []):
            assert init[1] <= s and e <= init[2]
    assert len(by_name["bwkm.init.start"]) == 1
    for _, s, e, ids in rounds:
        children = [c[0] for c in events
                    if c[0] != "bwkm.round" and s <= c[1] and c[2] <= e]
        for child in ("bwkm.lloyd", "bwkm.boundary", "bwkm.stop"):
            assert children.count(child) == 1, (ids, children)
        inside = [c for c in events if s <= c[1] and c[2] <= e and c[0] != "bwkm.round"]
        assert all(c[3]["round"] == ids["round"] for c in inside)


def test_results_are_bit_identical_with_the_profiler_on(x, traced):
    res_on, _ = traced
    res_off = _fit(x)
    np.testing.assert_array_equal(np.asarray(res_on.centroids), np.asarray(res_off.centroids))
    assert res_on.distances == res_off.distances
    assert res_on.iterations == res_off.iterations
    assert res_on.metadata["weighted_errors"] == res_off.metadata["weighted_errors"]
    assert res_on.metadata["counters"] == res_off.metadata["counters"]


@pytest.mark.parametrize("engine", ["incore", "streaming", "distributed"])
def test_every_engine_reports_its_counters(engine):
    res = _fit(np.asarray(_data(n=2000, seed=1)), engine=engine)
    counters = res.metadata["counters"]
    assert set(counters) == {"host_syncs", "data_passes"}
    assert counters["host_syncs"] > 0 and counters["data_passes"] > 0
    assert "health" in res.metadata


def test_trace_rows_carry_no_plane_specific_keys():
    res = repro.BWKM(k=K, engine="streaming", trace=True, max_iters=3).fit(
        np.asarray(_data(n=2000, seed=2)), key=jax.random.PRNGKey(0)).result_
    assert res.trace
    assert {tuple(sorted(row)) for row in res.trace} == {
        ("boundary", "centroids", "distances", "iteration", "n_blocks")}


def test_nested_scopes_count_once_and_pull_outside_counts_nothing():
    one = jnp.asarray(1.0)
    assert obs.pull(one) == 1.0
    with obs.fit_scope() as outer:
        obs.pull(one)
        with obs.fit_scope() as inner:
            assert inner is outer
            assert obs.pull(jnp.asarray(2), int) == 2
            assert obs.pull(jnp.asarray(True), bool) is True
            obs.data_pass()
        assert obs.pull(3.5) == 3.5  # a host value: no sync
    obs.pull(one)
    obs.data_pass()
    assert outer.counts == {"host_syncs": 3, "data_passes": 1}
    with obs.fit_scope() as later:
        assert later is not outer and later.seq > outer.seq


def test_fits_on_two_threads_keep_their_own_counters():
    barrier = threading.Barrier(2)
    seen = {}

    def work(i):
        with obs.fit_scope() as fc:
            barrier.wait()
            for _ in range(i + 1):
                obs.data_pass()
            barrier.wait()
            seen[i] = (fc.seq, dict(fc.counts))

    threads = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert seen[0][0] != seen[1][0]
    assert seen[0][1]["data_passes"] == 1 and seen[1][1]["data_passes"] == 2
