"""Distributed engine tests. Most run on the trivial 1×1×1 mesh (same code
paths, no collectives); the multi-device equivalence test spawns a
subprocess with 8 fake CPU devices so this process keeps its single-device
view."""

import json
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bwkm, metrics
from repro.distributed import dist_bwkm, sharding as sh
from repro.launch.mesh import make_smoke_mesh

from helpers import error_f64, gmm

SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")


def test_dist_bwkm_trivial_mesh_matches_quality():
    """The single cross-plane smoke kept here — the full engine-equivalence
    matrix (init × prune × impl × faults) lives in
    tests/test_engine_equivalence.py."""
    x = gmm(jax.random.PRNGKey(0), 8000, 4, 5)
    with sh.use_mesh(make_smoke_mesh()):
        xs = dist_bwkm.shard_points(x)
        res = dist_bwkm.fit_distributed(jax.random.PRNGKey(1), xs, bwkm.BWKMConfig(k=5, max_iters=20))
    res_core = bwkm.fit_incore(jax.random.PRNGKey(1), x, bwkm.BWKMConfig(k=5, max_iters=20))
    e_dist = error_f64(x, res.centroids)
    e_core = error_f64(x, res_core.centroids)
    best = min(e_dist, e_core)
    assert abs(e_dist - e_core) / best < 0.05, (e_dist, e_core)


def test_dist_assign_step_matches_single_host():
    x = gmm(jax.random.PRNGKey(2), 2000, 3, 4)
    c0 = x[:4]
    with sh.use_mesh(make_smoke_mesh()):
        c1, err = dist_bwkm.dist_assign_step(x, c0)
    # reference
    from repro.kernels import ref

    a, d1, _ = ref.assign_top2(x, c0)
    sums, counts = ref.cluster_sums(x, jnp.ones(2000), a, 4)
    c_ref = sums / counts[:, None]
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(err), float(jnp.sum(d1)), rtol=1e-5)


def test_dist_lloyd_pruned_matches_dense_on_mesh():
    """ADR 0004 sharded: bound state carried shard-local across iterations,
    drift replicated, psum'd stats — pruned ≡ dense to 1e-5, fewer
    kernel-reported distance ops, and both match the in-core loop."""
    from repro.core.lloyd import weighted_lloyd

    x = gmm(jax.random.PRNGKey(7), 6000, 4, 5, spread=25.0, noise=0.8)
    c0 = x[:5] + 0.25
    with sh.use_mesh(make_smoke_mesh()):
        xs = dist_bwkm.shard_points(x)
        pruned = dist_bwkm.dist_lloyd(xs, c0, max_iters=30, epsilon=1e-5,
                                      prune=True)
        dense = dist_bwkm.dist_lloyd(xs, c0, max_iters=30, epsilon=1e-5,
                                     prune=False)
    assert pruned.iters == dense.iters
    np.testing.assert_allclose(
        np.asarray(pruned.centroids), np.asarray(dense.centroids),
        rtol=0, atol=1e-5,
    )
    assert pruned.distances < dense.distances

    incore = weighted_lloyd(x, jnp.ones(6000), c0, max_iters=30, epsilon=1e-5)
    np.testing.assert_allclose(
        np.asarray(pruned.centroids), np.asarray(incore.centroids),
        rtol=1e-4, atol=1e-3,
    )
    np.testing.assert_allclose(pruned.error, float(incore.error), rtol=1e-4)


_MULTIDEV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import bwkm, metrics
    from repro.distributed import dist_bwkm, sharding as sh

    key = jax.random.PRNGKey(0)
    kc, kz, kn = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (5, 6)) * 8
    z = jax.random.randint(kz, (4096,), 0, 5)
    x = (centers[z] + jax.random.normal(kn, (4096, 6))).astype(jnp.float32)

    mesh = jax.make_mesh(
        (2, 2, 2), ("pod", "data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 3,
    )
    with sh.use_mesh(mesh):
        xs = dist_bwkm.shard_points(x)
        assert len(set(d.id for d in xs.devices())) == 8
        res = dist_bwkm.fit_distributed(jax.random.PRNGKey(1), xs,
                            bwkm.BWKMConfig(k=5, max_iters=15))
        c1, err = dist_bwkm.dist_assign_step(xs, res.centroids)
        # ADR 0005: k-means|| on real shards — psum'd phi, candidates
        # gathered to every shard — both standalone and as the config init
        from repro.distributed import dist_kmeans_ll
        c_ll = dist_kmeans_ll.dist_kmeans_parallel(jax.random.PRNGKey(2), xs, 5)
        res_ll = dist_bwkm.fit_distributed(jax.random.PRNGKey(1), xs,
                            bwkm.BWKMConfig(k=5, max_iters=15, init="kmeans||"))
        # ADR 0004: pruned dist_lloyd on real shards — bounds live with the
        # points, drift replicated, psum'd stats; must equal its dense mode
        ll_p = dist_bwkm.dist_lloyd(xs, x[:5] + 0.25, max_iters=20,
                                    epsilon=1e-5, prune=True)
        ll_d = dist_bwkm.dist_lloyd(xs, x[:5] + 0.25, max_iters=20,
                                    epsilon=1e-5, prune=False)
    cdiff = float(jnp.abs(ll_p.centroids - ll_d.centroids).max())
    e = float(metrics.kmeans_error(x, res.centroids))
    print(json.dumps({"e_dist": e,
                      "stop": res.stop_reason, "err_step": float(err),
                      "lloyd_cdiff": cdiff, "lloyd_iters": [ll_p.iters, ll_d.iters],
                      "lloyd_dist": [ll_p.distances, ll_d.distances],
                      "e_kmeans_ll_seed": float(metrics.kmeans_error(x, c_ll)),
                      "e_kmeans_ll_fit": float(metrics.kmeans_error(x, res_ll.centroids)),
                      "kmeans_ll_stop": res_ll.stop_reason}))
    """
)


def test_dist_bwkm_on_8_fake_devices():
    """Real sharded execution: points over (pod,data), features over model,
    psum-combined stats. Cross-plane agreement on 8 fake devices moved to
    test_engine_equivalence.py; this pins the sharded internals (ADR 0004
    pruned ≡ dense, ADR 0005 k-means|| on real shards)."""
    r = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT],
        capture_output=True, text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": "/root"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["stop"] in ("boundary-empty", "max-iters")
    assert out["lloyd_cdiff"] <= 1e-5, out  # pruned ≡ dense on 8 shards
    assert out["lloyd_dist"][0] < out["lloyd_dist"][1], out  # real saving
    # k-means|| on 8 fake devices: the fit converges and the standalone
    # seeding is sane (ADR 0005 acceptance); the two inits share one optimum
    assert out["kmeans_ll_stop"] in ("boundary-empty", "max-iters")
    rel_ll = abs(out["e_kmeans_ll_fit"] - out["e_dist"]) / out["e_dist"]
    assert rel_ll < 0.05, out
    assert out["e_kmeans_ll_seed"] < 10 * out["e_dist"], out


def test_checkpoint_roundtrip_and_elastic_restore(tmp_path):
    from repro import configs
    from repro.models import transformer
    from repro.train import checkpoint as ckpt
    from repro.train import optimizer as opt

    cfg = configs.reduced_config(configs.get_config("granite-8b"))
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    state = {"params": params, "opt": opt.adamw_init(params)}
    ckpt.save(tmp_path, 7, state, extra={"step": 7})
    assert ckpt.latest_step(tmp_path) == 7

    restored, extra = ckpt.restore(tmp_path, 7, state)
    assert extra["step"] == 7
    for (p1, l1), (p2, l2) in zip(
        jax.tree_util.tree_flatten_with_path(state)[0][:10],
        jax.tree_util.tree_flatten_with_path(restored)[0][:10],
    ):
        np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_checkpoint_atomic_overwrite(tmp_path):
    from repro.train import checkpoint as ckpt

    tree = {"w": jnp.arange(10.0)}
    ckpt.save(tmp_path, 1, {"s": tree})
    ckpt.save(tmp_path, 1, {"s": {"w": jnp.arange(10.0) * 2}})
    restored, _ = ckpt.restore(tmp_path, 1, {"s": tree})
    np.testing.assert_allclose(np.asarray(restored["s"]["w"]), np.arange(10.0) * 2)


def test_token_stream_deterministic_and_elastic():
    from repro.data.tokens import TokenStream

    s = TokenStream(vocab=100, seq_len=16, global_batch=8, seed=3)
    t1, _ = s.batch(5)
    t2, _ = s.batch(5)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    # elastic: 2-host shards concatenate to the 1-host global batch
    a, _ = s.batch(5, host_id=0, n_hosts=2)
    b, _ = s.batch(5, host_id=1, n_hosts=2)
    np.testing.assert_array_equal(np.concatenate([a, b]), np.asarray(t1))
