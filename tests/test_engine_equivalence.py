"""Cross-plane equivalence suite (ISSUE 10, ADR 0010).

All three engines now run the SAME outer loop — ``engine/driver.fit_plane``
— over their :class:`DataPlane`; what still differs per plane is how
routing/stats passes execute (in-core vmaps, chunked streaming passes,
psum'd shards) and which plane owns which PRNG stream. This suite pins the
consequence the refactor must preserve: on well-separated data every cell
of the {engine} × {init} × {prune} × {kernel-impl} matrix converges to the
same optimum and predicts the same labels (up to centroid permutation), and
fault-injected feeds — transient IOErrors on the streaming plane, a dropped
shard on 8 fake devices — do not move a plane away from the others.

This file replaces the scattered cross-engine agreement checks that used to
live in test_api.py / test_streaming.py / test_distributed.py; each of
those keeps a single smoke copy.
"""

import json
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import bwkm
from repro.data import chunks as ck
from repro.data.resilient import ResilientChunkSource, RetryPolicy
from repro.kernels import ops as kops
from repro.streaming import stream_bwkm
from repro.testing.faults import FakeClock, FlakyIOSource

from helpers import error_f64, gmm

SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")

ENGINES = ["incore", "streaming", "distributed"]


def _points(seed=13, n=1500, d=3, k=4):
    """Well-separated GMM: every plane converges to the same optimum, so
    cross-plane equivalence shows up as near-identical error and (after
    permutation matching) identical predictions."""
    return np.asarray(gmm(jax.random.PRNGKey(seed), n, d, k, spread=30.0, noise=0.5))


def _label_permutation(c_ref, c_other):
    """Map reference centroid j to its nearest counterpart; must be a
    bijection when both fits found the same optimum."""
    d2 = ((np.asarray(c_ref)[:, None, :] - np.asarray(c_other)[None]) ** 2).sum(-1)
    perm = d2.argmin(axis=1)
    assert sorted(perm.tolist()) == list(range(len(perm))), perm
    return perm


@pytest.fixture
def _restore_kernel_impl():
    yield
    kops.set_default_impl("auto")


# ------------------------------------- the engine × init × prune × impl matrix
@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("init", ["kmeans++", "forgy", "kmeans||"])
def test_fit_predict_matrix_agrees_across_planes(impl, init, _restore_kernel_impl):
    """One driver, three planes: fit_incore/fit_streaming/fit_distributed
    agreement must hold under the fused Pallas kernel (interpret mode on
    CPU) exactly as under the jnp oracle — same well-separated optimum for
    every cell of the matrix. ``weighted_lloyd``/the chunk programs key
    their jit caches on the resolved impl, so flipping the session default
    here exercises real retraces, not stale compilations.

    Data seed chosen so every cell converges to the shared optimum: with
    random-row inits (forgy) BWKM is seed-dependent on unlucky draws even on
    well-separated data (k-means local minima — see the verify notes).

    The prune dimension rides the same matrix (ADR 0004): every cell is
    fitted with the drift-bound pruned Lloyd ON and OFF, and the two fits
    must agree — same predicted assignments, centroids within 1e-5 —
    because pruning may change cost, never results."""
    x = _points(seed=13, n=1500)
    kops.set_default_impl(impl)
    errors, fitted = {}, {}
    for engine in ENGINES:
        fits = {}
        for prune in (True, False):
            m = repro.BWKM(
                k=4, engine=engine, init=init, max_iters=4, chunk_size=512,
                seed=0, prune=prune,
            ).fit(x)
            assert m.result_.stop_reason
            fits[prune] = m
        np.testing.assert_allclose(
            np.asarray(fits[True].centroids_),
            np.asarray(fits[False].centroids_),
            rtol=0, atol=1e-5, err_msg=f"{impl}/{init}/{engine}",
        )
        np.testing.assert_array_equal(
            fits[True].predict(x), fits[False].predict(x)
        )
        assert fits[True].result_.distances <= fits[False].result_.distances * 1.5
        errors[engine] = error_f64(x, fits[True].centroids_)
        fitted[engine] = fits[True]
    base = errors["incore"]
    for engine, err in errors.items():
        assert abs(err - base) / base < 1e-3, (impl, init, errors)

    # predict equivalence across planes: identical labels after matching
    # each plane's centroid permutation against the in-core one (planes own
    # different RNG streams, so centroid ORDER may differ — the partition of
    # the data must not). A tiny boundary tolerance absorbs ties.
    labels_ref = fitted["incore"].predict(x)
    for engine in ("streaming", "distributed"):
        perm = _label_permutation(
            fitted["incore"].centroids_, fitted[engine].centroids_
        )
        agree = np.mean(perm[labels_ref] == fitted[engine].predict(x))
        assert agree > 0.995, (impl, init, engine, agree)


# ------------------------------------------------------- the faults dimension
def test_streaming_faulty_feed_stays_equivalent_to_other_planes():
    """Transient IOErrors on the streaming feed must be invisible to the
    equivalence story: the injected run is bit-identical to the clean
    streaming run (retry determinism, ADR 0009) and therefore still lands
    on the in-core optimum."""
    x = _points(seed=17, n=4096)
    cfg = bwkm.BWKMConfig(k=4, max_iters=6)
    key = jax.random.PRNGKey(3)

    clean = stream_bwkm.fit_streaming(key, ck.ArrayChunkSource(x, 512), cfg)
    clock = FakeClock()
    faulty = ResilientChunkSource(
        FlakyIOSource(ck.ArrayChunkSource(x, 512), {0: 1, 3: 2, 6: 1}),
        policy=RetryPolicy(max_attempts=4, base_delay_s=0.001),
        sleep=clock.sleep, clock=clock.time,
    )
    injected = stream_bwkm.fit_streaming(key, faulty, cfg)
    np.testing.assert_array_equal(
        np.asarray(clean.centroids), np.asarray(injected.centroids)
    )
    assert injected.health.retries == 4

    e_inj = error_f64(x, injected.centroids)
    e_core = error_f64(
        x, bwkm.fit_incore(key, jnp.asarray(x), cfg).centroids
    )
    assert abs(e_inj - e_core) / e_core < 1e-3, (e_inj, e_core)


_MULTIDEV_EQUIV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import bwkm
    from repro.distributed import dist_bwkm, sharding as sh

    kc, kz, kn = jax.random.split(jax.random.PRNGKey(0), 3)
    centers = jax.random.normal(kc, (4, 5)) * 30
    z = jax.random.randint(kz, (4096,), 0, 4)
    x = (centers[z] + jax.random.normal(kn, (4096, 5)) * 0.5).astype(jnp.float32)
    cfg = bwkm.BWKMConfig(k=4, max_iters=8, init="kmeans||")

    mesh = jax.make_mesh(
        (2, 2, 2), ("pod", "data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 3,
    )
    with sh.use_mesh(mesh):
        xs = dist_bwkm.shard_points(x)
        assert dist_bwkm.n_data_shards() == 4
        res = dist_bwkm.fit_distributed(jax.random.PRNGKey(1), xs, cfg)
        # lose shard 2 in round 0, the initial routing round: on this
        # well-separated data the fit stops boundary-empty at iteration 1,
        # before split round 1 exists, so a later round would never fire
        lossy = dist_bwkm.fit_distributed(
            jax.random.PRNGKey(1), xs, cfg, shard_faults={0: [2]}
        )
    res_core = bwkm.fit_incore(jax.random.PRNGKey(1), x, cfg)

    xd = np.asarray(x, np.float64)
    def err(c):
        cd = np.asarray(c, np.float64)
        d2 = ((xd[:, None, :] - cd[None, :, :]) ** 2).sum(-1)
        return d2

    d_dist, d_core, d_lossy = (
        err(res.centroids), err(res_core.centroids), err(lossy.centroids)
    )
    # predict agreement after permutation-matching centroids
    cd = np.asarray(res_core.centroids, np.float64)
    cx = np.asarray(res.centroids, np.float64)
    perm = ((cd[:, None, :] - cx[None]) ** 2).sum(-1).argmin(axis=1)
    agree = float(np.mean(perm[d_core.argmin(1)] == d_dist.argmin(1)))
    print(json.dumps({
        "e_dist": float(d_dist.min(1).sum()),
        "e_core": float(d_core.min(1).sum()),
        "e_lossy": float(d_lossy.min(1).sum()),
        "perm_is_bijection": sorted(perm.tolist()) == list(range(4)),
        "predict_agree": agree,
        "lossy_health": lossy.health.as_dict(),
        "stop": res.stop_reason,
        "centroid_devices": len(res.centroids.devices()),
    }))
    """
)


def test_distributed_8_fake_devices_stays_equivalent():
    """The distributed plane on a real 2×2×2 mesh (4 data shards) must land
    on the same optimum as the in-core plane — same error to 5%, same
    predicted partition after permutation matching — and a dropped shard
    (drop-and-reweight, ADR 0009) must not break that equivalence."""
    r = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_EQUIV_SCRIPT],
        capture_output=True, text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": "/root"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    e_dist, e_core, e_lossy = out["e_dist"], out["e_core"], out["e_lossy"]
    assert abs(e_dist - e_core) / min(e_dist, e_core) < 0.05, out
    assert abs(e_lossy - e_core) / min(e_lossy, e_core) < 0.05, out
    assert out["perm_is_bijection"], out
    assert out["predict_agree"] > 0.995, out
    assert out["lossy_health"]["lost_shards"] == 1
    assert out["stop"] in ("boundary-empty", "max-iters")
    # the small per-fit state stays on one device: a Mosaic kernel cannot
    # be partitioned over operands replicated across the mesh
    assert out["centroid_devices"] == 1
