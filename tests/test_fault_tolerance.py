"""End-to-end fault-tolerance suite (ISSUE 9 tentpole acceptance).

Pins the layer's headline guarantees:

  * a streaming fit over a transient-IOError-injected source is
    **bit-identical** to the uninjected run (retry determinism), and the
    ``RunHealth`` counters match the injected schedule exactly;
  * skip-and-reweight mode completes a fit on the surviving mass and
    accounts for the loss;
  * the in-core engine quarantines non-finite rows deterministically;
  * the distributed engine survives losing one shard's round stats via
    drop-and-reweight (within 5% of the lossless run's final error, on 8
    fake devices) and aborts with :class:`ShardLossError` past the
    configured loss threshold;
  * every engine surfaces its ledger in ``FitResult.metadata["health"]``.
"""

import json
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro
from repro.core import bwkm
from repro.data import chunks as ck
from repro.data.resilient import ResilientChunkSource, RetryPolicy
from repro.distributed import dist_bwkm
from repro.distributed import sharding as sh
from repro.streaming import stream_bwkm
from repro.testing.faults import CorruptChunkSource, FakeClock, FlakyIOSource

SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")

N, D, K, CS = 4096, 4, 4, 512  # 8 chunks


def _points(seed: int = 0) -> np.ndarray:
    rng = np.random.RandomState(seed)
    centers = rng.randn(K, D).astype(np.float32) * 6
    z = rng.randint(0, K, N)
    return (centers[z] + rng.randn(N, D).astype(np.float32)).astype(np.float32)


CFG = bwkm.BWKMConfig(k=K, max_iters=6, lloyd_max_iters=20)


def _resilient(inner, **kw) -> ResilientChunkSource:
    clock = FakeClock()
    kw.setdefault("policy", RetryPolicy(max_attempts=4, base_delay_s=0.001))
    return ResilientChunkSource(inner, sleep=clock.sleep, clock=clock.time, **kw)


# ------------------------------------------------- streaming: bit-identical
def test_streaming_fit_bit_identical_under_transient_faults():
    """The acceptance bar: same seed, transient IOErrors injected on three
    chunks → the fit retries through them and the result is bit-identical
    to the clean run, with the retry count equal to the schedule's."""
    x = _points(1)
    key = jax.random.PRNGKey(3)
    clean = stream_bwkm.fit_streaming(key, ck.ArrayChunkSource(x, CS), CFG)

    schedule = {0: 1, 3: 2, 6: 1}
    faulty = _resilient(FlakyIOSource(ck.ArrayChunkSource(x, CS), schedule))
    injected = stream_bwkm.fit_streaming(key, faulty, CFG)

    np.testing.assert_array_equal(
        np.asarray(clean.centroids), np.asarray(injected.centroids)
    )
    assert injected.stop_reason == clean.stop_reason
    assert injected.health.retries == sum(schedule.values())
    assert injected.health.lost_chunks == 0
    assert not injected.health.degraded
    assert not clean.health.degraded  # clean run carries an all-zero ledger


def test_streaming_fit_deterministic_rerun_with_same_fault_schedule():
    """Two independent runs, same seed + same injected schedule → identical
    centroids AND identical health ledgers (retry determinism satellite)."""
    x = _points(2)
    schedule = {1: 1, 5: 3}

    def run():
        faulty = _resilient(FlakyIOSource(ck.ArrayChunkSource(x, CS), schedule))
        res = stream_bwkm.fit_streaming(jax.random.PRNGKey(9), faulty, CFG)
        return np.asarray(res.centroids), res.health.as_dict()

    c1, h1 = run()
    c2, h2 = run()
    np.testing.assert_array_equal(c1, c2)
    assert h1 == h2
    assert h1["retries"] == sum(schedule.values())


def test_streaming_skip_and_reweight_completes_and_accounts():
    x = _points(3)
    faulty = _resilient(
        FlakyIOSource(ck.ArrayChunkSource(x, CS), {2: 10**6}),
        on_exhausted="skip",
    )
    res = stream_bwkm.fit_streaming(jax.random.PRNGKey(5), faulty, CFG)
    assert np.isfinite(np.asarray(res.centroids)).all()
    assert res.health.lost_chunks == 1
    assert res.health.lost_points == CS
    assert res.health.degraded
    # quality sanity on the surviving mass: still a real clustering
    clean = stream_bwkm.fit_streaming(
        jax.random.PRNGKey(5), ck.ArrayChunkSource(x, CS), CFG
    )
    e_skip = float(res.weighted_errors[-1])
    e_clean = float(clean.weighted_errors[-1])
    assert e_skip <= e_clean * 1.5


def test_streaming_quarantine_counts_corrupt_rows():
    x = _points(4)
    faulty = _resilient(CorruptChunkSource(ck.ArrayChunkSource(x, CS), {4: 7}))
    res = stream_bwkm.fit_streaming(jax.random.PRNGKey(7), faulty, CFG)
    assert np.isfinite(np.asarray(res.centroids)).all()
    # cumulative over passes: a multiple of the 7 poisoned rows, ≥ one pass
    assert res.health.quarantined_rows >= 7
    assert res.health.quarantined_rows % 7 == 0
    assert res.health.degraded


# ------------------------------------------------------- in-core quarantine
def test_incore_quarantine_matches_prefiltered_fit():
    x = _points(5)
    bad = np.array([10, 999, 2048])
    x_bad = x.copy()
    x_bad[bad] = np.nan
    key = jax.random.PRNGKey(11)
    res_q = bwkm.fit_incore(key, jnp.asarray(x_bad), CFG)
    res_ref = bwkm.fit_incore(key, jnp.asarray(np.delete(x, bad, axis=0)), CFG)
    np.testing.assert_array_equal(
        np.asarray(res_q.centroids), np.asarray(res_ref.centroids)
    )
    assert res_q.health.quarantined_rows == 3
    assert res_q.health.degraded
    assert res_ref.health.quarantined_rows == 0


def test_incore_all_rows_nonfinite_raises():
    x = np.full((32, 3), np.nan, np.float32)
    with pytest.raises(ValueError, match="non-finite"):
        bwkm.fit_incore(jax.random.PRNGKey(0), jnp.asarray(x), bwkm.BWKMConfig(k=2))


# ------------------------------------------------------------ facade surface
def test_fit_result_metadata_carries_health():
    x = _points(6)
    model = repro.BWKM(k=K, max_iters=4, engine="incore").fit(x)
    health = model.result_.metadata["health"]
    assert health["degraded"] is False
    assert health["quarantined_rows"] == 0

    faulty = _resilient(
        FlakyIOSource(ck.ArrayChunkSource(x, CS), {0: 10**6}),
        on_exhausted="skip",
    )
    model_s = repro.BWKM(k=K, max_iters=4, engine="streaming").fit(faulty)
    health_s = model_s.result_.metadata["health"]
    assert health_s["degraded"] is True
    assert health_s["lost_chunks"] == 1


# -------------------------------------------------- distributed: shard loss
def test_distributed_shard_loss_abort_threshold():
    """Unmeshed path = one data shard; losing it exceeds any threshold and
    must abort, not fit thin air."""
    x = _points(7)
    with pytest.raises(dist_bwkm.ShardLossError, match="aborting"):
        dist_bwkm.fit_distributed(
            jax.random.PRNGKey(0), jnp.asarray(x), CFG, shard_faults={0: [0]}
        )


def test_distributed_nonfinite_stats_detected_unmeshed():
    """An Inf row poisons the single shard's stats; the (always-on)
    finite-sanitization zeroes the whole contribution → 100% loss → abort
    instead of NaN centroids."""
    x = _points(8).copy()
    x[5] = np.inf
    with pytest.raises(dist_bwkm.ShardLossError):
        dist_bwkm.fit_distributed(jax.random.PRNGKey(0), jnp.asarray(x), CFG)


_SHARD_LOSS_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.core import bwkm
    from repro.distributed import dist_bwkm, sharding as sh

    kc, kz, kn = jax.random.split(jax.random.PRNGKey(0), 3)
    centers = jax.random.normal(kc, (5, 6)) * 8
    z = jax.random.randint(kz, (4096,), 0, 5)
    x = (centers[z] + jax.random.normal(kn, (4096, 6))).astype(jnp.float32)
    cfg = bwkm.BWKMConfig(k=5, max_iters=12)

    mesh = jax.make_mesh(
        (2, 2, 2), ("pod", "data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 3,
    )
    with sh.use_mesh(mesh):
        xs = dist_bwkm.shard_points(x)
        n_shards = dist_bwkm.n_data_shards()
        assert n_shards == 4, n_shards
        clean = dist_bwkm.fit_distributed(jax.random.PRNGKey(1), xs, cfg)
        # lose shard 2's stats in round 1 (the first split round)
        lossy = dist_bwkm.fit_distributed(
            jax.random.PRNGKey(1), xs, cfg, shard_faults={1: [2]}
        )

    def err(c):
        xd = np.asarray(x, np.float64)
        cd = np.asarray(c, np.float64)
        d2 = ((xd[:, None, :] - cd[None, :, :]) ** 2).sum(-1)
        return float(d2.min(axis=1).sum())
    print(json.dumps({
        "err_clean": err(clean.centroids),
        "err_lossy": err(lossy.centroids),
        "iters_lossy": lossy.iterations,
        "health": lossy.health.as_dict(),
        "health_clean": clean.health.as_dict(),
    }))
    """
)


def test_distributed_shard_drop_and_reweight_on_8_fake_devices():
    """Acceptance: a distributed fit on 8 fake devices losing one shard's
    stats mid-round completes via drop-and-reweight, lands within 5% of the
    lossless run's final error, and reports accurate RunHealth counters."""
    r = subprocess.run(
        [sys.executable, "-c", _SHARD_LOSS_SCRIPT],
        capture_output=True, text=True,
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
             "HOME": "/root"},
    )
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    e_clean, e_lossy = out["err_clean"], out["err_lossy"]
    assert abs(e_lossy - e_clean) / min(e_clean, e_lossy) < 0.05, out
    h = out["health"]
    assert h["lost_shards"] == 1
    assert h["degraded_rounds"] == 1
    assert 0.2 < h["lost_mass_frac"] < 0.3  # one of four data shards
    assert h["degraded"] is True
    assert out["health_clean"]["degraded"] is False
