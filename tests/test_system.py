"""End-to-end system tests: the drivers, examples-level flows, and the
paper's qualitative claims at small scale."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, bwkm, metrics
from repro.data import paper_dataset
from repro.launch import cluster as cluster_driver
from repro.launch import train as train_driver

from helpers import gmm


def test_train_driver_end_to_end_loss_decreases(tmp_path):
    out = train_driver.main([
        "--arch", "granite-8b", "--reduced", "--steps", "12", "--batch", "2",
        "--seq", "64", "--lr", "5e-3",
        "--ckpt-dir", str(tmp_path), "--ckpt-every", "6",
    ])
    assert out["final_loss"] < out["losses"][0]
    # checkpoint written and resumable
    out2 = train_driver.main([
        "--arch", "granite-8b", "--reduced", "--steps", "14", "--batch", "2",
        "--seq", "64", "--ckpt-dir", str(tmp_path), "--ckpt-every", "6",
    ])
    assert len(out2["losses"]) == 14 - 12  # resumed from step 12


def test_cluster_driver_end_to_end():
    out = cluster_driver.main([
        "--dataset", "CIF", "--scale", "0.05", "--k", "3", "--compare",
    ])
    assert out["bwkm"]["error"] > 0
    # single-seed run: any method (incl. Forgy/KM++) can land in a worse
    # basin, so assert the robust paper claims — cost ordering + sane quality
    # (the averaged-protocol quality claim is test_paper_headline_tradeoff)
    assert out["bwkm"]["relative_error"] < 0.5
    assert out["bwkm"]["distances"] < out["km++"]["distances"]
    assert out["bwkm"]["distances"] < out["forgy"]["distances"]


def test_cluster_driver_distributed_checkpoint(tmp_path):
    out = cluster_driver.main([
        "--dataset", "3RN", "--scale", "0.01", "--k", "3",
        "--distributed", "--ckpt-dir", str(tmp_path),
    ])
    from repro.train import checkpoint as ckpt

    assert ckpt.latest_step(tmp_path) is not None
    assert out["bwkm"]["error"] > 0


def test_paper_headline_tradeoff():
    """The paper's core claim under the paper's averaged protocol: BWKM is
    quality-competitive with KM++ (within 10% on average) at a multiple
    fewer distance computations. (Per-seed results vary — the paper itself
    reports 12/15 configs under 1% only after 40-rep averaging.)"""
    x = jnp.asarray(paper_dataset("3RN", scale=0.05, seed=1))
    k = 9
    e_pp, d_pp, e_bw, d_bw = [], [], [], []
    for seed in range(3):
        pp = baselines.kmeanspp_kmeans(jax.random.PRNGKey(seed), x, k)
        c, d = pp.centroids, pp.distances
        e_pp.append(float(metrics.kmeans_error(x, c)))
        d_pp.append(d)
        res = bwkm.fit_incore(
            jax.random.PRNGKey(100 + seed), x, bwkm.BWKMConfig(k=k, max_iters=25)
        )
        e_bw.append(float(metrics.kmeans_error(x, res.centroids)))
        d_bw.append(res.distances)
    assert np.mean(e_bw) <= 1.10 * np.mean(e_pp), (e_bw, e_pp)
    # distance-ratio floor: ~3x at this n (the gap scales with n — the
    # paper's full-size 3RN shows 1–3 orders; BWKM's block count is
    # n-independent while Lloyd's cost is linear in n)
    assert np.mean(d_bw) * 3 <= np.mean(d_pp), (d_bw, d_pp)


def test_input_specs_cover_all_cells():
    from repro import configs

    for arch, sname in configs.runnable_cells():
        cfg = configs.get_config(arch)
        shape = configs.SHAPES[sname]
        specs = configs.input_specs(cfg, shape)
        if shape.kind == "train":
            assert specs["tokens"].shape == (shape.global_batch, shape.seq_len)
            assert "labels" in specs
        elif shape.kind == "prefill":
            assert specs["tokens"].shape == (shape.global_batch, shape.seq_len)
        else:
            assert specs["token"].shape == (shape.global_batch,)
            assert "cache" in specs
            leaves = jax.tree.leaves(specs["cache"])
            assert leaves and all(hasattr(l, "shape") for l in leaves)
        # no allocation: everything is a ShapeDtypeStruct
        for leaf in jax.tree.leaves(specs):
            assert isinstance(leaf, jax.ShapeDtypeStruct)


def test_swa_cache_bounded_for_long_context():
    """mixtral long_500k is runnable because the ring cache is window-bounded."""
    from repro import configs
    from repro.models import cache as cache_mod

    cfg = configs.get_config("mixtral-8x22b")
    specs = cache_mod.cache_specs(cfg, batch=1, seq_len=524_288)
    assert specs["k"].shape[2] == cfg.window  # 4096, not 524288


def test_compile_cache_dir_comes_from_env_or_checkout(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` is left to JAX; without it the entry
    points cache at ``<checkout>/.jax_cache``, a fixed path."""
    import pathlib

    from repro.launch import cache

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = pathlib.Path(__file__).resolve().parent.parent
    try:
        assert cache.enable_compile_cache() == str(checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(checkout / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
