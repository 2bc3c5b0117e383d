"""End-to-end massive-data clustering driver — the paper's own workload.

Runs BWKM (single-host core or the distributed shard_map engine) against a
paper-profile synthetic dataset, with checkpointing of the clustering state
and the full baseline suite for comparison.

  PYTHONPATH=src python -m repro.launch.cluster --dataset WUY --scale 0.002 \
      --k 27 --compare --ckpt-dir /tmp/bwkm_ckpt
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import baselines, bwkm, metrics
from repro.data import paper_dataset
from repro.distributed import dist_bwkm, sharding as sh
from repro.launch.mesh import make_data_mesh


def main(argv: list[str] | None = None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="CIF")
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--k", type=int, default=9)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-iters", type=int, default=25)
    ap.add_argument("--distributed", action="store_true",
                    help="use the shard_map engine over every attached device")
    ap.add_argument("--compare", action="store_true",
                    help="also run the paper's baselines")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)

    x = jnp.asarray(paper_dataset(args.dataset, scale=args.scale, seed=args.seed))
    print(f"[cluster] dataset {args.dataset} n={x.shape[0]} d={x.shape[1]} K={args.k}")
    cfg = bwkm.BWKMConfig(k=args.k, max_iters=args.max_iters)
    key = jax.random.PRNGKey(args.seed)

    t0 = time.time()
    if args.distributed:
        mesh = make_data_mesh()
        with sh.use_mesh(mesh):
            xs = dist_bwkm.shard_points(x)
            res = dist_bwkm.fit_distributed(key, xs, cfg, checkpoint_dir=args.ckpt_dir)
    else:
        res = bwkm.fit_incore(key, x, cfg)
    e_bwkm = float(metrics.kmeans_error(x, res.centroids))
    out = {
        "bwkm": {
            "error": e_bwkm,
            "distances": res.distances,
            "iterations": res.iterations,
            "blocks": res.n_blocks[-1] if res.n_blocks else 0,
            "stop": res.stop_reason,
            "seconds": round(time.time() - t0, 2),
        }
    }
    print(f"[cluster] BWKM E={e_bwkm:.4e} distances={res.distances:.3e} "
          f"stop={res.stop_reason} ({out['bwkm']['seconds']}s)")

    if args.compare:
        runs = {
            "forgy": lambda k_: baselines.forgy_kmeans(k_, x, args.k),
            "km++": lambda k_: baselines.kmeanspp_kmeans(k_, x, args.k),
            "kmc2": lambda k_: baselines.kmc2_kmeans(k_, x, args.k),
            "mb100": lambda k_: baselines.minibatch_kmeans(k_, x, args.k, batch=100),
            "grid-rpkm": lambda k_: baselines.grid_rpkm(k_, x, args.k),
        }
        for i, (name, fn) in enumerate(runs.items()):
            r = fn(jax.random.PRNGKey(args.seed + 100 + i))  # unified FitResult
            e = float(metrics.kmeans_error(x, r.centroids))
            out[name] = {"error": e, "distances": r.distances}
            print(f"[cluster] {name:10s} E={e:.4e} distances={r.distances:.3e}")
        errs = {k: v["error"] for k, v in out.items()}
        rel = metrics.relative_errors(errs)
        for k in out:
            out[k]["relative_error"] = rel[k]
        print("[cluster] relative errors:",
              {k: round(v, 4) for k, v in rel.items()})
    return out


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
