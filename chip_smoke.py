"""Smoke test of the BWKM fit, predict and service path on a TPU.

    python chip_smoke.py               # one chip: phases A-D below
    python chip_smoke.py --four-chips  # four chips: mesh fits vs one-chip fits

One chip, at the full SUSY profile of the paper's Table 1 (5,000,000 x 19
float32, K = 27), through the entry points a user calls:

  A. ``repro.BWKM(k=27).fit(x)`` with the defaults (k-means++, pruned Lloyd);
  B. ``repro.BWKM(k=27, init="kmeans||", prune=False).fit(x)``;
  C. ``predict`` on 8 request batches of 10,000 rows, ``score`` on all rows;
  D. ``repro.launch.serve --task clusters``: a ``partial_fit`` stream of
     1,048,576 rows, then 32 concurrent predict requests.

Every answer is checked against a float64 NumPy reference computed on the
host over all rows. The script runs in one process, starts no children, and
runs no phase on a fallback: without a TPU, or with a Mosaic kernel in
interpret mode, it stops before phase A. Any missed check makes it exit
non-zero without the result line. The phase times it prints are cold smoke
timings (compilation included), not benchmark numbers.

The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

K = 27
DATASET = "SUSY"
REF_CHUNK = 1 << 16

FAILURES: list[str] = []


def check(name: str, ok: bool, detail: str) -> None:
    print(f"[check] {name}: {'ok' if ok else 'FAILED'} ({detail})", flush=True)
    if not ok:
        FAILURES.append(name)


# ------------------------------------------------- float64 host reference
def ref_top2(x: np.ndarray, c) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Exact nearest and second-nearest squared distances in float64, over
    all rows in chunks: ``(labels, d1, d2)``."""
    c = np.asarray(c, np.float64)
    cn = (c * c).sum(1)
    n = x.shape[0]
    labels = np.empty(n, np.int64)
    d1 = np.empty(n, np.float64)
    d2 = np.empty(n, np.float64)
    for s in range(0, n, REF_CHUNK):
        xb = x[s : s + REF_CHUNK].astype(np.float64)
        dist = np.maximum((xb * xb).sum(1)[:, None] - 2.0 * xb @ c.T + cn, 0.0)
        part = np.partition(dist, 1, axis=1)
        labels[s : s + REF_CHUNK] = dist.argmin(1)
        d1[s : s + REF_CHUNK] = part[:, 0]
        d2[s : s + REF_CHUNK] = part[:, 1]
    return labels, d1, d2


def ref_lloyd_step(x: np.ndarray, c, labels: np.ndarray) -> np.ndarray:
    """One float64 Lloyd update; an empty cluster keeps its centroid."""
    c = np.asarray(c, np.float64)
    counts = np.bincount(labels, minlength=c.shape[0]).astype(np.float64)
    sums = np.stack(
        [np.bincount(labels, weights=x[:, j], minlength=c.shape[0])
         for j in range(x.shape[1])],
        axis=1,
    )
    return np.where(counts[:, None] > 0, sums / np.maximum(counts, 1)[:, None], c)


# ---------------------------------------------------------------- helpers
def device_line() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def mem(dev, key: str = "peak_bytes_in_use") -> int:
    return int(dev.memory_stats()[key])


def require_chip(n_devices: int) -> None:
    """No fallback: stop before any phase unless every seam runs a compiled
    Mosaic kernel on a TPU."""
    import jax

    from repro.kernels import ops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax.devices()[0] is {devs[0].platform!r})")
    if len(devs) < n_devices:
        sys.exit(f"chip_smoke: needs {n_devices} TPU devices, found {len(devs)}")
    if ops.backend() != "tpu":
        sys.exit(f"chip_smoke: kernel backend is {ops.backend()!r}, not 'tpu'")
    if ops.resolve_impl(None) != "pallas":
        sys.exit("chip_smoke: kernel impl resolves to "
                 f"{ops.resolve_impl(None)!r} (REPRO_KERNEL_IMPL set?)")
    if ops.interpret_mode():
        sys.exit("chip_smoke: Mosaic kernels would run in interpret mode")


def report_seams(n: int, d: int) -> None:
    """Print which path each kernel seam takes at the shapes the phases
    use, and check each compiles to a Mosaic kernel (``tpu_custom_call``)
    rather than an interpreted or pure-XLA program."""
    import jax
    import jax.numpy as jnp

    from repro.core import bwkm, kmeans_ll
    from repro.engine import driver
    from repro.kernels import fused_assign_update as fau
    from repro.kernels import ops

    p = bwkm.BWKMConfig(k=K).resolve(n, d)
    m = p["capacity"]  # weighted Lloyd runs over every partition row
    _, rounds, cap_round = driver.resolve_ll_params(
        K, kmeans_ll.default_oversampling(K), None
    )
    cands = 1 + rounds * cap_round
    f32, i32 = jnp.float32, jnp.int32

    def s(*shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype)

    def dense_path(k):
        return "fused" if fau.fused_supported(d, k) else "two-pass"

    seams = [
        (f"assign_update [{m},{d}]x[{K},{d}] ({dense_path(K)})",
         lambda x, w, c: ops.assign_update(x, w, c, impl="pallas"),
         (s(m, d), s(m), s(K, d))),
        (f"assign_update_pruned [{m},{d}]x[{K},{d}] ({dense_path(K)})",
         lambda x, w, c, a, act: ops.assign_update_pruned(
             x, w, c, a, act, impl="pallas"),
         (s(m, d), s(m), s(K, d), s(m, dtype=i32), s(m, dtype=jnp.bool_))),
        (f"min_sqdist_update [{m},{d}]x[{cap_round},{d}] (fold)",
         lambda x, w, c, v, md: ops.min_sqdist_update(
             x, w, c, v, md, impl="pallas"),
         (s(m, d), s(m), s(cap_round, d), s(cap_round), s(m))),
        (f"assign_update [{m},{d}]x[{cands},{d}] ({dense_path(cands)}, "
         "k-means|| weighting)",
         lambda x, w, c: ops.assign_update(x, w, c, impl="pallas"),
         (s(m, d), s(m), s(cands, d))),
        (f"assign_top2 [{REF_CHUNK},{d}]x[{K},{d}] (predict/score chunk)",
         lambda x, c: ops.assign_top2(x, c, impl="pallas"),
         (s(REF_CHUNK, d), s(K, d))),
    ]
    for name, fn, args in seams:
        text = jax.jit(fn).lower(*args).compile().as_text()
        check(f"seam {name}", "tpu_custom_call" in text, "Mosaic kernel compiled")


def timed(label: str, fn):
    t0 = time.perf_counter()
    out = fn()
    dt = time.perf_counter() - t0
    print(f"[time] {label}: {dt:.2f} s (cold smoke timing, compile included)",
          flush=True)
    return out


def check_fit(tag: str, model, x: np.ndarray, *, lloyd_step: bool) -> tuple:
    """Score against the float64 E^D of the returned centroids, and (for the
    default fit) one float64 Lloyd step from them."""
    r = model.result_
    print(f"[{tag}] engine={model.engine_} stop_reason={r.stop_reason} "
          f"iterations={r.iterations} distances={r.distances:.6e}", flush=True)
    labels, d1, d2 = ref_top2(x, model.centroids_)
    e64 = float(d1.sum())
    score = timed(f"{tag} score over {x.shape[0]} rows",
                  lambda: model.score(x))
    gap = abs(score - e64) / e64
    check(f"{tag} score vs float64 E^D", gap <= 1e-3,
          f"score={score:.9e} E64={e64:.9e} rel_gap={gap:.3e} <= 1e-3")
    if lloyd_step:
        e_next = float(ref_top2(x, ref_lloyd_step(x, model.centroids_, labels))[1].sum())
        drop = (e64 - e_next) / e64
        check(f"{tag} float64 Lloyd step", drop <= 1e-2,
              f"E64={e64:.9e} after_step={e_next:.9e} rel_drop={drop:.3e} <= 1e-2 "
              f"(stop_reason={r.stop_reason})")
    return labels, d1, d2


# ----------------------------------------------------------- one chip
def one_chip() -> None:
    import jax

    import repro
    from repro.data import paper_dataset
    from repro.launch import serve

    dev = jax.devices()[0]
    x = timed(f"generate {DATASET}", lambda: paper_dataset(DATASET, scale=1.0, seed=0))
    n, d = x.shape
    print(f"[data] {DATASET} n={n} d={d} K={K} dtype={x.dtype} "
          f"raw_bytes={x.nbytes}", flush=True)
    timed("seam compile checks", lambda: report_seams(n, d))

    # A. the default fit
    model = timed("A fit (kmeans++, pruned Lloyd)",
                  lambda: repro.BWKM(k=K).fit(x))
    check("A engine", model.engine_ == "incore", f"engine_={model.engine_}")
    labels, d1, d2 = check_fit("A", model, x, lloyd_step=True)
    print(f"[A] peak_bytes_in_use={mem(dev)}", flush=True)

    # B. k-means|| seeding, dense Lloyd
    model_b = timed("B fit (kmeans||, dense Lloyd)",
                    lambda: repro.BWKM(k=K, init="kmeans||", prune=False).fit(x))
    check("B engine", model_b.engine_ == "incore", f"engine_={model_b.engine_}")
    check_fit("B", model_b, x, lloyd_step=False)
    print(f"[B] peak_bytes_in_use={mem(dev)}", flush=True)

    # C. answers: predict on request batches drawn from x (score ran above)
    rng = np.random.RandomState(1)
    agree = total = 0
    t0 = time.perf_counter()
    for _ in range(8):
        idx = rng.randint(0, n, 10_000)
        pred = model.predict(x[idx])
        clear = (d2[idx] - d1[idx]) > 1e-4 * d1[idx]  # float64 top-2 gap
        agree += int(np.sum(pred[clear] == labels[idx][clear]))
        total += int(np.sum(clear))
    print(f"[time] C predict 8 x 10000 rows: {time.perf_counter() - t0:.2f} s "
          "(cold smoke timing, compile included)", flush=True)
    frac = agree / max(total, 1)
    check("C predict vs float64 argmin", frac >= 0.999,
          f"agree={frac:.6f} on {total} of 80000 rows with gap > 1e-4 rel, >= 0.999")
    print(f"[C] peak_bytes_in_use={mem(dev)}", flush=True)

    # D. the clustering service entry point, in this process
    out = timed("D serve --task clusters", lambda: serve.main([
        "--task", "clusters", "--dim", str(d), "--k", str(K),
        "--stream-chunks", "16", "--chunk-rows", "65536",
        "--requests", "32", "--request-rows", "1000",
    ]))
    lens = [int(lab.shape[0]) for lab in out["labels"]]
    check("D service answers", len(lens) == 32 and all(v == 1000 for v in lens),
          f"{len(lens)} requests, label lengths {sorted(set(lens))}")
    print(f"[D] peak_bytes_in_use={mem(dev)}", flush=True)


# --------------------------------------------------------- four chips
def separated_blobs(n: int, d: int, k: int, seed: int) -> np.ndarray:
    """``k`` well-separated Gaussian clusters (spread 30, noise 0.5): one
    optimum, so two engines must find the same partition of the rows."""
    rng = np.random.RandomState(seed)
    centers = rng.randn(k, d).astype(np.float32) * 30.0
    lab = rng.randint(0, k, n)
    return centers[lab] + 0.5 * rng.randn(n, d).astype(np.float32)


def mesh_vs_one_chip(tag: str, x: np.ndarray, mesh, *, gate_agreement: bool) -> None:
    """Fit ``x`` through the facade under ``mesh`` and on one chip, then
    compare float64 errors and, after permutation matching, the float64
    nearest-centroid labels of every row."""
    import repro
    from repro.distributed import sharding as sh
    from repro.engine import sharded

    n, d = x.shape
    with sh.use_mesh(mesh):
        xs = sharded.shard_points(x)
        devs = sorted(xs.sharding.device_set, key=lambda v: v.id)
        in_use = [mem(v, "bytes_in_use") for v in devs]
        check(f"{tag} x sharded over 4 devices",
              len(devs) == 4 and all(b > 0 for b in in_use),
              f"devices {[v.id for v in devs]}, bytes_in_use {in_use}")
        dist = timed(f"{tag} distributed fit", lambda: repro.BWKM(k=K).fit(xs))
    check(f"{tag} distributed engine", dist.engine_ == "distributed",
          f"engine_={dist.engine_}")
    core = timed(f"{tag} in-core fit (one chip)", lambda: repro.BWKM(k=K).fit(x))
    check(f"{tag} in-core engine", core.engine_ == "incore", f"engine_={core.engine_}")
    for name, m in (("distributed", dist), ("in-core", core)):
        r = m.result_
        print(f"[{tag} {name}] stop_reason={r.stop_reason} "
              f"iterations={r.iterations} distances={r.distances:.6e}", flush=True)

    lab_d, d1_d, _ = ref_top2(x, dist.centroids_)
    lab_c, d1_c, _ = ref_top2(x, core.centroids_)
    e_d, e_c = float(d1_d.sum()), float(d1_c.sum())
    gap = abs(e_d - e_c) / min(e_d, e_c)
    check(f"{tag} error distributed vs in-core", gap < 0.05,
          f"E64 distributed={e_d:.9e} in-core={e_c:.9e} rel_gap={gap:.3e} < 0.05")
    cc = np.asarray(core.centroids_, np.float64)
    cd = np.asarray(dist.centroids_, np.float64)
    perm = ((cc[:, None, :] - cd[None]) ** 2).sum(-1).argmin(1)
    bijection = sorted(perm.tolist()) == list(range(K))
    agree = float(np.mean(perm[lab_c] == lab_d))
    detail = f"bijection={bijection} agree={agree:.6f}"
    if gate_agreement:
        check(f"{tag} predict agreement after permutation matching",
              bijection and agree > 0.995, f"{detail} > 0.995")
    else:
        print(f"[{tag}] predict agreement after permutation matching: {detail} "
              "(not gated: K=27 over 10 overlapping modes has many local "
              "optima, so two engines' fits need not share one)", flush=True)


def four_chips() -> None:
    import jax

    from repro.data import paper_dataset
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh()
    print(f"[mesh] {dict(mesh.shape)}", flush=True)
    x = timed(f"generate {DATASET}", lambda: paper_dataset(DATASET, scale=1.0, seed=0))
    print(f"[data] {DATASET} n={x.shape[0]} d={x.shape[1]} K={K}", flush=True)
    mesh_vs_one_chip(DATASET, x, mesh, gate_agreement=False)
    sep = timed("generate separated", lambda: separated_blobs(*x.shape, K, seed=0))
    del x
    print(f"[data] separated n={sep.shape[0]} d={sep.shape[1]} K={K}", flush=True)
    mesh_vs_one_chip("separated", sep, mesh, gate_agreement=True)
    print(f"[mesh] peak_bytes_in_use {[mem(v) for v in jax.devices()]}", flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh fit and its one-chip "
                    "comparison")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    n_devices = 4 if args.four_chips else 1
    require_chip(n_devices)
    print(f"[setup] devices {device_line()}; compile cache {cache_dir}; "
          f"jax {jax.__version__}", flush=True)

    t0 = time.perf_counter()
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(f"[time] total {time.perf_counter() - t0:.2f} s", flush=True)
    if FAILURES:
        print(f"chip_smoke: {len(FAILURES)} check(s) failed: {FAILURES}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_line()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
