"""Benchmark entry point: one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # default (CPU-sized)
  PYTHONPATH=src python -m benchmarks.run --quick    # smoke subset
  PYTHONPATH=src python -m benchmarks.run --full     # larger scales

Emits ``name,us_per_call,derived`` CSV:
  * tradeoff_*  — Figures 2–6 (distances vs relative error, per dataset × K)
  * assign_*    — the assignment-kernel micro-bench
  * stream_*    — out-of-core streaming driver vs in-memory (throughput)
  * lloyd_*     — drift-bound pruned Lloyd vs dense (distance-op trajectory)
  * init_*      — seeding strategies at matched budgets (k-means|| vs
                  kmeans++/forgy/afkmc2: passes, distance ops, final error)
  * service_*   — online service under drift (sustained points/sec, refit
                  latency, checkpoint size)
  * faults_*    — fault-injected streaming (quality vs lost-mass curve,
                  retry/recovery wall-clock overhead)
  * vq_*        — KV-cache quantization (reconstruction MSE vs k, cache
                  bytes, fit distance ops streaming vs in-core, decode
                  tokens/s ± quantization)
  * wallclock_* — measured ms/iteration + GB/s per kernel seam vs the
                  analytic roofline (``--wallclock`` runs only this)

Every ``BENCH_*.json`` this package writes is schema-checked on exit:
the record and each entry must be tagged ``measurement: analytic |
measured`` so model numbers can never masquerade as timings.

``--check-regress`` re-runs the two deterministic-counter benches
(bench_lloyd, bench_kernels) into a temp dir and fails if any counter —
distance ops, HBM bytes, active rows, iteration counts — drifts more than
1% from the committed ``BENCH_lloyd.json``/``BENCH_kernels.json``.
Wall-clock fields are never compared. Runs in the bench-smoke CI job.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import re
import tempfile

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

_ENTRY_TAGS = ("analytic", "measured")
_RECORD_TAGS = _ENTRY_TAGS + ("mixed",)

# ------------------------------------------------------------ --check-regress
#
# The perf-trajectory gate (ISSUE 10): re-run the two benches whose outputs
# are pure deterministic counters — bench_lloyd (kernel-reported distance
# ops per iteration) and bench_kernels (analytic HBM bytes under the
# selected blocking) — and diff the counters against the committed
# BENCH_lloyd.json / BENCH_kernels.json within 1%. Wall-clock fields
# (``*_s``, ``seconds``, ``tpu_model_s``) never participate: only numbers a
# code change can move deterministically are gated, so the check is stable
# on any runner while still catching a refactor that silently changes how
# many distances the engines compute or how many bytes a pass touches.

_REGRESS_FILES = ("BENCH_lloyd.json", "BENCH_kernels.json")
# leaf keys that ARE deterministic counters (everything else is skipped)
_COUNTER_KEY = re.compile(
    r"(distance_ops|n_dist|_bytes$|^active_rows$|^iterations(_dense)?$"
    r"|^pruned_fraction$|^reduction)"
)


def _counter_leaves(obj, path=()):
    """Yield ``(path, value)`` for every numeric leaf whose key names a
    deterministic counter."""
    if isinstance(obj, dict):
        for k, v in obj.items():
            if isinstance(v, (dict, list)):
                yield from _counter_leaves(v, path + (k,))
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                if _COUNTER_KEY.search(k):
                    yield path + (k,), float(v)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            yield from _counter_leaves(v, path + (str(i),))


def check_regress(fresh_dir: pathlib.Path, root: pathlib.Path = REPO_ROOT,
                  rel_tol: float = 0.01) -> list[str]:
    """Compare fresh counter leaves against the committed records. A missing
    committed file is an error (the gate exists to protect it); a counter
    present on one side only is an error (schema drift is a regression too)."""
    errors = []
    for name in _REGRESS_FILES:
        committed_path, fresh_path = root / name, fresh_dir / name
        if not committed_path.exists():
            errors.append(f"{name}: no committed record at {committed_path}")
            continue
        committed = dict(_counter_leaves(json.loads(committed_path.read_text())))
        fresh = dict(_counter_leaves(json.loads(fresh_path.read_text())))
        for path in sorted(set(committed) | set(fresh)):
            dotted = ".".join(path)
            if path not in committed:
                errors.append(f"{name}: {dotted} only in fresh run")
            elif path not in fresh:
                errors.append(f"{name}: {dotted} only in committed record")
            else:
                want, got = committed[path], fresh[path]
                if abs(got - want) > rel_tol * max(abs(want), 1.0):
                    errors.append(
                        f"{name}: {dotted} moved {want} -> {got} "
                        f"(>{rel_tol:.0%} drift)"
                    )
    return errors


def _run_check_regress() -> None:
    from benchmarks import bench_kernels, bench_lloyd

    with tempfile.TemporaryDirectory() as td:
        tdp = pathlib.Path(td)
        bench_lloyd.main(["--out", str(tdp / "BENCH_lloyd.json")])
        bench_kernels.main(["--out", str(tdp / "BENCH_kernels.json")])
        errors = check_regress(tdp)
    if errors:
        raise SystemExit(
            "--check-regress: deterministic counters drifted from the "
            "committed BENCH records:\n  " + "\n  ".join(errors)
            + "\n(an intentional perf change must re-commit the records)"
        )
    print("# --check-regress: deterministic counters within 1% of committed")


def check_bench_schema(root: pathlib.Path = REPO_ROOT) -> list[str]:
    """Every ``BENCH_*.json``: the record carries ``measurement`` in
    {analytic, measured, mixed}; every dict element of a top-level list
    carries its own ``measurement`` in {analytic, measured}."""
    errors = []
    for path in sorted(root.glob("BENCH_*.json")):
        try:
            rec = json.loads(path.read_text())
        except ValueError as e:
            errors.append(f"{path.name}: unreadable JSON ({e})")
            continue
        if rec.get("measurement") not in _RECORD_TAGS:
            errors.append(
                f"{path.name}: record 'measurement' must be one of "
                f"{_RECORD_TAGS}, got {rec.get('measurement')!r}"
            )
        for key, val in rec.items():
            if not isinstance(val, list):
                continue
            for i, e in enumerate(val):
                if isinstance(e, dict) and e.get("measurement") not in _ENTRY_TAGS:
                    errors.append(
                        f"{path.name}: {key}[{i}] missing/invalid "
                        "'measurement' tag (analytic|measured)"
                    )
    return errors


def _check_or_die() -> None:
    errors = check_bench_schema()
    if errors:
        raise SystemExit(
            "BENCH_*.json schema check failed:\n  " + "\n  ".join(errors)
        )
    print("# BENCH_*.json schema check: ok")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--full", action="store_true")
    ap.add_argument(
        "--wallclock", action="store_true",
        help="run only the wall-clock seam harness + the schema check",
    )
    ap.add_argument(
        "--check-regress", action="store_true",
        help="re-run bench_lloyd/bench_kernels and fail if their "
             "deterministic counters drift >1%% from the committed "
             "BENCH_*.json records",
    )
    args = ap.parse_args()

    if args.check_regress:
        _run_check_regress()
        return

    if args.wallclock:
        from benchmarks import bench_wallclock

        bench_wallclock.main(["--quick"] if args.quick else [])
        _check_or_die()
        return

    from benchmarks import (
        bench_faults, bench_init, bench_kernels, bench_lloyd, bench_service,
        bench_streaming, bench_tradeoff, bench_vq, bench_wallclock,
    )

    if args.quick:
        bench_tradeoff.main(["--datasets", "CIF", "--ks", "3", "--reps", "1"])
        bench_streaming.main(["--n", "50000", "--max-iters", "8"])
    elif args.full:
        # the paper's full grid: 5 datasets x K in {3,9,27} x repetitions
        bench_tradeoff.main(["--full", "--ks", "3", "9", "27", "--reps", "3"])
        bench_streaming.main(["--n", "2000000", "--chunk", "65536"])
    else:
        # default CPU budget: every figure (all 5 datasets) at K=9 + the
        # K-sweep on the smallest dataset
        bench_tradeoff.main(["--ks", "9", "--reps", "1"])
        bench_tradeoff.main(["--datasets", "CIF", "--ks", "3", "27", "--reps", "1"])
        bench_streaming.main([])
    bench_kernels.main([])
    bench_lloyd.main([])
    bench_init.main(["--reps", "1"] if args.quick else [])
    bench_service.main([])
    bench_faults.main(
        ["--n", "30000", "--max-iters", "5"] if args.quick else []
    )
    bench_vq.main(["--ks", "16"] if args.quick else [])
    bench_wallclock.main(["--quick"] if args.quick else [])
    _check_or_die()


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
