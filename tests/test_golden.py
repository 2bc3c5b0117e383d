"""Golden FitResult regression (ISSUE 3 satellite).

A small fixed-seed fit per engine is serialized to
``tests/golden/bwkm_fitresult.json`` — centroids, exact f64 error,
distance-op count, iterations, stop reason. Every engine must keep
reproducing its golden record, guarding future kernel changes (fused
blocking tweaks, accumulation-order changes) against *silent* quality
drift: a kernel bug that degrades solutions without failing parity
tolerances shows up here as an error/centroid mismatch.

Regenerate deliberately after an intended algorithm change:

    PYTHONPATH=src python tests/test_golden.py --regen

and review the numeric diff like any other code change.
"""

import json
import os
import pathlib

# Mirror conftest.py so standalone --regen runs use the same backend as the
# pytest run that consumes the golden file.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

import repro  # noqa: E402
from helpers import error_f64, gmm

GOLDEN_PATH = pathlib.Path(__file__).parent / "golden" / "bwkm_fitresult.json"
ENGINES = ["incore", "streaming", "distributed"]

# Fixed-seed workload: small but with OVERLAPPING clusters, so every engine
# runs a full 5-outer-iteration trajectory (well-separated data stops at
# boundary-empty after one iteration — too little trajectory to guard).
DATA_SEED, N, D, K = 5, 2000, 3, 4


def _data():
    return np.asarray(
        gmm(jax.random.PRNGKey(DATA_SEED), N, D, K, spread=8.0, noise=2.0)
    )


def _fit(engine: str):
    x = _data()
    m = repro.BWKM(
        k=K, engine=engine, max_iters=5, chunk_size=512, seed=0
    ).fit(x)
    res = m.result_
    c = np.asarray(res.centroids, np.float64)
    c = c[np.lexsort(c.T[::-1])]  # row order is not part of the contract
    return {
        "centroids": c.round(6).tolist(),
        "error": round(error_f64(x, res.centroids), 4),
        "distances": float(res.distances),
        "iterations": int(res.iterations),
        "stop_reason": res.stop_reason,
    }


@pytest.mark.parametrize("engine", ENGINES)
def test_engines_reproduce_golden_fitresult(engine):
    assert GOLDEN_PATH.exists(), (
        f"{GOLDEN_PATH} missing — regenerate with "
        "PYTHONPATH=src python tests/test_golden.py --regen"
    )
    golden = json.loads(GOLDEN_PATH.read_text())[engine]
    got = _fit(engine)
    assert got["stop_reason"] == golden["stop_reason"]
    assert got["iterations"] == golden["iterations"]
    # distances may wiggle with trajectory fp jitter across BLAS builds (the
    # boundary draw is ∝ ε); error/centroids are the quality pin — a kernel
    # bug that corrupts sufficient statistics moves them far past these.
    np.testing.assert_allclose(got["distances"], golden["distances"], rtol=0.05)
    np.testing.assert_allclose(got["error"], golden["error"], rtol=1e-3)
    np.testing.assert_allclose(
        np.asarray(got["centroids"]),
        np.asarray(golden["centroids"]),
        rtol=5e-3,
        atol=5e-2,
    )


def _regen():
    GOLDEN_PATH.parent.mkdir(parents=True, exist_ok=True)
    record = {e: _fit(e) for e in ENGINES}
    GOLDEN_PATH.write_text(json.dumps(record, indent=2) + "\n")
    print(f"wrote {GOLDEN_PATH}")
    for e, r in record.items():
        print(f"  {e}: error={r['error']} distances={r['distances']} "
              f"iters={r['iterations']} stop={r['stop_reason']}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--regen", action="store_true")
    if ap.parse_args().regen:
        _regen()
