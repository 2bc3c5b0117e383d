"""The fit window at a CPU size: it holds at least ``quality_fits`` fits at
any length, its first ``quality_fits`` fits come from the pool's first keys
in order, the fit quality is read over those alone, the checked fits are
drawn among them, and only the checked fits and the window's last keep
their partition.

    python -m pytest chipbench/tests/test_window.py
"""

import numpy as np
import pytest

from chipbench import check, loops, manifest
from chipbench.spans import Spans
from chipbench.traffic import fit_keys

BENCH = manifest.load_manifest()
#: the fastest fit cell on the CPU: fewest rows and centroids
CELL = "3rn_k9.fit"
SEED = 2**33 + 5


@pytest.fixture(scope="module")
def window():
    w = manifest.workload(BENCH, CELL)
    cfg = manifest.config(BENCH, w["config"])
    cfg = {**cfg, **cfg["rehearsal"]}
    mix = manifest.traffic(w["traffic"])
    picks = check.checked_fits(SEED, mix["quality_fits"])
    state = loops.fit_setup(cfg)
    win = loops.fit_window(cfg, mix, 0.1, state, Spans(), keep=set(picks))
    return {"cfg": cfg, "mix": mix, "picks": picks, "state": state, "win": win}


def test_a_short_window_holds_quality_fits_from_the_first_keys(window):
    import repro

    win, q = window["win"], window["mix"]["quality_fits"]
    assert q == win["quality_fits"] == 4
    assert len(win["results"]) >= q
    keys = fit_keys(window["mix"])
    for j in range(q):
        model = repro.BWKM(k=window["cfg"]["k"]).fit(window["state"]["x"], key=keys[j])
        np.testing.assert_array_equal(np.asarray(win["results"][j].centroids),
                                      np.asarray(model.centroids_))


@pytest.mark.parametrize("seed", [0, 7, 99, 424242, 2**31 + 11, 2**40 + 3])
def test_checked_fits_fall_among_the_first(seed):
    picks = check.checked_fits(seed, 4)
    assert picks == sorted(set(picks)) and len(picks) == check.CHECKED_FITS
    assert set(picks) <= set(range(4))
    assert check.checked_fits(seed, 4) == picks
    assert check.checked_fits(seed, 2) == [0, 1]


def test_only_the_checked_fits_and_the_last_keep_their_partition(window):
    win, picks = window["win"], window["picks"]
    last = len(win["results"]) - 1
    assert win["kept"] == sorted(set(picks) | {last})
    for i, r in enumerate(win["results"]):
        assert ("partition" in r.metadata) == (i in win["kept"]), i
        assert r.metadata["weighted_errors"] and r.iterations >= 1 and r.stop_reason
        assert "counters" in r.metadata and r.distances > 0
        assert (r.centroids is not None) == (i < win["quality_fits"]), i


def test_fit_error_share_reads_exactly_the_first_quality_fits(window):
    win, state = window["win"], window["state"]
    q = win["quality_fits"]
    want = check.fit_error_share(state["x"], win["results"][:q], state["tss"])
    assert loops.fit_metrics(win, state)["fit_error_share"] == want
    # more fits past the first quality_fits, as a faster program's window
    # holds, leave it as it was; those hold no centroids to read
    more = {**win, "results": win["results"] + [loops.slim(win["results"][0], False)] * 5}
    assert loops.fit_metrics(more, state)["fit_error_share"] == want
    shares = [check.fit_error_share(state["x"], [r], state["tss"]) for r in win["results"][:q]]
    assert want == pytest.approx(np.mean(shares), rel=1e-12)
