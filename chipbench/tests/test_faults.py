"""A run with its timed path broken underneath reads ``correct`` false, and
so does the control.

The run skips the look for a chip and is shrunk to the CPU size under its
configuration file's ``"rehearsal"`` key; each fault of
``chipbench.faults`` is planted under the window in turn. The fault in
the distances' precision lives in the Mosaic kernels, so that run has them
in interpret mode, and runs on the SUSY cell only: at 3RN's CPU size
(6,000 rows, d = 3) one bfloat16 pass flips too few representatives to
show, and the readings at the cells' own sizes on the chip decide there.

    python -m pytest chipbench/tests/test_faults.py
"""

import pytest

from chipbench import check, faults, loops, manifest, run
from chipbench.spans import Spans
from repro.kernels import ops

BENCH = manifest.load_manifest()
FIT_CELLS = [w["name"] for w in BENCH["workloads"]
             if manifest.traffic(w["traffic"])["loop"] == "fit"]
#: the number each fault must push past its limit
FIT_CATCHES = {"unchanged": "err_gap", "half_batch": "count_err", "altered": "err_gap"}
#: one fit in a window, checked: a fault is in every fit, and the window's
#: own rules are ``test_window.py``'s
ONE_FIT = {"quality_fits": 1}


def _rehearsal(cell: str) -> dict:
    """The sizes the configuration's file gives a CPU run."""
    return manifest.config(BENCH, manifest.workload(BENCH, cell)["config"])["rehearsal"]


def _rehearse(cell: str, fault: str | None) -> dict:
    args = run.parse(["--workload", cell, "--seed", "424242", "--seconds", "0.5",
                      "--trace", "0"])
    return run.run(args, rehearsal={"config": _rehearsal(cell), "traffic": ONE_FIT},
                   fault=fault)


@pytest.mark.parametrize("fault", sorted(FIT_CATCHES))
@pytest.mark.parametrize("cell", FIT_CELLS)
def test_fit_fault_reads_not_correct(cell, fault):
    result = _rehearse(cell, fault)
    assert result["correct"] is False
    caught = result["checks"][FIT_CATCHES[fault]]
    assert caught["value"] > caught["limit"], result["checks"]


def test_lowp_distances_read_not_correct():
    assert "lowp_distances" in faults.FIT_FAULTS
    ops.set_default_impl("pallas")
    try:
        result = _rehearse("susy_k27.fit", "lowp_distances")
    finally:
        ops.set_default_impl("auto")
    assert result["correct"] is False
    caught = result["checks"]["err_gap"]
    assert caught["value"] > caught["limit"], result["checks"]


@pytest.mark.parametrize("cell", FIT_CELLS)
def test_fit_control_reads_not_correct(cell):
    w = manifest.workload(BENCH, cell)
    cfg = {**manifest.config(BENCH, w["config"]), **_rehearsal(cell)}
    mix = {**manifest.traffic(w["traffic"]), **ONE_FIT}
    picks = check.checked_fits(99, mix["quality_fits"])
    state = loops.fit_setup(cfg)
    win = loops.fit_window(cfg, mix, 0.5, state, Spans(), keep=set(picks))
    limits = manifest.limits(cell)
    sound = check.fit_numbers(state["x"], win["results"], win["kept"])
    control = check.fit_numbers(state["x"], win["results"], win["kept"], control=True)
    assert check.judge(sound, limits)[0] is True
    assert check.judge(control, limits)[0] is False
