"""CPU rehearsal of each cell at a tiny size, the kernels in interpret mode.

The run skips the look for a chip, shrinks the configuration to the sizes
under its file's ``"rehearsal"`` key, and prints a last line of the
result's shape marked ``"rehearsal": true``, with no metric: a CPU run
never reports a device metric.

    python -m pytest chipbench/tests/test_rehearsal.py
"""

import json

import pytest

from chipbench import manifest, run
from repro.kernels import ops

BENCH = manifest.load_manifest()


@pytest.fixture
def interpret_kernels():
    ops.set_default_impl("pallas")
    yield
    ops.set_default_impl("auto")


def rehearse(cell: str, seconds: float = 1.0, fault: str | None = None) -> dict:
    w = manifest.workload(BENCH, cell)
    args = run.parse(["--workload", cell, "--seed", str(2**33 + 17),
                      "--seconds", str(seconds), "--trace", "0"])
    cfg = manifest.config(BENCH, w["config"])
    return run.run(args, rehearsal={"config": cfg["rehearsal"]}, fault=fault)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_prints_the_result_shape(cell, interpret_kernels):
    result = json.loads(json.dumps(rehearse(cell)))
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert result["rehearsal"] is True
    assert result["metrics"] == {}
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"


def test_run_without_a_tpu_exits_nonzero_and_prints_nothing(capsys):
    cell = BENCH["workloads"][0]["name"]
    code = run.main(["--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert code != 0
    assert capsys.readouterr().out == ""
