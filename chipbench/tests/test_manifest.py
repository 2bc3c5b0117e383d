"""Every cell's configuration, traffic, limits and metric files are found by
name, and names and units keep to the allowed characters.

    python -m pytest chipbench/tests/test_manifest.py
"""

import json

import pytest

from chipbench import manifest

BENCH = manifest.load_manifest()


def test_manifest_has_no_faults():
    assert manifest.validate(BENCH) == []


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_finds_its_files(cell):
    w = manifest.workload(BENCH, cell)
    cfg = manifest.config(BENCH, w["config"])
    assert {"n", "d", "k", "generator", "reduced", "assumed", "rehearsal"} <= set(cfg)
    assert set(cfg["rehearsal"]) <= {"n", "k"} and cfg["rehearsal"]["n"] <= cfg["n"]
    assert manifest.traffic(w["traffic"])["loop"] == "fit"
    assert manifest.limits(cell)["limits"]
    reported = {m["name"] for m in manifest.end_to_end_for(BENCH, cell)}
    assert "setup_s" in reported and len(reported) >= 2
    for m in manifest.per_layer_for(BENCH, cell):
        assert m["moves"] in reported
        assert callable(manifest.metric_reader(m["name"]).read)


@pytest.mark.parametrize("bad", ["has space", "a,b", "a/b", "", ".lead", "x" * 65])
def test_bad_names_are_refused(bad):
    bench = json.loads(json.dumps(BENCH))
    bench["per_layer"][0]["name"] = bad
    assert manifest.validate(bench)


def test_bad_units_are_refused():
    bench = json.loads(json.dumps(BENCH))
    bench["end_to_end"][0]["unit"] = "tokens per second"
    assert manifest.validate(bench)


def test_manifest_keys_are_the_contracts():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
