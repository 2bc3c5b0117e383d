"""``BENCHMARK.json`` and the files it names, found by name.

Every configuration, traffic mix, per-layer metric, kernel work count and
limit set sits in a file of its own under ``chipbench/``; adding a cell or
a metric adds files and manifest entries and edits none:

* ``configs/<config>.json``       the deployment: sizes, generator, guarantees
* ``traffic/<traffic>.json``      the mix, read by the one generator
* ``metrics/<metric>.py``         a reader with ``read(ctx) -> float | None``
* ``limits/<workload>.json``      the limit of each number ``correct`` compares
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class ManifestError(ValueError):
    """The manifest or a file it names is missing or malformed."""


def load_manifest(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise ManifestError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise ManifestError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def workload(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError(f"no workload {name!r} in BENCHMARK.json")


def config(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            cfg = _read_json(ROOT / c["file"])
            if cfg.get("name") != name:
                raise ManifestError(f"{c['file']} names {cfg.get('name')!r}, not {name!r}")
            return cfg
    raise ManifestError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return _read_json(HERE / "traffic" / f"{name}.json")


def limits(workload_name: str) -> dict:
    return _read_json(HERE / "limits" / f"{workload_name}.json")


def _load_module(path: pathlib.Path, tag: str):
    if not path.is_file():
        raise ManifestError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{tag}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """The module ``metrics/<name>.py``; it defines ``read(ctx)``."""
    return _load_module(HERE / "metrics" / f"{name}.py", "metric")


def per_layer_for(manifest: dict, workload_name: str) -> list[dict]:
    """Per-layer metrics the cell reports: listed for it, or unlisted and
    moving an end-to-end metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(manifest, workload_name)}
    out = []
    for m in manifest["per_layer"]:
        cells = m.get("workloads")
        if (workload_name in cells) if cells is not None else (m["moves"] in e2e):
            out.append(m)
    return out


def end_to_end_for(manifest: dict, workload_name: str) -> list[dict]:
    return [
        m for m in manifest["end_to_end"]
        if m.get("workloads") is None or workload_name in m["workloads"]
    ]


def validate(manifest: dict) -> list[str]:
    """Names, units and files the manifest refers to; returns the faults."""
    faults = []
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            names.append((group, entry["name"]))
            if not NAME_RE.match(entry["name"]):
                faults.append(f"{group} name {entry['name']!r}")
            if "unit" in entry and not UNIT_RE.match(entry["unit"]):
                faults.append(f"unit {entry['unit']!r} of {entry['name']}")
    for group in ("configs", "workloads"):
        seen = [n for g, n in names if g == group]
        if len(seen) != len(set(seen)):
            faults.append(f"duplicate {group} names")
    metrics = [n for g, n in names if g in ("end_to_end", "per_layer")]
    if len(metrics) != len(set(metrics)):
        faults.append("duplicate metric names")
    for c in manifest["configs"]:
        for key in c["reduced"]:
            if not NAME_RE.match(key):
                faults.append(f"reduced key {key!r}")
        try:
            config(manifest, c["name"])
        except (ManifestError, json.JSONDecodeError) as e:
            faults.append(str(e))
    cfg_names = {c["name"] for c in manifest["configs"]}
    for w in manifest["workloads"]:
        for key in ("config", "traffic"):
            if not NAME_RE.match(w[key]):
                faults.append(f"{key} {w[key]!r} of {w['name']}")
        if w["config"] not in cfg_names:
            faults.append(f"{w['name']} names unknown config {w['config']!r}")
        for find in (lambda: traffic(w["traffic"]), lambda: limits(w["name"])):
            try:
                find()
            except (ManifestError, json.JSONDecodeError) as e:
                faults.append(str(e))
        if not per_layer_for(manifest, w["name"]):
            faults.append(f"{w['name']} reports no per-layer metric")
    for m in manifest["per_layer"]:
        try:
            if not hasattr(metric_reader(m["name"]), "read"):
                faults.append(f"metrics/{m['name']}.py defines no read()")
        except ManifestError as e:
            faults.append(str(e))
    return faults
