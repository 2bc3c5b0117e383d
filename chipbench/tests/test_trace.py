"""The trace reduction on a small trace in a TPU trace's layout.

``data/trace_small.textproto`` is an XSpace written by hand in the layout
of a TPU profile: a device plane ``/device:TPU:0`` whose ``XLA Ops`` line
holds two kernel calls (a fusion, a Mosaic kernel as a ``tpu_custom_call``,
a slice) and one XLA reduction, and a host plane with the benchmark's
``fit`` spans. ``trace_small.window.json`` holds the window and
the spans as the benchmark recorded them on ``time.perf_counter``. Every
expected number below is worked out by hand from those times (µs):

    device ops   12–14 pad, 14–20 kernel, 20–21 slice,
                 52–54 pad, 54–64 kernel, 64–65 slice, 75–95 reduce
    spans        fit 0–10, fit 10–21, fit 50–70, fit 70–100

``data/trace_chip_3rn.textproto`` is cut from a profiler trace of the
``3rn_k9.fit`` window on a TPU v5e chip: 3 ms of the device's ``XLA Ops``
line around its first Mosaic kernel, op names as the chip wrote them (each
op's HLO text), with ``while`` ops that span the ops of their bodies.

    python -m pytest chipbench/tests/test_trace.py
"""

import json
import pathlib

import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def fixture():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto((DATA / "trace_small.textproto").read_text())
    meta = json.loads((DATA / "trace_small.window.json").read_text())
    spans = [tuple(s) for s in meta["spans"]]
    return pd, spans, trace.reduce(pd, (meta["t0"], meta["t1"]), spans)


def test_busy_and_window(fixture):
    _, _, r = fixture
    assert r["window_s"] == pytest.approx(100e-6, rel=1e-6)
    assert r["busy_s"] == pytest.approx(42e-6, rel=1e-6)


def test_device_time_splits_into_mosaic_and_xla(fixture):
    _, _, r = fixture
    assert r["mosaic_s"] == pytest.approx(16e-6, rel=1e-6)
    assert r["xla_s"] == pytest.approx(26e-6, rel=1e-6)
    assert r["kernels"] == pytest.approx({"custom-call": 16e-6}, rel=1e-6)


def test_top_ops_by_device_time(fixture):
    _, _, r = fixture
    names = [n for n, _ in r["top_ops"]]
    assert names == ["reduce", "custom-call", "fusion", "slice"]
    assert [t for _, t in r["top_ops"]] == pytest.approx([20e-6, 16e-6, 4e-6, 2e-6], rel=1e-6)


def test_idle_gaps_are_named_by_the_open_span(fixture):
    _, _, r = fixture
    assert [g[0] for g in r["idle_gaps"]] == ["none", "fit", "fit", "fit"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx([31e-6, 12e-6, 10e-6, 5e-6],
                                                           rel=1e-6)


def test_spans_tie_the_two_clocks(fixture):
    pd, spans, _ = fixture
    traced = trace.host_spans(pd)
    assert [s[0] for s in traced] == [s[0] for s in spans]
    for (_, a, b), (_, c, d) in zip(traced, spans):
        assert (b - a) / 1e9 == pytest.approx(d - c, abs=1e-9)


def test_a_trace_without_the_benchmarks_spans_is_refused(fixture):
    pd, _, _ = fixture
    with pytest.raises(ValueError):
        trace.reduce(pd, (0.0, 1.0), [])


@pytest.fixture(scope="module")
def chip_cut():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto((DATA / "trace_chip_3rn.textproto").read_text())
    meta = json.loads((DATA / "trace_chip_3rn.window.json").read_text())
    spans = [tuple(s) for s in meta["spans"]]
    return pd, trace.reduce(pd, (meta["t0"], meta["t1"]), spans)


def test_chip_trace_finds_its_one_mosaic_kernel_by_name(chip_cut):
    pd, r = chip_cut
    mosaic = [e for e in trace.device_events(pd)[0] if e[3]]
    assert [e[4] for e in mosaic] == ["assign_top2_pallas"]
    # the fusions that slice the kernel's outputs name it as an operand only
    readers = [e[0] for e in trace.device_events(pd)[0] if "%pallas_call." in e[0]]
    assert readers and not any(trace.is_mosaic(n, {}) for n in readers)
    assert r["kernels"] == pytest.approx({"assign_top2_pallas": 7.511e-6}, rel=1e-6)


def test_chip_trace_charges_nested_ops_once(chip_cut):
    _, r = chip_cut
    assert r["busy_s"] == pytest.approx(107.004e-6, rel=1e-6)
    assert r["mosaic_s"] + r["xla_s"] == pytest.approx(r["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(3e-3, rel=1e-6)
