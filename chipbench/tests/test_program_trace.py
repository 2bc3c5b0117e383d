"""The program's spans and the device's idle time charged to them.

``data/trace_program_small.textproto`` is an XSpace written by hand in the
layout of a TPU trace. Times in µs after the lines' timestamp:

    benchmark spans   fit 0–100 (fit A), fit 100–160 (fit B)
    fit A spans       bwkm.fit 2–98 (fit=1, ids as event stats: the form
                        a v5e chip trace has, jax 0.9.0)
                        plane 3–8, init 10–40 (init.start 11–20,
                        init.grow 22–30 and 31–39), seed 41–45,
                        round 46–70 (round=1: lloyd 46–55, boundary 55–58,
                        stop 58–60, split 60–63, route 63–68, reps 68–70),
                        round 71–95 (round=2: lloyd 71–80, boundary 80–84,
                        stop 84–95), result 95–97
    fit B spans       bwkm.fit 101–159 (fit=2, ids in the names, the
                        ``name#fit=2#`` form a TraceMe encodes them in)
                        init 102–120, round 121–150 (round=1: lloyd
                        121–140, stop 140–150), result 150–158
    another thread    bwkm.fit 50–60 (fit=9) with a round 51–59
    device ops        1–5 12–25 33–50 (35–40 nested) 57–62 65–72 74–86
                      99–100 | 100–104 110–125 130–145 152–157

Device idle gaps in fit A and the innermost span open over each piece:

    0–1    outside 1
    5–12   plane 3 (5–8), bwkm.fit 2 (8–10), init 1, init.start 1
    25–33  init.grow 5 (25–30), init 1 (30–31), init.grow 2 (31–33)
    50–57  lloyd 5, boundary 2
    62–65  split 1, route 2
    72–74  lloyd 2
    86–99  stop 9 (86–95), result 2, bwkm.fit 1 (97–98), outside 1

so fit A is idle 41 µs: under bwkm.init with its children 2 + 1 + 7 = 10,
under bwkm.round 7 + 2 + 1 + 2 + 9 = 21. In fit B: 104–110 init 6; 125–130
lloyd 5; 145–152 stop 5, result 2; 157–160 result 1, bwkm.fit 1, outside 1:
idle 21, under init 6, under round 10. Per traced fit: bwkm.init's wall time
(30 + 18) / 2 = 24 µs, idle under init (10 + 6) / 2 = 8 µs, under rounds
(21 + 10) / 2 = 15.5 µs. The ops cover 59 + 39 = 98 of the 160 µs, so the
reduction's idle time over the two fits is 62 µs: the sum of the pieces.
The other thread's spans overlap fit A in time and are charged nothing.

    python -m pytest chipbench/tests/test_program_trace.py
"""

import pathlib
import types

import pytest

from chipbench import manifest, program_trace, trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
US = 1000.0  # ns


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto((DATA / "trace_program_small.textproto").read_text())


@pytest.fixture(scope="module")
def result(pd):
    return program_trace.analyse_profile(pd)


def test_ids_are_read_from_stats_and_from_names():
    assert program_trace.parse_name("bwkm.round#fit=2,round=1#", []) == (
        "bwkm.round", {"fit": 2, "round": 1})
    assert program_trace.parse_name("bwkm.round", [("fit", 3), ("round", 4)]) == (
        "bwkm.round", {"fit": 3, "round": 4})
    assert program_trace.parse_name("bwkm.fit", []) == ("bwkm.fit", {})


def test_each_traced_fit_takes_its_own_threads_spans(result):
    fits = result["fits"]
    assert [f["fit"] for f in fits] == [1, 2]
    assert [f["bwkm_fits"] for f in fits] == [1, 1]
    assert [f["window_ns"] for f in fits] == [100 * US, 60 * US]
    a = fits[0]["rows"]
    assert a["bwkm.round"]["count"] == 2 and a["bwkm.init.grow"]["count"] == 2
    assert a["bwkm.init"]["wall_ns"] == 30 * US
    assert a["bwkm.init"]["self_ns"] == (30 - 9 - 8 - 8) * US
    assert a["outside"]["self_ns"] == 4 * US


def test_idle_pieces_go_to_the_innermost_span(result):
    a, b = (f["rows"] for f in result["fits"])
    idle_a = {name: r["idle_ns"] / US for name, r in a.items() if r["idle_ns"]}
    assert idle_a == pytest.approx({
        "outside": 2, "bwkm.plane": 3, "bwkm.fit": 3, "bwkm.init": 2,
        "bwkm.init.start": 1, "bwkm.init.grow": 7, "bwkm.lloyd": 7,
        "bwkm.boundary": 2, "bwkm.split": 1, "bwkm.route": 2, "bwkm.stop": 9,
        "bwkm.result": 2})
    idle_b = {name: r["idle_ns"] / US for name, r in b.items() if r["idle_ns"]}
    assert idle_b == pytest.approx({"bwkm.init": 6, "bwkm.lloyd": 5, "bwkm.stop": 5,
                                    "bwkm.result": 3, "bwkm.fit": 1, "outside": 1})
    # a gap split between two runs of one span counts once for it
    assert a["bwkm.init.grow"]["gaps"] == 1 and a["bwkm.lloyd"]["gaps"] == 2
    assert b["bwkm.result"]["gaps"] == 2


def test_idle_under_a_span_counts_its_children(result):
    a, b = result["fits"]
    assert a["idle_under_ns"]["bwkm.init"] == pytest.approx(10 * US)
    assert a["idle_under_ns"]["bwkm.round"] == pytest.approx(21 * US)
    assert b["idle_under_ns"]["bwkm.init"] == pytest.approx(6 * US)
    assert b["idle_under_ns"]["bwkm.round"] == pytest.approx(10 * US)


def test_pieces_sum_to_the_reductions_idle_time(pd, result):
    spans = [(n, s / 1e9, e / 1e9) for n, s, e in trace.host_spans(pd)]
    r = trace.reduce(pd, (spans[0][1], spans[-1][2]), spans)
    idle_s = r["window_s"] - r["busy_s"]
    assert idle_s == pytest.approx(62e-6, rel=1e-6)
    pieces = sum(row["idle_ns"] for f in result["fits"] for row in f["rows"].values())
    assert pieces / 1e9 == pytest.approx(idle_s, rel=1e-6)
    assert sum(f["idle_ns"] for f in result["fits"]) == pytest.approx(62 * US)


def test_without_benchmark_spans_the_program_fits_are_the_windows(pd):
    lines = [([], spans) for _, spans in program_trace.host_lines(pd)]
    windows = program_trace.fit_windows(lines)
    assert [(lo / US - 1000, hi / US - 1000) for lo, hi, _ in windows] == [
        (2, 98), (50, 60), (101, 159)]


def _ctx(traced_fits, results=(), quality_fits=4):
    return {"trace": {"busy_s": 1.0, "window_s": 2.0}, "cell": {"name": "3rn_k9.fit"},
            "traced_fits": traced_fits,
            "window": {"results": list(results), "quality_fits": quality_fits}}


@pytest.fixture
def analysed(monkeypatch, result):
    monkeypatch.setattr(trace, "latest_xplane", lambda d: d)
    monkeypatch.setattr(program_trace, "analyse", lambda path: result)


def test_trace_readers(analysed):
    read = {n: manifest.metric_reader(n).read
            for n in ("init_ms_per_fit", "init_idle_ms_per_fit", "round_idle_ms_per_fit")}
    assert read["init_ms_per_fit"](_ctx(2)) == pytest.approx(0.024)
    assert read["init_idle_ms_per_fit"](_ctx(2)) == pytest.approx(0.008)
    assert read["round_idle_ms_per_fit"](_ctx(2)) == pytest.approx(0.0155)
    with pytest.raises(ValueError):
        read["init_ms_per_fit"](_ctx(3))


def test_trace_readers_read_nothing_from_a_program_without_spans(monkeypatch):
    from jax.profiler import ProfileData

    bare = program_trace.analyse_profile(
        ProfileData.from_text_proto((DATA / "trace_small.textproto").read_text()))
    assert [f["bwkm_fits"] for f in bare["fits"]] == [0, 0, 0, 0]
    monkeypatch.setattr(trace, "latest_xplane", lambda d: d)
    monkeypatch.setattr(program_trace, "analyse", lambda path: bare)
    for name in ("init_ms_per_fit", "init_idle_ms_per_fit", "round_idle_ms_per_fit"):
        assert manifest.metric_reader(name).read(_ctx(4)) is None
    assert program_trace.for_run({**_ctx(4), "trace": None}) is None


def test_counter_readers():
    def fit(counters, distances=0.0):
        meta = {"counters": counters} if counters else {}
        return types.SimpleNamespace(metadata=meta, distances=distances)

    results = [fit({"host_syncs": 80, "data_passes": 40}, 100.0),
               fit({"host_syncs": 90, "data_passes": 44}, 300.0)]
    # fits past the window's first quality_fits are not read: a faster
    # program's longer window reads the same fits
    later = [fit({"host_syncs": 1000, "data_passes": 1000}, 1e9), fit(None)]
    syncs = manifest.metric_reader("host_syncs_per_fit").read
    passes = manifest.metric_reader("data_passes_per_fit").read
    distances = manifest.metric_reader("fit_distances").read
    assert syncs(_ctx(1, results + later, quality_fits=2)) == 85
    assert passes(_ctx(1, results + later, quality_fits=2)) == 42
    assert distances(_ctx(1, results + later, quality_fits=2)) == 200.0
    assert syncs(_ctx(1, results + later, quality_fits=3)) == 390
    assert syncs(_ctx(1, [fit(None)])) is None
    assert passes(_ctx(1, [])) is None
    assert distances(_ctx(1, [])) is None


def test_table_lists_every_span_and_outside(result):
    lines = program_trace.table(result)
    assert lines[0].startswith("traced fit 0 (fit=1)")
    assert any(line.split()[0] == "outside" for line in lines if line.strip())
    assert lines[-1].startswith("  under bwkm.round with its children: 0.031 ms idle, 50.00%")
