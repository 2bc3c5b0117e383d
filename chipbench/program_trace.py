"""The program's own spans in a profiler trace, and the device's idle time
charged to them.

    python3 -m chipbench.program_trace --workload 3rn_k9.fit
    python3 -m chipbench.program_trace --dir <a jax.profiler.trace directory>

The BWKM driver opens ``jax.profiler.TraceAnnotation`` spans named
``bwkm.*`` (``repro.obs``; the tree is in PERF.md §3). They sit on the host
planes, on the same clock as the device's ``XLA Ops``. Each span carries
``fit=`` and, inside a round, ``round=``: as event stats, or as a
``name#fit=3,round=2#`` suffix of the event's name; both are read.

The traced fits are the benchmark's ``fit`` spans (``trace.SPAN_NAMES``) or,
in a trace without them, the program's ``bwkm.fit`` spans. Inside each
fit, the device's idle gaps (``trace.device_events`` and ``trace._union``,
as the reduction takes them) are split over the host spans they overlap,
and each piece is charged to the innermost ``bwkm.*`` span open at that
moment, or to ``outside`` where none is. The pieces of a fit add up to its
idle time. ``analyse`` parses a trace file once and keeps the result, so
every metric reader of a run shares one parse.
"""

from __future__ import annotations

import argparse
import functools
import pathlib
import sys

import numpy as np

from chipbench import manifest, trace

PREFIX = "bwkm."
FIT_SPAN = "bwkm.fit"
OUTSIDE = "outside"
TRACE_DIR = manifest.HERE / "_trace"


def parse_name(name: str, stats) -> tuple[str, dict]:
    """``(span name, {"fit": .., "round": ..})`` from an event's name and stats."""
    base, _, suffix = name.partition("#")
    ids = {}
    for item in suffix.strip("#").split(","):
        key, eq, value = item.partition("=")
        if eq:
            ids[key.strip()] = value.strip()
    for key, value in stats:
        if key in ("fit", "round"):
            ids[key] = value
    return base, {k: int(v) for k, v in ids.items() if k in ("fit", "round")}


def host_lines(pd) -> list[tuple[list, list]]:
    """Per host line (one thread): the benchmark's spans ``(start_ns, end_ns)``
    and the ``bwkm.*`` spans ``(name, start_ns, end_ns, ids)``, each sorted by
    start, outer spans first."""
    lines = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            bench, spans = [], []
            for ev in line.events:
                if ev.name in trace.SPAN_NAMES:
                    bench.append((int(ev.start_ns), int(ev.end_ns)))
                elif ev.name.startswith(PREFIX):
                    name, ids = parse_name(ev.name, ev.stats)
                    spans.append((name, int(ev.start_ns), int(ev.end_ns), ids))
            if bench or spans:
                lines.append((sorted(bench), sorted(spans, key=lambda s: (s[1], -s[2]))))
    return lines


def segments(spans) -> list[tuple[int, int, tuple[str, ...]]]:
    """The line cut where a span opens or closes: ``(t0, t1, path)``, where
    ``path`` names the spans open over [t0, t1), outermost first. Spans on
    one line nest, so the last name of a path is the innermost span."""
    marks = []
    for i, (_, s, e, _) in enumerate(spans):
        marks.append((s, 1, -e, i))
        marks.append((e, 0, 0, i))
    marks.sort()
    out, stack, prev = [], [], None
    for t, opens, _, i in marks:
        if prev is not None and t > prev and stack:
            out.append((prev, t, tuple(spans[j][0] for j in stack)))
        if opens:
            stack.append(i)
        else:
            stack.remove(i)
        prev = t
    return out


def fit_windows(lines) -> list[tuple[int, int, list]]:
    """The traced fits, each with the ``bwkm.*`` spans of its thread inside
    it: the benchmark's ``fit`` spans, else the program's ``bwkm.fit`` spans."""
    out = []
    use_bench = any(bench for bench, _ in lines)
    for bench, spans in lines:
        windows = bench if use_bench else [(s, e) for n, s, e, _ in spans if n == FIT_SPAN]
        for lo, hi in windows:
            out.append((lo, hi, [sp for sp in spans if lo <= sp[1] and sp[2] <= hi]))
    return sorted(out, key=lambda w: w[0])


def _charge(gaps, segs, lo: int, hi: int):
    """Split ``gaps`` over ``segs`` clipped to [lo, hi]; the time no segment
    covers is ``outside``. Yields ``(path, length_ns, gap_index)``."""
    cover, cur = [], lo
    for s, e, path in segs:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            cover.append((cur, s, ()))
        cover.append((s, e, path))
        cur = max(cur, e)
    if hi > cur:
        cover.append((cur, hi, ()))
    j = 0
    for g, (g0, g1) in enumerate(gaps):
        while j < len(cover) and cover[j][1] <= g0:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < g1:
            s, e, path = cover[k]
            piece = min(e, g1) - max(s, g0)
            if piece > 0:
                yield path, piece, g
            k += 1


@functools.lru_cache(maxsize=4)
def _analyse(path: str, mtime_ns: int, size: int) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return analyse_profile(pd)


def analyse(xplane: pathlib.Path) -> dict:
    """``analyse_profile`` of a ``.xplane.pb``, parsed once per file."""
    st = pathlib.Path(xplane).stat()
    return _analyse(str(xplane), st.st_mtime_ns, st.st_size)


def analyse_profile(pd) -> dict:
    """Per traced fit: each span name's count, wall and self time, and the
    idle time and gaps charged to it; and the idle time under each span
    with its children (``idle_under_ns``). Times in ns; idle time is the
    mean over devices. A fit's spans are those of its own thread, so
    another thread's spans never overlap them."""
    devices = [(evs, np.array([e[1] for e in evs], dtype=np.int64),
                np.array([e[2] for e in evs], dtype=np.int64))
               for evs in trace.device_events(pd).values()]
    n_dev = max(len(devices), 1)
    fits = []
    for lo, hi, inside in fit_windows(host_lines(pd)):
        segs = segments(inside)
        fit_ids = [sp[3].get("fit") for sp in inside if sp[0] == FIT_SPAN]
        rows: dict[str, dict] = {}
        for name in [sp[0] for sp in inside] + [OUTSIDE]:
            rows.setdefault(name, {"count": 0, "wall_ns": 0, "self_ns": 0,
                                   "idle_ns": 0.0, "gaps": 0})
        for name, s, e, _ in inside:
            rows[name]["count"] += 1
            rows[name]["wall_ns"] += e - s
        for path, length, _ in _charge([(lo, hi)], segs, lo, hi):  # the whole fit
            rows[path[-1] if path else OUTSIDE]["self_ns"] += length
        rows[OUTSIDE]["wall_ns"] = rows[OUTSIDE]["self_ns"]
        under: dict[str, float] = {}
        idle = 0
        for evs, starts, ends in devices:
            keep = np.nonzero((ends > lo) & (starts < hi))[0]
            busy, gaps = trace._union([(evs[i][1], evs[i][2]) for i in keep], lo, hi)
            idle += (hi - lo) - busy
            charged = set()
            for path, length, g in _charge(gaps, segs, lo, hi):
                name = path[-1] if path else OUTSIDE
                rows[name]["idle_ns"] += length / n_dev
                if (name, g) not in charged:
                    charged.add((name, g))
                    rows[name]["gaps"] += 1
                for outer in set(path):
                    under[outer] = under.get(outer, 0.0) + length / n_dev
        fits.append({"fit": fit_ids[0] if len(fit_ids) == 1 else None,
                     "bwkm_fits": len(fit_ids), "window_ns": hi - lo,
                     "idle_ns": idle / n_dev, "rows": rows, "idle_under_ns": under})
    return {"fits": fits}


# ---------------------------------------------------------------- readers
def for_run(ctx) -> dict | None:
    """The analysis of a ``--trace 1`` run's trace, or None where the program
    made no ``bwkm.fit`` span (a program without spans). Raises when the
    traced fits and the program's complete ``bwkm.fit`` spans disagree."""
    if ctx.get("trace") is None:
        return None
    xplane = trace.latest_xplane(TRACE_DIR / ctx["cell"]["name"])
    result = analyse(xplane)
    n_program = sum(f["bwkm_fits"] for f in result["fits"])
    if n_program == 0:
        return None
    if n_program != ctx["traced_fits"] or len(result["fits"]) != ctx["traced_fits"]:
        raise ValueError(f"the trace holds {n_program} complete bwkm.fit spans in "
                         f"{len(result['fits'])} traced fits, the run traced "
                         f"{ctx['traced_fits']}")
    return result


def per_fit_ms(result: dict, value) -> float:
    """``value(fit)`` in ns summed over the traced fits, in ms per fit."""
    return sum(value(f) for f in result["fits"]) / len(result["fits"]) / 1e6


# -------------------------------------------------------------- the table
def table(result: dict) -> list[str]:
    out = []
    for i, f in enumerate(result["fits"]):
        charged = sum(r["idle_ns"] for r in f["rows"].values())
        out.append(f"traced fit {i} (fit={f['fit']}): window {f['window_ns'] / 1e6:.3f} ms, "
                   f"device idle {f['idle_ns'] / 1e6:.3f} ms, charged {charged / 1e6:.3f} ms")
        out.append(f"  {'span':<18}{'count':>7}{'wall ms':>12}{'self ms':>12}"
                   f"{'idle ms':>12}{'gaps':>8}")
        for name, r in sorted(f["rows"].items(), key=lambda kv: -kv[1]["idle_ns"]):
            out.append(f"  {name:<18}{r['count']:>7}{r['wall_ns'] / 1e6:>12.3f}"
                       f"{r['self_ns'] / 1e6:>12.3f}{r['idle_ns'] / 1e6:>12.3f}{r['gaps']:>8}")
    total_idle = sum(f["idle_ns"] for f in result["fits"])
    names = {n for f in result["fits"] for n in f["rows"]}
    out.append(f"all {len(result['fits'])} traced fits: device idle "
               f"{total_idle / 1e6:.3f} ms")
    out.append(f"  {'span':<18}{'idle ms':>12}{'share %':>10}{'self ms':>12}{'gaps':>8}")
    for name in sorted(names, key=lambda n: -sum(f["rows"].get(n, {}).get("idle_ns", 0)
                                                  for f in result["fits"])):
        idle = sum(f["rows"].get(name, {}).get("idle_ns", 0) for f in result["fits"])
        self_ns = sum(f["rows"].get(name, {}).get("self_ns", 0) for f in result["fits"])
        gaps = sum(f["rows"].get(name, {}).get("gaps", 0) for f in result["fits"])
        share = 100.0 * idle / total_idle if total_idle else 0.0
        out.append(f"  {name:<18}{idle / 1e6:>12.3f}{share:>10.2f}{self_ns / 1e6:>12.3f}{gaps:>8}")
    for name in ("bwkm.init", "bwkm.round"):
        under = sum(f["idle_under_ns"].get(name, 0) for f in result["fits"])
        share = 100.0 * under / total_idle if total_idle else 0.0
        out.append(f"  under {name} with its children: {under / 1e6:.3f} ms idle, "
                   f"{share:.2f}% of the fits' idle time")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    where = ap.add_mutually_exclusive_group(required=True)
    where.add_argument("--workload", help="a cell whose --trace 1 run left its trace")
    where.add_argument("--dir", help="a jax.profiler.trace directory")
    args = ap.parse_args(argv)
    trace_dir = pathlib.Path(args.dir) if args.dir else TRACE_DIR / args.workload
    result = analyse(trace.latest_xplane(trace_dir))
    if not result["fits"]:
        print(f"no traced fit in {trace_dir}", file=sys.stderr)
        return 1
    print("\n".join(table(result)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
