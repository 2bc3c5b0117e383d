"""Reduction of a profiler trace of the window to device metrics.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``; it
is read with ``jax.profiler.ProfileData``. On a TPU every device is a plane
``/device:TPU:<i>``; its line ``XLA Ops`` holds one event per executed HLO
operation, with a start and a duration in nanoseconds on the same clock as
the host planes, where the benchmark's ``TraceAnnotation`` spans are.

What comes out, over the traced window:

* ``busy_s``    union of the device's operation intervals (mean over chips);
* ``mosaic_s``  device time in Mosaic kernels (custom calls), ``xla_s`` in
  every other operation, and ``kernels``: device time per kernel name.
  Ops nest on the line (a ``while`` spans the ops of its body), so each op
  is charged its own time only, the time that no op nested in it covers:
  ``mosaic_s + xla_s`` is then ``busy_s``;
* ``top_ops``   the ten operation names with the most device time;
* ``idle_gaps`` the ten longest gaps between device operations, each named
  by the benchmark span that was open on the host when it began.
"""

from __future__ import annotations

import pathlib
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: spans the benchmark records around its calls into the program
SPAN_NAMES = ("fit",)
#: an op's HLO text is cut to this many characters in ``top_ops``
TOP_OP_CHARS = 160


def start(trace_dir) -> None:
    """Start the profiler without its Python function tracer: the
    benchmark's spans and the runtime's own events are what the reduction
    reads, and per-call Python events would swamp the trace and the host."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def latest_xplane(trace_dir: pathlib.Path) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def is_mosaic(name: str, stats: dict) -> bool:
    """A Mosaic kernel is a custom call whose target is ``tpu_custom_call``.

    On a v5e the op's name is its HLO text, ``%assign_top2_pallas.1 = (...)
    custom-call(...), custom_call_target="tpu_custom_call", ...``; the ops
    that read a kernel's outputs name it as an operand (``%pallas_call.5``)
    and are not kernels, so only the target attribute counts."""
    text = name + " " + str(stats.get("long_name", ""))
    return 'custom_call_target="tpu_custom_call"' in text


def op_name(name: str) -> str:
    """The HLO instruction's name without its numeric suffix:
    ``assign_top2_pallas`` for ``%assign_top2_pallas.1 = (...) custom-call(...)``."""
    m = re.match(r"%?([A-Za-z0-9_.\-]+)\s*=", name)
    return re.sub(r"\.\d+$", "", m.group(1) if m else name)


def kernel_name(name: str, stats: dict) -> str:
    """The Mosaic kernel's name: its instruction's name."""
    return op_name(name)


def device_events(pd) -> dict[int, list[tuple[str, int, int, bool, str]]]:
    """``{device: [(op, start_ns, end_ns, mosaic, kernel)]}`` from ``XLA Ops``."""
    out: dict[int, list] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        evs = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                st = _stats(ev)
                mosaic = is_mosaic(ev.name, st)
                evs.append((ev.name, int(ev.start_ns), int(ev.end_ns), mosaic,
                            kernel_name(ev.name, st) if mosaic else ""))
        out[int(m.group(1))] = sorted(evs, key=lambda e: (e[1], -e[2]))
    return out


def host_spans(pd) -> list[tuple[str, int, int]]:
    """The benchmark's spans as the trace saw them, on the trace clock."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPAN_NAMES:
                    spans.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
    return sorted(spans, key=lambda s: s[1])


def _union(intervals: list[tuple[int, int]], lo: int, hi: int) -> tuple[int, list]:
    """Covered length of ``intervals`` clipped to [lo, hi], and the gaps."""
    busy, gaps, cur = 0, [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            gaps.append((cur, s))
        if e > cur:
            busy += e - max(s, cur)
            cur = e
    if hi > cur:
        gaps.append((cur, hi))
    return busy, gaps


def _own_times(events, lo: int, hi: int) -> list[int]:
    """Each event's time in [lo, hi] that no event nested in it covers.

    ``events`` are sorted by start; on one line they nest or follow each
    other, so each event's direct children are those it is the innermost
    open event for."""
    own = [min(e[2], hi) - max(e[1], lo) for e in events]
    stack: list[int] = []
    for i, (_, s, e, *_rest) in enumerate(events):
        while stack and events[stack[-1]][2] < e:
            stack.pop()
        if stack and s >= events[stack[-1]][1]:
            own[stack[-1]] -= own[i]
        stack.append(i)
    return own


def reduce(pd, window: tuple[float, float], host_spans_perf) -> dict:
    """Metrics of the traced window.

    ``window`` is ``(t0, t1)`` on ``time.perf_counter``; ``host_spans_perf``
    are the benchmark's own ``(name, start, end)`` spans on that clock, which
    tie it to the trace's clock through the same spans in the trace.
    """
    traced = host_spans(pd)
    if not traced or not host_spans_perf:
        raise ValueError("the trace holds none of the benchmark's spans")
    first_perf = min(host_spans_perf, key=lambda s: s[1])
    first_trace = next(s for s in traced if s[0] == first_perf[0])
    offset_ns = first_trace[1] - first_perf[1] * 1e9
    lo = int(window[0] * 1e9 + offset_ns)
    hi = int(window[1] * 1e9 + offset_ns)
    devices = device_events(pd)
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    busy_all, mosaic_s, xla_s = [], 0.0, 0.0
    per_op: dict[str, float] = {}
    kernels: dict[str, float] = {}
    gaps_all = []
    for evs in devices.values():
        inside = [e for e in evs if e[2] > lo and e[1] < hi]
        busy, gaps = _union([(e[1], e[2]) for e in inside], lo, hi)
        busy_all.append(busy)
        gaps_all.extend(gaps)
        for (name, s, e, mosaic, kname), own in zip(inside, _own_times(inside, lo, hi)):
            dur = own / 1e9
            if mosaic:
                mosaic_s += dur
                kernels[kname] = kernels.get(kname, 0.0) + dur
            else:
                xla_s += dur
            key = kname if mosaic else re.sub(r"\.\d+$", "", name)[:TOP_OP_CHARS]
            per_op[key] = per_op.get(key, 0.0) + dur
    n_dev = len(devices)
    starts = np.array([s[1] for s in traced])
    labelled = []
    for g0, g1 in sorted(gaps_all, key=lambda g: g[0] - g[1])[:10]:
        label = "none"
        for name, s, e in traced[: int(np.searchsorted(starts, g0, side="right"))][::-1]:
            if s <= g0 < e:
                label = name
                break
        labelled.append([label, (g1 - g0) / 1e9])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": float(np.mean(busy_all)) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "mosaic_s": mosaic_s / n_dev,
        "xla_s": xla_s / n_dev,
        "kernels": {k: v / n_dev for k, v in kernels.items()},
        "top_ops": [[k, v / n_dev] for k, v in top],
        "idle_gaps": labelled,
    }


def reduce_dir(trace_dir, window: tuple[float, float], spans) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(latest_xplane(trace_dir)))
    return reduce(pd, window, spans.items)
