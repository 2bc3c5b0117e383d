"""Run one benchmark cell and print its result as the last line of stdout.

    python3 -m chipbench.run --workload susy_k27.fit --seed 7 --seconds 30 --trace 0

In order: the compile cache is set to ``<checkout>/.jax_cache`` (unless
``JAX_COMPILATION_CACHE_DIR`` is set), the data is made on the device from
``--seed``, the cell's shapes are warmed up, the window runs for
``--seconds`` and for at least the mix's ``quality_fits`` fits, and then
the reference checks the fits that the seed drew before the window, and
the window's last fit.
``--trace 1`` records a profiler trace of the window's first fits, those
that begin in its first ``TRACE_SECONDS``, and reports the cell's
per-layer metrics instead of its end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

from chipbench import manifest  # noqa: E402

sys.path.insert(0, str(manifest.ROOT / "src"))

import jax  # noqa: E402

from chipbench import check, faults, loops  # noqa: E402
from chipbench.spans import CompileCounter, Spans  # noqa: E402

CACHE_DIR = manifest.ROOT / ".jax_cache"
TRACE_DIR = manifest.HERE / "_trace"
#: a traced run traces the fits that begin in the window's first this many
#: seconds: a trace of a whole window is too large to read within a run
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def enable_cache() -> str:
    """The persistent compilation cache, at a fixed path in the checkout.

    Every program is cached, however quick its compile: a fresh process of
    this benchmark then compiles nothing that an earlier run compiled.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)


def device_info(chips: int, *, rehearsal: bool) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if rehearsal:
        return info
    from repro.kernels import ops

    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0] is {info['platform']!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX finds {len(devs)}")
    if ops.resolve_impl(None) != "pallas" or ops.interpret_mode():
        raise NoChip("the kernels would not run as compiled Mosaic kernels")
    return info


def peak_bytes() -> int:
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def run(args, rehearsal: dict | None = None, fault: str | None = None) -> dict:
    """One run of one cell; returns the result object (not yet printed).

    ``rehearsal`` (tests only) overrides sizes, skips the look for a chip
    and reports no metric; ``fault`` (tests only) plants a fault of
    ``chipbench.faults`` under the window.
    """
    bench = manifest.load_manifest()
    cell = manifest.workload(bench, args.workload)
    cfg = manifest.config(bench, cell["config"])
    mix = manifest.traffic(cell["traffic"])
    limits = manifest.limits(cell["name"])
    if rehearsal:
        cfg = {**cfg, **rehearsal.get("config", {})}
        mix = {**mix, **rehearsal.get("traffic", {})}
        cache = "off (rehearsal)"
    else:
        cache = enable_cache()
    device = device_info(cell["chips"], rehearsal=rehearsal is not None)
    log(f"[setup] {cell['name']} device {device}; compile cache {cache}; jax {jax.__version__}")

    spans = Spans()
    compiles = CompileCounter()
    if mix["loop"] != "fit":
        raise manifest.ManifestError(
            f"unknown loop {mix['loop']!r} in traffic {cell['traffic']!r}")
    seconds = float(args.seconds)
    state = loops.fit_setup(cfg)
    picks = check.checked_fits(args.seed, mix["quality_fits"])

    trace_dir = TRACE_DIR / cell["name"]
    if args.trace:
        from chipbench import trace as trace_mod

        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_mod.start(trace_dir)
    traced: dict = {}

    def stop_trace(elapsed_s: float, fits: int) -> None:
        if args.trace and not traced and elapsed_s >= TRACE_SECONDS:
            traced.update(t1=time.perf_counter(), fits=fits)
            jax.profiler.stop_trace()

    compiles_before, loads_before = compiles.count, compiles.loads
    setup_s = time.perf_counter() - T_START
    with faults.planted(fault) if fault else contextlib.nullcontext():
        win = loops.fit_window(cfg, mix, seconds, state, spans, stop_trace, keep=set(picks))
    window_compiles = compiles.count - compiles_before
    window_loads = compiles.loads - loads_before
    reduction = None
    if args.trace:
        if not traced:
            traced.update(t1=win["t1"], fits=len(win["results"]))
            jax.profiler.stop_trace()
        reduction = trace_mod.reduce_dir(trace_dir, (win["t0"], traced["t1"]), spans)
        log(f"[trace] fits={traced['fits']} traced_s={traced['t1'] - win['t0']!r} "
            f"reduced_at_s={time.perf_counter() - T_START:.1f}")
    memory_peak = peak_bytes()
    t_check = time.perf_counter()

    # -------------------------------------------------- after the window
    results = win["results"]
    e2e = {**loops.fit_metrics(win, state), "setup_s": setup_s}
    numbers = check.fit_numbers(state["x"], results, win["kept"])
    attempted, failed = len(results), 0
    log(f"[window] fits={len(results)} window_s={win['window_s']!r} "
        f"fit_s_each={[round(b - a, 4) for _, a, b in spans.items]} "
        f"stop_reasons={sorted({r.stop_reason for r in results})} "
        f"iterations={[r.iterations for r in results]}")
    log(f"[window] quality_fits={win['quality_fits']} checked={win['kept']}")
    log(f"[window] compiles_in_window={window_compiles} cache_loads_in_window={window_loads} "
        f"peak_bytes_in_use={memory_peak}")
    correct, table = check.judge(numbers, limits)
    log(f"[check] reference_s={time.perf_counter() - t_check:.1f}")

    if args.trace:
        ctx = {"window": win, "spans": spans, "trace": reduction, "config": cfg,
               "cell": cell, "traced_fits": traced["fits"]}
        metrics = {}
        for m in manifest.per_layer_for(bench, cell["name"]):
            value = manifest.metric_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        metrics = {}
        for m in manifest.end_to_end_for(bench, cell["name"]):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": float(e2e[m["name"]]), "unit": m["unit"]}
    if rehearsal:
        # a CPU rehearsal reports no device metric under a device metric's name
        metrics = {}
    device["memory_peak_bytes"] = memory_peak
    result = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
              "metrics": metrics, "device": device}
    if rehearsal:
        result["rehearsal"] = True
    if reduction is not None:
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = {"device_ops": reduction["top_ops"],
                               "idle_gaps": reduction["idle_gaps"]}
    for name, value, limit, ok in table:
        log(f"[check] {name} {value!r} limit {limit!r} {'ok' if ok else 'FAILED'}")
    result["checks"] = {name: {"value": value, "limit": limit} for name, value, limit, _ in table}
    return result


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except NoChip as e:
        log(f"chipbench: {e}")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
