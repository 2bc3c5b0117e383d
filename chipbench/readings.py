"""Readings that the limits of ``correct`` are set from, in one process.

    python3 -m chipbench.readings --workload susy_k27.fit --seconds 51

The cell's set-up, then one window of its own timed path (with ``--fault``,
a fault of ``chipbench.faults`` planted under it), then every compared
number twice for each fit of the window: from what the program produced,
and from the control (the reference at the precision below the
configuration's, put in the program's place). A run checks the fits that
its seed draws among its window's first ``quality_fits``, which every run
fits from the same keys, and its window's last; this reads every fit of
its window, so it keeps every fit's partition. One JSON line per fit on
stdout. Benchmark runs never run this.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

from chipbench import manifest

sys.path.insert(0, str(manifest.ROOT / "src"))

from chipbench import check, faults, loops, run  # noqa: E402
from chipbench.spans import Spans  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", default=None,
                    help="plant this fault (chipbench.faults) under the window")
    args = ap.parse_args(argv)
    bench = manifest.load_manifest()
    cell = manifest.workload(bench, args.workload)
    cfg = manifest.config(bench, cell["config"])
    mix = manifest.traffic(cell["traffic"])
    run.enable_cache()
    run.device_info(cell["chips"], rehearsal=False)
    if mix["loop"] != "fit":
        raise manifest.ManifestError(f"no readings for the {mix['loop']!r} loop")
    state = loops.fit_setup(cfg)
    spans = Spans()
    with faults.planted(args.fault) if args.fault else contextlib.nullcontext():
        win = loops.fit_window(cfg, mix, args.seconds, state, spans)
    results = win["results"]
    for i, r in enumerate(results):
        t0 = time.perf_counter()
        numbers = {side: check.fit_numbers(state["x"], results, [i],
                                           control=side == "control")
                   for side in ("program", "control")}
        print(json.dumps({
            "workload": cell["name"], "fault": args.fault, "fit": i, **numbers,
            "fit_s": spans.items[i][2] - spans.items[i][1],
            "fit_error_share": check.fit_error_share(state["x"], [r], state["tss"]),
            "stop_reason": r.stop_reason, "iterations": r.iterations,
            "n_blocks": int(r.metadata["partition"].n_blocks),
            "rows_max": float(r.metadata["partition"].count.max()),
            "check_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
