"""The timed loops: set-up, warm-up and the measured window of each mix.

Each loop returns a dict of what the window produced (for the checks) and
of what it measured (for the metrics). The program is driven only through
the entry a user calls: ``repro.BWKM(k=K).fit``.
"""

from __future__ import annotations

import time

from chipbench import reference
from chipbench.data import Mixture, key_for
from chipbench.spans import Spans
from chipbench.traffic import fit_keys


def fit_setup(cfg: dict) -> dict:
    """Make the configuration's data set on the device and warm up one whole
    fit. The data set is the configuration's, the same in every run: the
    run seed does not draw it (``chipbench.data``)."""
    import repro

    gen = cfg["generator"]
    mix = Mixture(gen, cfg["d"])
    x = mix.sample(key_for(gen["structure_seed"], 1), cfg["n"])
    x.block_until_ready()
    tss = reference.total_sum_of_squares(x)
    model = repro.BWKM(k=cfg["k"]).fit(x, key=key_for(gen["structure_seed"], 2))
    model.centroids_.block_until_ready()
    # compile the reference now; its results after the window are not timed
    reference.error(x, model.centroids_)
    return {"x": x, "tss": tss}


def fit_window(cfg: dict, mix_cfg: dict, seconds: float, state: dict, spans: Spans,
               after_fit=None) -> dict:
    """Fits back to back until the first fit that ends ``seconds`` after the
    first one began. ``after_fit(elapsed_s, fits)``, if given, is called
    after each fit."""
    import repro

    x = state["x"]
    keys = fit_keys(mix_cfg)
    results = []
    t0 = time.perf_counter()
    i = 0
    while True:
        key = keys[i % len(keys)]
        with spans.span("fit"):
            model = repro.BWKM(k=cfg["k"]).fit(x, key=key)
            model.centroids_.block_until_ready()
        results.append(model.result_)
        i += 1
        if after_fit is not None:
            after_fit(time.perf_counter() - t0, len(results))
        if time.perf_counter() - t0 >= seconds:
            break
    t1 = time.perf_counter()
    return {"results": results, "t0": t0, "t1": t1, "window_s": t1 - t0}
