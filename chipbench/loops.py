"""The timed loops: set-up, warm-up and the measured window of each mix.

Each loop returns a dict of what the window produced (for the checks) and
of what it measured (for the metrics). The program is driven only through
the entry a user calls: ``repro.BWKM(k=K).fit``.
"""

from __future__ import annotations

import dataclasses
import time

from chipbench import check, reference
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
               after_fit=None, keep=None) -> dict:
    """Fits back to back until the first fit that ends ``seconds`` after the
    first one began, and at least the mix's ``quality_fits``. The first
    ``quality_fits`` fits start from the pool's first keys, in order, in
    every run. ``after_fit(elapsed_s, fits)``, if given, is called after
    each fit. Of the fits whose index ``keep`` holds, and of the window's
    last fit, the whole result is kept (of every fit where ``keep`` is
    None), and their indices are ``kept``; of the others, what the metrics
    read (``slim``)."""
    import repro

    x = state["x"]
    keys = fit_keys(mix_cfg)
    quality_fits = mix_cfg["quality_fits"]
    results, kept = [], []
    t0 = time.perf_counter()
    while True:
        i = len(results)
        with spans.span("fit"):
            model = repro.BWKM(k=cfg["k"]).fit(x, key=keys[i % len(keys)])
            model.centroids_.block_until_ready()
        r = model.result_
        del model
        if after_fit is not None:
            after_fit(time.perf_counter() - t0, i + 1)
        last = time.perf_counter() - t0 >= seconds and i + 1 >= quality_fits
        if keep is None or i in keep or last:
            kept.append(i)
            results.append(r)
        else:
            results.append(slim(r, i < quality_fits))
        del r
        if last:
            break
    t1 = time.perf_counter()
    return {"results": results, "kept": kept, "quality_fits": quality_fits, "t0": t0,
            "t1": t1, "window_s": t1 - t0}


def slim(result, centroids: bool):
    """``result`` with what the metrics and the run's log read, and no
    partition: counters, distances, stop reason, iterations, the last
    weighted error, and the centroids where ``centroids``."""
    meta = {k: result.metadata[k] for k in ("counters",) if k in result.metadata}
    meta["weighted_errors"] = result.metadata["weighted_errors"][-1:]
    return dataclasses.replace(result, centroids=result.centroids if centroids else None,
                               trace=[], metadata=meta)


def fit_metrics(win: dict, state: dict) -> dict:
    """A fit window's end-to-end metrics: ``fit_s`` over all its fits, and
    ``fit_error_share`` over its first ``quality_fits``, the same fits in
    every run whatever the program's speed."""
    results = win["results"]
    quality = results[:win["quality_fits"]]
    return {"fit_s": win["window_s"] / len(results),
            "fit_error_share": check.fit_error_share(state["x"], quality, state["tss"])}
