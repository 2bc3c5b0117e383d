"""Whether what the timed path produced is correct, number by number.

Each check returns ``{name: value}``; a value passes when it is at most the
limit that ``limits/<workload>.json`` gives it. The same functions with
``control=True`` put the reference, at the precision below the one the
configuration states, in the program's place: that is the control.

Fit cells compare the partition and centroids a fit returns:

* ``count_err``   blocks whose row count differs from a recount of the
  fit's block ids, plus rows assigned to a block that is not live (exact);
* ``box_err``     live blocks whose bounding box differs from the min/max
  of their rows (exact);
* ``psum_gap``    widest gap of a block's coordinate sum from the float64
  sum, over ``count × max|x|``;
* ``err_gap``     how far the weighted error the fit reports for its
  returned centroids (the last of ``weighted_errors``: its final Lloyd's
  pass over the representatives) lies from the float64 weighted error of
  those representatives at those centroids, over the latter. The fit's
  distances make that error, so this is the number that reads their
  precision.

The control computes the block statistics in bfloat16 (the configuration
states float32 statistics), and runs the final weighted Lloyd from the
fit's centroids and reports its weighted error with the distances'
product at ``Precision.HIGH``, three bfloat16 passes (the configuration
states ``HIGHEST``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from chipbench import reference
from chipbench.data import rng_for

#: the precision below the configuration's float32 at HIGHEST: 3-pass bf16
CONTROL_PRECISION = lax.Precision.HIGH

#: fits of one run's first ``quality_fits`` whose partitions are recounted,
#: besides the window's last fit (``loops.fit_window``)
CHECKED_FITS = 3


def checked_fits(seed: int, quality_fits: int) -> list[int]:
    """The fits a run checks, drawn from ``seed`` before its window among
    its first ``quality_fits``, which every run fits from the same keys."""
    return sorted(rng_for(seed, 9).choice(quality_fits, size=min(CHECKED_FITS, quality_fits),
                                          replace=False).tolist())


def fit_numbers(x: jax.Array, results: list, picks: list[int], *,
                control: bool = False) -> dict[str, float]:
    """The numbers, each the worst over the fits of ``results`` that
    ``picks`` names."""
    xabs = float(jnp.max(jnp.abs(x)))
    out = {"count_err": 0.0, "box_err": 0.0, "psum_gap": 0.0, "err_gap": 0.0}
    for i in picks:
        r = results[i]
        part = r.metadata["partition"]
        m = part.lo.shape[0]
        bid = np.asarray(part.block_id)
        active = np.asarray(part.active)
        if control:
            stats = reference.block_stats_low(x, part.block_id, m=m)
        else:
            stats = (part.psum, part.count, part.lo, part.hi)
        psum_p, count_p = (np.asarray(a, np.float64) for a in stats[:2])
        lo_p, hi_p = (np.asarray(a, np.float32) for a in stats[2:])
        psum_r, count_r, lo_r, hi_r = reference.block_stats(x, part.block_id, m)
        live = active & (count_r > 0)
        count_err = int(np.sum(count_p[active] != count_r[active]))
        count_err += int(np.sum(~active[np.clip(bid, 0, m - 1)] | (bid < 0) | (bid >= m)))
        box_err = int(np.sum(np.any(lo_p[live] != lo_r[live], axis=1)
                             | np.any(hi_p[live] != hi_r[live], axis=1)))
        gap = np.max(np.abs(psum_p[live] - psum_r[live]), axis=1) / (count_r[live] * xabs)
        # the representatives as the fit holds them
        held = live & (count_p > 0)
        reps = psum_p[held] / count_p[held][:, None]
        w = count_p[held]
        c = np.asarray(r.centroids, np.float64)
        reported = float(r.metadata["weighted_errors"][-1])
        if control:
            args = (jnp.asarray(reps, jnp.float32), jnp.asarray(w, jnp.float32))
            c32 = reference.weighted_lloyd(*args, jnp.asarray(c, jnp.float32),
                                           precision=CONTROL_PRECISION)
            reported = reference.weighted_error_f32(*args, c32, precision=CONTROL_PRECISION)
            c = np.asarray(c32, np.float64)
        e_c = reference.weighted_error_f64(reps, w, c)
        err_gap = abs(reported - e_c) / e_c
        out["count_err"] = max(out["count_err"], float(count_err))
        out["box_err"] = max(out["box_err"], float(box_err))
        out["psum_gap"] = max(out["psum_gap"], float(np.max(gap, initial=0.0)))
        out["err_gap"] = max(out["err_gap"], err_gap)
    return out


def fit_error_share(x: jax.Array, results: list, tss: float) -> float:
    """Mean over the fits of E^D(C) / TSS, in percent."""
    return float(np.mean([reference.error(x, r.centroids) / tss for r in results]) * 100.0)


def judge(numbers: dict[str, float], limits: dict) -> tuple[bool, list[tuple]]:
    """``(correct, [(name, value, limit, ok)])`` against the limit file."""
    table = []
    for name, value in numbers.items():
        limit = limits["limits"][name]
        table.append((name, value, limit, bool(np.isfinite(value) and value <= limit)))
    missing = set(limits["limits"]) - set(numbers)
    for name in sorted(missing):
        table.append((name, float("nan"), limits["limits"][name], False))
    return all(ok for *_, ok in table), table
