"""The one traffic generator: reads a mix's data file and builds its schedule.

A mix names its loop by its ``"loop"`` key. The one loop so far:

* ``"fit"``   whole fits back to back through ``repro.BWKM(k=K).fit``; the
  fits' PRNG keys are a fixed pool of ``key_pool`` keys from the mix's
  ``structure_seed``, cycled in a fixed order. A window holds at least
  ``quality_fits`` fits, whatever its length.

Every seed of a mix gets the same work: every run fits the configuration's
data set from the same keys in the same order, so a window of a given
length holds the same fits whatever the seed, and its first
``quality_fits`` fits are the same whatever the program's speed: the fit
quality and the counters are read over those, and the run seed draws
among them the fits that the reference checks (``chipbench.check``),
besides the window's last.
"""

from __future__ import annotations

import jax

from chipbench.data import key_for


def fit_keys(mix: dict) -> list[jax.Array]:
    """The fits' PRNG keys, in the order every run cycles through them."""
    if not 1 <= mix["quality_fits"] <= mix["key_pool"]:
        raise ValueError(f"quality_fits {mix['quality_fits']} is not in 1..key_pool")
    return [key_for(mix["structure_seed"], 3, j) for j in range(mix["key_pool"])]
