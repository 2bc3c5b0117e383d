"""In-core data plane: the resident-array implementation of ``DataPlane``.

The simplest plane: the dataset is one device array, memberships live in
``Partition.block_id``, a "data pass" is a single fused kernel dispatch,
and the pruned-Lloyd bound state is the ``while_loop`` carry inside
``core.lloyd.weighted_lloyd`` (this plane's ``lloyd`` simply delegates to
it — the resident case needs no host round-trip per iteration).

Fault posture (DESIGN.md §5): non-finite rows are quarantined up front —
one NaN row would otherwise poison every centroid — and the filter is a
deterministic function of the data, so reruns are bit-identical.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import bwkm as core_bwkm
from repro.core import init_partition, kmeanspp
from repro.core import kmeans_ll as core_ll
from repro.core import partition as part_mod
from repro.core.partition import Partition, SplitPlan
from repro.health import RunHealth
from repro.kernels import ops

__all__ = ["InCoreLLSession", "InCorePlane"]

_BIG = 3.0e38


class InCorePlane:
    """Resident-array execution plane (``engine="incore"``)."""

    name = "incore"

    def __init__(self, x: jax.Array):
        health = RunHealth()
        with obs.span("bwkm.plane"):
            finite_rows = jnp.all(jnp.isfinite(x), axis=1)
            obs.data_pass()
            n_bad = obs.pull(x.shape[0] - jnp.sum(finite_rows), int)
            if n_bad:
                health.quarantined_rows = n_bad
                x = jnp.asarray(x)[finite_rows]
                if x.shape[0] == 0:
                    raise ValueError("every input row was non-finite; nothing to cluster")
        self.x = x
        self.run_health = health

    @property
    def n_points(self) -> int:
        return int(self.x.shape[0])

    @property
    def dim(self) -> int:
        return int(self.x.shape[1])

    def split_key(self, key):
        key, k_init, k_pp = jax.random.split(key, 3)
        return key, k_init, k_pp

    def build_partition(self, k_init, config, p) -> Partition:
        return init_partition.build_initial_partition(
            k_init, self.x, config.k,
            m=p["m"], m_prime=p["m_prime"], s=p["s"], r=p["r"],
            capacity=p["capacity"],
        )

    def extent(self, part: Partition) -> float:
        obs.data_pass()
        return obs.pull(
            jnp.linalg.norm(jnp.max(self.x, axis=0) - jnp.min(self.x, axis=0))
        )

    def route_round(self, part: Partition, plan: SplitPlan, round_index: int) -> Partition:
        # split_blocks minus the plan (the driver resolves that): route every
        # point, activate the new rows, re-tighten all boxes in one pass.
        new_bid = part_mod.route_split(self.x, part.block_id, plan)
        obs.data_pass()
        out = part_mod.apply_split_plan(part._replace(block_id=new_bid), plan)
        return part_mod.recompute_stats(out, self.x)

    def on_iteration(self, it, c, part, distances) -> None:
        pass

    def make_result(self, **fields) -> core_bwkm.BWKMResult:
        return core_bwkm.BWKMResult(health=self.run_health, **fields)


# ------------------------------------------------------- k-means|| session
class InCoreLLSession:
    """Resident k-means|| session: min-d² state and candidates on device.

    Keys match the historical fully-jitted loop exactly — ``keys[0]`` the
    weighted first seed, ``keys[rnd]`` round ``rnd``'s uniforms, ``keys[-1]``
    the final K-means++ reduction — and candidate folds run the identical
    ``min_sqdist_update`` op sequence, so the sharded no-mesh path (which
    delegates here) stays bit-identical by construction.
    """

    def __init__(self, key, x, w, *, k, l, rounds, cap_round, impl):  # noqa: E741
        self.x = x
        self.w = w.astype(jnp.float32)
        self.k, self.l, self.rounds, self.cap_round = k, l, rounds, cap_round
        self.impl = impl
        self.keys = jax.random.split(key, rounds + 2)
        self.n, self.d = x.shape
        cap_total = 1 + rounds * cap_round
        self.cand = jnp.full((cap_total, self.d), core_ll._FAR, x.dtype)
        self.cvalid = jnp.zeros((cap_total,), jnp.float32).at[0].set(1.0)
        self.pending = None  # (newc, newv) selected but not yet folded
        self.n_dist = jnp.zeros((), jnp.float32)

    def seed(self) -> None:
        logw = jnp.where(
            self.w > 0, jnp.log(jnp.maximum(self.w, 1e-30)), -jnp.inf
        )
        first = self.x[jax.random.categorical(self.keys[0], logw)]
        self.cand = self.cand.at[0].set(first)
        out = ops.min_sqdist_update(
            self.x, self.w, self.cand[:1], self.cvalid[:1],
            jnp.full((self.n,), _BIG, jnp.float32), impl=self.impl,
        )
        self.mind2, self.phi, self.n_dist = out.mind2, out.cost, out.n_dist

    def _fold_pending(self) -> None:
        newc, newv = self.pending
        out = ops.min_sqdist_update(
            self.x, self.w, newc, newv, self.mind2, impl=self.impl
        )
        self.mind2, self.phi = out.mind2, out.cost
        self.n_dist = self.n_dist + out.n_dist
        self.pending = None

    def begin_round(self, rnd: int):
        if self.pending is not None:
            self._fold_pending()
        u = jax.random.uniform(self.keys[rnd], (self.n,))
        return u, self.w, self.mind2, self.phi

    def select(self, rnd: int, u, accept) -> None:
        # pack accepted rows into the round's fixed-capacity batch in
        # acceptance-priority order: the smallest uniforms are the draws any
        # smaller acceptance probability would also have kept
        neg, idx = jax.lax.top_k(
            -jnp.where(accept, u, jnp.inf), self.cap_round
        )
        newv = jnp.isfinite(neg).astype(jnp.float32)
        newc = self.x[idx]
        start = 1 + (rnd - 1) * self.cap_round
        self.cand = self.cand.at[start : start + self.cap_round].set(
            jnp.where(newv[:, None] > 0, newc, core_ll._FAR)
        )
        self.cvalid = self.cvalid.at[start : start + self.cap_round].set(newv)
        self.pending = (newc, newv)

    def finish(self, normalisers: tuple) -> dict:
        if self.pending is not None:
            self._fold_pending()  # last round's fold (historical r+2 passes)
        # weighting pass: each candidate inherits the total weight of the
        # points nearest to it; parked rows attract nothing and weigh 0
        au = ops.assign_update(self.x, self.w, self.cand, impl=self.impl)
        n_valid = jnp.sum(self.cvalid)
        n_active = jnp.sum((self.w > 0).astype(jnp.float32))
        n_dist = self.n_dist + n_active * n_valid  # valid columns only
        n_dist = n_dist + n_valid * max(self.k - 1, 1)  # K-means++ reduction
        c = kmeanspp.weighted_kmeanspp(self.keys[-1], self.cand, au.counts, self.k)
        return {
            "centroids": c,
            "n_candidates": n_valid,
            "distances": n_dist,
            "passes": self.rounds + 2,
            "normalisers": normalisers,
        }
