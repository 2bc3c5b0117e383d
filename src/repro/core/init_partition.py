"""Construction of BWKM's initial partition (paper Algorithms 2, 3, 4).

Algorithm 3 grows the bounding box to ``m'`` blocks by repeatedly sampling
``min(|B|, m'−|B|)`` blocks *with replacement* with probability
``∝ l_B · |B(S)|`` (diagonal × sample occupancy) and splitting them.

Algorithm 4 estimates, for each block, how likely it is to be badly
assigned: for ``r`` subsamples ``S^i`` of size ``s``, run K-means++ over
the representatives of ``B(S^i)`` and accumulate ε_{S^i,C^i}(B); Eq. 5
normalises the accumulated ε into cutting probabilities.

Algorithm 2 alternates Algorithm-4 probabilities with ∝-sampled splits
until ``m`` blocks exist.

Deviation (documented in DESIGN.md §8): we keep the full-dataset point
routing up to date during construction (one compiled O(n·d) routing pass
per split round) instead of a single pass at the end. Each round's split
planes come from the tight boxes of all rows, so every round needs the
routed rows: end-only routing would give a different partition.

Paper defaults (Section 2.4.1): m = 10·√(K·d), s = √n, r = 5, and our
m' = max(K+1, m/10) (the paper requires K < m' < m but fixes no value).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import misassignment as mis
from repro.core import partition as part_mod
from repro.core.kmeanspp import weighted_kmeanspp
from repro.core.partition import Partition

__all__ = ["default_params", "starting_partition", "cutting_probabilities_alg4", "build_initial_partition"]


def default_params(n: int, k: int, d: int) -> dict:
    """The paper's experimental defaults (Section 2.4.1)."""
    m = max(k + 1, int(math.ceil(10.0 * math.sqrt(k * d))))
    return {
        "m": m,
        "m_prime": max(k + 1, m // 10),
        "s": max(1, int(math.ceil(math.sqrt(n)))),
        "r": 5,
    }


def _sample_split_round(
    key: jax.Array,
    part: Partition,
    x: jax.Array,
    weights_per_block: jax.Array,
    target: int,
) -> Partition:
    """One round: sample ``min(|B|, target−|B|)`` blocks ∝ weights, split them."""
    num = jnp.minimum(part.n_blocks, target - part.n_blocks)
    chosen = mis.sample_boundary(key, weights_per_block, num)
    return part_mod.split_blocks(part, x, chosen)


def starting_partition(
    key: jax.Array, x: jax.Array, m_prime: int, s: int, capacity: int
) -> Partition:
    """Algorithm 3: grow to ``m'`` blocks with Pr ∝ l_B · |B(S)|."""
    part = part_mod.create_partition(x, capacity)
    n = x.shape[0]
    # Worst case one net split per round; typical rounds ~ log2(m').
    for _ in range(4 * m_prime):
        if obs.pull(part.n_blocks, int) >= m_prime:
            break
        key, k_s, k_c = jax.random.split(key, 3)
        sample_idx = jax.random.randint(k_s, (s,), 0, n)
        occ = jax.ops.segment_sum(
            jnp.ones((s,), jnp.float32),
            part.block_id[sample_idx],
            num_segments=part.capacity,
        )
        w = part_mod.diagonals(part) * occ
        # If the sample missed every splittable block, fall back to diagonals
        # so the round cannot stall (occupied blocks with ≥2 points exist).
        splittable = (part.count > 1) & part.active
        w = jnp.where(
            jnp.any(jnp.where(splittable, w, 0.0) > 0),
            w,
            jnp.where(splittable, part_mod.diagonals(part), 0.0),
        )
        part = _sample_split_round(k_c, part, x, w, m_prime)
    return part


def cutting_probabilities_alg4(
    key: jax.Array, part: Partition, x: jax.Array, k: int, s: int, r: int
) -> jax.Array:
    """Algorithm 4: accumulated ε over ``r`` K-means++ runs on subsample-induced
    representatives, normalised by Eq. 5. Returns the *unnormalised* ε sum
    (callers normalise; Pr(B) = eps_sum / Σ eps_sum)."""
    n = x.shape[0]
    m = part.capacity
    eps_sum = jnp.zeros((m,), jnp.float32)
    for _ in range(r):
        key, k_s, k_pp = jax.random.split(key, 3)
        idx = jax.random.randint(k_s, (s,), 0, n)
        xs = x[idx]
        bid = part.block_id[idx]
        # Representatives of the sample-induced partition P = B(S^i).
        ssum = jax.ops.segment_sum(xs, bid, num_segments=m)
        scount = jax.ops.segment_sum(jnp.ones((s,), jnp.float32), bid, num_segments=m)
        reps = ssum / jnp.maximum(scount, 1.0)[:, None]
        w = jnp.where(part.active, scount, 0.0)
        c_i = weighted_kmeanspp(k_pp, reps, w, k)
        from repro.kernels import ops as kops

        _, d1, d2 = kops.assign_top2(reps, c_i)
        sample_part = part._replace(count=scount)  # ε over B(S^i): occupancy of S^i
        eps_sum = eps_sum + mis.misassignment(sample_part, d1, d2)
    return eps_sum


def build_initial_partition(
    key: jax.Array,
    x: jax.Array,
    k: int,
    *,
    m: int,
    m_prime: int,
    s: int,
    r: int,
    capacity: int,
) -> Partition:
    """Algorithm 2: starting partition (Alg 3), then grow to ``m`` blocks by
    sampling ∝ Alg-4 cutting probabilities."""
    key, k0 = jax.random.split(key)
    with obs.span("bwkm.init.start"):
        part = starting_partition(k0, x, m_prime, s, capacity)
    for _ in range(4 * m):
        if obs.pull(part.n_blocks, int) >= m:
            break
        with obs.span("bwkm.init.grow"):
            key, k_p, k_c = jax.random.split(key, 3)
            eps_sum = cutting_probabilities_alg4(k_p, part, x, k, s, r)
            splittable = (part.count > 1) & part.active
            eps_sum = jnp.where(splittable, eps_sum, 0.0)
            # All blocks already well assigned for every (S^i, C^i): Pr ≡ 0.
            # The partition is as good as the samples can tell — stop growing.
            if not obs.pull(jnp.any(eps_sum > 0), bool):
                break
            part = _sample_split_round(k_c, part, x, eps_sum, m)
    return part
