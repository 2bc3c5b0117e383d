"""Seeding strategies: Forgy, (weighted) K-means++, and AFK-MC².

The paper uses a *weighted* K-means++ run over the representatives of the
current dataset partition (Algorithm 5 Step 1, Algorithm 4), and compares
against Forgy (FKM), K-means++ (KM++) and the MCMC approximation of
K-means++ (KMC2, reference [3] = Bachem et al. 2016, AFK-MC²) as baselines.

All samplers are jit-compatible with a static ``K`` (lax.scan over seeds).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ref

__all__ = ["forgy", "weighted_kmeanspp", "kmeanspp", "afkmc2"]


def forgy(key: jax.Array, x: jax.Array, k: int, w: jax.Array | None = None) -> jax.Array:
    """K instances selected uniformly at random (weight-proportional if ``w``).

    With fewer than ``k`` positive-weight rows the Gumbel top-k runs out of
    finite scores, so the short slots are filled by cycling through the
    valid draws (duplicated seeds — the degenerate-but-safe choice; a
    zero-weight row is an inactive/padding partition row and must never
    become a seed). No positive weight at all is an error.
    """
    n = x.shape[0]
    if w is None:
        idx = jax.random.choice(key, n, shape=(k,), replace=False)
    else:
        if not isinstance(w, jax.core.Tracer) and not obs.pull(jnp.any(w > 0), bool):
            raise ValueError("forgy: no rows with positive weight to seed from")
        # Weight-proportional without replacement via Gumbel top-k on log-weights.
        logw = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)), -jnp.inf)
        g = jax.random.gumbel(key, (n,)) + logw
        gv, idx = jax.lax.top_k(g, k)
        # top_k sorts descending, so the finite (valid) draws occupy a
        # prefix; remap the -inf tail onto that prefix cyclically
        n_pos = jnp.maximum(jnp.sum(jnp.isfinite(gv)), 1)
        idx = jnp.where(
            jnp.isfinite(gv), idx, idx[jnp.arange(k) % n_pos]
        )
    return x[idx]


@partial(jax.jit, static_argnames=("k",))
def weighted_kmeanspp(key: jax.Array, x: jax.Array, w: jax.Array, k: int) -> jax.Array:
    """Weighted K-means++ (Arthur & Vassilvitskii 2007) over weighted points.

    Each seed is drawn with probability ``∝ w_i · d(x_i, C)^2`` (first seed
    ``∝ w_i``). Zero-weight rows (inactive/empty partition rows) are never
    selected.
    """
    n = x.shape[0]
    w = w.astype(jnp.float32)
    logw = jnp.where(w > 0, jnp.log(jnp.maximum(w, 1e-30)), -jnp.inf)

    key0, key_scan = jax.random.split(key)
    first = x[jax.random.categorical(key0, logw)]
    centroids = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(first)
    mind2 = jnp.sum((x - first[None, :]) ** 2, axis=-1)

    def step(carry, i):
        centroids, mind2, key = carry
        key, sub = jax.random.split(key)
        logits = logw + jnp.log(jnp.maximum(mind2, 1e-30))
        # If every remaining mass is zero (all points coincide with chosen
        # seeds), categorical over -inf logits would nan; fall back to logw.
        logits = jnp.where(jnp.all(~jnp.isfinite(logits)), logw, logits)
        idx = jax.random.categorical(sub, logits)
        c_new = x[idx]
        centroids = centroids.at[i].set(c_new)
        mind2 = jnp.minimum(mind2, jnp.sum((x - c_new[None, :]) ** 2, axis=-1))
        return (centroids, mind2, key), None

    (centroids, _, _), _ = jax.lax.scan(
        step, (centroids, mind2, key_scan), jnp.arange(1, k)
    )
    return centroids


def kmeanspp(key: jax.Array, x: jax.Array, k: int) -> jax.Array:
    """Unweighted K-means++ (the paper's KM++ baseline init)."""
    return weighted_kmeanspp(key, x, jnp.ones(x.shape[0], jnp.float32), k)


@partial(jax.jit, static_argnames=("k", "chain_length"))
def afkmc2(key: jax.Array, x: jax.Array, k: int, chain_length: int = 200) -> jax.Array:
    """AFK-MC²: assumption-free MCMC approximation of K-means++ (paper ref [3]).

    Proposal ``q(x) = 0.5 · d(x,c1)²/Σd(·,c1)² + 0.5/n``; for each of the
    remaining ``k−1`` seeds a Metropolis-Hastings chain of length
    ``chain_length`` is run, giving ``O(k²·m·d)`` distance computations —
    sublinear in ``n``.
    """
    n = x.shape[0]
    key0, key_q, key_scan = jax.random.split(key, 3)
    c1 = x[jax.random.randint(key0, (), 0, n)]
    d1 = jnp.sum((x - c1[None, :]) ** 2, axis=-1)
    q = 0.5 * d1 / jnp.maximum(jnp.sum(d1), 1e-30) + 0.5 / n  # [n]
    logq = jnp.log(q)

    centroids = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(c1)

    def sample_seed(carry, i):
        centroids, key = carry
        key, kidx, kacc = jax.random.split(key, 3)
        # Chain: propose chain_length candidates i.i.d. from q, then do the
        # sequential MH accept pass over them (vectorised distance evals).
        # The batch shape comes from `shape=`, NOT from materialising an
        # [chain_length, n] logits matrix — same draws (categorical
        # broadcasts the logits over the batch), O(n) live memory.
        cand = jax.random.categorical(kidx, logq, shape=(chain_length,))
        xc = x[cand]  # [m, d]
        dc = jnp.min(
            jnp.sum((xc[:, None, :] - centroids[None, :, :]) ** 2, axis=-1)
            + jnp.where(jnp.arange(k) < i, 0.0, jnp.inf)[None, :],
            axis=-1,
        )  # d(x_cand, C_so_far)^2, masked to the i seeds chosen so far
        ratio = (dc / q[cand])  # MH target/proposal (unnormalised)
        u = jax.random.uniform(kacc, (chain_length,))

        def mh(state, j):
            cur, cur_ratio = state
            accept = u[j] < ratio[j] / jnp.maximum(cur_ratio, 1e-30)
            cur = jnp.where(accept, cand[j], cur)
            cur_ratio = jnp.where(accept, ratio[j], cur_ratio)
            return (cur, cur_ratio), None

        (sel, _), _ = jax.lax.scan(mh, (cand[0], ratio[0]), jnp.arange(chain_length))
        centroids = centroids.at[i].set(x[sel])
        return (centroids, key), None

    (centroids, _), _ = jax.lax.scan(
        sample_seed, (centroids, key_scan), jnp.arange(1, k)
    )
    return centroids
