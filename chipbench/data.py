"""Seeded data made on the device: the configuration's mixture.

The mixture's structure (centres, weights, per-mode scales) and its sample
are both fixed by the configuration's ``structure_seed``, so every run
clusters the same data set, as a user clusters one file. A sample drawn
from the run seed would change the work: on 3RN the error share of one
fit spread from 5.4% to 11.9% over a dozen samples (PERF.md). The structure
follows the Table-1 stand-ins of the paper's datasets: centres N(0,
spread²), Dirichlet (0.5) mode weights, per-coordinate scales U(0.5,
anisotropy).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


class Mixture:
    """The configuration's mixture; ``sample`` runs as one jitted call."""

    def __init__(self, gen: dict, d: int):
        rng = np.random.RandomState(int(gen["structure_seed"]))
        modes = int(gen["modes"])
        self.centers = jnp.asarray(rng.randn(modes, d) * gen["spread"], jnp.float32)
        self.log_weights = jnp.asarray(
            np.log(rng.dirichlet(np.full(modes, 0.5))), jnp.float32
        )
        self.scales = jnp.asarray(
            rng.uniform(0.5, gen["anisotropy"], size=(modes, d)), jnp.float32
        )
        self.d = d

    def sample(self, key: jax.Array, n: int) -> jax.Array:
        """``n`` rows of the mixture."""
        return _sample(key, self.centers, self.log_weights, self.scales, n=n)


@partial(jax.jit, static_argnames=("n",))
def _sample(key, centers, log_weights, scales, *, n):
    k_comp, k_noise = jax.random.split(key)
    comp = jax.random.categorical(k_comp, log_weights, shape=(n,))
    noise = jax.random.normal(k_noise, (n, centers.shape[1]), jnp.float32)
    return centers[comp] + noise * scales[comp]


def key_for(seed: int, *purpose: int) -> jax.Array:
    """A PRNG key from a run seed of any size and a purpose tag."""
    state = np.random.SeedSequence([int(seed) % (1 << 64), *purpose]).generate_state(1)
    return jax.random.PRNGKey(int(state[0]))


def rng_for(seed: int, *purpose: int) -> np.random.Generator:
    """A host generator from a run seed of any size and a purpose tag."""
    return np.random.default_rng(np.random.SeedSequence([int(seed) % (1 << 64), *purpose]))
