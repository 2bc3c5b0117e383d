"""Weighted cluster update as an on-the-fly one-hot MXU matmul.

The update step ``sums[assign_i] += w_i · x_i`` is a scatter — hostile to
the TPU vector unit. We rewrite it as ``onehot(assign)ᵀ @ (w ⊙ X)`` where
the ``[bn, K]`` one-hot tile is built in-registers from a broadcasted iota
compare, so the contraction runs on the MXU and the ``[K, d]`` accumulator
stays resident in VMEM across the n-tile (reduction) grid dimension.

K·d for this framework's workloads (K ≤ a few thousand codebook entries,
d ≤ 8192) fits VMEM as a single f32 block; the wrapper asserts this.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["cluster_sums_pallas"]


def _kernel(x_ref, w_ref, a_ref, sums_ref, counts_ref, *, bn: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    xb = x_ref[...].astype(jnp.float32)  # [bn, d]
    wb = w_ref[...].astype(jnp.float32)  # [bn, 1]
    ab = a_ref[...]  # [bn, 1] int32 (padded rows carry weight 0)

    kp = sums_ref.shape[0]
    onehot = (
        ab == jax.lax.broadcasted_iota(jnp.int32, (bn, kp), 1)
    ).astype(jnp.float32) * wb  # [bn, K] weighted one-hot

    sums_ref[...] += jax.lax.dot_general(
        onehot, xb, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
    )  # [K, d] via MXU
    counts_ref[...] += jnp.sum(onehot, axis=0, keepdims=True).T  # [K, 1]


@functools.partial(jax.jit, static_argnames=("num_clusters", "interpret", "bn"))
def cluster_sums_pallas(
    x: jax.Array,
    w: jax.Array,
    assign: jax.Array,
    num_clusters: int,
    *,
    interpret: bool = False,
    bn: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Pallas-accelerated ``ref.cluster_sums``: ``(sums [K,d], counts [K])``."""
    n, d = x.shape
    k = num_clusters

    dp = pl.cdiv(d, 128) * 128
    kp = pl.cdiv(k, 8) * 8
    assert kp * dp * 4 <= 8 * 1024 * 1024, "K·d accumulator must fit VMEM"
    if bn is None:
        bn = max(8, min(512, (2 * 1024 * 1024 // (4 * dp)) // 8 * 8))
    np_ = pl.cdiv(n, bn) * bn

    xpad = jnp.pad(x, ((0, np_ - n), (0, dp - d)))
    wpad = jnp.pad(w.astype(jnp.float32), (0, np_ - n))[:, None]  # pad rows -> w=0
    apad = jnp.pad(assign.astype(jnp.int32), (0, np_ - n))[:, None]

    sums, counts = pl.pallas_call(
        functools.partial(_kernel, bn=bn),
        grid=(np_ // bn,),
        in_specs=[
            pl.BlockSpec((bn, dp), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((kp, dp), lambda i: (0, 0)),
            pl.BlockSpec((kp, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, dp), jnp.float32),
            jax.ShapeDtypeStruct((kp, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(xpad, wpad, apad)

    return sums[:k, :d], counts[:k, 0]
