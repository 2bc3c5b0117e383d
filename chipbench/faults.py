"""Faults planted under the timed path, to show that ``correct`` catches them.

Each fault is a context manager that patches the program in this process
only, for the faults test and for ``readings --fault``; benchmark runs never
plant one.

* ``unchanged``       weighted Lloyd returns the centroids it was given;
* ``half_batch``      block statistics are taken over the first half of the
  rows only;
* ``altered``         the fit's answer is altered where it is made: one
  returned centroid moves by one unit in every coordinate;
* ``lowp_distances``  every ``lax.dot_general`` the kernels make at
  ``Precision.HIGHEST`` gets its operands rounded to bfloat16 first: one
  bfloat16 pass, the TPU's default precision for float32. Compiled
  programs are dropped on the way in and out, so the fits retrace with it.
"""

from __future__ import annotations

import contextlib
from unittest import mock

FIT_FAULTS = ("unchanged", "half_batch", "altered", "lowp_distances")


@contextlib.contextmanager
def planted(name: str):
    import jax
    import jax.numpy as jnp

    from repro.core import lloyd, partition
    from repro.engine import incore

    if name == "unchanged":
        real = lloyd.weighted_lloyd

        def stuck(x, w, init_centroids, **kw):
            return real(x, w, init_centroids, **kw)._replace(centroids=init_centroids)

        patch = mock.patch.object(lloyd, "weighted_lloyd", stuck)
    elif name == "half_batch":
        real_stats = partition.block_stats

        def half(part, x):
            h = x.shape[0] // 2
            st = real_stats(x[:h], part.block_id[:h], part.capacity)
            return part._replace(psum=st.psum, count=st.count, lo=st.lo, hi=st.hi)

        patch = mock.patch.object(partition, "recompute_stats", half)
    elif name == "altered":
        real_make = incore.InCorePlane.make_result

        def moved(self, **fields):
            fields["centroids"] = fields["centroids"].at[0].add(1.0)
            return real_make(self, **fields)

        patch = mock.patch.object(incore.InCorePlane, "make_result", moved)
    elif name == "lowp_distances":
        real_dot = jax.lax.dot_general

        def one_pass(lhs, rhs, *args, precision=None, **kw):
            if precision == jax.lax.Precision.HIGHEST:
                lhs = lhs.astype(jnp.bfloat16).astype(lhs.dtype)
                rhs = rhs.astype(jnp.bfloat16).astype(rhs.dtype)
            return real_dot(lhs, rhs, *args, precision=precision, **kw)

        patch = _retraced(mock.patch.object(jax.lax, "dot_general", one_pass))
    else:
        raise ValueError(f"no fault {name!r}")
    with patch:
        yield


@contextlib.contextmanager
def _retraced(patch):
    import jax

    jax.clear_caches()
    try:
        with patch:
            yield
    finally:
        jax.clear_caches()
