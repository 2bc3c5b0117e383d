"""The Mosaic kernels compile for a TPU v5e chip at the main path's widths,
and the split-round routing compiles to one pass that reads ``x`` in place.

Nothing runs: each kernel is lowered from shapes alone with
``interpret=False`` and compiled by the TPU compiler for a described (not
attached) v5e chip, so what the chip's compiler would refuse — scalar stores
into VMEM, blocks off the (8, 128) tiling, more VMEM than a kernel may use —
fails here at no chip time. The topology is described inside a fixture, never
at import: only one process at a time may load the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.sharding import SingleDeviceSharding

from repro.core import partition
from repro.kernels import distance_assign, fused_assign_update, min_sqdist_update
from repro.roofline import analysis


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize(
    "n,d,k,dtype",
    [
        (65536, 19, 27, jnp.float32),
        (65536, 128, 1024, jnp.float32),
        (65536, 128, 256, jnp.bfloat16),
    ],
)
def test_dense_fused_assign_update_compiles(one_chip, n, d, k, dtype):
    compiled = _compile(
        lambda x, w, c: fused_assign_update.fused_assign_update_pallas(
            x, w, c, interpret=False
        ),
        one_chip, ((n, d), dtype), ((n,), jnp.float32), ((k, d), dtype),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_pruned_fused_assign_update_compiles(one_chip):
    n, d, k = 65536, 19, 27
    compiled = _compile(
        lambda x, w, c, a, act: fused_assign_update.fused_assign_update_pruned_pallas(
            x, w, c, a, act, interpret=False
        ),
        one_chip,
        ((n, d), jnp.float32), ((n,), jnp.float32), ((k, d), jnp.float32),
        ((n,), jnp.int32), ((n,), jnp.bool_),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_min_sqdist_update_compiles(one_chip):
    n, d, l = 65536, 19, 64
    compiled = _compile(
        lambda x, w, c, v, m: min_sqdist_update.min_sqdist_update_pallas(
            x, w, c, v, m, interpret=False
        ),
        one_chip,
        ((n, d), jnp.float32), ((n,), jnp.float32), ((l, d), jnp.float32),
        ((l,), jnp.float32), ((n,), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_assign_top2_compiles(one_chip):
    n, d, k = 65536, 19, 27
    compiled = _compile(
        lambda x, c: distance_assign.assign_top2_pallas(x, c, interpret=False),
        one_chip, ((n, d), jnp.float32), ((k, d), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_planned_vmem_is_admitted_by_the_compiler(one_chip):
    """The blockings plan against ``analysis.VMEM_BYTES``: a kernel whose
    double-buffered input and output blocks fill exactly that much must
    compile under the compiler's default VMEM limit."""
    rows = analysis.VMEM_BYTES // (2 * 2 * 128 * 4)  # in + out, two buffers each

    def double(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    def copy(x):
        return pl.pallas_call(
            double,
            grid=(4,),
            in_specs=[pl.BlockSpec((rows, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((rows, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((4 * rows, 128), jnp.float32),
        )(x)

    compiled = _compile(copy, one_chip, ((4 * rows, 128), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,d,m", [(5_000_000, 19, 14528), (434_874, 3, 3328)])
def test_route_split_reads_x_in_place_at_the_fit_cells_sizes(one_chip, n, d, m):
    """The split-round routing at the SUSY and 3RN cells' sizes, as the TPU
    compiler optimises it: one gather (the packed plan lookup) and no copy,
    transpose or gather of the ``[n, d]`` rows."""
    compiled = _compile(
        lambda x, b, f, a, mid, r: partition.route_split(
            x, b, partition.SplitPlan(f, a, mid, r, jnp.sum(f))
        ),
        one_chip,
        ((n, d), jnp.float32), ((n,), jnp.int32), ((m,), jnp.bool_),
        ((m,), jnp.int32), ((m,), jnp.float32), ((m,), jnp.int32),
    )
    lines = compiled.as_text().splitlines()
    assert sum(" gather(" in line for line in lines) == 1
    rows = f"f32[{n},{d}]"
    for op in (" copy(", " transpose(", " gather("):
        assert not [line for line in lines if op in line and rows in line.split(op, 1)[1]]
