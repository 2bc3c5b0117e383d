"""The Mosaic kernels compile for a described TPU v5e at the cells' widths.

Nothing runs: each kernel is lowered from shapes with ``interpret=False``
and compiled for a v5e chip that is described, not attached. The shapes are
those the cells drive: the fit kernels over the 3RN partition's 3,328 block
rows at d = 3, K = 9 (``3rn_k9``), and the predictor's 2,048-row chunk at
d = 19, K = 27 (``susy_k27``). The topology is described inside a fixture,
never at import: one process at a time may load the TPU library.

    python -m pytest chipbench/tests/test_compile_rehearsal.py
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bwkm import BWKMConfig
from repro.kernels import distance_assign, fused_assign_update
from repro.service import BatchedPredictor

N_3RN, D_3RN, K_3RN = 434_874, 3, 9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _rows_3rn() -> int:
    return BWKMConfig(k=K_3RN).resolve(N_3RN, D_3RN)["capacity"]


def test_3rn_partition_capacity():
    assert _rows_3rn() == 3328


def test_3rn_dense_fused_kernel_compiles(one_chip):
    m = _rows_3rn()
    compiled = _compile(
        lambda x, w, c: fused_assign_update.fused_assign_update_pallas(
            x, w, c, interpret=False),
        one_chip, ((m, D_3RN), jnp.float32), ((m,), jnp.float32),
        ((K_3RN, D_3RN), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_3rn_pruned_fused_kernel_compiles(one_chip):
    m = _rows_3rn()
    compiled = _compile(
        lambda x, w, c, a, act: fused_assign_update.fused_assign_update_pruned_pallas(
            x, w, c, a, act, interpret=False),
        one_chip, ((m, D_3RN), jnp.float32), ((m,), jnp.float32),
        ((K_3RN, D_3RN), jnp.float32), ((m,), jnp.int32), ((m,), jnp.bool_),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_3rn_top2_kernel_compiles(one_chip):
    m = _rows_3rn()
    compiled = _compile(
        lambda x, c: distance_assign.assign_top2_pallas(x, c, interpret=False),
        one_chip, ((m, D_3RN), jnp.float32), ((K_3RN, D_3RN), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_predictor_chunk_top2_kernel_compiles(one_chip):
    rows = BatchedPredictor(jnp.zeros((27, 19), jnp.float32)).chunk_size
    compiled = _compile(
        lambda x, c: distance_assign.assign_top2_pallas(x, c, interpret=False),
        one_chip, ((rows, 19), jnp.float32), ((27, 19), jnp.float32),
    )
    assert "tpu_custom_call" in compiled.as_text()
