"""Compile the served path's device programs for a described TPU v5e.

Interpret-mode kernel tests cannot see what the chip's compiler refuses
(tiling alignment, VMEM limits, programs that do not fit HBM).  These
tests compile, without a chip, at the shapes ``chip_smoke.py`` serves:
the block-copy kernels at MiniCPM3-4B's latent KV pool and Mamba2-370M's
constant-state pool, and full-width MiniCPM3-4B decode steps on one chip
and sharded over four.

The topology is described inside a fixture: only one process may load
the TPU library, and the library stays loaded until that process exits,
so nothing here may touch it while modules are imported.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs.base import get_config
from repro.core.paged_kv import PagedKVCache
from repro.kernels import block_copy as bc
from repro.launch.steps import build_serve_step
from repro.models.api import build_model

HBM_BYTES = 16e9                  # one v5e chip
NB_KV = 24                        # chip_smoke's MiniCPM3-4B pool
NB_STATE = 6                      # chip_smoke's Mamba2-370M pool
N_COPY = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A described-chip compile is written to the persistent cache but
    can never be read back without a chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _place(tree, shardings):
    return jax.tree.map(lambda x, s: _sds(x.shape, x.dtype, s), tree,
                        shardings)


def _peak(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _minicpm3():
    return build_model(get_config("minicpm3_4b"))


def _latent_pool(one_chip):
    kv = _minicpm3().kv_config(max_seq=2048, num_blocks=NB_KV, batch=8)
    assert kv.latent
    return _sds(kv.pool_shape(), kv.dtype, one_chip)


@pytest.mark.parametrize("kernel", ["gather_blocks", "scatter_blocks",
                                    "copy_pool_blocks"])
def test_layered_block_kernels_compile(one_chip, kernel):
    pool = _latent_pool(one_chip)
    idx = _sds((N_COPY,), jnp.int32, one_chip)
    if kernel == "gather_blocks":
        fn, args = bc.gather_blocks, (pool, idx)
    elif kernel == "scatter_blocks":
        payload = _sds((pool.shape[0], N_COPY) + pool.shape[2:], pool.dtype,
                       one_chip)
        fn, args = bc.scatter_blocks, (pool, idx, payload)
    else:
        fn, args = bc.copy_pool_blocks, (pool, idx, idx)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_block_copy_compiles_on_state_pool(one_chip):
    """The constant-state pool's blocks are whole sequence states (52 MB
    for Mamba2-370M): copied HBM to HBM, never staged through VMEM."""
    model = build_model(get_config("mamba2_370m"))
    assert model.state_elems % 128 == 0
    pool = _sds((NB_STATE, 1, model.state_elems), jnp.float32, one_chip)
    idx = _sds((N_COPY,), jnp.int32, one_chip)
    compiled = jax.jit(bc.block_copy, donate_argnums=0).lower(
        pool, idx, idx).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_fused_decode_step_fits_one_chip(one_chip):
    """Full-width MiniCPM3-4B resident decode at 8 slots x 2048 tokens."""
    model = _minicpm3()
    params, _ = model.param_specs()
    params = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip), params)
    B, W = 8, 8
    kv = model.kv_config(max_seq=2048, num_blocks=256, batch=B)
    cache = jax.tree.map(lambda x: _sds(x.shape, x.dtype, one_chip),
                         PagedKVCache.specs(kv, B))
    i32 = lambda *shape: _sds(shape, jnp.int32, one_chip)
    compiled = jax.jit(model._decode_fused_impl, donate_argnums=(2,)).lower(
        params, i32(B), cache, i32(W), i32(W, kv.max_blocks_per_seq),
        i32(W)).compile()
    assert _peak(compiled) < HBM_BYTES, compiled.memory_analysis()


def test_sharded_serve_step_compiles_on_four_chips(topo):
    """``chip_smoke.py --four-chips``: the (data=1, model=4) serve step
    spreads the parameters (about a quarter on each chip)."""
    model = _minicpm3()
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    B = 4
    cache = PagedKVCache.specs(model.kv_config(max_seq=1024, batch=B), B)
    step = build_serve_step(model, mesh, cache, donate=False)
    pshard, tshard, sshard = step.in_shardings
    params, _ = model.param_specs()
    compiled = step.lower(_place(params, pshard),
                          _sds((B,), jnp.int32, tshard),
                          _place(cache, sshard)).compile()
    pbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert compiled.memory_analysis().argument_size_in_bytes < pbytes / 3
