"""Compiles for a described TPU v5e at real widths, with no chip attached.

The TPU compiler refuses what interpret mode accepts: blocks off the
(8, 128) tiling, primitives Mosaic cannot lower, programs over the chip's
memory.  Each test lowers a kernel or a served step against the described
``v5e:2x2`` topology and compiles it; nothing runs.  The topology is
described inside a fixture, so only the process that runs this file loads
the TPU library.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.core.sst_exchange import ROW_WIDTH, make_sst_allgather
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ssd_scan import ssd_scan
from repro.launch.mesh import make_mesh
from repro.models import abstract_params, decode_step, init_cache, prefill
from repro.training import make_serve_step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    cache_was_on = jax.config.jax_enable_compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# (kernel, argument shapes) at the widths the configs use:
# ssd_scan — mamba2-780m (H=48, P=64, N=128, chunk 128);
# decode_attention / flash_attention — mistral-nemo-12b (32 q heads,
# 8 KV heads, head dim 128); moe_gmm — qwen3-moe-30b-a3b (128 experts,
# d_model 2048, d_ff_expert 768).
KERNELS = {
    "ssd_scan": (
        lambda x, dt, a, b, c: ssd_scan(x, dt, a, b, c, chunk=128),
        [((2, 1024, 48, 64), jnp.bfloat16), ((2, 1024, 48), jnp.float32),
         ((48,), jnp.float32), ((2, 1024, 48, 128), jnp.bfloat16),
         ((2, 1024, 48, 128), jnp.bfloat16)],
    ),
    "decode_attention": (
        decode_attention,
        [((4, 32, 128), jnp.bfloat16), ((4, 4096, 8, 128), jnp.bfloat16),
         ((4, 4096, 8, 128), jnp.bfloat16), ((4,), jnp.int32)],
    ),
    "flash_attention": (
        flash_attention,
        [((1, 2048, 32, 128), jnp.bfloat16), ((1, 2048, 8, 128), jnp.bfloat16),
         ((1, 2048, 8, 128), jnp.bfloat16)],
    ),
    "moe_gmm": (
        moe_gmm,
        [((2048, 2048), jnp.bfloat16), ((128, 2048, 768), jnp.bfloat16),
         ((128,), jnp.int32)],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel


def test_mamba2_780m_decode_step_compiles_for_v5e(one_chip):
    """The served step of chip_smoke.py: full width, bf16, one chip."""
    cfg = get_config("mamba2-780m")

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree,
        )

    params = on_chip(abstract_params(cfg))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, 2, 73)))
    tokens = jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    compiled = _compile(
        lambda p, c, t: decode_step(p, c, t, cfg, moe_dispatch="scan"),
        params, cache, tokens,
    )
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 1.5e9  # ~1.6 GB of bf16 weights
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_nemo_12b_s10_prefill_compiles_for_v5e(one_chip):
    """The served whole-prompt prefill of the benchmark's nemo-12b stage
    (10 of 40 layers, bf16) at its longest prompt, 256, on one chip."""
    cfg = dataclasses.replace(get_config("mistral-nemo-12b"), n_layers=10,
                              dtype="bfloat16")

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree,
        )

    params = on_chip(abstract_params(cfg))
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, 1, 256 + 9)))
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32, sharding=one_chip)
    compiled = _compile(lambda p, c, t: prefill(p, c, t, cfg),
                        params, cache, tokens)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 8e9  # ~8.1 GB of bf16 weights
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_nemo_12b_serve_step_compiles_for_four_v5e(topo):
    """The sharded serve step on a (data=1, model=4) mesh: each chip holds
    a quarter of the ~24 GB of weights."""
    cfg = get_config("mistral-nemo-12b")
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices[:4])
    params = abstract_params(cfg)
    cache = jax.eval_shape(lambda: init_cache(cfg, 2, 8))
    tokens = jax.ShapeDtypeStruct((2,), jnp.int32)
    _, jit_step = make_serve_step(cfg, mesh)
    compiled = jit_step(params, cache, tokens).lower(
        params, cache, tokens
    ).compile()
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert total / 4 <= per_chip < total / 3
    assert "all-reduce" in compiled.as_text()


def test_sst_allgather_compiles_for_four_v5e(topo):
    mesh = make_mesh((1, 4), ("data", "model"), devices=topo.devices[:4])
    rows = jax.ShapeDtypeStruct(
        (4, ROW_WIDTH), jnp.uint32, sharding=NamedSharding(mesh, P("model"))
    )
    compiled = make_sst_allgather(mesh, axis="model").lower(rows).compile()
    assert "all-gather" in compiled.as_text()
    assert np.prod(compiled.output_shardings.shard_shape((4, ROW_WIDTH))) == (
        4 * ROW_WIDTH
    )
