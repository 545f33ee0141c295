"""Ahead-of-time compiles of the crossbar kernels for a TPU v5e at
qwen3-1.7B widths (d_model 2048, d_ff 6144, vocab 151936, DoRA rank 8).

Nothing runs: each case lowers a launcher at the tile plan the serving
path would pick on the chip (``autotune.select_tiles(..., interpret=False)``)
and compiles it for a described ``v5e:2x2`` topology, so the Mosaic
compiler refuses here what it would refuse on the device (unsupported
casts, unaligned blocks, VMEM overruns). The topology is described inside
a fixture: only the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels.crossbar_mvm import crossbar_mvm
from repro.kernels.dora_linear import dora_linear, dora_linear_gemv


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler installed / loadable
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


# (launcher, accum, M, K, N, r): decode batches are a handful of slots,
# prefill a 512-token chunk. r is the summed rank of a fused launch.
_DORA_CASES = [
    ("prefill", "f32", 512, 2048, 4096, 24),     # fused q+k+v
    ("prefill", "int8", 512, 6144, 2048, 8),     # mlp down
    ("decode", "f32", 8, 2048, 12288, 16),       # fused gate+up
    ("decode", "int8", 8, 2048, 4096, 24),       # fused q+k+v
    ("decode", "f32", 8, 2048, 151936, 8),       # lm_head-sized N
]


@pytest.mark.parametrize("launcher,accum,m,k,n,r", _DORA_CASES)
def test_dora_linear_compiles_for_v5e(
    one_chip, no_persistent_cache, launcher, accum, m, k, n, r
):
    plan = autotune.select_tiles(
        m, k, n, r, interpret=False, int8=(accum == "int8")
    )
    assert plan.gemv == (launcher == "decode"), plan

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (
        spec((plan.m_pad, plan.k_pad), jnp.bfloat16),
        spec((plan.k_pad, plan.n_pad), jnp.uint8),
        spec((plan.k_pad, plan.n_pad), jnp.uint8),
        spec((1, plan.n_pad), jnp.float32),
        spec((plan.k_pad, r), jnp.float32),
        spec((r, plan.n_pad), jnp.float32),
        spec((1, plan.n_pad), jnp.float32),
    )
    if plan.gemv:
        fn = lambda *a: dora_linear_gemv(
            *a, bn=plan.bn, bk=plan.bk, accum=accum
        )
    else:
        fn = lambda *a: dora_linear(
            *a, bm=plan.bm, bn=plan.bn, bk=plan.bk, accum=accum
        )
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_crossbar_mvm_compiles_for_v5e(one_chip, no_persistent_cache):
    m, k, n = 512, 2048, 4096

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(crossbar_mvm).lower(
        spec((m, k), jnp.bfloat16), spec((k, n), jnp.uint8),
        spec((k, n), jnp.uint8), spec((1, n), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
