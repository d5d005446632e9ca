"""Compile-only checks of the live path's Pallas kernels and one MobileNetV2
stage step for a described TPU v5e, at the shapes the chip runs: 32x32x3
inputs, batch 128, the default 3-way split (layers 0-6 / 7-12 / 13-18).

Nothing runs. The TPU compiler, which is installed without a chip attached,
refuses here what the chip would refuse (unaligned tiles, more scoped VMEM
than a kernel may use), which the Pallas interpreter of the other kernel
tests cannot show. The topology is described inside a fixture, never at
import: only one process may load the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_sgd.ops import fused_sgd
from repro.kernels.quant.ops import dequantize, quantize_ef
from repro.runtime.stage_executor import StageExecutor
from repro.runtime.workload import mobilenet_chain

BATCH, HW = 128, 32
# boundary activations of the default 3-way split at 32x32: after layer 6
# and after layer 12
BOUNDARIES = [(BATCH, 16, 16, 32), (BATCH, 8, 8, 96)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


@pytest.fixture(scope="module")
def chain():
    return mobilenet_chain(jax.random.PRNGKey(0))


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def test_fused_sgd_compiles_at_largest_stage_slice(spec, chain):
    sizes = [chain.flat_layout().slice(a, e).size
             for a, e in ((0, 6), (7, 12), (13, 18))]
    n = max(sizes)
    f = jax.jit(lambda p, g, m: fused_sgd(p, g, m, lr=0.05,
                                          interpret=False))
    compiled = f.lower(spec((n,)), spec((n,)), spec((n,))).compile()
    assert _custom_calls(compiled) >= 1


@pytest.mark.parametrize("shape", BOUNDARIES)
def test_quantize_ef_compiles_at_boundary(spec, shape):
    f = jax.jit(lambda z, r: quantize_ef(z, r, interpret=False))
    compiled = f.lower(spec(shape), spec(shape)).compile()
    assert _custom_calls(compiled) >= 1
    # the kernel must fit the chip's scoped VMEM, not the whole tensor:
    # nothing beyond the in/out tensors and small per-channel vectors
    mem = compiled.memory_analysis()
    tensor = 4 * int(jnp.prod(jnp.array(shape)))
    assert mem.temp_size_in_bytes < 2 * tensor


@pytest.mark.parametrize("shape", BOUNDARIES)
def test_dequantize_compiles_at_boundary(spec, shape):
    f = jax.jit(lambda q, lo, s: dequantize(q, lo, s, interpret=False))
    compiled = f.lower(spec(shape, jnp.uint8), spec(shape[-1:]),
                       spec(shape[-1:])).compile()
    assert _custom_calls(compiled) >= 1


def test_mobilenet_stage_step_compiles(spec, chain):
    # the middle stage (layers 7-12): a full fused backward + fused_sgd
    # update, its input the first boundary activation
    sl, _ = chain.flat_slice(7, 12)
    ex = StageExecutor(chain, sl, last=False, lr=0.05, interpret=False)
    x = BOUNDARIES[0]
    y = jax.eval_shape(ex._forward, jax.ShapeDtypeStruct((sl.size,),
                                                         jnp.float32),
                       jax.ShapeDtypeStruct(x, jnp.float32), None)
    assert y.shape == BOUNDARIES[1]
    buf = spec((sl.size,))
    compiled = ex._step.lower(buf, buf, buf, spec(x), spec(y.shape),
                              None).compile()
    assert _custom_calls(compiled) >= 1
