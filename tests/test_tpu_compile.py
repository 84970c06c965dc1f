"""The four Pallas kernels compile for a TPU v5e at their deployment shapes.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology.  Everything built from the topology is built inside a fixture or
a test, never while this module is imported, so every test worker collects
the same tests and only the worker running this file loads the TPU library.
A compile that passes is not a chip run; nothing here runs a kernel.
"""

import os

import jax
import pytest

from repro.core.profiles import TPU_V5E
from repro.core.registry import resolve

GEMM = {"M": 2048, "N": 2048, "K": 2048, "dtype": "float32"}
GEMM_GRANITE = {"M": 2048, "N": 8192, "K": 2048, "dtype": "float32"}
FLASH_D128 = {"Sq": 4096, "Sk": 4096, "D": 128, "causal": True}
FLASH_D64 = {"Sq": 4096, "Sk": 4096, "D": 64, "causal": True}
CONV3 = {"H": 4096, "W": 4096, "Fh": 3, "Fw": 3}
CONV7 = {"H": 4096, "W": 4096, "Fh": 7, "Fw": 7}
#: DeepSeek-V3's routed experts as one chip of 32 holds them
MOE_DSV3 = {"N": 32768, "d": 7168, "m": 2048, "E": 256, "E_held": 8, "k": 8,
            "dtype": "float32"}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe with
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(kernel, shape, config, sharding):
    k = resolve(kernel)
    specs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
             for s in k.arg_specs(shape)]
    fn = jax.jit(k.builder(shape, config, interpret=False))
    return fn.lower(*specs).compile()


#: each kernel's Pallas names, which the compiled program and the device
#: trace carry whatever the configuration
KERNEL_NAMES = {"gemm": ("gemm",), "flash_attention": ("flash_attention",),
                "conv2d": ("conv2d",),
                "moe_experts": ("moe_experts_gate_up", "moe_experts_down",
                                "moe_experts_combine")}


def _assert_named_kernel(kernel, compiled):
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls, "no Pallas kernel in the compiled program"
    for name in KERNEL_NAMES[kernel]:
        assert any(f"%{name}." in line for line in calls), calls


def _heuristic(kernel, shape):
    return resolve(kernel).heuristic(dict(shape))


def _conv(unroll):
    return {"BLOCK_H": 16, "BLOCK_W": 256, "SUB_H": 2, "UNROLL": unroll,
            "HALO_MODE": "materialize"}


@pytest.mark.parametrize("kernel,shape,config", [
    ("gemm", GEMM, None),
    ("gemm", GEMM_GRANITE, None),
    ("flash_attention", FLASH_D128, None),
    ("flash_attention", FLASH_D64, None),
    ("conv2d", CONV3, _conv(True)),
    ("conv2d", CONV3, _conv(False)),
    ("conv2d", CONV7, _conv(True)),
    ("conv2d", CONV7, _conv(False)),
    ("moe_experts", MOE_DSV3, None),
], ids=["gemm-2048", "gemm-granite-mlp", "flash-d128", "flash-d64",
        "conv3x3-unrolled", "conv3x3-rolled", "conv7x7-unrolled",
        "conv7x7-rolled", "moe-deepseek-v3"])
def test_kernel_compiles_for_v5e(one_chip, kernel, shape, config):
    config = config or _heuristic(kernel, shape)
    compiled = _compile(kernel, shape, config, one_chip)
    _assert_named_kernel(kernel, compiled)


@pytest.mark.parametrize("kernel,shape,config", [
    ("gemm", GEMM, {"BLOCK_M": 1024, "BLOCK_N": 1024, "BLOCK_K": 1024}),
    ("flash_attention", FLASH_D128, {"BLOCK_Q": 1024, "BLOCK_K": 2048,
                                     "PIPELINE_DEPTH": 2}),
    ("moe_experts", MOE_DSV3, {"BLOCK_M": 512, "BLOCK_N": 1024,
                               "BLOCK_K": 1024}),
], ids=["gemm-1024-blocks", "flash-1024x2048", "moe-largest-blocks"])
def test_vmem_budget_agrees_with_analyzer(one_chip, kernel, shape, config):
    """The largest blocks of the spaces fit the profile's VMEM by the
    declared footprint, and the compiler, given the same budget, agrees
    (its default scoped limit of 16 MiB refused the first two)."""
    k = resolve(kernel)
    config = dict(_heuristic(kernel, shape), **config)
    assert TPU_V5E.fits_vmem(k.vmem_footprint(shape, config))
    compiled = _compile(kernel, shape, config, one_chip)
    _assert_named_kernel(kernel, compiled)


@pytest.mark.parametrize("override,match", [
    ({"ACC_DTYPE": "bfloat16"}, "32-bit"),
    ({"BLOCK_M": 32, "BLOCK_N": 32, "BLOCK_K": 32}, "divisible by 8 and 128"),
], ids=["bf16-accumulator", "blocks-of-32"])
def test_compiler_refuses_extended_gemm_configs(one_chip, override, match):
    """Extended-space GEMM configs the chip's compiler refuses while the
    analyzer calls them feasible; kept visible until the spaces drop them."""
    config = dict(_heuristic("gemm", GEMM), **override)
    k = resolve("gemm")
    assert TPU_V5E.fits_vmem(k.vmem_footprint(GEMM, config))
    with pytest.raises(Exception, match=match):
        _compile("gemm", GEMM, config, one_chip)
