"""Compile-only checks for a described TPU v5e, plus the device guard.

The main path's kernels and the jitted int8 executor are compiled at real
widths for one chip of a described ``v5e:2x2`` topology.  Nothing runs:
these catch what Mosaic or XLA's TPU compiler refuses (int32 MXU operands,
strided vector slices, VMEM overruns) at no chip time.  The topology is
described inside a module fixture, never at import time, so that every
pytest-xdist worker collects the same tests and only the worker running
this file loads the TPU library.

The device guard tests run wherever JAX sees no TPU (``JAX_PLATFORMS=cpu``).
"""

import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.device import NoTPUError, require_tpu
from repro.kernels.conv2d import imc_conv2d
from repro.kernels.flash_attention import flash_attention
from repro.kernels.imc_mvm import imc_mvm
from repro.models.cnn import executor, graphs, resnet

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent cache off around the
    compiles (an entry for a described device cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("M,K,N", [(8, 256, 10), (1024, 1024, 1024)])
def test_imc_mvm_compiles(one_chip, M, K, N):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    fn = jax.jit(lambda x, w, sx, sw, b: imc_mvm(x, w, sx, sw, b))
    compiled = fn.lower(s((M, K), jnp.int8), s((K, N), jnp.int8),
                        s((), jnp.float32), s((N,), jnp.float32),
                        s((N,), jnp.float32)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("H,Cin,Cout,stride", [
    (32, 3, 32, 1),      # ResNet-18 stem
    (32, 32, 64, 2),     # first stride-2 conv
    (4, 256, 256, 1),    # widest conv
])
def test_imc_conv2d_compiles(one_chip, H, Cin, Cout, stride):
    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    fn = jax.jit(lambda x, w, sx, sw, b: imc_conv2d(x, w, sx, sw, b,
                                                    stride=stride))
    compiled = fn.lower(s((8, H, H, Cin), jnp.int8),
                        s((3, 3, Cin, Cout), jnp.int8), s((), jnp.float32),
                        s((Cout,), jnp.float32),
                        s((Cout,), jnp.float32)).compile()
    _assert_kernel(compiled)


def test_flash_attention_compiles(one_chip):
    q = _spec(one_chip, (1, 8, 1024, 128), jnp.float32)
    compiled = jax.jit(flash_attention).lower(q, q, q).compile()
    _assert_kernel(compiled)


def test_int8_executor_compiles_resnet18(one_chip):
    cfg = resnet.RESNET18_CIFAR
    g = graphs.build_resnet_graph(cfg)
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: resnet.init(jax.random.PRNGKey(0), cfg)))
    x = _spec(one_chip, (8, 32, 32, 3), jnp.float32)
    fn = jax.jit(lambda p, x: executor.execute(g, p, x, mode="int8"))
    compiled = fn.lower(params, x).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("batch", [256, 1])
def test_served_frames_reach_the_program_in_host_order(one_chip, batch):
    # the int8 ResNet-18 program as served: host frames put as their
    # [rows, 128] view, whose layout must be the host buffer's linear order
    # (row-major, 8 x 128 tiles), so that the put needs no host transpose
    cfg = resnet.RESNET18_CIFAR
    g = graphs.build_resnet_graph(cfg)
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: resnet.init(jax.random.PRNGKey(0), cfg)))
    names = tuple(sorted(executor.weighted_nodes(g)))
    shape = (batch, 32, 32, 3)
    rows = batch * 32 * 32 * 3 // executor.LANES
    program = executor._program(g, "int8", names)
    scales = _spec(one_chip, (len(names),), jnp.float32)
    flat = program.lower(
        params, _spec(one_chip, (rows, executor.LANES), jnp.float32),
        scales, shape).compile()
    layout = flat.input_formats[0][1].layout
    assert layout.major_to_minor == (0, 1)
    assert layout.tiling == ((8, 128),)
    # the device re-lays the frames at little cost: within 5% of the bytes
    # the program accesses with NHWC frames (a plain reshape: +15% at 256)
    nhwc = program.lower(params, _spec(one_chip, shape, jnp.float32),
                         scales, None).compile()
    assert _bytes_accessed(flat) <= 1.05 * _bytes_accessed(nhwc)


def _bytes_accessed(compiled):
    cost = compiled.cost_analysis()
    return (cost[0] if isinstance(cost, list) else cost)["bytes accessed"]


def _skip_on_tpu():
    if jax.devices()[0].platform == "tpu":
        pytest.skip("JAX sees a TPU: the guard has nothing to refuse")


def test_require_tpu_refuses_cpu():
    _skip_on_tpu()
    with pytest.raises(NoTPUError, match="no TPU"):
        require_tpu()


def test_chip_smoke_stops_at_device_guard(capsys):
    _skip_on_tpu()
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    with pytest.raises(NoTPUError):
        smoke.main()
    assert '"ok"' not in capsys.readouterr().out
