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
import math
import os
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.device import NoTPUError, require_tpu
from repro.kernels.conv2d import imc_conv2d
from repro.kernels.flash_attention import flash_attention
from repro.kernels.imc_mvm import imc_mvm
from repro.models.cnn import executor, graphs, resnet, yolo

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


def _int8_program(one_chip, g, params):
    """The int8 program of ``g`` with every weighted node calibrated, and
    a lowering of it to a described chip: ``build(shape, planar)`` for
    host frames of NHWC ``shape`` put as their [rows, 128] view and laid
    out as ``planar`` says, ``build(shape)`` for NHWC frames."""
    names = tuple(sorted(executor.weighted_nodes(g)))
    program = executor._program(g, "int8", names)
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), params)
    scales = _spec(one_chip, (len(names),), jnp.float32)

    def build(shape, planar=None):
        if planar is None:
            x, shape = _spec(one_chip, shape, jnp.float32), None
        else:
            rows = math.prod(shape) // executor.LANES
            x = _spec(one_chip, (rows, executor.LANES), jnp.float32)
        return program.lower(params, x, scales, shape, planar).compile()

    return names, build


def _resnet18_int8(one_chip):
    cfg = resnet.RESNET18_CIFAR
    g = graphs.build_resnet_graph(cfg)
    return g, _int8_program(one_chip, g, jax.eval_shape(
        lambda: resnet.init(jax.random.PRNGKey(0), cfg)))


@pytest.mark.parametrize("batch", [256, 1])
def test_served_frames_reach_the_program_in_host_order(one_chip, batch):
    # the int8 ResNet-18 program as served: host frames put as their
    # [rows, 128] view, whose layout must be the host buffer's linear order
    # (row-major, 8 x 128 tiles), so that the put needs no host transpose
    g, (names, build) = _resnet18_int8(one_chip)
    shape = (batch, 32, 32, 3)
    flat = build(shape, executor._planar(g, "int8", names, shape))
    layout = flat.input_formats[0][1].layout
    assert layout.major_to_minor == (0, 1)
    assert layout.tiling == ((8, 128),)
    # the device re-lays the frames at little cost: within 5% of the bytes
    # the program accesses with NHWC frames (a plain reshape: +15% at 256)
    assert _bytes_accessed(flat) <= 1.05 * _bytes_accessed(build(shape))


def test_planar_frames_access_no_more_than_the_batch_minor_route(one_chip):
    # below 128 frames the view is laid out channel planar: at 8 ResNet-18
    # frames that reads and writes no more than the batch-minor route
    g, (names, build) = _resnet18_int8(one_chip)
    shape = (8, 32, 32, 3)
    assert executor._planar(g, "int8", names, shape)
    assert _bytes_accessed(build(shape, True)) \
        <= _bytes_accessed(build(shape, False))


def test_a_single_frame_is_not_relaid_through_padded_lanes(one_chip):
    # YOLOv8n's one int8 frame, 128 x 128: through the batch-minor route
    # XLA reshapes the quantised s8[128,1,128,3] into 3 of 128 lanes and
    # copies it out again (1.028x the bytes of NHWC frames); laid out
    # channel planar no reshape or copy of the frame's shape is left
    side = 128
    cfg = dict(yolo.YOLOV8N, image_hw=(side, side))
    g = graphs.build_yolov8n_graph(cfg)
    names, build = _int8_program(one_chip, g, jax.eval_shape(
        lambda: yolo.init(jax.random.PRNGKey(0), cfg)))
    shape = (1, side, side, 3)
    assert executor._planar(g, "int8", names, shape)
    served = build(shape, True)
    assert _bytes_accessed(served) <= 1.02 * _bytes_accessed(build(shape))
    relaid = [m.group(0) for m in re.finditer(
        r"= s8\[([0-9,]*),3\]\S* (reshape|copy)\(", served.as_text())
        if 3 * math.prod(map(int, m.group(1).split(","))) == math.prod(shape)]
    assert relaid == []


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
