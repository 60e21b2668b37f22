"""INT8 post-training quantization (the paper deploys INT8 models).

Scheme (matches common IMC deployments and our Pallas ``imc_mvm`` kernel):

* **Weights** — symmetric per-output-channel INT8:
  ``q_w[..., c] = round(w[..., c] / s_w[c])``, ``s_w[c] = max|w[...,c]| / 127``.
* **Activations** — symmetric per-tensor INT8 with calibration:
  ``s_x = max|x| / 127`` over a calibration batch.
* **Compute** — INT8 x INT8 -> INT32 accumulate (exact), then dequantize
  ``y = acc * s_x * s_w + b`` (bias kept float, folded from BN).
* **Optional AIMC noise hook** — additive Gaussian on the accumulator,
  emulating analog crossbar noise (the IMCE's "optional noise modeling").

All functions are pure-jnp and jit-safe; the Pallas kernel in
``repro.kernels.imc_mvm`` implements the same integer semantics on TPU
and is tested against ``quantized_matmul`` bit-exactly.

Calibration has one path for every model: ``calibrate_graph`` takes each
weighted node's input scale from the deployment graph's own float
program, and ``calibrate_resnet`` is it on ``graphs.build_resnet_graph``.

Spans (``repro.obs``, recorded only inside ``obs.recording()``):
``quantized_conv2d`` and ``quantized_matmul`` time their four phases as
``quant.act`` (activation scale if computed, the scale as a float32 array,
divide, round, clip, cast), ``quant.weight`` (``quantize_weight``),
``int8.acc`` (the integer conv or dot with its casts) and ``dequant``
(cast, optional noise, the two scale multiplies, bias); an input passed
already quantised (a ``QTensor``) has no ``quant.act`` here, its
quantisation being timed where it was made.  Counters:
``quant.weight.tensors``, one per weight tensor quantised, and
``quant.grouped_convs``, one per grouped conv.  Called eagerly
they time the device launches; inside a function ``jax.jit`` traces, as
the graph executor's program, they time the tracing and the counters
count per trace, once per compiled program.
``calibrate_graph`` records one ``calibrate`` span (``node`` = the graph's
name, ``kind`` = "<n> nodes", ``batch`` = the calibration frames) and
counts the scales it makes in ``calibrate.scales``.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs


class QTensor(NamedTuple):
    q: jnp.ndarray          # int8 values
    scale: jnp.ndarray      # per-channel (weights) or scalar (activations)


def weight_scale(w: jnp.ndarray, channel_axis: int = -1) -> jnp.ndarray:
    axes = tuple(i for i in range(w.ndim) if i != channel_axis % w.ndim)
    amax = jnp.max(jnp.abs(w), axis=axes)
    return jnp.maximum(amax, 1e-8) / 127.0


def quantize_weight(w: jnp.ndarray, channel_axis: int = -1) -> QTensor:
    s = weight_scale(w, channel_axis)
    shape = [1] * w.ndim
    shape[channel_axis % w.ndim] = -1
    q = jnp.clip(jnp.round(w / s.reshape(shape)), -127, 127).astype(jnp.int8)
    return QTensor(q, s)


def act_scale(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 127.0


def quantize_act(x: jnp.ndarray, scale: Optional[jnp.ndarray] = None) -> QTensor:
    s = act_scale(x) if scale is None else scale
    q = jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8)
    return QTensor(q, s)


def dequantize(t: QTensor, channel_axis: int = -1) -> jnp.ndarray:
    s = t.scale
    if s.ndim > 0 and s.size > 1:
        shape = [1] * t.q.ndim
        shape[channel_axis % t.q.ndim] = -1
        s = s.reshape(shape)
    return t.q.astype(jnp.float32) * s


# ---------------------------------------------------------------------------
# integer compute paths (bit-exact oracles for the Pallas kernels)
# ---------------------------------------------------------------------------

def int8_matmul_acc(qx: jnp.ndarray, qw: jnp.ndarray) -> jnp.ndarray:
    """INT8 x INT8 -> INT32 exact accumulation."""
    return jax.lax.dot_general(
        qx.astype(jnp.int32), qw.astype(jnp.int32),
        (((qx.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def int8_conv_acc(qx: jnp.ndarray, qw: jnp.ndarray, stride: int = 1,
                  padding: str = "SAME",
                  feature_group_count: int = 1) -> jnp.ndarray:
    """INT8 NHWC x HWIO conv -> INT32 exact accumulation; grouped, the
    weights are ``[k, k, cin / feature_group_count, cout]``."""
    return jax.lax.conv_general_dilated(
        qx.astype(jnp.int32), qw.astype(jnp.int32),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=feature_group_count,
        preferred_element_type=jnp.int32,
    )


def _quantize_operands(x, w, x_scale):
    """The ``quant.act`` and ``quant.weight`` phases; ``x_scale`` is a
    float, a 0-d array or None (then computed from ``x``).  An ``x``
    already quantised, a ``QTensor``, is taken as it is: no ``quant.act``,
    and ``x_scale`` is not read."""
    if isinstance(x, QTensor):
        qx = x
    else:
        with obs.span("quant.act"):
            qx = quantize_act(x, None if x_scale is None
                              else jnp.asarray(x_scale, jnp.float32))
    with obs.span("quant.weight"):
        obs.count("quant.weight.tensors")
        qw = quantize_weight(w, channel_axis=-1)
    return qx, qw


def _dequantize_acc(acc, qx, qw, b, noise_std, key):
    """The ``dequant`` phase: int32 accumulator -> float32 output."""
    with obs.span("dequant"):
        acc = acc.astype(jnp.float32)
        if noise_std > 0.0 and key is not None:
            acc = acc + noise_std * jax.random.normal(key, acc.shape)
        y = acc * qx.scale * qw.scale
        if b is not None:
            y = y + b
    return y


def quantized_matmul(x: jnp.ndarray, w: jnp.ndarray,
                     b: Optional[jnp.ndarray] = None,
                     x_scale: Optional[Union[float, jnp.ndarray]] = None,
                     noise_std: float = 0.0,
                     key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Quantize -> integer matmul -> dequantize (+ optional AIMC noise)."""
    qx, qw = _quantize_operands(x, w, x_scale)
    with obs.span("int8.acc"):
        acc = int8_matmul_acc(qx.q, qw.q)
    return _dequantize_acc(acc, qx, qw, b, noise_std, key)


def quantized_conv2d(x: Union[jnp.ndarray, QTensor], w: jnp.ndarray,
                     b: Optional[jnp.ndarray] = None,
                     stride: int = 1, padding: str = "SAME",
                     x_scale: Optional[Union[float, jnp.ndarray]] = None,
                     noise_std: float = 0.0,
                     key: Optional[jax.Array] = None,
                     feature_group_count: int = 1) -> jnp.ndarray:
    """INT8 conv via integer accumulate, NHWC/HWIO.  ``x`` is float, or
    already quantised (a ``QTensor``, as the graph executor passes frames
    it has quantised and laid out itself).  Grouped (``feature_group_count``
    > 1, a depthwise conv where it equals the input channels), ``w`` is
    ``[k, k, cin / feature_group_count, cout]``, its scales still one per
    output channel."""
    qx, qw = _quantize_operands(x, w, x_scale)
    if feature_group_count > 1:
        obs.count("quant.grouped_convs")
    with obs.span("int8.acc"):
        acc = int8_conv_acc(qx.q, qw.q, stride, padding, feature_group_count)
    return _dequantize_acc(acc, qx, qw, b, noise_std, key)


# ---------------------------------------------------------------------------
# whole-model PTQ calibration
# ---------------------------------------------------------------------------

def calibrate_resnet(params: Dict, x: jnp.ndarray, cfg: dict) -> Dict[str, float]:
    """``calibrate_graph`` of the deployment graph of the ResNet ``cfg``."""
    from .cnn import graphs

    return calibrate_graph(graphs.build_resnet_graph(cfg), params, x)


def calibrate_graph(g, params: Dict, x: jnp.ndarray,
                    block: int = 16) -> Dict[str, float]:
    """Per-tensor input scales of every CONV and MVM node of graph ``g``
    with learned weights, from the graph's own float program over the
    calibration frames ``x``, ``block`` frames a call: a large calibration
    set never sits on the device as one batch of activations, and each
    block is waited for before the next is sliced, so that no two blocks
    are on the device at once.  A last, shorter block is run at its own
    size, not padded."""
    from .cnn import executor

    names = executor.weighted_nodes(g)
    with obs.span("calibrate", node=g.name, kind=f"{len(names)} nodes",
                  batch=len(x)):
        amax = None
        for i in range(0, len(x), block):
            m = executor.input_magnitudes(g, params, x[i:i + block])
            m.block_until_ready()
            amax = m if amax is None else jnp.maximum(amax, m)
        scales = np.asarray(jnp.maximum(amax, 1e-8) / 127.0)
    obs.count("calibrate.scales", len(names))
    return {n: float(s) for n, s in zip(names, scales)}
