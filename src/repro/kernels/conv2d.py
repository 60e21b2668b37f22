"""Pallas TPU kernel: INT8 direct convolution (weight-stationary).

TPU-native adaptation of the paper's IMC conv nodes: the (K, K, Cin, bn)
filter block stays resident in VMEM (the crossbar analogue) while the
kernel sweeps the batch grid; the conv is computed as an unrolled
K x K tap accumulation of MXU matmuls over the full spatial map:

    out[i, j, co] = sum_{di, dj}  x[i*s+di, j*s+dj, :] @ w[di, dj, :, co]

Accumulation is INT32 (exact), with fused per-channel requantization in
the epilogue — bit-compatible with ``repro.models.quant.quantized_conv2d``.

Strides above 1 are split into stride phases by the wrapper: phase
``(a, b)`` holds ``xpad[a::s, b::s]``, so tap ``(di, dj)`` is the unit-stride
window of phase ``(di % s, dj % s)`` at offset ``(di // s, dj // s)``.  The
kernel then takes only unit-stride slices, which Mosaic requires.  The int8
operands go to the MXU as int8 (``preferred_element_type=int32``).

Scope: SAME padding, any stride, spatial maps that fit VMEM as one block
(the paper's CIFAR-scale workloads; 34x34x512 int8 = 0.6 MB).  Larger maps
(YOLO 640x640 early layers) need spatial tiling, which this kernel does not
have; nothing routes them here.

Grid: (B, Cout/bn); x block (1, s*s, Hq, Wq, Cin); w block (K, K, Cin, bn).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _conv_kernel(x_ref, w_ref, sx_ref, sw_ref, b_ref, o_ref, *,
                 ksize: int, stride: int, h_out: int, w_out: int):
    cin = x_ref.shape[-1]
    acc = jnp.zeros((h_out * w_out, o_ref.shape[-1]), jnp.int32)
    for di in range(ksize):
        for dj in range(ksize):
            phase = (di % stride) * stride + dj % stride
            oi, oj = di // stride, dj // stride
            tap = x_ref[0, phase, oi:oi + h_out, oj:oj + w_out, :]
            acc += jax.lax.dot_general(
                tap.reshape(h_out * w_out, cin), w_ref[di, dj],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * sx_ref[0, 0] * sw_ref[0, :] + b_ref[0, :]
    o_ref[...] = y.reshape(1, h_out, w_out, -1)


def _stride_phases(xp: jnp.ndarray, stride: int) -> jnp.ndarray:
    """(B, Hp, Wp, C) -> (B, s*s, ceil(Hp/s), ceil(Wp/s), C); phase
    ``a*s + b`` holds ``xp[:, a::s, b::s]`` (zero-padded at the far edge)."""
    B, Hp, Wp, C = xp.shape
    hq, wq = -(-Hp // stride), -(-Wp // stride)
    xp = jnp.pad(xp, ((0, 0), (0, hq * stride - Hp), (0, wq * stride - Wp),
                      (0, 0)))
    xp = xp.reshape(B, hq, stride, wq, stride, C)
    return xp.transpose(0, 2, 4, 1, 3, 5).reshape(
        B, stride * stride, hq, wq, C)


@functools.partial(jax.jit,
                   static_argnames=("stride", "bn", "interpret"))
def imc_conv2d(qx: jnp.ndarray, qw: jnp.ndarray, sx: jnp.ndarray,
               sw: jnp.ndarray, bias: Optional[jnp.ndarray] = None,
               *, stride: int = 1, bn: int = 128,
               interpret: bool = False) -> jnp.ndarray:
    """INT8 conv: x (B, H, W, Cin) int8, w (K, K, Cin, Cout) int8,
    SAME padding -> (B, H/s, W/s, Cout) f32."""
    B, H, W, Cin = qx.shape
    K, K2, Cin2, Cout = qw.shape
    assert K == K2 and Cin == Cin2
    h_out = -(-H // stride)
    w_out = -(-W // stride)
    # SAME padding (matches XLA for odd kernels)
    pad_h = max((h_out - 1) * stride + K - H, 0)
    pad_w = max((w_out - 1) * stride + K - W, 0)
    xp = jnp.pad(qx, ((0, 0), (pad_h // 2, pad_h - pad_h // 2),
                      (pad_w // 2, pad_w - pad_w // 2), (0, 0)))
    bn_ = min(bn, Cout)
    rem = Cout % bn_
    wp = qw if rem == 0 else jnp.pad(qw, ((0, 0), (0, 0), (0, 0),
                                          (0, bn_ - rem)))
    swp = sw if rem == 0 else jnp.pad(sw, (0, bn_ - rem))
    bias = bias if bias is not None else jnp.zeros((Cout,), jnp.float32)
    bp = bias if rem == 0 else jnp.pad(bias, (0, bn_ - rem))
    Np = wp.shape[-1]
    xs = _stride_phases(xp, stride)
    P, Hq, Wq = xs.shape[1:4]

    out = pl.pallas_call(
        functools.partial(_conv_kernel, ksize=K, stride=stride,
                          h_out=h_out, w_out=w_out),
        grid=(B, Np // bn_),
        in_specs=[
            pl.BlockSpec((1, P, Hq, Wq, Cin), lambda b, n: (b, 0, 0, 0, 0)),
            pl.BlockSpec((K, K, Cin, bn_), lambda b, n: (0, 0, 0, n)),
            pl.BlockSpec((1, 1), lambda b, n: (0, 0)),
            pl.BlockSpec((1, bn_), lambda b, n: (0, n)),
            pl.BlockSpec((1, bn_), lambda b, n: (0, n)),
        ],
        out_specs=pl.BlockSpec((1, h_out, w_out, bn_),
                               lambda b, n: (b, 0, 0, n)),
        out_shape=jax.ShapeDtypeStruct((B, h_out, w_out, Np), jnp.float32),
        interpret=interpret,
    )(xs, wp, jnp.asarray(sx, jnp.float32).reshape(1, 1),
      swp.reshape(1, -1).astype(jnp.float32), bp.reshape(1, -1))
    return out[..., :Cout]
