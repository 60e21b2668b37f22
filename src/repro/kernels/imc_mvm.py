"""Pallas TPU kernel: INT8 weight-stationary matrix-vector/matrix multiply
— the TPU-native analogue of the paper's IMC crossbar.

Hardware adaptation (DESIGN.md §2): the IMC crossbar holds INT8 weights
stationary and streams activations through; on TPU the analogue is a
weight-stationary MXU matmul with INT8 operands and INT32 accumulation,
with the *weight block resident in VMEM across the whole M-grid sweep*
(the pallas grid iterates M-majored so the (K, N) weight tile is reused,
exactly like crossbar reuse).  Per-output-channel requantization
(acc * s_x * s_w[n] + bias) is fused into the kernel epilogue, matching
``repro.models.quant`` semantics bit-for-bit (integer part) so the
quantized CNN/MVM layers can swap implementations freely.

Grid: (M/bm, N/bn, K/bk) with K innermost (accumulate in a VMEM f32/i32
scratch); blocks default to MXU-aligned 128x128x128.

The operands reach the MXU as int8 (``preferred_element_type=int32``);
Mosaic refuses an int32 x int32 dot.  The tests run the kernel with
``interpret=True`` against the pure-jnp oracle in ``ref.py``;
``tests/test_tpu_compile.py`` compiles it for a described v5e, and
``chip_smoke.py`` runs it natively on one chip against the same oracle.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _imc_mvm_kernel(x_ref, w_ref, sx_ref, sw_ref, b_ref, o_ref, acc_ref,
                    *, n_k: int):
    """One (bm, bn) output tile; K-loop accumulated in i32 scratch.

    x_ref:  (bm, bk) int8    activations tile
    w_ref:  (bk, bn) int8    stationary weight tile
    sx_ref: (1, 1)   f32     per-tensor activation scale
    sw_ref: (1, bn)  f32     per-channel weight scales
    b_ref:  (1, bn)  f32     bias (folded BN)
    o_ref:  (bm, bn) f32     output tile
    acc_ref:(bm, bn) i32     VMEM accumulator scratch
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        o_ref[...] = acc * sx_ref[0, 0] * sw_ref[0, :] + b_ref[0, :]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def imc_mvm(qx: jnp.ndarray, qw: jnp.ndarray, sx: jnp.ndarray,
            sw: jnp.ndarray, bias: Optional[jnp.ndarray] = None,
            *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
            bk: int = DEFAULT_BK, interpret: bool = False) -> jnp.ndarray:
    """Quantized matmul: (M, K) int8 x (K, N) int8 -> (M, N) f32.

    ``sx`` scalar f32; ``sw`` (N,) f32; ``bias`` (N,) f32 or None.
    M/K/N are padded to block multiples internally.
    """
    M, K = qx.shape
    K2, N = qw.shape
    assert K == K2, (qx.shape, qw.shape)
    bm_, bn_, bk_ = min(bm, M), min(bn, N), min(bk, K)

    def pad_to(a, mult, axis):
        rem = a.shape[axis] % mult
        if rem == 0:
            return a
        pad = [(0, 0)] * a.ndim
        pad[axis] = (0, mult - rem)
        return jnp.pad(a, pad)

    xp = pad_to(pad_to(qx, bm_, 0), bk_, 1)
    wp = pad_to(pad_to(qw, bk_, 0), bn_, 1)
    swp = pad_to(sw.reshape(1, -1), bn_, 1)
    bp = pad_to((bias if bias is not None else
                 jnp.zeros((N,), jnp.float32)).reshape(1, -1), bn_, 1)
    Mp, Kp = xp.shape
    _, Np = wp.shape
    n_k = Kp // bk_

    out = pl.pallas_call(
        functools.partial(_imc_mvm_kernel, n_k=n_k),
        grid=(Mp // bm_, Np // bn_, n_k),
        in_specs=[
            pl.BlockSpec((bm_, bk_), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk_, bn_), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, 1), lambda m, n, k: (0, 0)),
            pl.BlockSpec((1, bn_), lambda m, n, k: (0, n)),
            pl.BlockSpec((1, bn_), lambda m, n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((bm_, bn_), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.int32)],
        interpret=interpret,
    )(xp, wp, jnp.asarray(sx, jnp.float32).reshape(1, 1), swp, bp)
    return out[:M, :N]
