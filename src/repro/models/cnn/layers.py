"""JAX building blocks for the paper's CNN workloads.

Pure-functional layers: every layer is ``init(key, ...) -> params`` plus
``apply(params, x, ...) -> y``.  Layouts are NHWC (TPU-native).  BatchNorm
is *folded* into the preceding conv at deployment time, matching the IMCE
software stack (the paper deploys quantized inference graphs where BN is
absorbed into weights/bias).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

try:
    import jax
    import jax.numpy as jnp
except ModuleNotFoundError:  # cost helpers stay importable without jax
    jax = jnp = None  # type: ignore[assignment]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def _he_normal(key, shape, fan_in):
    return jax.random.normal(key, shape, dtype=jnp.float32) * math.sqrt(2.0 / fan_in)


def conv_init(key, k: int, cin: int, cout: int) -> Dict[str, jnp.ndarray]:
    """HWIO conv weights + bias (bias holds folded BN offsets)."""
    wkey, _ = jax.random.split(key)
    fan_in = k * k * cin
    return {
        "w": _he_normal(wkey, (k, k, cin, cout), fan_in),
        "b": jnp.zeros((cout,), jnp.float32),
    }


def dense_init(key, cin: int, cout: int) -> Dict[str, jnp.ndarray]:
    wkey, _ = jax.random.split(key)
    return {
        "w": _he_normal(wkey, (cin, cout), cin),
        "b": jnp.zeros((cout,), jnp.float32),
    }


# ---------------------------------------------------------------------------
# functional ops
# ---------------------------------------------------------------------------

def conv2d(params, x: jnp.ndarray, stride: int = 1, padding="SAME",
           act: Optional[str] = None, groups: int = 1) -> jnp.ndarray:
    """NHWC conv with HWIO weights ``[k, k, cin / groups, cout]``."""
    y = jax.lax.conv_general_dilated(
        x, params["w"],
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )
    y = y + params["b"]
    return activate(y, act)


def dense(params, x: jnp.ndarray, act: Optional[str] = None) -> jnp.ndarray:
    y = x @ params["w"] + params["b"]
    return activate(y, act)


def activate(x: jnp.ndarray, act: Optional[str]) -> jnp.ndarray:
    if act is None:
        return x
    if act == "relu":
        return jax.nn.relu(x)
    if act == "silu":
        return jax.nn.silu(x)
    if act == "sigmoid":
        return jax.nn.sigmoid(x)
    raise ValueError(f"unknown activation {act!r}")


def max_pool(x: jnp.ndarray, k: int, stride: Optional[int] = None,
             padding: str = "SAME") -> jnp.ndarray:
    stride = stride or k
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max,
        window_dimensions=(1, k, k, 1),
        window_strides=(1, stride, stride, 1),
        padding=padding,
    )


def avg_pool(x: jnp.ndarray, k: int, stride: Optional[int] = None,
             padding: str = "VALID") -> jnp.ndarray:
    stride = stride or k
    summed = jax.lax.reduce_window(
        x, 0.0, jax.lax.add,
        window_dimensions=(1, k, k, 1),
        window_strides=(1, stride, stride, 1),
        padding=padding,
    )
    return summed / float(k * k)


def global_avg_pool(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean(x, axis=(1, 2))


def upsample_nearest(x: jnp.ndarray, factor: int = 2) -> jnp.ndarray:
    b, h, w, c = x.shape
    x = jnp.repeat(x, factor, axis=1)
    return jnp.repeat(x, factor, axis=2)


def softmax(x: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    return jax.nn.softmax(x, axis=axis)


# ---------------------------------------------------------------------------
# shape/cost bookkeeping shared with the deployment-graph builders
# ---------------------------------------------------------------------------

def conv_out_hw(h: int, w: int, k: int, stride: int, padding) -> Tuple[int, int]:
    """Output map of a conv; ``padding`` is "SAME", "VALID" or explicit
    ``((top, bottom), (left, right))``."""
    if padding == "SAME":
        return (math.ceil(h / stride), math.ceil(w / stride))
    if padding == "VALID":
        padding = ((0, 0), (0, 0))
    (t, b), (le, r) = padding
    return ((h + t + b - k) // stride + 1, (w + le + r - k) // stride + 1)


def conv_cost(h: int, w: int, k: int, cin: int, cout: int, stride: int,
              padding="SAME", groups: int = 1) -> dict:
    """FLOPs/bytes/IMC-metadata for one conv node (per single frame).  Each
    of ``groups`` groups maps ``cin / groups`` inputs to ``cout / groups``
    outputs, so a weight column reads ``k * k * cin / groups`` values."""
    ho, wo = conv_out_hw(h, w, k, stride, padding)
    cin_kk = k * k * cin // groups
    macs = ho * wo * cin_kk * cout
    params = cin_kk * cout + cout
    return {
        "flops": 2.0 * macs,
        "weight_bytes": float(params),            # INT8 deployment: 1 B/param
        "out_bytes": float(ho * wo * cout),       # INT8 activations
        "out_elems": float(ho * wo * cout),
        "meta": {"cin_kk": cin_kk, "cout": cout, "n_vectors": ho * wo,
                 "out_hw": (ho, wo)},
    }


def dense_cost(cin: int, cout: int) -> dict:
    return {
        "flops": 2.0 * cin * cout,
        "weight_bytes": float(cin * cout + cout),
        "out_bytes": float(cout),
        "out_elems": float(cout),
        "meta": {"cin_kk": cin, "cout": cout, "n_vectors": 1},
    }


def elem_cost(n_elems: float) -> dict:
    return {
        "flops": float(n_elems),
        "weight_bytes": 0.0,
        "out_bytes": float(n_elems),
        "out_elems": float(n_elems),
        "meta": {},
    }


def count_params(tree) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))
