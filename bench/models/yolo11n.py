"""YOLO11n: weights, plain reference, int4 control, and the deployment through
the program under test.

The weights, the reference and the control are the benchmark's own and take
nothing from the program.  The reference reads the configuration's copy of
Ultralytics' ``ultralytics/cfg/models/11/yolo11.yaml`` (``backbone`` and
``head``: from, repeats, module, args) at its scale, as Ultralytics'
``parse_model`` does: repeats ``n`` > 1 become ``max(round(n *
depth_multiple), 1)``, widths ``min(c, max_channels) * width_multiple``
rounded up to a multiple of 8.  The modules follow
``ultralytics/nn/modules``:

* Conv: a conv padded k // 2 on every side, its bias (BatchNorm folded) and
  SiLU; DWConv the same with one group per channel.
* Bottleneck(c1, c2, shortcut, k=(3, 3), e): Conv k[0] to ``c2 * e``, Conv
  k[1] to c2, plus the input where shortcut and c1 == c2.
* C3k2(c1, c2, n, c3k, e=0.5; shortcut True): C2f with ``c = c2 * e``:
  cv1 to 2c, split in halves, n blocks each on the last piece, concat, cv2.
  A block is C3k(c, c, 2) where c3k, else Bottleneck(c, c) at e = 0.5.
* C3k(c1, c2, n, e=0.5, k=3): cv3(concat(m(cv1(x)), cv2(x))), cv1 and cv2
  1x1 to ``c_ = c2 * e``, m n bottlenecks at e = 1.0.
* SPPF(c1, c2, k): cv1 to c1 // 2, three chained k x k max pools of stride
  1, concat, cv2.
* C2PSA(c1, c2, n, e=0.5): cv1, split in halves of ``c = c1 * e``, n
  PSABlocks on the second, concat, cv2.  PSABlock(c, attn_ratio 0.5,
  heads c // 64): x + Attention(x), then x + FFN(x), the FFN Conv 1x1 to
  2c then a 1x1 conv without activation.  Attention: qkv a 1x1 conv
  without activation to ``c + 2 * heads * key_dim`` (key_dim = head_dim *
  0.5), viewed as (heads, 2 * key_dim + head_dim) per token and split into
  q, k, v; ``softmax((qᵀk) * key_dim^-0.5)`` over the keys; ``v attnᵀ``
  plus ``pe`` (a depthwise 3x3 conv without activation) on v; ``proj`` (a
  1x1 conv without activation).
* Upsample: nearest, x2.  Concat: along the channels.
* Detect at strides 8, 16 and 32: a box branch (two 3x3 Convs, then a
  1x1 conv to 4 x ``reg_max`` bins) and a class branch (DWConv 3x3, Conv
  1x1, DWConv 3x3, Conv 1x1, then a 1x1 conv to ``nc`` logits).  Decode: a
  softmax over each side's bins and its expectation (DFL), then dist2bbox
  about each anchor's centre, times its stride; class scores after a
  sigmoid.

Departures: frames and maps are NHWC, each channel in PyTorch's channel
order (the attention's heads and widths included); the answer is
anchors-major, (anchors, 4 + nc), where Ultralytics returns the transpose,
as in ``yolov8n``.

``deploy`` is the only function that imports the program: graph, LBLP
placement, int8 calibration from the graph, and the executor's call.
"""

from __future__ import annotations

import math
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from bench.models.yolov8n import (CHUNK, HIGHEST, _chunks, _fake_quant,
                                  _lists, _quant_weight, anchors)

HEAD_DIM = 64       # C2PSA: heads = channels // 64 (Ultralytics' choice)


def _width(cfg: Dict, c: int) -> int:
    return math.ceil(min(c, cfg["max_channels"]) * cfg["width_multiple"] / 8) * 8


def _repeats(cfg: Dict, n: int) -> int:
    return max(round(n * cfg["depth_multiple"]), 1) if n > 1 else n


def forward(params: Optional[Dict], x: jnp.ndarray, cfg: Dict, amax=None,
            qmax=None, record=None, plan: Optional[List] = None) -> jnp.ndarray:
    """NHWC frames -> (frames, anchors, 4 + nc) in float32 at full
    precision.

    With ``qmax`` set, every conv input is fake-quantized per tensor with
    the scale ``amax[name] / qmax`` and every weight per output channel:
    the same network at a lower integer precision (the attention's
    products and softmax stay float32).  ``record``, a dict, receives each
    conv input's largest magnitude.  With ``plan``, a list, no weight is
    read: each conv appends its shapes and answers zeros (for
    ``jax.eval_shape``).
    """

    def conv(path, h, k, cout, stride=1, groups=1, silu=True):
        name = ".".join(map(str, path))
        cin = h.shape[-1]
        pad = k // 2
        if plan is not None:
            ho = (h.shape[1] + 2 * pad - k) // stride + 1
            wo = (h.shape[2] + 2 * pad - k) // stride + 1
            plan.append({"name": name, "path": path, "kind": "conv", "k": k,
                         "cin": cin, "cout": cout, "stride": stride,
                         "groups": groups, "in_hw": tuple(h.shape[1:3]),
                         "out_hw": (ho, wo)})
            return jnp.zeros(h.shape[:1] + (ho, wo, cout), h.dtype)
        p = params
        for step in path:
            p = p[step]
        if record is not None:
            record[name] = jnp.max(jnp.abs(h))
        w = p["w"]
        if qmax is not None:
            h = _fake_quant(h, jnp.maximum(amax[name], 1e-8) / qmax, qmax)
            w = _quant_weight(w, qmax)
        y = jax.lax.conv_general_dilated(
            h, w, (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups, precision=HIGHEST) + p["b"]
        return y * jax.nn.sigmoid(y) if silu else y

    def bottleneck(path, h, c2, k, e, shortcut):
        y = conv(path + ("cv1",), h, k[0], int(c2 * e))
        y = conv(path + ("cv2",), y, k[1], c2)
        return h + y if shortcut and h.shape[-1] == c2 else y

    def c3k(path, h, c2, n, e=0.5, k=3):
        c_ = int(c2 * e)
        y = conv(path + ("cv1",), h, 1, c_)
        for i in range(n):
            y = bottleneck(path + ("m", i), y, c_, (k, k), 1.0, True)
        return conv(path + ("cv3",), jnp.concatenate(
            [y, conv(path + ("cv2",), h, 1, c_)], -1), 1, c2)

    def c3k2(path, h, c2, n, use_c3k=False, e=0.5):
        c = int(c2 * e)
        y = conv(path + ("cv1",), h, 1, 2 * c)
        ys = [y[..., :c], y[..., c:]]
        for i in range(n):
            sub = path + ("m", i)
            ys.append(c3k(sub, ys[-1], c, 2) if use_c3k
                      else bottleneck(sub, ys[-1], c, (3, 3), 0.5, True))
        return conv(path + ("cv2",), jnp.concatenate(ys, -1), 1, c2)

    def sppf(path, h, c2, k):
        ys = [conv(path + ("cv1",), h, 1, h.shape[-1] // 2)]
        for _ in range(3):
            ys.append(jax.lax.reduce_window(
                ys[-1], -jnp.inf, jax.lax.max, (1, k, k, 1), (1, 1, 1, 1),
                ((0, 0), (k // 2, k // 2), (k // 2, k // 2), (0, 0))))
        return conv(path + ("cv2",), jnp.concatenate(ys, -1), 1, c2)

    def attention(path, h):
        b, hh, ww, c = h.shape
        heads = c // HEAD_DIM
        key_dim = HEAD_DIM // 2
        qkv = conv(path + ("qkv",), h, 1, c + 2 * heads * key_dim, silu=False)
        qkv = qkv.reshape(b, hh * ww, heads, 2 * key_dim + HEAD_DIM)
        q, k, v = (qkv[..., :key_dim], qkv[..., key_dim:2 * key_dim],
                   qkv[..., 2 * key_dim:])
        attn = jnp.einsum("bihd,bjhd->bhij", q, k, precision=HIGHEST)
        attn = jax.nn.softmax(attn * key_dim ** -0.5, axis=-1)
        y = jnp.einsum("bjhd,bhij->bihd", v, attn, precision=HIGHEST)
        v = v.reshape(b, hh, ww, c)
        y = y.reshape(b, hh, ww, c) + conv(path + ("pe",), v, 3, c, groups=c,
                                           silu=False)
        return conv(path + ("proj",), y, 1, c, silu=False)

    def c2psa(path, h, c2, n, e=0.5):
        c = int(h.shape[-1] * e)
        y = conv(path + ("cv1",), h, 1, 2 * c)
        a, t = y[..., :c], y[..., c:]
        for i in range(n):
            sub = path + ("m", i)
            t = t + attention(sub + ("attn",), t)
            t = t + conv(sub + ("ffn", 1), conv(sub + ("ffn", 0), t, 1, 2 * c),
                         1, c, silu=False)
        return conv(path + ("cv2",), jnp.concatenate([a, t], -1), 1, c2)

    def up(h):
        b, hh, ww, c = h.shape
        h = jnp.broadcast_to(h[:, :, None, :, None, :], (b, hh, 2, ww, 2, c))
        return h.reshape(b, 2 * hh, 2 * ww, c)

    def detect(feats):
        reg, nc = cfg["reg_max"], cfg["nc"]
        c2 = max(16, feats[0].shape[-1] // 4, 4 * reg)
        c3 = max(feats[0].shape[-1], min(nc, 100))
        rows = []
        for i, f in enumerate(feats):
            box = ("head", "cv2", i)
            y = conv(box + ("1",), conv(box + ("0",), f, 3, c2), 3, c2)
            y = conv(box + ("2",), y, 1, 4 * reg, silu=False)
            cls, z = ("head", "cv3", i), f
            for j in ("0", "1"):
                z = conv(cls + (j, "0"), z, 3, z.shape[-1], groups=z.shape[-1])
                z = conv(cls + (j, "1"), z, 1, c3)
            z = conv(cls + ("2",), z, 1, nc, silu=False)
            b, hh, ww, _ = f.shape
            rows.append(jnp.concatenate([y, z], -1).reshape(b, hh * ww, -1))
        z = jnp.concatenate(rows, 1)
        bins = z[..., :4 * reg].reshape(*z.shape[:2], 4, reg)
        dist = jnp.sum(jax.nn.softmax(bins, -1)
                       * jnp.arange(reg, dtype=jnp.float32), -1)
        centre, stride = anchors(cfg)
        x1y1 = centre - dist[..., :2]
        x2y2 = centre + dist[..., 2:]
        box = jnp.concatenate([(x1y1 + x2y2) / 2, x2y2 - x1y1], -1) * stride
        return jnp.concatenate([box, jax.nn.sigmoid(z[..., 4 * reg:])], -1)

    n_backbone = len(cfg["backbone"])
    outs: List = []
    for i, (src, n, module, args) in enumerate(cfg["backbone"] + cfg["head"]):
        path = (f"b{i}" if i < n_backbone else f"n{i}",)
        n = _repeats(cfg, n)
        h = outs[-1] if i else x
        if module == "Conv":
            h = conv(path, h, args[1], _width(cfg, args[0]), args[2])
        elif module == "C3k2":
            h = c3k2(path, h, _width(cfg, args[0]), n, *args[1:])
        elif module == "SPPF":
            h = sppf(path, h, _width(cfg, args[0]), args[1])
        elif module == "C2PSA":
            h = c2psa(path, h, _width(cfg, args[0]), n)
        elif module == "Upsample":
            h = up(h)
        elif module == "Concat":
            h = jnp.concatenate([outs[j] for j in src], -1)
        elif module == "Detect":
            return detect([outs[j] for j in src])
        else:
            raise ValueError(f"no module {module!r} in the reference")
        outs.append(h)
    raise ValueError("the configuration's head ends without a Detect")


def layers(cfg: Dict) -> List[Dict]:
    """Every conv of one frame, in order, with its pytree path, shapes and
    ``groups``."""
    out: List[Dict] = []
    h, w = cfg["image_hw"]
    jax.eval_shape(lambda x: forward(None, x, cfg, plan=out),
                   jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32))
    return out


def init_params(key: jax.Array, cfg: Dict) -> Dict:
    """He-normal weights (fan-in k² · cin / groups) and small biases
    (folded BatchNorm offsets), in the pytree layout the graph executor
    reads: lists for blocks and the head's scales.  One jitted call."""
    convs = layers(cfg)

    def make(key):
        keys = iter(jax.random.split(key, 2 * len(convs)))
        tree: Dict = {}
        for c in convs:
            cin = c["cin"] // c["groups"]
            w = jax.random.normal(next(keys), (c["k"], c["k"], cin, c["cout"]),
                                  jnp.float32)
            b = jax.random.normal(next(keys), (c["cout"],), jnp.float32)
            node = tree
            for step in c["path"][:-1]:
                node = node.setdefault(step, {})
            node[c["path"][-1]] = {"w": w * math.sqrt(2.0 / (c["k"] ** 2 * cin)),
                                   "b": 0.1 * b}
        return _lists(tree)

    return jax.jit(make)(key)


def reference_fn(cfg: Dict) -> Callable:
    """Jitted float32 reference: ``(params, x) -> (frames, anchors, 84)``."""
    return jax.jit(lambda params, x: _chunks(
        lambda c: forward(params, c, cfg), x))


def control_fn(cfg: Dict, qmax: int) -> Callable:
    """Jitted ``(params, calib, x) -> answers`` of the reference at the lower
    precision, calibrated on ``calib`` as the deployment is."""

    def magnitudes(params, c):
        amax: Dict = {}
        forward(params, c, cfg, record=amax)
        return amax

    def run(params, calib, x):
        per_chunk = jax.lax.map(
            lambda c: magnitudes(params, c),
            calib[:len(calib) - len(calib) % CHUNK].reshape(
                (-1, CHUNK) + calib.shape[1:]))
        amax = {k: jnp.max(v) for k, v in per_chunk.items()}
        return _chunks(lambda c: forward(params, c, cfg, amax=amax, qmax=qmax),
                       x)

    return jax.jit(run)


def deploy(params: Dict, cfg: Dict, calib: jnp.ndarray, phases: Dict) -> Callable:
    """Run the program's deployment flow; returns ``serve(frames) -> answers``.

    ``serve`` calls ``executor.execute`` on the program's YOLO11n graph for
    the configuration's frame size.  ``phases`` receives the seconds of each
    step.
    """
    from repro.core import CostModel, get_scheduler, make_pus
    from repro.models import quant
    from repro.models.cnn import executor, graphs

    dep = cfg["deployment"]
    t = time.perf_counter()
    graph = graphs.build_yolo11n_graph({"name": cfg["name"],
                                        "image_hw": tuple(cfg["image_hw"])})
    fleet = make_pus(dep["imc_units"], dep["dpu_units"])
    get_scheduler(dep["scheduler"], CostModel()).schedule(graph, fleet)
    phases["placement_s"] = time.perf_counter() - t
    t = time.perf_counter()
    scales = quant.calibrate_graph(graph, params, calib)
    phases["calibration_s"] = time.perf_counter() - t
    mode = dep["mode"]

    def serve(frames):
        return executor.execute(graph, params, frames, mode=mode,
                                act_scales=scales)

    return serve
