"""Executable YOLO11n in pure JAX (Ultralytics YOLO11 at scale n).

Source: ``ultralytics/cfg/models/11/yolo11.yaml`` at scale n (depth 0.50,
width 0.25, max channels 1024) and its modules (``nn/modules/block.py``:
C3k2, C3k, Bottleneck, C2PSA, PSABlock, Attention, SPPF; ``conv.py``: Conv,
DWConv; ``head.py``: Detect).  Pytree keys are the yaml's layer indices
(``b0``..``b10`` backbone, ``n13``..``n22`` neck) and the modules' own
attribute names.  At scale n every C3k2 and C2PSA repeats once.

* C3k2 is C2f with ``c = cout * e`` and its shortcut on: cv1 to 2c, split,
  one block on the second half, concat, cv2.  Its block is a Bottleneck of
  hidden width c / 2 (layers 2, 4, 13, 16, 19; e = 0.25 at 2 and 4), or
  at layers 6, 8 and 22 a C3k: two 1x1 branches of c / 2, two bottlenecks
  at e = 1.0 on the first, concat, cv3.
* C2PSA (layer 10, 256 channels at 20 x 20): cv1, split in halves of 128,
  a PSABlock on the second, concat, cv2.  PSABlock adds multi-head
  self-attention (2 heads, key width 32, value width 64, scale 32^-1/2,
  a depthwise 3x3 ``pe`` conv on V added to the attended values, then
  ``proj``) and a 1x1 FFN of width x2, each with its residual.
* Detect as YOLOv8's, but for the class branch: DWConv 3x3 -> 1x1 Conv ->
  DWConv 3x3 -> 1x1 Conv -> 1x1 conv to the logits.  Decode:
  ``yolo.decode_raw``.

87 convs (7 depthwise), 3,239,993,600 conv MACs and 30,720,000 MACs in the
attention's two activation products at 640x640, 2,616,232 learned
parameters (BatchNorm folded into each conv's bias), as Ultralytics
publishes (2.6 M parameters, 6.5 GFLOPs).  ``graphs.build_yolo11n_graph``
mirrors this model node for node.
"""

from __future__ import annotations

from typing import Dict

try:
    import jax
    import jax.numpy as jnp
except ModuleNotFoundError:  # arch specs stay importable without jax
    jax = jnp = None  # type: ignore[assignment]

from . import layers as L
from .yolo import CH, NC, REG_MAX, _conv, _sppf, decode_raw

YOLO11N = {
    "name": "yolo11n",
    "image_hw": (640, 640),
    "nc": NC,
    "reg_max": REG_MAX,
}

HEAD_DIM = 64           # C2PSA: heads = channels // HEAD_DIM
KEY_DIM = HEAD_DIM // 2  # Attention's attn_ratio 0.5
#: C3k2 width share e and whether its block is a C3k, by layer
C3K2 = {"b2": (0.25, False), "b4": (0.25, False), "b6": (0.5, True),
        "b8": (0.5, True), "n13": (0.5, False), "n16": (0.5, False),
        "n19": (0.5, False), "n22": (0.5, True)}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _conv_init(key, k, cin, cout, groups=1):
    """HWIO weights [k, k, cin / groups, cout] (He fan-in k²·cin/groups)."""
    return L.conv_init(key, k, cin // groups, cout)


def _bottleneck_init(key, c, hidden):
    k1, k2 = jax.random.split(key)
    return {"cv1": _conv_init(k1, 3, c, hidden),
            "cv2": _conv_init(k2, 3, hidden, c)}


def _c3k_init(key, c):
    keys = jax.random.split(key, 5)
    h = c // 2
    return {"cv1": _conv_init(keys[0], 1, c, h),
            "cv2": _conv_init(keys[1], 1, c, h),
            "cv3": _conv_init(keys[2], 1, 2 * h, c),
            "m": [_bottleneck_init(keys[3 + i], h, h) for i in range(2)]}


def _c3k2_init(key, name, cin, cout):
    e, c3k = C3K2[name]
    c = int(cout * e)
    keys = jax.random.split(key, 3)
    block = _c3k_init(keys[1], c) if c3k else _bottleneck_init(keys[1], c,
                                                               c // 2)
    return {"cv1": _conv_init(keys[0], 1, cin, 2 * c), "m": [block],
            "cv2": _conv_init(keys[2], 1, 3 * c, cout)}


def _c2psa_init(key, c):
    keys = jax.random.split(key, 7)
    half = c // 2
    heads = half // HEAD_DIM
    attn = {"qkv": _conv_init(keys[0], 1, half, half + 2 * heads * KEY_DIM),
            "proj": _conv_init(keys[1], 1, half, half),
            "pe": _conv_init(keys[2], 3, half, half, groups=half)}
    ffn = [_conv_init(keys[3], 1, half, 2 * half),
           _conv_init(keys[4], 1, 2 * half, half)]
    return {"cv1": _conv_init(keys[5], 1, c, c),
            "m": [{"attn": attn, "ffn": ffn}],
            "cv2": _conv_init(keys[6], 1, c, c)}


def _detect_init(key, chs):
    c2 = max(16, chs[0] // 4, 4 * REG_MAX)      # 64
    c3 = max(chs[0], min(NC, 100))              # 80
    keys = iter(jax.random.split(key, 8 * len(chs)))
    head = {"cv2": [], "cv3": []}
    for c in chs:
        head["cv2"].append({
            "0": _conv_init(next(keys), 3, c, c2),
            "1": _conv_init(next(keys), 3, c2, c2),
            "2": _conv_init(next(keys), 1, c2, 4 * REG_MAX)})
        head["cv3"].append({
            "0": {"0": _conv_init(next(keys), 3, c, c, groups=c),
                  "1": _conv_init(next(keys), 1, c, c3)},
            "1": {"0": _conv_init(next(keys), 3, c3, c3, groups=c3),
                  "1": _conv_init(next(keys), 1, c3, c3)},
            "2": _conv_init(next(keys), 1, c3, NC)})
    return head


def init(key, cfg: dict = YOLO11N) -> Dict:
    keys = iter(jax.random.split(key, 24))
    p3, p4, p5 = CH["p3"], CH["p4"], CH["p5"]          # 64, 128, 256
    p = {"b0": _conv_init(next(keys), 3, 3, CH["p1"]),
         "b1": _conv_init(next(keys), 3, CH["p1"], CH["p2"]),
         "b2": _c3k2_init(next(keys), "b2", CH["p2"], p3),
         "b3": _conv_init(next(keys), 3, p3, p3),
         "b4": _c3k2_init(next(keys), "b4", p3, p4),
         "b5": _conv_init(next(keys), 3, p4, p4),
         "b6": _c3k2_init(next(keys), "b6", p4, p4),
         "b7": _conv_init(next(keys), 3, p4, p5),
         "b8": _c3k2_init(next(keys), "b8", p5, p5),
         "b9": {"cv1": _conv_init(next(keys), 1, p5, p5 // 2),
                "cv2": _conv_init(next(keys), 1, 2 * p5, p5)},
         "b10": _c2psa_init(next(keys), p5),
         "n13": _c3k2_init(next(keys), "n13", p5 + p4, p4),
         "n16": _c3k2_init(next(keys), "n16", p4 + p4, p3),
         "n17": _conv_init(next(keys), 3, p3, p3),
         "n19": _c3k2_init(next(keys), "n19", p3 + p4, p4),
         "n20": _conv_init(next(keys), 3, p4, p4),
         "n22": _c3k2_init(next(keys), "n22", p4 + p5, p5),
         "head": _detect_init(next(keys), (p3, p4, p5))}
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _bottleneck(p, x):
    return x + _conv(p["cv2"], _conv(p["cv1"], x))


def _c3k(p, x):
    y = _conv(p["cv1"], x)
    for bn in p["m"]:
        y = _bottleneck(bn, y)
    return _conv(p["cv3"], jnp.concatenate([y, _conv(p["cv2"], x)], -1))


def _c3k2(p, x):
    chunks = list(jnp.split(_conv(p["cv1"], x), 2, axis=-1))
    for m in p["m"]:
        chunks.append((_c3k if "cv3" in m else _bottleneck)(m, chunks[-1]))
    return _conv(p["cv2"], jnp.concatenate(chunks, -1))


def _attention(p, x):
    b, h, w, c = x.shape
    heads = c // HEAD_DIM
    qkv = _conv(p["qkv"], x, act=None).reshape(b, h * w, heads, -1)
    q, k, v = jnp.split(qkv, [KEY_DIM, 2 * KEY_DIM], axis=-1)
    hi = jax.lax.Precision.HIGHEST
    attn = jnp.einsum("bnhd,bmhd->bhnm", q, k, precision=hi) * KEY_DIM ** -0.5
    attn = jax.nn.softmax(attn, axis=-1)
    out = jnp.einsum("bmhd,bhnm->bnhd", v, attn, precision=hi)
    v = v.reshape(b, h, w, c)
    y = out.reshape(b, h, w, c) + _conv(p["pe"], v, act=None)
    return _conv(p["proj"], y, act=None)


def _c2psa(p, x):
    a, y = jnp.split(_conv(p["cv1"], x), 2, axis=-1)
    for blk in p["m"]:
        y = y + _attention(blk["attn"], y)
        y = y + _conv(blk["ffn"][1], _conv(blk["ffn"][0], y), act=None)
    return _conv(p["cv2"], jnp.concatenate([a, y], -1))


def backbone_neck(params, x):
    """Returns the three scale features (P3, P4, P5)."""
    x = _conv(params["b1"], _conv(params["b0"], x, stride=2), stride=2)
    x = _c3k2(params["b2"], x)
    p3 = _c3k2(params["b4"], _conv(params["b3"], x, stride=2))
    p4 = _c3k2(params["b6"], _conv(params["b5"], p3, stride=2))
    x = _c3k2(params["b8"], _conv(params["b7"], p4, stride=2))
    p5 = _c2psa(params["b10"], _sppf(params["b9"], x))
    up = L.upsample_nearest
    n13 = _c3k2(params["n13"], jnp.concatenate([up(p5), p4], -1))
    n16 = _c3k2(params["n16"], jnp.concatenate([up(n13), p3], -1))
    n17 = _conv(params["n17"], n16, stride=2)
    n19 = _c3k2(params["n19"], jnp.concatenate([n17, n13], -1))
    n20 = _conv(params["n20"], n19, stride=2)
    n22 = _c3k2(params["n22"], jnp.concatenate([n20, p5], -1))
    return n16, n19, n22


def forward(params, x, cfg: dict = YOLO11N, decode: bool = True):
    """NHWC image -> (B, anchors, 4+NC) decoded predictions (or raw per-
    scale outputs with decode=False)."""
    raw = []
    for i, f in enumerate(backbone_neck(params, x)):
        box = params["head"]["cv2"][i]
        b = _conv(box["2"], _conv(box["1"], _conv(box["0"], f)), act=None)
        cls = params["head"]["cv3"][i]
        y = f
        for j in ("0", "1"):
            y = _conv(cls[j]["1"], _conv(cls[j]["0"], y))
        raw.append(jnp.concatenate([b, _conv(cls["2"], y, act=None)], -1))
    return decode_raw(raw) if decode else raw


def num_params(cfg: dict = YOLO11N) -> int:
    return L.count_params(init(jax.random.PRNGKey(0), cfg))
