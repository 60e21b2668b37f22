"""Executable YOLOv8n (the paper's §V.C workload) in pure JAX.

Standard ultralytics YOLOv8n topology at width 0.25 / depth 0.33:
backbone (P1..P5 + SPPF), PAN neck, decoupled Detect head with DFL
decoding.  ~3.16M parameters (paper: "3.17M").  The deployment graph
(`graphs.build_yolov8n_graph`) mirrors this model at ONNX-node
granularity: 233 nodes, 63 convolutional, 57 followed by SiLU — the
paper's exact counts (asserted in tests).

The "3 parallel main branches" the paper describes are the three
detection scales (P3/P4/P5) flowing through the neck: each has one long
sub-branch (C2f path: cv1 + 2 bottleneck convs + cv2 = 5 conv chain) and
two short ones (the 3-conv box/cls head branches).
"""

from __future__ import annotations

from typing import Dict, Tuple

try:
    import jax
    import jax.numpy as jnp
except ModuleNotFoundError:  # arch specs stay importable without jax
    jax = jnp = None  # type: ignore[assignment]

from . import layers as L

# width-scaled channel plan for v8n
CH = {"p1": 16, "p2": 32, "p3": 64, "p4": 128, "p5": 256}
NC = 80              # COCO classes
REG_MAX = 16         # DFL bins
STRIDES = (8, 16, 32)

YOLOV8N = {
    "name": "yolov8n",
    "image_hw": (640, 640),
    "nc": NC,
    "reg_max": REG_MAX,
}


# ---------------------------------------------------------------------------
# modules
# ---------------------------------------------------------------------------

def _conv_module_init(key, k, cin, cout):
    """Conv + folded-BN + SiLU ("Conv" module in ultralytics)."""
    return L.conv_init(key, k, cin, cout)


def _bottleneck_init(key, c):
    k1, k2 = jax.random.split(key)
    return {"cv1": _conv_module_init(k1, 3, c, c),
            "cv2": _conv_module_init(k2, 3, c, c)}


def _c2f_init(key, cin, cout, n):
    keys = jax.random.split(key, n + 2)
    c = cout // 2
    return {
        "cv1": _conv_module_init(keys[0], 1, cin, cout),
        "m": [_bottleneck_init(keys[i + 1], c) for i in range(n)],
        "cv2": _conv_module_init(keys[-1], 1, (2 + n) * c, cout),
    }


def _sppf_init(key, c):
    k1, k2 = jax.random.split(key)
    return {"cv1": _conv_module_init(k1, 1, c, c // 2),
            "cv2": _conv_module_init(k2, 1, 2 * c, c)}


def _detect_init(key, chs: Tuple[int, ...]):
    c2 = max(16, chs[0] // 4, 4 * REG_MAX)      # 64 for v8n
    c3 = max(chs[0], min(NC, 100))              # 80 for v8n
    keys = iter(jax.random.split(key, 64))
    head = {"cv2": [], "cv3": []}
    for c in chs:
        head["cv2"].append({
            "0": _conv_module_init(next(keys), 3, c, c2),
            "1": _conv_module_init(next(keys), 3, c2, c2),
            "2": L.conv_init(next(keys), 1, c2, 4 * REG_MAX),   # plain conv
        })
        head["cv3"].append({
            "0": _conv_module_init(next(keys), 3, c, c3),
            "1": _conv_module_init(next(keys), 3, c3, c3),
            "2": L.conv_init(next(keys), 1, c3, NC),            # plain conv
        })
    return head


def init(key, cfg: dict = YOLOV8N) -> Dict:
    keys = iter(jax.random.split(key, 32))
    p = {}
    p["b0"] = _conv_module_init(next(keys), 3, 3, CH["p1"])
    p["b1"] = _conv_module_init(next(keys), 3, CH["p1"], CH["p2"])
    p["b2"] = _c2f_init(next(keys), CH["p2"], CH["p2"], 1)
    p["b3"] = _conv_module_init(next(keys), 3, CH["p2"], CH["p3"])
    p["b4"] = _c2f_init(next(keys), CH["p3"], CH["p3"], 2)
    p["b5"] = _conv_module_init(next(keys), 3, CH["p3"], CH["p4"])
    p["b6"] = _c2f_init(next(keys), CH["p4"], CH["p4"], 2)
    p["b7"] = _conv_module_init(next(keys), 3, CH["p4"], CH["p5"])
    p["b8"] = _c2f_init(next(keys), CH["p5"], CH["p5"], 1)
    p["b9"] = _sppf_init(next(keys), CH["p5"])
    # neck
    p["n12"] = _c2f_init(next(keys), CH["p4"] + CH["p5"], CH["p4"], 1)
    p["n15"] = _c2f_init(next(keys), CH["p3"] + CH["p4"], CH["p3"], 1)
    p["n16"] = _conv_module_init(next(keys), 3, CH["p3"], CH["p3"])
    p["n18"] = _c2f_init(next(keys), CH["p3"] + CH["p4"], CH["p4"], 1)
    p["n19"] = _conv_module_init(next(keys), 3, CH["p4"], CH["p4"])
    p["n21"] = _c2f_init(next(keys), CH["p4"] + CH["p5"], CH["p5"], 1)
    p["head"] = _detect_init(next(keys), (CH["p3"], CH["p4"], CH["p5"]))
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def autopad(k: int):
    """Ultralytics' padding of a k x k conv: k // 2 on every side.  For a
    stride-2 3x3 conv it is not "SAME", which pads 0 before and 1 after."""
    return ((k // 2, k // 2), (k // 2, k // 2))


def _conv(p, x, stride=1, act="silu"):
    """A conv padded ``autopad``; grouped where its HWIO weights take fewer
    input channels than ``x`` has (a depthwise conv's take one)."""
    return L.conv2d(p, x, stride=stride, padding=autopad(p["w"].shape[0]),
                    act=act, groups=x.shape[-1] // p["w"].shape[2])


def _c2f(p, x, shortcut: bool):
    y = _conv(p["cv1"], x)
    a, b = jnp.split(y, 2, axis=-1)
    chunks = [a, b]
    h = b
    for bn in p["m"]:
        out = _conv(bn["cv2"], _conv(bn["cv1"], h))
        h = h + out if shortcut else out
        chunks.append(h)
    return _conv(p["cv2"], jnp.concatenate(chunks, axis=-1))


def _sppf(p, x):
    y = _conv(p["cv1"], x)
    p1 = L.max_pool(y, 5, stride=1, padding="SAME")
    p2 = L.max_pool(p1, 5, stride=1, padding="SAME")
    p3 = L.max_pool(p2, 5, stride=1, padding="SAME")
    return _conv(p["cv2"], jnp.concatenate([y, p1, p2, p3], axis=-1))


def backbone_neck(params, x):
    """Returns the three scale features (P3, P4, P5)."""
    x = _conv(params["b0"], x, stride=2)
    x = _conv(params["b1"], x, stride=2)
    x = _c2f(params["b2"], x, shortcut=True)
    x = _conv(params["b3"], x, stride=2)
    p3 = _c2f(params["b4"], x, shortcut=True)
    x = _conv(params["b5"], p3, stride=2)
    p4 = _c2f(params["b6"], x, shortcut=True)
    x = _conv(params["b7"], p4, stride=2)
    x = _c2f(params["b8"], x, shortcut=True)
    p5 = _sppf(params["b9"], x)
    # PAN neck
    u1 = L.upsample_nearest(p5)
    n12 = _c2f(params["n12"], jnp.concatenate([u1, p4], axis=-1), shortcut=False)
    u2 = L.upsample_nearest(n12)
    n15 = _c2f(params["n15"], jnp.concatenate([u2, p3], axis=-1), shortcut=False)
    d1 = _conv(params["n16"], n15, stride=2)
    n18 = _c2f(params["n18"], jnp.concatenate([d1, n12], axis=-1), shortcut=False)
    d2 = _conv(params["n19"], n18, stride=2)
    n21 = _c2f(params["n21"], jnp.concatenate([d2, p5], axis=-1), shortcut=False)
    return n15, n18, n21


def _head_branch(branch, x):
    y = _conv(branch["0"], x)
    y = _conv(branch["1"], y)
    return _conv(branch["2"], y, act=None)      # plain conv, no act


def forward(params, x, cfg: dict = YOLOV8N, decode: bool = True):
    """NHWC image -> (B, anchors, 4+NC) decoded predictions (or raw per-
    scale outputs with decode=False)."""
    feats = backbone_neck(params, x)
    raw = []
    for i, f in enumerate(feats):
        box = _head_branch(params["head"]["cv2"][i], f)
        cls = _head_branch(params["head"]["cv3"][i], f)
        raw.append(jnp.concatenate([box, cls], axis=-1))
    return decode_raw(raw) if decode else raw


def decode_raw(raw):
    """Detect's decode of each scale's (B, h, w, 4 * REG_MAX + NC) output
    (P3 first): DFL, dist2bbox and the class sigmoid -> (B, anchors,
    4 + NC).  YOLOv8 and YOLO11 decode alike."""
    b = raw[0].shape[0]
    flat, anchors, strides = [], [], []
    for f, s in zip(raw, STRIDES):
        _, h, w, c = f.shape
        flat.append(f.reshape(b, h * w, c))
        ys, xs = jnp.meshgrid(jnp.arange(h), jnp.arange(w), indexing="ij")
        anchors.append(jnp.stack([xs.reshape(-1) + 0.5, ys.reshape(-1) + 0.5], -1))
        strides.append(jnp.full((h * w, 1), float(s)))
    z = jnp.concatenate(flat, axis=1)
    anchor = jnp.concatenate(anchors, axis=0)
    stride = jnp.concatenate(strides, axis=0)
    box, cls = z[..., : 4 * REG_MAX], z[..., 4 * REG_MAX:]
    # DFL: softmax over bins, expectation via fixed conv [0..15]
    box = box.reshape(b, -1, 4, REG_MAX)
    box = jax.nn.softmax(box, axis=-1) @ jnp.arange(REG_MAX, dtype=jnp.float32)
    lt, rb = box[..., :2], box[..., 2:]
    x1y1 = anchor - lt
    x2y2 = anchor + rb
    cxy = (x1y1 + x2y2) / 2.0
    wh = x2y2 - x1y1
    bbox = jnp.concatenate([cxy, wh], axis=-1) * stride
    return jnp.concatenate([bbox, jax.nn.sigmoid(cls)], axis=-1)


def num_params(cfg: dict = YOLOV8N) -> int:
    return L.count_params(init(jax.random.PRNGKey(0), cfg))
