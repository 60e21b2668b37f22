"""YOLOv8n: weights, plain reference, int4 control, and the deployment through
the program under test.

The weights, the reference and the control are the benchmark's own and take
nothing from the program.  The reference follows Ultralytics YOLOv8
(``ultralytics/cfg/models/v8/yolov8.yaml``) at the configuration's scale:
widths ``channels`` x ``width_multiple`` (capped at ``max_channels``, rounded
up to a multiple of 8), C2f repeats ``repeats`` x ``depth_multiple``.  Every
"Conv" module is a conv padded k // 2 on every side, its bias (BatchNorm
folded) and SiLU.  Backbone: two stride-2 convs, C2f, and three more pairs
of stride-2 conv and C2f, then SPPF (three chained ``sppf_k`` x ``sppf_k``
max pools, stride 1).
Neck (PAN): upsample x2 and concat with P4 and P3 on the way up, a stride-2
conv and concat on the way down, a C2f without shortcut after each concat.
Detect head at strides 8, 16 and 32: a box branch (two 3x3 convs, then a 1x1
conv to 4 x ``reg_max`` distance bins) and a class branch (two 3x3 convs,
then a 1x1 conv to ``nc`` logits).  Decode: a softmax over each side's bins
and its expectation (DFL), then dist2bbox about each anchor's centre, times
its stride.  An answer is (anchors, 4 + nc): box centre x, y, width and
height in pixels, then the class scores after a sigmoid.  The one departure:
the answer is anchors-major, (anchors, 84), where Ultralytics returns the
transpose, (84, anchors).

``deploy`` is the only function that imports the program: graph, LBLP
placement, int8 calibration from the graph, and the executor's call.
"""

from __future__ import annotations

import math
import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 16      # frames per step of the reference's and control's loop


def widths(cfg: Dict):
    """Backbone widths P1..P5, backbone C2f repeats, and the neck's C2f
    repeats, at the configuration's scale."""
    w, d = cfg["width_multiple"], cfg["depth_multiple"]
    ch = [math.ceil(min(c, cfg["max_channels"]) * w / 8) * 8
          for c in cfg["channels"]]
    reps = [max(round(n * d), 1) for n in cfg["repeats"]]
    return ch, reps, max(round(cfg["head_repeats"] * d), 1)


def head_widths(cfg: Dict, p3: int):
    """The box and class branches' hidden widths (Ultralytics' Detect)."""
    return (max(16, p3 // 4, 4 * cfg["reg_max"]),
            max(p3, min(cfg["nc"], 100)))


def layers(cfg: Dict) -> List[Dict]:
    """Every conv of one frame, in order, with its pytree path and shapes."""
    ch, reps, nh = widths(cfg)
    out: List[Dict] = []

    def conv(path, k, cin, cout, stride, hw):
        ho, wo = (hw[0] - 1) // stride + 1, (hw[1] - 1) // stride + 1
        out.append({"name": ".".join(map(str, path)), "path": path,
                    "kind": "conv", "k": k, "cin": cin, "cout": cout,
                    "stride": stride, "in_hw": tuple(hw), "out_hw": (ho, wo)})
        return ho, wo

    def c2f(name, cin, cout, n, hw):
        c = cout // 2
        conv((name, "cv1"), 1, cin, 2 * c, 1, hw)
        for i in range(n):
            conv((name, "m", i, "cv1"), 3, c, c, 1, hw)
            conv((name, "m", i, "cv2"), 3, c, c, 1, hw)
        conv((name, "cv2"), 1, (2 + n) * c, cout, 1, hw)

    hw = conv(("b0",), 3, 3, ch[0], 2, cfg["image_hw"])
    hw = conv(("b1",), 3, ch[0], ch[1], 2, hw)
    c2f("b2", ch[1], ch[1], reps[0], hw)
    sizes = {}
    for i, name in enumerate(("b3", "b5", "b7")):
        hw = sizes[i + 3] = conv((name,), 3, ch[i + 1], ch[i + 2], 2, hw)
        c2f(f"b{4 + 2 * i}", ch[i + 2], ch[i + 2], reps[i + 1], hw)
    conv(("b9", "cv1"), 1, ch[4], ch[4] // 2, 1, hw)
    conv(("b9", "cv2"), 1, 2 * ch[4], ch[4], 1, hw)
    c2f("n12", ch[4] + ch[3], ch[3], nh, sizes[4])
    c2f("n15", ch[3] + ch[2], ch[2], nh, sizes[3])
    conv(("n16",), 3, ch[2], ch[2], 2, sizes[3])
    c2f("n18", ch[2] + ch[3], ch[3], nh, sizes[4])
    conv(("n19",), 3, ch[3], ch[3], 2, sizes[4])
    c2f("n21", ch[3] + ch[4], ch[4], nh, sizes[5])
    c2, c3 = head_widths(cfg, ch[2])
    for i, (c, scale) in enumerate(zip(ch[2:], (3, 4, 5))):
        for branch, width, cout in (("cv2", c2, 4 * cfg["reg_max"]),
                                    ("cv3", c3, cfg["nc"])):
            conv(("head", branch, i, "0"), 3, c, width, 1, sizes[scale])
            conv(("head", branch, i, "1"), 3, width, width, 1, sizes[scale])
            conv(("head", branch, i, "2"), 1, width, cout, 1, sizes[scale])
    return out


def init_params(key: jax.Array, cfg: Dict) -> Dict:
    """He-normal weights and small biases (folded BatchNorm offsets), in
    the pytree layout the graph executor reads (``yolo.init``'s: lists for
    a C2f's bottlenecks and the head's scales).  One jitted call."""
    convs = layers(cfg)

    def make(key):
        keys = iter(jax.random.split(key, 2 * len(convs)))
        tree: Dict = {}
        for c in convs:
            w = jax.random.normal(next(keys), (c["k"], c["k"], c["cin"],
                                               c["cout"]), jnp.float32)
            b = jax.random.normal(next(keys), (c["cout"],), jnp.float32)
            node = tree
            for step in c["path"][:-1]:
                node = node.setdefault(step, {})
            node[c["path"][-1]] = {
                "w": w * math.sqrt(2.0 / (c["k"] ** 2 * c["cin"])),
                "b": 0.1 * b}
        return _lists(tree)

    return jax.jit(make)(key)


def _lists(tree):
    """Dicts keyed 0..n-1 as lists, all the way down."""
    if not isinstance(tree, dict):
        return tree
    if tree and all(isinstance(k, int) for k in tree):
        return [_lists(tree[i]) for i in range(len(tree))]
    return {k: _lists(v) for k, v in tree.items()}


# ---------------------------------------------------------------------------
# plain reference, and the same with int4 fake quantization (the control)
# ---------------------------------------------------------------------------

def _fake_quant(x, scale, qmax):
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def _quant_weight(w, qmax):
    axes = tuple(range(w.ndim - 1))
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axes), 1e-8) / qmax
    return jnp.clip(jnp.round(w / s), -qmax, qmax) * s


def anchors(cfg: Dict):
    """Each anchor's centre (x, y) in grid cells and its stride, scale by
    scale, each grid row by row."""
    h, w = cfg["image_hw"]
    points, strides = [], []
    for s in cfg["strides"]:
        ys, xs = jnp.meshgrid(jnp.arange(h // s, dtype=jnp.float32) + 0.5,
                              jnp.arange(w // s, dtype=jnp.float32) + 0.5,
                              indexing="ij")
        points.append(jnp.stack([xs.ravel(), ys.ravel()], -1))
        strides.append(jnp.full((xs.size, 1), float(s), jnp.float32))
    return jnp.concatenate(points), jnp.concatenate(strides)


def forward(params: Dict, x: jnp.ndarray, cfg: Dict, amax=None, qmax=None,
            record=None) -> jnp.ndarray:
    """NHWC frames -> (frames, anchors, 4 + nc) in float32 at full
    precision.

    With ``qmax`` set, every conv input is fake-quantized per tensor with
    the scale ``amax[name] / qmax`` and every weight per output channel:
    the same network at a lower integer precision.  ``record``, a dict,
    receives each conv input's largest magnitude.
    """
    _, reps, nh = widths(cfg)

    def conv(path, h, stride=1, silu=True):
        name = ".".join(map(str, path))
        p = params
        for step in path:
            p = p[step]
        if record is not None:
            record[name] = jnp.max(jnp.abs(h))
        w = p["w"]
        if qmax is not None:
            h = _fake_quant(h, jnp.maximum(amax[name], 1e-8) / qmax, qmax)
            w = _quant_weight(w, qmax)
        pad = w.shape[0] // 2
        y = jax.lax.conv_general_dilated(
            h, w, (stride, stride), ((pad, pad), (pad, pad)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HIGHEST) + p["b"]
        return y * jax.nn.sigmoid(y) if silu else y

    def c2f(name, h, n, shortcut):
        y = conv((name, "cv1"), h)
        c = y.shape[-1] // 2
        ys = [y[..., :c], y[..., c:]]
        for i in range(n):
            t = conv((name, "m", i, "cv2"), conv((name, "m", i, "cv1"), ys[-1]))
            ys.append(ys[-1] + t if shortcut else t)
        return conv((name, "cv2"), jnp.concatenate(ys, -1))

    def sppf(name, h, k=cfg["sppf_k"]):
        ys = [conv((name, "cv1"), h)]
        for _ in range(3):
            ys.append(jax.lax.reduce_window(
                ys[-1], -jnp.inf, jax.lax.max, (1, k, k, 1), (1, 1, 1, 1),
                ((0, 0), (k // 2, k // 2), (k // 2, k // 2), (0, 0))))
        return conv((name, "cv2"), jnp.concatenate(ys, -1))

    def up(h):
        b, hh, ww, c = h.shape
        h = jnp.broadcast_to(h[:, :, None, :, None, :], (b, hh, 2, ww, 2, c))
        return h.reshape(b, 2 * hh, 2 * ww, c)

    def cat(*hs):
        return jnp.concatenate(hs, -1)

    h = conv(("b1",), conv(("b0",), x, 2), 2)
    h = c2f("b2", h, reps[0], True)
    p3 = c2f("b4", conv(("b3",), h, 2), reps[1], True)
    p4 = c2f("b6", conv(("b5",), p3, 2), reps[2], True)
    p5 = sppf("b9", c2f("b8", conv(("b7",), p4, 2), reps[3], True))
    n12 = c2f("n12", cat(up(p5), p4), nh, False)
    n15 = c2f("n15", cat(up(n12), p3), nh, False)
    n18 = c2f("n18", cat(conv(("n16",), n15, 2), n12), nh, False)
    n21 = c2f("n21", cat(conv(("n19",), n18, 2), p5), nh, False)

    rows = []
    for i, f in enumerate((n15, n18, n21)):
        out = []
        for branch in ("cv2", "cv3"):
            y = conv(("head", branch, i, "1"), conv(("head", branch, i, "0"), f))
            out.append(conv(("head", branch, i, "2"), y, silu=False))
        b, hh, ww, _ = f.shape
        rows.append(cat(*out).reshape(b, hh * ww, -1))
    z = jnp.concatenate(rows, 1)

    reg = cfg["reg_max"]
    bins = z[..., :4 * reg].reshape(*z.shape[:2], 4, reg)
    dist = jnp.sum(jax.nn.softmax(bins, -1) * jnp.arange(reg, dtype=jnp.float32),
                   -1)
    centre, stride = anchors(cfg)
    x1y1 = centre - dist[..., :2]
    x2y2 = centre + dist[..., 2:]
    box = cat((x1y1 + x2y2) / 2, x2y2 - x1y1) * stride
    return cat(box, jax.nn.sigmoid(z[..., 4 * reg:]))


def _chunks(fn, x):
    """``fn`` over ``x`` in steps of ``CHUNK`` frames (``lax.map``), so
    that a large block of frames never runs as one batch; results stacked
    along the frames again."""
    n = x.shape[0]
    pad = -n % CHUNK
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    out = jax.lax.map(fn, x.reshape((-1, CHUNK) + x.shape[1:]))
    return jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:])[:n], out)


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


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def deploy(params: Dict, cfg: Dict, calib: jnp.ndarray, phases: Dict) -> Callable:
    """Run the program's deployment flow; returns ``serve(frames) -> answers``.

    ``serve`` calls ``executor.execute`` on the program's YOLOv8n graph for
    the configuration's frame size.  ``phases`` receives the seconds of each
    step.
    """
    from repro.core import CostModel, get_scheduler, make_pus
    from repro.models import quant
    from repro.models.cnn import executor, graphs

    dep = cfg["deployment"]
    t = time.perf_counter()
    graph = graphs.build_yolov8n_graph({"name": cfg["name"],
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
