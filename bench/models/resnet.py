"""ResNet-8 and ResNet-18-CIFAR: weights, plain reference, int4 control, and
the deployment through the program under test.

The weights, the reference and the control are the benchmark's own and take
nothing from the program.  The reference follows the published basic-block
ResNet: a 3x3 stem conv with ReLU (no max pool), stages of two 3x3 convs per
block with a 1x1 projection where the stride or width changes, ReLU after
the residual add, global average pooling and one dense layer.  BatchNorm is
folded into each conv's bias, as in an inference deployment.

``deploy`` is the only function that imports the program: it runs the
deployment flow of ``examples/schedule_and_run_cnn.py`` (graph, LBLP
placement, int8 calibration) and returns the call the timed window makes.
"""

from __future__ import annotations

import math
import time
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def blocks(cfg: Dict) -> List[Dict]:
    """The residual blocks in order, with their names, widths and strides."""
    out, cin = [], cfg["stem_width"]
    for si, (width, n) in enumerate(zip(cfg["stage_widths"],
                                        cfg["blocks_per_stage"])):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            out.append({"name": f"s{si}b{bi}", "si": si, "bi": bi,
                        "cin": cin, "cout": width, "stride": stride,
                        "down": stride != 1 or cin != width})
            cin = width
    return out


def layers(cfg: Dict) -> List[Dict]:
    """Every conv and dense layer of one frame, with its shapes."""
    h, w = cfg["image_hw"]
    out = [{"name": "stem", "kind": "conv", "k": 3, "cin": 3,
            "cout": cfg["stem_width"], "in_hw": (h, w), "out_hw": (h, w)}]
    for b in blocks(cfg):
        ho, wo = math.ceil(h / b["stride"]), math.ceil(w / b["stride"])
        out.append({"name": b["name"] + ".conv1", "kind": "conv", "k": 3,
                    "cin": b["cin"], "cout": b["cout"], "in_hw": (h, w),
                    "out_hw": (ho, wo)})
        out.append({"name": b["name"] + ".conv2", "kind": "conv", "k": 3,
                    "cin": b["cout"], "cout": b["cout"], "in_hw": (ho, wo),
                    "out_hw": (ho, wo)})
        if b["down"]:
            out.append({"name": b["name"] + ".down", "kind": "conv", "k": 1,
                        "cin": b["cin"], "cout": b["cout"], "in_hw": (h, w),
                        "out_hw": (ho, wo)})
        h, w = ho, wo
    width = cfg["stage_widths"][-1]
    out.append({"name": "fc", "kind": "fc", "k": 1, "cin": width,
                "cout": cfg["num_classes"], "in_hw": (1, 1), "out_hw": (1, 1)})
    return out


def init_params(key: jax.Array, cfg: Dict) -> Dict:
    """He-normal weights and small biases (folded BatchNorm offsets), in
    the pytree layout the graph executor reads.  One jitted call."""

    def make(key):
        keys = iter(jax.random.split(key, 2 * len(layers(cfg))))

        def conv(k, cin, cout):
            w = jax.random.normal(next(keys), (k, k, cin, cout), jnp.float32)
            b = jax.random.normal(next(keys), (cout,), jnp.float32)
            return {"w": w * math.sqrt(2.0 / (k * k * cin)), "b": 0.1 * b}

        params = {"stem": conv(3, 3, cfg["stem_width"]), "stages": []}
        for b in blocks(cfg):
            if b["bi"] == 0:
                params["stages"].append([])
            block = {"conv1": conv(3, b["cin"], b["cout"]),
                     "conv2": conv(3, b["cout"], b["cout"])}
            if b["down"]:
                block["down"] = conv(1, b["cin"], b["cout"])
            params["stages"][-1].append(block)
        width = cfg["stage_widths"][-1]
        w = jax.random.normal(next(keys), (width, cfg["num_classes"]))
        b = jax.random.normal(next(keys), (cfg["num_classes"],))
        params["fc"] = {"w": w * math.sqrt(2.0 / width), "b": 0.1 * b}
        return params

    return jax.jit(make)(key)


# ---------------------------------------------------------------------------
# plain reference, and the same with int4 fake quantization (the control)
# ---------------------------------------------------------------------------

def _fake_quant(x, scale, qmax):
    return jnp.clip(jnp.round(x / scale), -qmax, qmax) * scale


def _quant_weight(w, qmax):
    axes = tuple(range(w.ndim - 1))
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axes), 1e-8) / qmax
    return jnp.clip(jnp.round(w / s), -qmax, qmax) * s


def forward(params: Dict, x: jnp.ndarray, cfg: Dict, amax=None, qmax=None,
            record=None) -> jnp.ndarray:
    """NHWC frames -> logits in float32 at full precision.

    With ``qmax`` set, every conv and dense input is fake-quantized per
    tensor with the scale ``amax[name] / qmax`` and every weight per output
    channel: the same network at a lower integer precision.  ``record``, a
    dict, receives each layer input's largest magnitude.
    """

    def layer(name, p, h, fn):
        if record is not None:
            record[name] = jnp.max(jnp.abs(h))
        w = p["w"]
        if qmax is not None:
            h = _fake_quant(h, jnp.maximum(amax[name], 1e-8) / qmax, qmax)
            w = _quant_weight(w, qmax)
        return fn(h, w) + p["b"]

    def conv(name, p, h, stride):
        return layer(name, p, h, lambda h, w: jax.lax.conv_general_dilated(
            h, w, (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST))

    h = jax.nn.relu(conv("stem", params["stem"], x, 1))
    for b in blocks(cfg):
        p = params["stages"][b["si"]][b["bi"]]
        y = jax.nn.relu(conv(b["name"] + ".conv1", p["conv1"], h, b["stride"]))
        y = conv(b["name"] + ".conv2", p["conv2"], y, 1)
        if b["down"]:
            h = conv(b["name"] + ".down", p["down"], h, b["stride"])
        h = jax.nn.relu(y + h)
    g = jnp.mean(h, axis=(1, 2))
    return layer("fc", params["fc"], g,
                 lambda g, w: jnp.dot(g, w, precision=HIGHEST))


def reference_fn(cfg: Dict) -> Callable:
    """Jitted float32 reference: ``(params, x) -> logits``."""
    return jax.jit(lambda params, x: forward(params, x, cfg))


def control_fn(cfg: Dict, qmax: int) -> Callable:
    """Jitted ``(params, calib, x) -> logits`` of the reference at the lower
    precision, calibrated on ``calib`` as the deployment is."""

    def run(params, calib, x):
        amax: Dict = {}
        forward(params, calib, cfg, record=amax)
        return forward(params, x, cfg, amax=amax, qmax=qmax)

    return jax.jit(run)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def deploy(params: Dict, cfg: Dict, calib: jnp.ndarray, phases: Dict) -> Callable:
    """Run the program's deployment flow; returns ``serve(frames) -> logits``.

    ``serve`` calls ``executor.execute`` as the program's example does.  The
    executor compiles the graph into one program at the first call of each
    batch shape (set-up's warm-up) and launches it once a call after that.
    ``phases`` receives the seconds of each step.
    """
    from repro.core import CostModel, get_scheduler, make_pus
    from repro.models import quant
    from repro.models.cnn import executor, graphs

    dep = cfg["deployment"]
    t = time.perf_counter()
    graph = graphs.build_resnet_graph(cfg)
    fleet = make_pus(dep["imc_units"], dep["dpu_units"])
    get_scheduler(dep["scheduler"], CostModel()).schedule(graph, fleet)
    phases["placement_s"] = time.perf_counter() - t
    t = time.perf_counter()
    scales = quant.calibrate_resnet(params, calib, cfg)
    phases["calibration_s"] = time.perf_counter() - t
    mode = dep["mode"]

    def serve(frames):
        return executor.execute(graph, params, frames, mode=mode,
                                act_scales=scales)

    return serve
