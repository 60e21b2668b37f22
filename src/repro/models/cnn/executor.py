"""Graph executor: run a deployment ``Graph`` as a real program.

The scheduler decides *where* nodes run (timing is emulated by the DES);
numerics are placement-invariant, so the executor walks the DAG in
topological order and evaluates each node with jnp ops, reading conv/fc
parameters from the model pytree via ``node.meta["param"]`` paths.

Two arithmetic modes:
* ``mode="float"`` — float32 reference.
* ``mode="int8"``  — per-node INT8 quantized execution (per-channel
  weights, per-tensor activations quantized at every node boundary),
  matching the paper's INT8 deployment.

Numerics parity with the un-scheduled reference model is asserted in
tests (float mode: exact; int8 mode: bounded quantization error).

Supported node kinds cover the ResNet graphs: CONV, MVM, ADD,
GLOBAL_POOL, INPUT and OUTPUT.  The YOLO 233-node graph is scheduled and
simulated, but ``execute`` raises on its other kinds; ``yolo.forward`` runs
that model outside the graph path.

Spans (``repro.obs``, recorded only inside ``obs.recording()``): one
``execute`` per call (``kind`` = the mode, ``batch`` = frames), and under
it one ``node`` per graph node in topological order (``node`` = the
graph's node name, ``kind`` = its ``OpKind``).  In int8 mode the conv and
dense nodes add the phase spans of ``quant.quantized_conv2d`` and
``quant.quantized_matmul``.  Counter: ``execute.frames``.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp

from repro import obs
from repro.core.graph import Graph, OpKind

from .. import quant
from . import layers as L


def _param_at(params, path):
    node = params
    for p in path:
        node = node[p]
    return node


def execute(g: Graph, params: Dict, x: jnp.ndarray, mode: str = "float",
            act_scales: Optional[Dict[str, float]] = None) -> jnp.ndarray:
    """Run graph ``g`` on batch ``x`` (NHWC).  Returns the sink output."""
    obs.count("execute.frames", x.shape[0])
    with obs.span("execute", kind=mode, batch=x.shape[0]):
        env: Dict[int, jnp.ndarray] = {}
        out = None
        for nid in g.topo_order():
            node = g.nodes[nid]
            ins = [env[p] for p in g.predecessors(nid)]
            with obs.span("node", node=node.name, kind=node.kind.name):
                out = env[nid] = _run_node(node, ins, params, x, mode,
                                           act_scales)
    return out


def _run_node(node, ins, params, x, mode, act_scales):
    if node.kind == OpKind.CONV:
        inp = ins[0] if ins else x
        p = _param_at(params, node.meta["param"])
        if mode == "int8":
            y = quant.quantized_conv2d(
                inp, p["w"], p["b"], stride=node.meta["stride"],
                padding=node.meta["padding"],
                x_scale=(act_scales or {}).get(node.name))
            return L.activate(y, node.meta.get("act"))
        return L.conv2d(p, inp, stride=node.meta["stride"],
                        padding=node.meta["padding"], act=node.meta.get("act"))
    if node.kind == OpKind.MVM:
        p = _param_at(params, node.meta["param"])
        if mode == "int8":
            return quant.quantized_matmul(ins[0], p["w"], p["b"])
        return L.dense(p, ins[0])
    if node.kind == OpKind.ADD:
        return L.activate(ins[0] + ins[1], node.meta.get("act"))
    if node.kind == OpKind.GLOBAL_POOL:
        return L.global_avg_pool(ins[0])
    if node.kind == OpKind.INPUT:
        return x
    if node.kind == OpKind.OUTPUT:
        return ins[0]
    raise NotImplementedError(
        f"executor does not implement {node.kind} (node {node.name}); "
        "ResNet-family graphs only — see module docstring")
