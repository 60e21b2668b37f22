"""Graph executor: run a deployment ``Graph`` as a real program.

The scheduler decides *where* nodes run (timing is emulated by the DES);
numerics are placement-invariant, so the executor walks the DAG in
topological order and evaluates each node with jnp ops, reading conv/fc
parameters from the model pytree via ``node.meta["param"]`` paths.

The walk is traced once into one ``jax.jit`` program per graph, mode and
set of node names in ``act_scales``, kept in ``g.scratch()`` (so any
mutation of the graph drops it); ``jax.jit`` keeps one compiled program
per input shape and dtype.  A later call of the same graph, mode and shape
is one launch, whose only host-to-device transfer is ``x`` itself: the
activation scales reach the program as one device vector, made once per
set of values.  Each node's operations carry its name as a
``jax.named_scope``, so device time can be put down to graph nodes.

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
``execute`` per call (``kind`` = the mode, ``batch`` = frames).  Under it,
only in a call that traces the program, one ``node`` per graph node in
topological order (``node`` = the graph's node name, ``kind`` = its
``OpKind``), and in int8 mode under each conv and dense node the phase
spans of ``quant.quantized_conv2d`` and ``quant.quantized_matmul``: these
time the tracing, not the device.  Counters: ``execute.frames`` (per call)
and ``execute.traces`` (programs traced).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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
        names = tuple(sorted(act_scales)) if act_scales else ()
        return _program(g, mode, names)(params, x,
                                        _scale_vector(g, act_scales, names))


def _program(g: Graph, mode: str, names: Tuple[str, ...]):
    """The jitted walk of ``g`` in ``mode``, reading the scale of node
    ``names[i]`` from element ``i`` of its third argument."""
    key = ("executor.program", mode, names)
    program = g.scratch().get(key)
    if program is not None:
        return program
    steps = [(g.nodes[nid], g.predecessors(nid)) for nid in g.topo_order()]
    index = {name: i for i, name in enumerate(names)}

    def execute_graph(params, x, scales):
        obs.count("execute.traces")
        env: Dict[int, jnp.ndarray] = {}
        out = None
        for node, preds in steps:
            ins = [env[p] for p in preds]
            i = index.get(node.name)
            with jax.named_scope(node.name), \
                    obs.span("node", node=node.name, kind=node.kind.name):
                out = env[node.node_id] = _run_node(
                    node, ins, params, x, mode,
                    None if i is None else scales[i])
        return out

    program = g.scratch()[key] = jax.jit(execute_graph)
    return program


def _scale_vector(g: Graph, act_scales, names):
    """``act_scales`` in the order of ``names`` as one float32 device
    vector, made again only when a value changes."""
    if not names:
        return None
    values = tuple(float(act_scales[n]) for n in names)
    key = ("executor.scales", names)
    hit = g.scratch().get(key)
    if hit is None or hit[0] != values:
        # concrete even when ``execute`` is traced inside an outer jit
        with jax.ensure_compile_time_eval():
            vec = jax.device_put(np.asarray(values, np.float32))
        hit = g.scratch()[key] = (values, vec)
    return hit[1]


def _run_node(node, ins, params, x, mode, x_scale):
    if node.kind == OpKind.CONV:
        inp = ins[0] if ins else x
        p = _param_at(params, node.meta["param"])
        if mode == "int8":
            y = quant.quantized_conv2d(
                inp, p["w"], p["b"], stride=node.meta["stride"],
                padding=node.meta["padding"], x_scale=x_scale)
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
