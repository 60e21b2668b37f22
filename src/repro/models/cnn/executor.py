"""Graph executor: run a deployment ``Graph`` as a real program.

The scheduler decides *where* nodes run (timing is emulated by the DES);
numerics are placement-invariant, so the executor walks the DAG in
topological order and evaluates each node with jnp ops.  One rule, the
same for every model: a node's operands are its ``meta["inputs"]``, in
that order (``graphs.py`` documents the pairs; ``[]`` reads the frames),
and conv/fc parameters come from the model pytree via
``node.meta["param"]`` paths.  A node without ``inputs``, or whose
``inputs`` do not name its predecessors, is refused with a
``ValueError`` naming it.

The walk is traced once into one ``jax.jit`` program per graph, mode and
set of node names in ``act_scales``, kept in ``g.scratch()`` (so any
mutation of the graph drops it); ``jax.jit`` keeps one compiled program
per input shape and dtype.  A later call of the same graph, mode and shape
is one launch, whose only host-to-device transfer is the frames: the
activation scales reach the program as one device vector, made once per
set of values.  Host frames (a C-contiguous ``np.ndarray`` whose size is a
multiple of 128) travel as their [rows, 128] view, and the program's first
step lays them out again as NHWC, their shape a static argument.  On a TPU
that view's default layout (8 x 128 tiles, row-major) is the host buffer's
own order, so the put is a plain copy; NHWC frames would be laid out batch
or channel minor, which the runtime does by transposing them on a host
thread at every put.  A device array, a tracer of an outer ``jit`` or any
other size goes as it is.  The view is laid out by one of two routes,
chosen from what the call shows (``_planar``): in int8 mode, fewer than
128 frames of a multiple of 128 pixels each, whose one reader is a CONV
with a calibrated scale, are quantised on the view and de-interleaved to
channel-planar order by an exact int8 permutation (``_planar_frames``),
and that CONV takes them already quantised; every other view goes through
the batch-minor order (``_nhwc``).  Each node's operations carry its name as a
``jax.named_scope``, so device time can be put down to graph nodes.
``input_magnitudes``, through which ``quant`` calibrates every model, is
a second program per graph: the float walk, returning the largest input
magnitude of each node with learned weights.

Two arithmetic modes:
* ``mode="float"`` — float32 reference.
* ``mode="int8"``  — per-node INT8 quantized execution (per-channel
  weights, per-tensor activations quantized at every conv input),
  matching the paper's INT8 deployment.

Numerics parity with the un-scheduled reference model is asserted in
tests (float mode: ``resnet.forward``, ``yolo.forward`` and
``yolo11.forward`` within float rounding; int8 mode: bounded quantization
error).

Node kinds: CONV, MVM, ADD and GLOBAL_POOL (the ResNet graphs); ACT, MUL,
CONCAT, SPLIT, POOL_MAX, UPSAMPLE, RESHAPE and SOFTMAX (YOLOv8n); grouped
CONVs and PRODUCT, the contraction of two activations (YOLO11n's
depthwise convs and C2PSA attention; ``graphs.py`` documents their
metadata); INPUT (the frames) and OUTPUT (its operand).  In int8 mode only
the nodes with learned weights are quantised: each CONV's input per tensor
and its weights per output channel, grouped or not, and each MVM with a
``param``.  Everything else runs in float32 by design, inside the same
program: the detectors' SiLU (ACT + MUL), residual adds, concats, pools,
upsamples, the attention's products (at ``HIGHEST``) and softmax, and the
whole decode (the DFL softmax, the fixed-weight ``dfl.conv``, dist2bbox
and the class sigmoid), none of which has learned weights.

Spans (``repro.obs``, recorded only inside ``obs.recording()``): one
``execute`` per call (``kind`` = the mode, ``batch`` = frames).  Under it,
only in a call that traces the program, one ``node`` per graph node in
topological order (``node`` = the graph's node name, ``kind`` = its
``OpKind``), and in int8 mode under each conv and dense node the phase
spans of ``quant.quantized_conv2d`` and ``quant.quantized_matmul``: these
time the tracing, not the device.  Frames laid out by ``_planar_frames``
are quantised under a ``quant.act`` span before the walk, so the trace
still has one ``quant.act`` per conv.  Counters: ``execute.frames`` (per
call), ``execute.flat_puts`` (calls whose frames went as the [rows, 128] view),
``execute.planar_puts`` (those of them laid out by ``_planar_frames``),
``execute.traces`` (programs traced), and ``execute.bytes_in`` and
``execute.bytes_out``, the bytes of ``x`` and of the result, from their
shapes and dtypes (no wait for the device).  Two count what a trace puts
into a program, once per compiled program and not per call:
``execute.act_products``, the PRODUCT nodes traced (2 for YOLO11n, one
q·kᵀ and one v·attnᵀ, each over both heads), and ``quant``'s
``quant.grouped_convs``, the grouped int8 convs (7 for YOLO11n in int8
mode); both read 0 for YOLOv8n and the ResNets.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

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


def _nbytes(a) -> int:
    return math.prod(a.shape) * a.dtype.itemsize


#: columns of the view in which host frames are put: a TPU's tile is 8 x 128
#: (sublanes x lanes), so an f32[R, 128] array's default layout is the
#: linear order of a C-contiguous host buffer and its put is a plain copy
LANES = 128


def execute(g: Graph, params: Dict, x: jnp.ndarray, mode: str = "float",
            act_scales: Optional[Dict[str, float]] = None) -> jnp.ndarray:
    """Run graph ``g`` on batch ``x`` (NHWC).  Returns the sink output."""
    obs.count("execute.frames", x.shape[0])
    with obs.span("execute", kind=mode, batch=x.shape[0]):
        names = tuple(sorted(act_scales)) if act_scales else ()
        shape, planar = None, False  # device arrays and tracers go as they are
        if isinstance(x, np.ndarray) and x.flags.c_contiguous \
                and x.size % LANES == 0:
            obs.count("execute.flat_puts")
            shape, x = x.shape, x.reshape(-1, LANES)
            planar = _planar(g, mode, names, shape)
            obs.count("execute.planar_puts", int(planar))
        out = _program(g, mode, names)(params, x,
                                       _scale_vector(g, act_scales, names),
                                       shape, planar)
    if obs.on():
        obs.count("execute.bytes_in", _nbytes(x))
        obs.count("execute.bytes_out", _nbytes(out))
    return out


def weighted_nodes(g: Graph) -> List[str]:
    """Names of the CONV and MVM nodes with learned weights (a ``param``),
    in topological order: the nodes an int8 program quantises."""
    return [g.nodes[n].name for n in g.topo_order()
            if g.nodes[n].kind in (OpKind.CONV, OpKind.MVM)
            and g.nodes[n].meta.get("param") is not None]


def input_magnitudes(g: Graph, params: Dict, x: jnp.ndarray) -> jnp.ndarray:
    """The largest magnitude of the input of each of ``weighted_nodes(g)``
    over batch ``x``, in float mode, as one vector: one compiled program
    per graph."""
    key = ("executor.magnitudes",)
    program = g.scratch().get(key)
    if program is None:
        steps = _steps(g)
        tapped = set(weighted_nodes(g))

        def magnitudes(params, x):
            taps = []
            _walk(steps, params, x, "float", None, {}, tapped, taps)
            return jnp.stack(taps)

        program = g.scratch()[key] = jax.jit(magnitudes)
    return program(params, x)


def _steps(g: Graph):
    """Each node in topological order with its operands, ``meta["inputs"]``,
    as (node id, part) pairs (``part`` picks a SPLIT's output)."""
    ids = {node.name: nid for nid, node in g.nodes.items()}
    steps = []
    for nid in g.topo_order():
        node, preds = g.nodes[nid], g.predecessors(nid)
        refs = node.meta.get("inputs")
        ops = [] if refs is None else [(ids[name], part)
                                       for name, part in refs]
        if refs is None or sorted({i for i, _ in ops}) != sorted(preds):
            raise ValueError(f"node {node.name}: meta inputs {refs} do not "
                             "name its predecessors")
        steps.append((node, ops))
    return steps


def _walk(steps, params, x, mode, scales, index, tapped=(), taps=None):
    """Evaluate ``steps``; returns the last node's output.  The largest
    magnitude of the input of each node named in ``tapped`` is appended
    to ``taps``."""
    env: Dict[int, jnp.ndarray] = {}
    out = None
    for node, ops in steps:
        ins = [env[n] if part is None else env[n][part] for n, part in ops]
        i = index.get(node.name)
        with jax.named_scope(node.name), \
                obs.span("node", node=node.name, kind=node.kind.name):
            if node.name in tapped:
                taps.append(jnp.max(jnp.abs(ins[0] if ins else x)))
            out = env[node.node_id] = _run_node(
                node, ins, params, x, mode,
                None if i is None else scales[i])
    return out


def _program(g: Graph, mode: str, names: Tuple[str, ...]):
    """The jitted walk of ``g`` in ``mode``, reading the scale of node
    ``names[i]`` from element ``i`` of its third argument.  Its fourth and
    fifth, static, are the NHWC shape of frames passed as a flat view, or
    None for frames passed as they are, and whether the view is laid out
    by ``_planar_frames`` (as ``_planar`` decides) or by ``_nhwc``."""
    key = ("executor.program", mode, names)
    program = g.scratch().get(key)
    if program is not None:
        return program
    steps = _steps(g)
    index = {name: i for i, name in enumerate(names)}

    def execute_graph(params, x, scales, shape, planar):
        obs.count("execute.traces")
        if planar:
            reader = _frame_readers(steps)[0].name
            with jax.named_scope(reader):
                x = _planar_frames(x, shape, scales[index[reader]])
        elif shape is not None:
            x = _nhwc(x, shape)
        return _walk(steps, params, x, mode, scales, index)

    program = g.scratch()[key] = jax.jit(execute_graph, static_argnums=(3, 4))
    return program


def _frame_readers(steps):
    """The nodes that read the frames: those with no operands."""
    return [node for node, ops in steps if not ops]


def _planar(g: Graph, mode: str, names: Tuple[str, ...], shape) -> bool:
    """Whether frames of NHWC ``shape`` put as their flat view are laid out
    by ``_planar_frames``: in int8 mode, fewer than ``LANES`` frames (in
    the batch-minor order of ``_nhwc`` such a batch is what is padded to
    the lanes) of H·W a multiple of ``LANES``, and one reader of the
    frames, a CONV among ``names`` (calibrated).  The reader is looked for
    once per graph and ``names``."""
    n, h, w, _ = shape
    if mode != "int8" or n >= LANES or h * w % LANES:
        return False
    key = ("executor.planar", names)
    hit = g.scratch().get(key)
    if hit is None:
        readers = _frame_readers(_steps(g))
        hit = g.scratch()[key] = (len(readers) == 1
                                  and readers[0].kind == OpKind.CONV
                                  and readers[0].name in names)
    return hit


def _nhwc(flat, shape):
    """Frames of NHWC ``shape`` from their flat view, by way of the
    batch-minor [H, W, C, N] order in which a TPU's convs take a batch: XLA
    then re-lays the frames, already int8 in a conv's input, by one dense
    2-D transpose.  A plain reshape is laid out through [..., 3] padded to
    128 lanes instead: on a TPU v5e 1.24 ms of device time a ResNet-18 call
    of 256 frames, against 0.61 ms this way or with NHWC frames.  With one
    frame the two are the same program, and a 640 x 640 frame is re-laid
    through s8[640,640,3] padded to 128 lanes (0.57 ms of a v5e's time):
    such small batches take ``_planar_frames`` where ``_planar`` allows,
    and this route otherwise (float mode, calibration, 128 frames or
    more).  Data movement only: the values are unchanged."""
    n = shape[0]
    return jnp.moveaxis(flat.reshape(n, -1).T.reshape(*shape[1:], n), -1, 0)


def _planar_frames(flat, shape, scale) -> quant.QTensor:
    """Frames of NHWC ``shape`` from their flat view, quantised with
    ``scale`` and laid out channel planar, as the frames' reader takes
    them.  The quantisation is ``quant.quantize_act``'s, elementwise, so
    it commutes with any reordering; each row of ``LANES`` pixels, its
    channels interleaved, is then de-interleaved by an int8 product with a
    0/1 permutation matrix, int32 accumulated: one term a sum, so exact
    for every input (round, clip and cast have made every pixel, a
    non-finite one too, an int8).  The planar [N, C, H, W] result is
    handed on as NHWC, which XLA lays out as it is, no lanes padded."""
    n, h, w, c = shape
    with obs.span("quant.act"):
        q = quant.quantize_act(flat, scale)
    width = LANES * c                       # a row: LANES pixels, interleaved
    order = np.arange(width).reshape(LANES, c).T.ravel()
    perm = np.eye(width, dtype=np.int8)[:, order]   # column j takes order[j]
    rows = jax.lax.dot(q.q.reshape(-1, width), perm,
                       preferred_element_type=jnp.int32).astype(jnp.int8)
    planar = rows.reshape(n, h * w // LANES, c, LANES).transpose(0, 2, 1, 3)
    return quant.QTensor(planar.reshape(n, c, h, w).transpose(0, 2, 3, 1),
                         q.scale)


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
    meta = node.meta
    if node.kind == OpKind.CONV:
        inp = ins[0] if ins else x
        p = _param_at(params, meta["param"])
        groups = meta.get("groups", 1)
        if mode == "int8":
            y = quant.quantized_conv2d(
                inp, p["w"], p["b"], stride=meta["stride"],
                padding=meta["padding"], x_scale=x_scale,
                feature_group_count=groups)
            return L.activate(y, meta.get("act"))
        return L.conv2d(p, inp, stride=meta["stride"],
                        padding=meta["padding"], act=meta.get("act"),
                        groups=groups)
    if node.kind == OpKind.PRODUCT:         # no learned weights: float32
        obs.count("execute.act_products")
        return jnp.einsum(meta["einsum"], *ins,
                          precision=jax.lax.Precision.HIGHEST)
    if node.kind == OpKind.MVM:
        if meta.get("param") is None:       # fixed weights, float32 in any mode
            return jnp.matmul(ins[0], jnp.asarray(meta["weights"], jnp.float32),
                              precision=jax.lax.Precision.HIGHEST)
        p = _param_at(params, meta["param"])
        if mode == "int8":
            return quant.quantized_matmul(ins[0], p["w"], p["b"])
        return L.dense(p, ins[0])
    if node.kind == OpKind.ADD:
        # ``const`` (if any) plus each operand times its sign, then ``act``
        out = np.asarray(meta["const"], np.float32) if "const" in meta else None
        for sign, t in zip(meta.get("signs", [1] * len(ins)), ins):
            if out is None:
                out = t if sign > 0 else -t
            else:
                out = out + t if sign > 0 else out - t
        return L.activate(out, meta.get("act"))
    if node.kind == OpKind.MUL:
        if "const" in meta:
            return ins[0] * np.asarray(meta["const"], np.float32)
        return ins[0] * ins[1]
    if node.kind == OpKind.ACT:
        return L.activate(ins[0], meta["act"])
    if node.kind == OpKind.CONCAT:
        return jnp.concatenate(ins, axis=meta["axis"])
    if node.kind == OpKind.SPLIT:
        return tuple(jax.lax.slice_in_dim(ins[0], a, b, axis=meta["axis"])
                     for a, b in meta["sections"])
    if node.kind == OpKind.POOL_MAX:
        return L.max_pool(ins[0], meta["size"], stride=meta["stride"],
                          padding=meta["padding"])
    if node.kind == OpKind.UPSAMPLE:
        return L.upsample_nearest(ins[0], meta["factor"])
    if node.kind == OpKind.RESHAPE:
        return ins[0].reshape((ins[0].shape[0], *meta["shape"]))
    if node.kind == OpKind.SOFTMAX:
        return L.softmax(ins[0], axis=meta["axis"])
    if node.kind == OpKind.GLOBAL_POOL:
        return L.global_avg_pool(ins[0])
    if node.kind == OpKind.INPUT:
        return x
    if node.kind == OpKind.OUTPUT:
        return ins[0]
    raise NotImplementedError(
        f"executor does not implement {node.kind} (node {node.name}); "
        "the kinds it runs are listed in the module docstring")
