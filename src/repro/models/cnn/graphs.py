"""Deployment-graph builders for the paper's CNN workloads.

Each builder mirrors the corresponding executable model one-to-one and
emits a ``repro.core.Graph`` whose nodes carry:

* scheduling cost metadata (flops, weight_bytes, out_bytes/elems, IMC
  tiling meta) consumed by ``repro.core.cost.CostModel``;
* execution metadata consumed by ``repro.models.cnn.executor``, so that a
  scheduled graph remains a *runnable program*, not just a cost table:
  every node's ordered operands (``meta["inputs"]``), each conv and dense
  node's ``meta["param"]`` path into the model's parameter pytree, and
  the attributes of each kind (see the YOLOv8n section below).

``meta["inputs"]`` lists pairs of (producer's name, part): a SPLIT yields
a tuple of slices and ``part`` picks one, for any other producer it is
None.  A node with no operands (``[]``) reads the frames.  The graph keeps
one edge per producer, so which slice of a split a node takes is told
only here.

Node numbering is topological and matches the paper's Table I ids for
ResNet18-CIFAR (verified in tests/test_cnn_models.py).
"""

from __future__ import annotations

from typing import List, Optional, Tuple, Union

from repro.core.graph import Graph, OpKind

from . import layers as L
from .resnet import RESNET8, RESNET18_CIFAR
from .yolo import CH, NC, REG_MAX, STRIDES, YOLOV8N, autopad


Ref = Union[int, Tuple[int, int]]       # node id, or (SPLIT id, part)


def _operands(g: Graph, refs: List[Ref]) -> Tuple[List[int], list]:
    """The deps (one per producer, in order) and ``meta["inputs"]`` of a
    node reading ``refs``."""
    pairs = [r if isinstance(r, tuple) else (r, None) for r in refs]
    return ([nid for nid, _ in pairs],
            [(g.nodes[nid].name, part) for nid, part in pairs])


def _add_conv(g: Graph, name: str, refs: List[Ref], h: int, w: int, k: int,
              cin: int, cout: int, stride: int, act: Optional[str],
              param: tuple, padding="SAME") -> Tuple[int, int, int]:
    cost = L.conv_cost(h, w, k, cin, cout, stride, padding)
    meta = dict(cost.pop("meta"))
    meta.update(param=param, stride=stride, act=act, padding=padding, k=k)
    deps, meta["inputs"] = _operands(g, refs)
    n = g.add(name, OpKind.CONV, deps=deps, fused_act=act, meta=meta, **cost)
    ho, wo = meta["out_hw"]
    return n.node_id, ho, wo


def build_resnet_graph(cfg: dict) -> Graph:
    """Deployment DAG for either ResNet variant (no INPUT/OUTPUT glue —
    the paper's node counts include compute nodes only)."""
    g = Graph(cfg["name"])
    h, w = cfg["image_hw"]
    cin = 3

    nid, h, w = _add_conv(g, "stem", [], h, w, 3, cin, cfg["stem_width"], 1,
                          "relu", ("stem",))
    cin = cfg["stem_width"]
    prev = nid

    for si, (width, nblocks) in enumerate(
        zip(cfg["stage_widths"], cfg["blocks_per_stage"])
    ):
        for bi in range(nblocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            needs_down = stride != 1 or cin != width
            identity = prev
            c1, h1, w1 = _add_conv(
                g, f"s{si}b{bi}.conv1", [prev], h, w, 3, cin, width, stride,
                "relu", ("stages", si, bi, "conv1"))
            c2, h2, w2 = _add_conv(
                g, f"s{si}b{bi}.conv2", [c1], h1, w1, 3, width, width, 1,
                None, ("stages", si, bi, "conv2"))
            if needs_down:
                identity, _, _ = _add_conv(
                    g, f"s{si}b{bi}.down", [identity], h, w, 1, cin, width,
                    stride, None, ("stages", si, bi, "down"))
            cost = L.elem_cost(h2 * w2 * width)
            meta = dict(cost.pop("meta"))
            deps, inputs = _operands(g, [c2, identity])
            meta.update(act="relu", inputs=inputs)
            add = g.add(f"s{si}b{bi}.add", OpKind.ADD, deps=deps,
                        fused_act="relu", meta=meta, **cost)
            prev, h, w, cin = add.node_id, h2, w2, width

    cost = L.elem_cost(cin)
    cost.pop("meta")
    deps, inputs = _operands(g, [prev])
    gap = g.add("gap", OpKind.GLOBAL_POOL, deps=deps,
                meta={"inputs": inputs}, **cost)
    fc_cost = L.dense_cost(cin, cfg["num_classes"])
    meta = dict(fc_cost.pop("meta"))
    deps, inputs = _operands(g, [gap.node_id])
    meta.update(param=("fc",), inputs=inputs)
    g.add("fc", OpKind.MVM, deps=deps, meta=meta, **fc_cost)
    g.validate()
    return g


def resnet8_graph() -> Graph:
    return build_resnet_graph(RESNET8)


def resnet18_graph() -> Graph:
    return build_resnet_graph(RESNET18_CIFAR)


#: Table I (paper): the 21 MVM/conv node ids of ResNet18-CIFAR.
TABLE1_IMC_NODE_IDS = frozenset(
    {1, 2, 3, 5, 6, 8, 9, 10, 12, 13, 15, 16, 17, 19, 20, 22, 23, 24, 26, 27, 30}
)


# ===========================================================================
# YOLOv8n — ONNX-granularity deployment graph (paper §V.C: 233 nodes,
# 63 convolutional, 57 followed by SiLU).
#
# At ONNX level a "Conv" ultralytics module is Conv + Sigmoid + Mul (SiLU
# is NOT fused in the exported graph the paper deploys — that is what
# makes the count 233); the DFL expectation is a fixed-weight 1x1 conv,
# modelled as an MVM node (the paper counts 63 *convolutional* nodes,
# excluding it).  The three detection scales are the paper's "3 parallel
# main branches".
#
# Execution metadata.  Each node lists its operands in ``meta["inputs"]``
# (module docstring): which half of a C2f split its concat and its first
# bottleneck take is told there.  Layout is NHWC, and (batch, anchors,
# ...) after the head's reshapes; every ``axis`` counts the batch.  Per
# kind:
#
#   CONV      param (path into ``yolo.init``'s pytree), stride, padding
#             (explicit, ``yolo.autopad``: k // 2 on every side)
#   ACT       act ("sigmoid")
#   MUL       the product of two operands, or of one and ``const``
#   ADD       ``const`` (if any) plus each operand times its ``signs``
#             (default all +1, as the residual adds)
#   SPLIT     axis, sections ([start, stop) of each slice)
#   CONCAT    axis
#   POOL_MAX  size, stride, padding
#   UPSAMPLE  factor (nearest neighbour)
#   RESHAPE   shape (without the batch)
#   SOFTMAX   axis
#   MVM       weights: dfl.conv's fixed bins 0..15, a constant (no param)
#
# ``const`` is a number, or nested lists that broadcast against the
# operands: the decode's anchor centres (anchors, 2) and strides
# (anchors, 1), computed here.
# ===========================================================================

class _Emit:
    """Stateful helper emitting ONNX-level nodes with cost and execution
    metadata; operands are ``Ref``s."""

    def __init__(self, g: Graph):
        self.g = g

    def conv(self, name, path, src: Optional[Ref], h, w, k, cin, cout,
             stride=1):
        """A plain conv reading ``src`` (the frames where None)."""
        pad = [list(side) for side in autopad(k)]
        return _add_conv(self.g, name, [] if src is None else [src], h, w, k,
                         cin, cout, stride, None, param=path, padding=pad)

    def conv_module(self, name, path, src, h, w, k, cin, cout, stride=1):
        """Conv + Sigmoid + Mul (SiLU) -> returns (mul_id, ho, wo)."""
        cid, ho, wo = self.conv(f"{name}.conv", path, src, h, w, k, cin,
                                cout, stride)
        n_el = ho * wo * cout
        sig = self.node(f"{name}.sigmoid", OpKind.ACT, [cid], n_el,
                        act="sigmoid")
        mul = self.node(f"{name}.mul", OpKind.MUL, [cid, sig], n_el)
        return mul, ho, wo

    def node(self, name, kind, refs: List[Ref], n_elems, **meta):
        cost = L.elem_cost(n_elems)
        cost.pop("meta")
        deps, meta["inputs"] = _operands(self.g, refs)
        return self.g.add(name, kind, deps=deps, meta=meta, **cost).node_id

    def c2f(self, name, src, h, w, cin, cout, n, shortcut):
        c = cout // 2
        cv1, h, w = self.conv_module(f"{name}.cv1", (name, "cv1"), src, h, w,
                                     1, cin, cout)
        split = self.node(f"{name}.split", OpKind.SPLIT, [cv1], h * w * cout,
                          axis=-1, sections=[[0, c], [c, cout]])
        chunks: List[Ref] = [(split, 0), (split, 1)]
        prev: Ref = (split, 1)
        for i in range(n):
            m1, _, _ = self.conv_module(f"{name}.m{i}.cv1",
                                        (name, "m", i, "cv1"), prev, h, w,
                                        3, c, c)
            m2, _, _ = self.conv_module(f"{name}.m{i}.cv2",
                                        (name, "m", i, "cv2"), m1, h, w,
                                        3, c, c)
            if shortcut:
                prev = self.node(f"{name}.m{i}.add", OpKind.ADD, [prev, m2],
                                 h * w * c)
            else:
                prev = m2
            chunks.append(prev)
        cat = self.node(f"{name}.concat", OpKind.CONCAT, chunks,
                        h * w * (2 + n) * c, axis=-1)
        return self.conv_module(f"{name}.cv2", (name, "cv2"), cat, h, w, 1,
                                (2 + n) * c, cout)

    def sppf(self, name, src, h, w, c):
        cv1, h, w = self.conv_module(f"{name}.cv1", (name, "cv1"), src, h, w,
                                     1, c, c // 2)
        n_el = h * w * (c // 2)
        pool = dict(size=5, stride=1, padding="SAME")
        p1 = self.node(f"{name}.pool1", OpKind.POOL_MAX, [cv1], n_el, **pool)
        p2 = self.node(f"{name}.pool2", OpKind.POOL_MAX, [p1], n_el, **pool)
        p3 = self.node(f"{name}.pool3", OpKind.POOL_MAX, [p2], n_el, **pool)
        cat = self.node(f"{name}.concat", OpKind.CONCAT, [cv1, p1, p2, p3],
                        h * w * 2 * c, axis=-1)
        return self.conv_module(f"{name}.cv2", (name, "cv2"), cat, h, w, 1,
                                2 * c, c)


def build_yolov8n_graph(cfg: dict = YOLOV8N) -> Graph:
    """The deployment DAG of YOLOv8n for frames of ``cfg["image_hw"]``,
    each side a multiple of the largest stride, 32."""
    h, w = cfg["image_hw"]
    if h % STRIDES[-1] or w % STRIDES[-1]:
        raise ValueError(f"image_hw {cfg['image_hw']} is not a multiple of "
                         f"{STRIDES[-1]}")
    g = Graph(cfg["name"])
    e = _Emit(g)

    # ---- backbone -------------------------------------------------------
    b0, h, w = e.conv_module("b0", ("b0",), None, h, w, 3, 3, CH["p1"], 2)
    b1, h, w = e.conv_module("b1", ("b1",), b0, h, w, 3, CH["p1"], CH["p2"], 2)
    b2, h, w = e.c2f("b2", b1, h, w, CH["p2"], CH["p2"], 1, True)
    b3, h, w = e.conv_module("b3", ("b3",), b2, h, w, 3, CH["p2"], CH["p3"], 2)
    p3, h3, w3 = e.c2f("b4", b3, h, w, CH["p3"], CH["p3"], 2, True)
    b5, h, w = e.conv_module("b5", ("b5",), p3, h3, w3, 3, CH["p3"], CH["p4"],
                             2)
    p4, h4, w4 = e.c2f("b6", b5, h, w, CH["p4"], CH["p4"], 2, True)
    b7, h, w = e.conv_module("b7", ("b7",), p4, h4, w4, 3, CH["p4"], CH["p5"],
                             2)
    b8, h, w = e.c2f("b8", b7, h, w, CH["p5"], CH["p5"], 1, True)
    p5, h5, w5 = e.sppf("b9", b8, h, w, CH["p5"])

    # ---- neck (PAN) ------------------------------------------------------
    u1 = e.node("n10.upsample", OpKind.UPSAMPLE, [p5], h4 * w4 * CH["p5"],
                factor=2)
    c1 = e.node("n11.concat", OpKind.CONCAT, [u1, p4],
                h4 * w4 * (CH["p4"] + CH["p5"]), axis=-1)
    n12, _, _ = e.c2f("n12", c1, h4, w4, CH["p4"] + CH["p5"], CH["p4"], 1, False)
    u2 = e.node("n13.upsample", OpKind.UPSAMPLE, [n12], h3 * w3 * CH["p4"],
                factor=2)
    c2 = e.node("n14.concat", OpKind.CONCAT, [u2, p3],
                h3 * w3 * (CH["p3"] + CH["p4"]), axis=-1)
    n15, _, _ = e.c2f("n15", c2, h3, w3, CH["p3"] + CH["p4"], CH["p3"], 1, False)
    n16, _, _ = e.conv_module("n16", ("n16",), n15, h3, w3, 3, CH["p3"],
                              CH["p3"], 2)
    c3 = e.node("n17.concat", OpKind.CONCAT, [n16, n12],
                h4 * w4 * (CH["p3"] + CH["p4"]), axis=-1)
    n18, _, _ = e.c2f("n18", c3, h4, w4, CH["p3"] + CH["p4"], CH["p4"], 1, False)
    n19, _, _ = e.conv_module("n19", ("n19",), n18, h4, w4, 3, CH["p4"],
                              CH["p4"], 2)
    c4 = e.node("n20.concat", OpKind.CONCAT, [n19, p5],
                h5 * w5 * (CH["p4"] + CH["p5"]), axis=-1)
    n21, _, _ = e.c2f("n21", c4, h5, w5, CH["p4"] + CH["p5"], CH["p5"], 1, False)

    # ---- detect head: 3 scales, box (cv2) + cls (cv3) branches -----------
    feats = [(n15, h3, w3, CH["p3"]), (n18, h4, w4, CH["p4"]),
             (n21, h5, w5, CH["p5"])]
    c2_, c3_ = max(16, CH["p3"] // 4, 4 * REG_MAX), max(CH["p3"], min(NC, 100))
    no = 4 * REG_MAX + NC
    scale_outs = []
    for i, (f, fh, fw, fc) in enumerate(feats):
        branches = []
        for cv, width, cout in (("cv2", c2_, 4 * REG_MAX), ("cv3", c3_, NC)):
            name = f"head.{cv}.{i}"
            x, _, _ = e.conv_module(f"{name}.0", ("head", cv, i, "0"), f, fh,
                                    fw, 3, fc, width)
            x, _, _ = e.conv_module(f"{name}.1", ("head", cv, i, "1"), x, fh,
                                    fw, 3, width, width)
            x, _, _ = e.conv(f"{name}.2", ("head", cv, i, "2"), x, fh, fw, 1,
                             width, cout)
            branches.append(x)
        n_el = fh * fw * no
        cat = e.node(f"head.concat.{i}", OpKind.CONCAT, branches, n_el,
                     axis=-1)
        rs = e.node(f"head.reshape.{i}", OpKind.RESHAPE, [cat], n_el,
                    shape=[fh * fw, no])
        scale_outs.append((rs, fh * fw))

    anchors = sum(a for _, a in scale_outs)          # 8400 at 640x640
    zcat = e.node("head.concat_scales", OpKind.CONCAT,
                  [nid for nid, _ in scale_outs], anchors * no, axis=1)
    spl = e.node("head.split_box_cls", OpKind.SPLIT, [zcat], anchors * no,
                 axis=-1, sections=[[0, 4 * REG_MAX], [4 * REG_MAX, no]])

    # DFL: Reshape -> Transpose -> Softmax -> Conv(1x1 fixed) -> Reshape.
    # ONNX's Transpose brings the bins to the softmax's axis; channels-last,
    # they are there already, so it is a reshape to the same shape here.
    dfl_el = anchors * 4 * REG_MAX
    bins = [anchors, 4, REG_MAX]
    d1 = e.node("dfl.reshape1", OpKind.RESHAPE, [(spl, 0)], dfl_el, shape=bins)
    d2 = e.node("dfl.transpose", OpKind.RESHAPE, [d1], dfl_el, shape=bins)
    d3 = e.node("dfl.softmax", OpKind.SOFTMAX, [d2], dfl_el, axis=-1)
    dfl_cost = L.dense_cost(REG_MAX, 1)
    dfl_meta = dict(dfl_cost.pop("meta"))
    deps, inputs = _operands(g, [d3])
    dfl_meta.update(param=None, n_vectors=anchors * 4, inputs=inputs,
                    weights=[[float(i)] for i in range(REG_MAX)])
    dfl_cost["flops"] = 2.0 * dfl_el
    dfl_cost["out_bytes"] = dfl_cost["out_elems"] = float(anchors * 4)
    d4 = g.add("dfl.conv", OpKind.MVM, deps=deps, meta=dfl_meta,
               **dfl_cost).node_id
    d5 = e.node("dfl.reshape2", OpKind.RESHAPE, [d4], anchors * 4,
                shape=[anchors, 4])

    # dist2bbox: slices, subs/adds, concat, stride mul.  Constants: each
    # anchor's centre in grid cells, row by row of each scale, and its stride
    centres, strides = [], []
    for (_, fh, fw, _), s in zip(feats, STRIDES):
        centres += [[x + 0.5, y + 0.5] for y in range(fh) for x in range(fw)]
        strides += [[float(s)] for _ in range(fh * fw)]
    half = anchors * 2
    lt = e.node("box.slice_lt", OpKind.SPLIT, [d5], half, axis=-1,
                sections=[[0, 2]])
    rb = e.node("box.slice_rb", OpKind.SPLIT, [d5], half, axis=-1,
                sections=[[2, 4]])
    x1y1 = e.node("box.sub_x1y1", OpKind.ADD, [(lt, 0)], half, signs=[-1],
                  const=centres)
    x2y2 = e.node("box.add_x2y2", OpKind.ADD, [(rb, 0)], half, signs=[1],
                  const=centres)
    csum = e.node("box.add_center", OpKind.ADD, [x1y1, x2y2], half,
                  signs=[1, 1])
    cdiv = e.node("box.div_center", OpKind.MUL, [csum], half, const=0.5)
    wh = e.node("box.sub_wh", OpKind.ADD, [x1y1, x2y2], half, signs=[-1, 1])
    bcat = e.node("box.concat_xywh", OpKind.CONCAT, [cdiv, wh], anchors * 4,
                  axis=-1)
    bmul = e.node("box.mul_strides", OpKind.MUL, [bcat], anchors * 4,
                  const=strides)
    csig = e.node("cls.sigmoid", OpKind.ACT, [(spl, 1)], anchors * NC,
                  act="sigmoid")
    e.node("out.concat", OpKind.CONCAT, [bmul, csig], anchors * (4 + NC),
           axis=-1)

    g.validate()
    return g


def yolov8n_graph() -> Graph:
    return build_yolov8n_graph()
