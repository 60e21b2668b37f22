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
from .yolo11 import C3K2, HEAD_DIM, KEY_DIM, YOLO11N


Ref = Union[int, Tuple[int, int]]       # node id, or (SPLIT id, part)


def _operands(g: Graph, refs: List[Ref]) -> Tuple[List[int], list]:
    """The deps (one per producer, in order) and ``meta["inputs"]`` of a
    node reading ``refs``."""
    pairs = [r if isinstance(r, tuple) else (r, None) for r in refs]
    return ([nid for nid, _ in pairs],
            [(g.nodes[nid].name, part) for nid, part in pairs])


def _add_conv(g: Graph, name: str, refs: List[Ref], h: int, w: int, k: int,
              cin: int, cout: int, stride: int, act: Optional[str],
              param: tuple, padding="SAME", groups=1) -> Tuple[int, int, int]:
    cost = L.conv_cost(h, w, k, cin, cout, stride, padding, groups)
    meta = dict(cost.pop("meta"))
    meta.update(param=param, stride=stride, act=act, padding=padding, k=k)
    if groups != 1:
        meta["groups"] = groups
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
#             (explicit, ``yolo.autopad``: k // 2 on every side), groups
#             (absent where 1; a depthwise conv's equals its channels)
#   PRODUCT   einsum: the contraction of its two operands (YOLO11n's
#             attention), float32 in every mode
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
             stride=1, groups=1):
        """A plain conv reading ``src`` (the frames where None)."""
        pad = [list(side) for side in autopad(k)]
        return _add_conv(self.g, name, [] if src is None else [src], h, w, k,
                         cin, cout, stride, None, param=path, padding=pad,
                         groups=groups)

    def conv_module(self, name, path, src, h, w, k, cin, cout, stride=1,
                    groups=1):
        """Conv + Sigmoid + Mul (SiLU) -> returns (mul_id, ho, wo)."""
        cid, ho, wo = self.conv(f"{name}.conv", path, src, h, w, k, cin,
                                cout, stride, groups)
        n_el = ho * wo * cout
        sig = self.node(f"{name}.sigmoid", OpKind.ACT, [cid], n_el,
                        act="sigmoid")
        mul = self.node(f"{name}.mul", OpKind.MUL, [cid, sig], n_el)
        return mul, ho, wo

    def dwconv(self, name, path, src, h, w, c, k=3):
        """DWConv: a depthwise conv module (one group per channel)."""
        return self.conv_module(name, path, src, h, w, k, c, c, groups=c)[0]

    def node(self, name, kind, refs: List[Ref], n_elems, **meta):
        cost = L.elem_cost(n_elems)
        cost.pop("meta")
        deps, meta["inputs"] = _operands(self.g, refs)
        return self.g.add(name, kind, deps=deps, meta=meta, **cost).node_id

    def product(self, name, refs: List[Ref], einsum, macs, n_elems):
        """The contraction ``einsum`` of two activations: ``macs`` of MAC
        work, no stationary weights."""
        cost = L.elem_cost(n_elems)
        cost.pop("meta")
        cost["flops"] = 2.0 * macs
        deps, inputs = _operands(self.g, refs)
        return self.g.add(name, OpKind.PRODUCT, deps=deps,
                          meta={"einsum": einsum, "inputs": inputs},
                          **cost).node_id

    def bottleneck(self, name, path, src, h, w, c, hidden, shortcut=True):
        """Two 3x3 conv modules (c -> hidden -> c), then the residual add
        where ``shortcut``."""
        m1, _, _ = self.conv_module(f"{name}.cv1", path + ("cv1",), src, h, w,
                                    3, c, hidden)
        m2, _, _ = self.conv_module(f"{name}.cv2", path + ("cv2",), m1, h, w,
                                    3, hidden, c)
        if not shortcut:
            return m2
        return self.node(f"{name}.add", OpKind.ADD, [src, m2], h * w * c)

    def c2f(self, name, src, h, w, cin, cout, n, shortcut, c=None,
            block=None):
        """cv1 to 2c, split in halves, ``n`` blocks on the second, concat,
        cv2.  ``c`` defaults to cout // 2 and ``block(name, path, ref, c)``
        to YOLOv8's bottleneck of two c-channel convs."""
        c = c or cout // 2
        if block is None:
            def block(bname, path, ref, c):
                return self.bottleneck(bname, path, ref, h, w, c, c, shortcut)
        cv1, h, w = self.conv_module(f"{name}.cv1", (name, "cv1"), src, h, w,
                                     1, cin, 2 * c)
        split = self.node(f"{name}.split", OpKind.SPLIT, [cv1],
                          h * w * 2 * c, axis=-1, sections=[[0, c], [c, 2 * c]])
        chunks: List[Ref] = [(split, 0), (split, 1)]
        for i in range(n):
            chunks.append(block(f"{name}.m{i}", (name, "m", i), chunks[-1], c))
        cat = self.node(f"{name}.concat", OpKind.CONCAT, chunks,
                        h * w * (2 + n) * c, axis=-1)
        return self.conv_module(f"{name}.cv2", (name, "cv2"), cat, h, w, 1,
                                (2 + n) * c, cout)

    def c3k(self, name, path, src, h, w, c):
        """C3k: two 1x1 branches of c // 2, two bottlenecks at e=1.0 on the
        first, concat, cv3."""
        half = c // 2
        a, _, _ = self.conv_module(f"{name}.cv1", path + ("cv1",), src, h, w,
                                   1, c, half)
        for i in range(2):
            a = self.bottleneck(f"{name}.m{i}", path + ("m", i), a, h, w,
                                half, half)
        b, _, _ = self.conv_module(f"{name}.cv2", path + ("cv2",), src, h, w,
                                   1, c, half)
        cat = self.node(f"{name}.concat", OpKind.CONCAT, [a, b],
                        h * w * 2 * half, axis=-1)
        return self.conv_module(f"{name}.cv3", path + ("cv3",), cat, h, w, 1,
                                2 * half, c)[0]

    def c3k2(self, name, src, h, w, cin, cout):
        """C3k2 (shortcut on): C2f of width ``cout * e`` whose block is a
        C3k or a bottleneck of half its width (``yolo11.C3K2``)."""
        e, use_c3k = C3K2[name]

        def block(bname, path, ref, c):
            if use_c3k:
                return self.c3k(bname, path, ref, h, w, c)
            return self.bottleneck(bname, path, ref, h, w, c, c // 2)

        return self.c2f(name, src, h, w, cin, cout, 1, True, int(cout * e),
                        block)

    def c2psa(self, name, src, h, w, c):
        """C2PSA: cv1, split in halves, a PSABlock on the second, concat,
        cv2."""
        half = c // 2
        cv1, _, _ = self.conv_module(f"{name}.cv1", (name, "cv1"), src, h, w,
                                     1, c, c)
        split = self.node(f"{name}.split", OpKind.SPLIT, [cv1], h * w * c,
                          axis=-1, sections=[[0, half], [half, c]])
        y = self.psa(f"{name}.m0", (name, "m", 0), (split, 1), h, w, half)
        cat = self.node(f"{name}.concat", OpKind.CONCAT, [(split, 0), y],
                        h * w * c, axis=-1)
        return self.conv_module(f"{name}.cv2", (name, "cv2"), cat, h, w, 1,
                                c, c)

    def psa(self, name, path, src, h, w, c):
        """PSABlock: attention and a 1x1 FFN of width 2c, each with its
        residual.  The attention: qkv conv, reshape to (tokens, heads,
        2 key + value widths), split into q, k, v, q·kᵀ, scale, softmax
        over keys, v·attnᵀ, reshape back, plus the depthwise ``pe`` conv
        on v, then ``proj``."""
        n, heads = h * w, c // HEAD_DIM
        width = 2 * KEY_DIM + HEAD_DIM
        at, ap = f"{name}.attn", path + ("attn",)
        qkv, _, _ = self.conv(f"{at}.qkv", ap + ("qkv",), src, h, w, 1, c,
                              heads * width)
        rs = self.node(f"{at}.reshape", OpKind.RESHAPE, [qkv], n * heads * width,
                       shape=[n, heads, width])
        qkv = self.node(f"{at}.split", OpKind.SPLIT, [rs], n * heads * width,
                        axis=-1, sections=[[0, KEY_DIM], [KEY_DIM, 2 * KEY_DIM],
                                           [2 * KEY_DIM, width]])
        scores = heads * n * n
        qk = self.product(f"{at}.qk", [(qkv, 0), (qkv, 1)], "bnhd,bmhd->bhnm",
                          scores * KEY_DIM, scores)
        scaled = self.node(f"{at}.scale", OpKind.MUL, [qk], scores,
                           const=KEY_DIM ** -0.5)
        attn = self.node(f"{at}.softmax", OpKind.SOFTMAX, [scaled], scores,
                         axis=-1)
        av = self.product(f"{at}.av", [(qkv, 2), attn], "bmhd,bhnm->bnhd",
                          scores * HEAD_DIM, n * c)
        out = self.node(f"{at}.reshape_out", OpKind.RESHAPE, [av], n * c,
                        shape=[h, w, c])
        v = self.node(f"{at}.reshape_v", OpKind.RESHAPE, [(qkv, 2)], n * c,
                      shape=[h, w, c])
        pe, _, _ = self.conv(f"{at}.pe", ap + ("pe",), v, h, w, 3, c, c,
                             groups=c)
        add = self.node(f"{at}.add_pe", OpKind.ADD, [out, pe], n * c)
        proj, _, _ = self.conv(f"{at}.proj", ap + ("proj",), add, h, w, 1, c, c)
        y = self.node(f"{name}.add_attn", OpKind.ADD, [src, proj], n * c)
        f0, _, _ = self.conv_module(f"{name}.ffn.0", path + ("ffn", 0), y, h,
                                    w, 1, c, 2 * c)
        f1, _, _ = self.conv(f"{name}.ffn.1", path + ("ffn", 1), f0, h, w, 1,
                             2 * c, c)
        return self.node(f"{name}.add_ffn", OpKind.ADD, [y, f1], n * c)

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

    def branch(self, name, path, src, h, w, cin, width, cout):
        """Two 3x3 conv modules to ``width``, then a plain 1x1 conv."""
        x, _, _ = self.conv_module(f"{name}.0", path + ("0",), src, h, w, 3,
                                   cin, width)
        x, _, _ = self.conv_module(f"{name}.1", path + ("1",), x, h, w, 3,
                                   width, width)
        return self.conv(f"{name}.2", path + ("2",), x, h, w, 1, width,
                         cout)[0]

    def detect(self, feats, branches):
        """Detect on ``feats`` [(ref, h, w, channels)] at ``STRIDES``:
        ``branches(i, ref, h, w, channels)`` emits scale i's box and class
        branches and returns their refs, which are concatenated and
        reshaped to (h·w, 4·REG_MAX + NC); then the decode: DFL, dist2bbox,
        the class sigmoid and the output concat (anchors, 4 + NC)."""
        g = self.g
        no = 4 * REG_MAX + NC
        scale_outs = []
        for i, (f, fh, fw, fc) in enumerate(feats):
            n_el = fh * fw * no
            cat = self.node(f"head.concat.{i}", OpKind.CONCAT,
                            branches(i, f, fh, fw, fc), n_el, axis=-1)
            rs = self.node(f"head.reshape.{i}", OpKind.RESHAPE, [cat], n_el,
                           shape=[fh * fw, no])
            scale_outs.append((rs, fh * fw))

        anchors = sum(a for _, a in scale_outs)          # 8400 at 640x640
        zcat = self.node("head.concat_scales", OpKind.CONCAT,
                         [nid for nid, _ in scale_outs], anchors * no, axis=1)
        spl = self.node("head.split_box_cls", OpKind.SPLIT, [zcat],
                        anchors * no, axis=-1,
                        sections=[[0, 4 * REG_MAX], [4 * REG_MAX, no]])

        # DFL: Reshape -> Transpose -> Softmax -> Conv(1x1 fixed) -> Reshape.
        # ONNX's Transpose brings the bins to the softmax's axis; channels-
        # last, they are there already, so it is a reshape to the same shape.
        dfl_el = anchors * 4 * REG_MAX
        bins = [anchors, 4, REG_MAX]
        d1 = self.node("dfl.reshape1", OpKind.RESHAPE, [(spl, 0)], dfl_el,
                       shape=bins)
        d2 = self.node("dfl.transpose", OpKind.RESHAPE, [d1], dfl_el,
                       shape=bins)
        d3 = self.node("dfl.softmax", OpKind.SOFTMAX, [d2], dfl_el, axis=-1)
        dfl_cost = L.dense_cost(REG_MAX, 1)
        dfl_meta = dict(dfl_cost.pop("meta"))
        deps, inputs = _operands(g, [d3])
        dfl_meta.update(param=None, n_vectors=anchors * 4, inputs=inputs,
                        weights=[[float(i)] for i in range(REG_MAX)])
        dfl_cost["flops"] = 2.0 * dfl_el
        dfl_cost["out_bytes"] = dfl_cost["out_elems"] = float(anchors * 4)
        d4 = g.add("dfl.conv", OpKind.MVM, deps=deps, meta=dfl_meta,
                   **dfl_cost).node_id
        d5 = self.node("dfl.reshape2", OpKind.RESHAPE, [d4], anchors * 4,
                       shape=[anchors, 4])

        # dist2bbox: slices, subs/adds, concat, stride mul.  Constants: each
        # anchor's centre in grid cells, row by row of each scale, and its
        # stride
        centres, strides = [], []
        for (_, fh, fw, _), s in zip(feats, STRIDES):
            centres += [[x + 0.5, y + 0.5] for y in range(fh) for x in range(fw)]
            strides += [[float(s)] for _ in range(fh * fw)]
        half = anchors * 2
        lt = self.node("box.slice_lt", OpKind.SPLIT, [d5], half, axis=-1,
                       sections=[[0, 2]])
        rb = self.node("box.slice_rb", OpKind.SPLIT, [d5], half, axis=-1,
                       sections=[[2, 4]])
        x1y1 = self.node("box.sub_x1y1", OpKind.ADD, [(lt, 0)], half,
                         signs=[-1], const=centres)
        x2y2 = self.node("box.add_x2y2", OpKind.ADD, [(rb, 0)], half,
                         signs=[1], const=centres)
        csum = self.node("box.add_center", OpKind.ADD, [x1y1, x2y2], half,
                         signs=[1, 1])
        cdiv = self.node("box.div_center", OpKind.MUL, [csum], half, const=0.5)
        wh = self.node("box.sub_wh", OpKind.ADD, [x1y1, x2y2], half,
                       signs=[-1, 1])
        bcat = self.node("box.concat_xywh", OpKind.CONCAT, [cdiv, wh],
                         anchors * 4, axis=-1)
        bmul = self.node("box.mul_strides", OpKind.MUL, [bcat], anchors * 4,
                         const=strides)
        csig = self.node("cls.sigmoid", OpKind.ACT, [(spl, 1)], anchors * NC,
                         act="sigmoid")
        self.node("out.concat", OpKind.CONCAT, [bmul, csig],
                  anchors * (4 + NC), axis=-1)


def _detector_hw(cfg: dict) -> Tuple[int, int]:
    """``cfg["image_hw"]``, each side a multiple of the largest stride."""
    h, w = cfg["image_hw"]
    if h % STRIDES[-1] or w % STRIDES[-1]:
        raise ValueError(f"image_hw {cfg['image_hw']} is not a multiple of "
                         f"{STRIDES[-1]}")
    return h, w


def build_yolov8n_graph(cfg: dict = YOLOV8N) -> Graph:
    """The deployment DAG of YOLOv8n for frames of ``cfg["image_hw"]``,
    each side a multiple of the largest stride, 32."""
    h, w = _detector_hw(cfg)
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

    def branches(i, f, fh, fw, fc):
        return [e.branch(f"head.{cv}.{i}", ("head", cv, i), f, fh, fw, fc,
                         width, cout)
                for cv, width, cout in (("cv2", c2_, 4 * REG_MAX),
                                        ("cv3", c3_, NC))]

    e.detect(feats, branches)
    g.validate()
    return g


def yolov8n_graph() -> Graph:
    return build_yolov8n_graph()


def build_yolo11n_graph(cfg: dict = YOLO11N) -> Graph:
    """The deployment DAG of YOLO11n (``yolo11``, node for node) for frames
    of ``cfg["image_hw"]``, each side a multiple of the largest stride,
    32.  Node names follow the yaml's layer indices."""
    h, w = _detector_hw(cfg)
    g = Graph(cfg["name"])
    e = _Emit(g)
    p1, p2, p3, p4, p5 = (CH[k] for k in ("p1", "p2", "p3", "p4", "p5"))

    # ---- backbone -------------------------------------------------------
    b0, h, w = e.conv_module("b0", ("b0",), None, h, w, 3, 3, p1, 2)
    b1, h, w = e.conv_module("b1", ("b1",), b0, h, w, 3, p1, p2, 2)
    b2, h, w = e.c3k2("b2", b1, h, w, p2, p3)
    b3, h3, w3 = e.conv_module("b3", ("b3",), b2, h, w, 3, p3, p3, 2)
    b4, _, _ = e.c3k2("b4", b3, h3, w3, p3, p4)
    b5, h4, w4 = e.conv_module("b5", ("b5",), b4, h3, w3, 3, p4, p4, 2)
    b6, _, _ = e.c3k2("b6", b5, h4, w4, p4, p4)
    b7, h5, w5 = e.conv_module("b7", ("b7",), b6, h4, w4, 3, p4, p5, 2)
    b8, _, _ = e.c3k2("b8", b7, h5, w5, p5, p5)
    b9, _, _ = e.sppf("b9", b8, h5, w5, p5)
    b10, _, _ = e.c2psa("b10", b9, h5, w5, p5)

    # ---- neck (PAN) ------------------------------------------------------
    u = e.node("n11.upsample", OpKind.UPSAMPLE, [b10], h4 * w4 * p5, factor=2)
    c = e.node("n12.concat", OpKind.CONCAT, [u, b6], h4 * w4 * (p5 + p4),
               axis=-1)
    n13, _, _ = e.c3k2("n13", c, h4, w4, p5 + p4, p4)
    u = e.node("n14.upsample", OpKind.UPSAMPLE, [n13], h3 * w3 * p4, factor=2)
    c = e.node("n15.concat", OpKind.CONCAT, [u, b4], h3 * w3 * (p4 + p4),
               axis=-1)
    n16, _, _ = e.c3k2("n16", c, h3, w3, p4 + p4, p3)
    n17, _, _ = e.conv_module("n17", ("n17",), n16, h3, w3, 3, p3, p3, 2)
    c = e.node("n18.concat", OpKind.CONCAT, [n17, n13], h4 * w4 * (p3 + p4),
               axis=-1)
    n19, _, _ = e.c3k2("n19", c, h4, w4, p3 + p4, p4)
    n20, _, _ = e.conv_module("n20", ("n20",), n19, h4, w4, 3, p4, p4, 2)
    c = e.node("n21.concat", OpKind.CONCAT, [n20, b10], h5 * w5 * (p4 + p5),
               axis=-1)
    n22, _, _ = e.c3k2("n22", c, h5, w5, p4 + p5, p5)

    # ---- detect head: box (cv2) as YOLOv8's, cls (cv3) depthwise-separable
    c2_, c3_ = max(16, p3 // 4, 4 * REG_MAX), max(p3, min(NC, 100))

    def branches(i, f, fh, fw, fc):
        box = e.branch(f"head.cv2.{i}", ("head", "cv2", i), f, fh, fw, fc,
                       c2_, 4 * REG_MAX)
        x, cin = f, fc
        for j in ("0", "1"):
            name, path = f"head.cv3.{i}.{j}", ("head", "cv3", i, j)
            x = e.dwconv(f"{name}.0", path + ("0",), x, fh, fw, cin)
            x, _, _ = e.conv_module(f"{name}.1", path + ("1",), x, fh, fw, 1,
                                    cin, c3_)
            cin = c3_
        cls, _, _ = e.conv(f"head.cv3.{i}.2", ("head", "cv3", i, "2"), x, fh,
                           fw, 1, c3_, NC)
        return [box, cls]

    e.detect([(n16, h3, w3, p3), (n19, h4, w4, p4), (n22, h5, w5, p5)],
             branches)
    g.validate()
    return g
