"""CNN workloads: node/param counts vs the paper, executor numerics
parity, INT8 quantization properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers import example, given, settings, st

from repro.core.graph import OpKind, PUType
from repro.models import quant
from repro.models.cnn import executor, graphs, resnet, yolo, yolo11
from repro.models.cnn import layers as L
from repro.models.cnn.layers import count_params


class TestPaperCounts:
    def test_resnet8_counts(self):
        g = graphs.resnet8_graph()
        assert len(g) == 14                                  # paper: 14 nodes
        assert g.num_nodes(pu_type=PUType.IMC) == 10         # 10 convolutional
        n = count_params(resnet.init(jax.random.PRNGKey(0), resnet.RESNET8))
        assert 76_000 <= n <= 80_000                         # paper: 78K

    def test_resnet18_counts_and_table1_ids(self):
        g = graphs.resnet18_graph()
        assert len(g) == 30                                  # paper: 30 nodes
        assert g.num_nodes(kind=OpKind.CONV) == 20           # 20 conv layers
        assert g.num_nodes(kind=OpKind.MVM) == 1
        imc = {nid for nid, nd in g.nodes.items() if nd.pu_type == PUType.IMC}
        assert imc == set(graphs.TABLE1_IMC_NODE_IDS)        # Table I ids
        n = count_params(resnet.init(jax.random.PRNGKey(0),
                                     resnet.RESNET18_CIFAR))
        assert 2.7e6 <= n <= 2.9e6                           # paper: 2.8M

    def test_yolov8n_counts(self):
        g = graphs.yolov8n_graph()
        assert len(g) == 233                                 # paper: 233 nodes
        assert g.num_nodes(kind=OpKind.CONV) == 63           # 63 convolutional
        silu = sum(
            1 for n in g.nodes.values()
            if n.kind == OpKind.CONV and any(
                g.nodes[s].kind == OpKind.ACT
                for s in g.successors(n.node_id))
        )
        assert silu == 57                                    # 57 with SiLU
        n = yolo.num_params()
        assert 3.0e6 <= n <= 3.25e6                          # paper: 3.17M

    def test_yolo_parallel_branches(self):
        """The three detection scales are parallel branches (paper: '3
        parallel main branches')."""
        g = graphs.yolov8n_graph()
        heads = [nid for nid, n in g.nodes.items()
                 if n.name.startswith("head.cv3") and n.name.endswith(".2")]
        assert len(heads) == 3
        for i in range(3):
            for j in range(i + 1, 3):
                assert g.is_parallel(heads[i], heads[j])


class TestExecutorParity:
    @pytest.mark.parametrize("cfg", [resnet.RESNET8, resnet.RESNET18_CIFAR],
                             ids=["resnet8", "resnet18"])
    def test_graph_execution_matches_reference(self, cfg):
        key = jax.random.PRNGKey(0)
        params = resnet.init(key, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
        ref = resnet.forward(params, x, cfg)
        g = graphs.build_resnet_graph(cfg)
        got = executor.execute(g, params, x, mode="float")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_int8_execution_close_to_float(self):
        cfg = resnet.RESNET8
        params = resnet.init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
        g = graphs.build_resnet_graph(cfg)
        f32 = executor.execute(g, params, x, mode="float")
        i8 = executor.execute(g, params, x, mode="int8")
        assert jnp.isfinite(i8).all()
        # top-1 agreement on most samples + bounded relative error
        agree = jnp.mean(
            (jnp.argmax(f32, -1) == jnp.argmax(i8, -1)).astype(jnp.float32))
        assert agree >= 0.75
        rel = jnp.linalg.norm(i8 - f32) / jnp.linalg.norm(f32)
        assert rel < 0.25

    def test_yolo_graph_execution_matches_reference(self, yolo64):
        g, params, x, _ = yolo64
        ref = yolo.forward(params, x)
        got = executor.execute(g, params, x, mode="float")
        assert got.shape == (2, 8 * 8 + 4 * 4 + 2 * 2, 4 + yolo.NC)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    # Box and class parts of int8 YOLOv8n against float, each as the norm
    # of the differences over that of the float answers less their mean
    # over the batch.  Per-tensor int8 rounding at the 63 conv inputs reads
    # about 0.15 in both parts on these 2 frames; int4 weights alone read
    # about 1.0.  The bound lies between, with room on both sides.
    PART_BOUND = {"box": 0.4, "cls": 0.4}

    @staticmethod
    def _part_errors(got, want):
        out = {}
        for part, (lo, hi) in {"box": (0, 4), "cls": (4, 4 + yolo.NC)}.items():
            d = np.asarray(got)[..., lo:hi] - np.asarray(want)[..., lo:hi]
            w = np.asarray(want)[..., lo:hi]
            out[part] = np.linalg.norm(d) / np.linalg.norm(w - w.mean(0))
        return out

    def test_yolo_int8_parts_close_to_float(self, yolo64):
        g, params, x, scales = yolo64
        f32 = executor.execute(g, params, x, mode="float")
        i8 = executor.execute(g, params, x, mode="int8", act_scales=scales)
        err = self._part_errors(i8, f32)
        assert all(err[p] < b for p, b in self.PART_BOUND.items()), err

    def test_yolo_int4_weights_fail_a_part_bound(self, yolo64):
        g, params, x, scales = yolo64

        def int4(path, a):
            if path[-1].key != "w":
                return a
            s = jnp.maximum(jnp.max(jnp.abs(a), axis=(0, 1, 2)), 1e-8) / 7
            return jnp.clip(jnp.round(a / s), -7, 7) * s

        p4 = jax.tree_util.tree_map_with_path(int4, params)
        f32 = executor.execute(g, params, x, mode="float")
        i4 = executor.execute(g, p4, x, mode="int8", act_scales=scales)
        err = self._part_errors(i4, f32)
        assert any(err[p] >= b for p, b in self.PART_BOUND.items()), err

    def test_every_yolo_node_kind_executes(self, yolo64):
        from repro import obs
        _, params, x, scales = yolo64
        g = graphs.build_yolov8n_graph(YOLO64)      # a fresh program: traced
        with obs.recording() as rec:
            out = executor.execute(g, params, x[:1], mode="int8",
                                   act_scales=scales)
        assert np.isfinite(np.asarray(out)).all()
        r = rec.rows()
        nodes = r["name"] == obs.SPAN_NAMES.index("node")
        ran = {rec.label(k) for k in r["kind"][nodes]}
        assert ran == {n.kind.name for n in g.nodes.values()} == {
            "CONV", "MVM", "ADD", "MUL", "ACT", "CONCAT", "SPLIT", "POOL_MAX",
            "UPSAMPLE", "RESHAPE", "SOFTMAX"}
        assert nodes.sum() == len(g) == 233

    def test_every_yolo_conv_param_resolves(self, yolo64):
        g, params, _, _ = yolo64
        convs = [n for n in g.nodes.values() if n.kind == OpKind.CONV]
        assert len(convs) == 63
        for n in convs:
            p = executor._param_at(params, n.meta["param"])
            k, cout = n.meta["k"], n.meta["cout"]
            assert p["w"].shape == (k, k, n.meta["cin_kk"] // (k * k), cout)
            assert p["b"].shape == (cout,)
        leaves = len(jax.tree_util.tree_leaves(params))
        assert leaves == 2 * len(convs)         # every weight is read
        dfl = [n for n in g.nodes.values() if n.kind == OpKind.MVM]
        assert [n.name for n in dfl] == ["dfl.conv"]
        assert dfl[0].meta["param"] is None
        assert dfl[0].meta["weights"] == [[float(i)] for i in range(16)]

    def test_yolo_graph_needs_a_multiple_of_32(self):
        with pytest.raises(ValueError):
            graphs.build_yolov8n_graph(dict(yolo.YOLOV8N, image_hw=(48, 64)))

    def test_yolo_forward_shapes(self):
        params = yolo.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64, 3))
        out = yolo.forward(params, x)
        assert out.shape == (1, 8 * 8 + 4 * 4 + 2 * 2, 4 + yolo.NC)
        assert jnp.isfinite(out).all()
        raw = yolo.forward(params, x, decode=False)
        assert [r.shape for r in raw] == [
            (1, 8, 8, 144), (1, 4, 4, 144), (1, 2, 2, 144)]


YOLO64 = dict(yolo.YOLOV8N, image_hw=(64, 64))


@pytest.fixture(scope="module")
def yolo64():
    """YOLOv8n's graph at 64x64, seeded ``yolo.init`` weights, 2 frames
    and their scales from ``quant.calibrate_graph``."""
    params = yolo.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64, 3))
    g = graphs.build_yolov8n_graph(YOLO64)
    return g, params, x, quant.calibrate_graph(g, params, x)


@pytest.fixture(scope="module")
def resnet8_int8():
    cfg = resnet.RESNET8
    params = resnet.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    return cfg, params, x, quant.calibrate_resnet(params, x, cfg)


@pytest.fixture(scope="module")
def resnet18_int8():
    cfg = resnet.RESNET18_CIFAR
    params = resnet.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    return cfg, params, x, quant.calibrate_resnet(params, x, cfg)


def _traced(fn):
    """``fn()``'s result and the ``execute`` spans and programs traced."""
    from repro import obs
    with obs.recording() as rec:
        out = fn()
    calls = int((rec.rows()["name"] == obs.SPAN_NAMES.index("execute")).sum())
    return np.asarray(out), calls, rec.counters["execute.traces"]


class TestOperands:
    """Every graph names each node's operands; the executor reads nothing
    else."""

    @pytest.mark.parametrize("build", [
        lambda: graphs.build_resnet_graph(resnet.RESNET8),
        lambda: graphs.build_resnet_graph(resnet.RESNET18_CIFAR),
        lambda: graphs.build_yolov8n_graph(YOLO64)],
        ids=["resnet8", "resnet18_cifar", "yolov8n"])
    def test_every_node_lists_its_predecessors_as_inputs(self, build):
        g = build()
        for nid, node in g.nodes.items():
            assert "inputs" in node.meta, node.name
            named = {name for name, _ in node.meta["inputs"]}
            assert named == {g.nodes[p].name for p in g.predecessors(nid)}, \
                node.name

    def test_a_node_without_inputs_is_refused(self, resnet8_int8):
        cfg, params, x, _ = resnet8_int8
        g = graphs.build_resnet_graph(cfg)
        add = next(n for n in g.nodes.values() if n.name == "s1b0.add")
        del add.meta["inputs"]
        with pytest.raises(ValueError, match=r"node s1b0\.add: meta inputs"):
            executor.execute(g, params, x, mode="float")


class TestCompiledExecutor:
    """One jitted program per graph, mode and set of scaled nodes."""

    @pytest.mark.parametrize("model", ["resnet8", "yolov8n"])
    def test_second_call_traces_nothing(self, request, model):
        if model == "resnet8":
            cfg, params, x, scales = request.getfixturevalue("resnet8_int8")
            g = graphs.build_resnet_graph(cfg)
        else:
            _, params, x, scales = request.getfixturevalue("yolo64")
            g = graphs.build_yolov8n_graph(YOLO64)
        x = x[:1]                               # a stream's single frame
        run = lambda: executor.execute(g, params, x, mode="int8",  # noqa: E731
                                       act_scales=scales)
        first, calls, traces = _traced(run)
        assert (calls, traces) == (1, 1)
        again, calls, traces = _traced(run)
        assert (calls, traces) == (1, 0)
        np.testing.assert_array_equal(again, first)

    def test_new_batch_size_traces_once(self, resnet8_int8):
        cfg, params, x, scales = resnet8_int8
        g = graphs.build_resnet_graph(cfg)
        executor.execute(g, params, x, mode="int8", act_scales=scales)
        x3 = jax.random.normal(jax.random.PRNGKey(2), (3, 32, 32, 3))
        run = lambda: executor.execute(g, params, x3, mode="int8",  # noqa: E731
                                       act_scales=scales)
        out, _, traces = _traced(run)
        assert out.shape == (3, 10) and traces == 1
        assert _traced(run)[2] == 0
        # host numpy frames are put as a flat view: one program of their
        # own a shape, reused by the next call
        host = lambda: executor.execute(  # noqa: E731
            g, params, np.asarray(x3), mode="int8", act_scales=scales)
        assert _traced(host)[2] == 1
        assert _traced(host)[2] == 0

    @pytest.mark.parametrize("mutation", ["node", "edge"])
    def test_graph_mutation_traces_again(self, resnet8_int8, mutation):
        cfg, params, x, scales = resnet8_int8
        g = graphs.build_resnet_graph(cfg)
        run = lambda: executor.execute(g, params, x, mode="int8",  # noqa: E731
                                       act_scales=scales)
        before, _, _ = _traced(run)
        sink = g.topo_order()[-1]
        if mutation == "node":
            g.add("out2", OpKind.OUTPUT, deps=[sink],   # a new sink, same logits
                  meta={"inputs": [(g.nodes[sink].name, None)]})
        else:
            g.add_edge(g.predecessors(sink)[0], sink)   # present: invalidates only
        after, _, traces = _traced(run)
        assert traces == 1
        np.testing.assert_array_equal(after, before)

    def test_changed_scale_values_give_their_own_logits(self, resnet8_int8):
        cfg, params, x, scales = resnet8_int8
        g = graphs.build_resnet_graph(cfg)
        wider = {k: 1.5 * v for k, v in scales.items()}

        def run(graph, s):
            return _traced(lambda: executor.execute(graph, params, x, mode="int8",
                                                    act_scales=s))

        base, _, _ = run(g, scales)
        changed, _, traces = run(g, wider)
        assert traces == 0                      # same names: same program
        fresh, _, _ = run(graphs.build_resnet_graph(cfg), wider)
        np.testing.assert_array_equal(changed, fresh)
        assert not np.array_equal(changed, base)
        np.testing.assert_array_equal(run(g, scales)[0], base)

    @pytest.mark.parametrize("calibrated", [True, False],
                             ids=["act_scales", "scales_from_x"])
    def test_int8_matches_the_op_by_op_walk(self, resnet8_int8, calibrated):
        cfg, params, x, scales = resnet8_int8
        g = graphs.build_resnet_graph(cfg)
        s = scales if calibrated else None
        compiled = np.asarray(executor.execute(g, params, x, mode="int8",
                                               act_scales=s))
        with jax.disable_jit():
            eager = np.asarray(executor.execute(g, params, x, mode="int8",
                                                act_scales=s))
        rel = np.linalg.norm(compiled - eager) / np.linalg.norm(eager)
        assert rel <= 1e-5

    # (model, batch, frame side): 32x32x3 and 64x64x3 frames are multiples
    # of 128 floats and are put as a flat view; 30x30x3 (2,700) is not.  In
    # int8 a view of fewer than 128 frames is laid out channel planar
    # (``execute.planar_puts``), 256 frames through the batch-minor order
    @pytest.mark.parametrize("mode", ["float", "int8"])
    @pytest.mark.parametrize("model,batch,side", [
        ("resnet8", 1, 32), ("resnet8", 8, 32), ("resnet18", 1, 32),
        ("resnet18", 8, 32), ("yolov8n", 2, 64), ("resnet8", 1, 30),
        ("yolov8n", 1, 64), ("resnet8", 256, 32)])
    def test_host_frames_match_device_frames(self, request, model, batch,
                                             side, mode):
        host = np.random.default_rng(batch).standard_normal(
            (batch, side, side, 3), dtype=np.float32)
        flat = host.size % executor.LANES == 0
        planar = flat and mode == "int8" and batch < executor.LANES
        run = self._runner(request, model, mode)

        on_device, counters = run(jnp.asarray(host))
        assert counters["execute.flat_puts"] == 0
        assert counters["execute.planar_puts"] == 0
        got, counters = run(host)
        assert counters["execute.flat_puts"] == int(flat)
        assert counters["execute.planar_puts"] == int(planar)
        np.testing.assert_array_equal(got, on_device)
        again, counters = run(host)
        assert counters["execute.traces"] == 0
        assert counters["execute.flat_puts"] == int(flat)
        assert counters["execute.planar_puts"] == int(planar)
        np.testing.assert_array_equal(again, got)

    @pytest.mark.parametrize("model,side", [
        ("resnet8", 32), ("resnet18", 32), ("yolov8n", 64)])
    def test_planar_frames_keep_non_finite_and_saturating_pixels(
            self, request, model, side):
        # the planar route quantises before it permutes: +-Inf, NaN and
        # pixels far beyond the calibrated range reach the conv as the
        # device-array route's int8 values, and no other pixel is touched
        host = np.random.default_rng(7).standard_normal(
            (1, side, side, 3), dtype=np.float32)
        host[0, 0, 0, 0], host[0, 1, 2, 1], host[0, 3, 5, 2] = \
            np.inf, -np.inf, np.nan
        host[0, 9, 4, :] = [1e6, -1e6, 3e38]
        host[0, side - 1, side - 1, 2] = 1e3
        run = self._runner(request, model, "int8")
        got, counters = run(host)
        assert counters["execute.planar_puts"] == 1
        on_device, _ = run(jnp.asarray(host))
        np.testing.assert_array_equal(got, on_device)

    @staticmethod
    def _runner(request, model, mode):
        """``execute`` of ``model`` (a fresh graph) in ``mode``, recorded:
        returns the answer on the host and the counters."""
        from repro import obs
        if model == "yolov8n":
            _, params, _, scales = request.getfixturevalue("yolo64")
            g = graphs.build_yolov8n_graph(YOLO64)
        else:
            cfg, params, _, scales = request.getfixturevalue(f"{model}_int8")
            g = graphs.build_resnet_graph(cfg)
        s = scales if mode == "int8" else None

        def run(x):
            with obs.recording() as rec:
                out = np.asarray(executor.execute(g, params, x, mode=mode,
                                                  act_scales=s))
            return out, rec.counters

        return run

    def test_inside_an_outer_jit_twice_leaks_no_tracer(self, resnet8_int8):
        from repro import obs
        cfg, params, x, scales = resnet8_int8
        g = graphs.build_resnet_graph(cfg)      # the scale memo is made inside
        fn = jax.jit(lambda p, x: executor.execute(g, p, x, mode="int8",
                                                   act_scales=scales))
        with obs.recording() as rec:            # host frames, traced: no view
            a = np.asarray(fn(params, np.asarray(x)))
        assert rec.counters["execute.flat_puts"] == 0
        b = np.asarray(fn(params, x[:1]))       # a second trace of the outer jit
        eager = np.asarray(executor.execute(g, params, x, mode="int8",
                                            act_scales=scales))
        np.testing.assert_allclose(a, eager, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(b, eager[:1], rtol=1e-5, atol=1e-5)

    def test_node_names_reach_the_program_metadata(self, resnet8_int8):
        cfg, params, x, _ = resnet8_int8
        g = graphs.build_resnet_graph(cfg)
        hlo = jax.jit(lambda p, x: executor.execute(g, p, x, mode="int8")) \
            .lower(params, x).compile().as_text()
        for n in g.topo_order():
            if g.nodes[n].kind in (OpKind.CONV, OpKind.MVM):
                assert f"/{g.nodes[n].name}/" in hlo, g.nodes[n].name


YOLO11_64 = dict(yolo11.YOLO11N, image_hw=(64, 64))


@pytest.fixture(scope="module")
def yolo11_64():
    """YOLO11n's graph at 64x64, seeded ``yolo11.init`` weights, 2 frames
    and their scales from ``quant.calibrate_graph``."""
    params = yolo11.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, 64, 3))
    g = graphs.build_yolo11n_graph(YOLO11_64)
    return g, params, x, quant.calibrate_graph(g, params, x)


class TestYolo11n:
    def test_counts_match_the_published_model(self):
        # Ultralytics publishes 2.6 M parameters and 6.5 GFLOPs (2 x MACs)
        g = graphs.build_yolo11n_graph()
        convs = [n for n in g.nodes.values() if n.kind == OpKind.CONV]
        assert len(convs) == 87
        grouped = [n for n in convs if n.meta.get("groups", 1) != 1]
        assert len(grouped) == 7
        for n in grouped:       # depthwise: groups == cin (one input each)
            assert n.meta["cin_kk"] == n.meta["k"] ** 2
            assert n.meta["groups"] == n.meta["cout"]
        assert sum(n.flops for n in convs) == 2 * 3_239_993_600
        products = [n for n in g.nodes.values() if n.kind == OpKind.PRODUCT]
        assert [n.name for n in products] == ["b10.m0.attn.qk",
                                              "b10.m0.attn.av"]
        assert sum(n.flops for n in products) == 2 * 30_720_000
        assert sum(n.weight_bytes for n in convs) == 2_616_232
        assert yolo11.num_params() == 2_616_232

    def test_every_conv_param_resolves(self, yolo11_64):
        g, params, _, _ = yolo11_64
        convs = [n for n in g.nodes.values() if n.kind == OpKind.CONV]
        for n in convs:
            p = executor._param_at(params, n.meta["param"])
            k, cout = n.meta["k"], n.meta["cout"]
            assert p["w"].shape == (k, k, n.meta["cin_kk"] // (k * k), cout)
            assert p["b"].shape == (cout,)
        assert len(jax.tree_util.tree_leaves(params)) == 2 * len(convs) == 174

    def test_float_execution_matches_reference(self, yolo11_64):
        g, params, x, _ = yolo11_64
        got = executor.execute(g, params, x, mode="float")
        assert got.shape == (2, 8 * 8 + 4 * 4 + 2 * 2, 4 + yolo.NC)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(yolo11.forward(params, x)),
                                   rtol=1e-5, atol=1e-5)

    # The same measure and bound as YOLOv8n's (TestExecutorParity): per-
    # tensor int8 rounding at the 87 conv inputs, the products and softmax
    # in float32, reads about 0.13 (box) and 0.17 (cls) on these 2 frames;
    # int4 weights read about 1 in at least one part.
    def test_int8_parts_close_to_float(self, yolo11_64):
        g, params, x, scales = yolo11_64
        f32 = executor.execute(g, params, x, mode="float")
        i8 = executor.execute(g, params, x, mode="int8", act_scales=scales)
        err = TestExecutorParity._part_errors(i8, f32)
        bound = TestExecutorParity.PART_BOUND
        assert all(err[p] < b for p, b in bound.items()), err

    def test_int4_weights_fail_a_part_bound(self, yolo11_64):
        g, params, x, scales = yolo11_64

        def int4(path, a):
            if path[-1].key != "w":
                return a
            s = jnp.maximum(jnp.max(jnp.abs(a), axis=(0, 1, 2)), 1e-8) / 7
            return jnp.clip(jnp.round(a / s), -7, 7) * s

        p4 = jax.tree_util.tree_map_with_path(int4, params)
        f32 = executor.execute(g, params, x, mode="float")
        i4 = executor.execute(g, p4, x, mode="int8", act_scales=scales)
        err = TestExecutorParity._part_errors(i4, f32)
        bound = TestExecutorParity.PART_BOUND
        assert any(err[p] >= b for p, b in bound.items()), err

    def test_lblp_puts_every_product_on_a_dpu(self):
        from repro.core import CostModel, get_scheduler, make_pus
        g = graphs.build_yolo11n_graph()
        fleet = make_pus(8, 4)
        kind = {p.pu_id: p.pu_type for p in fleet}
        placed = get_scheduler("lblp", CostModel()).schedule(g, fleet).mapping
        products = [n for n, node in g.nodes.items()
                    if node.kind == OpKind.PRODUCT]
        assert len(products) == 2
        assert all(kind[placed[n]] == PUType.DPU for n in products)
        assert all(kind[placed[n]] == PUType.IMC for n, node in g.nodes.items()
                   if node.kind == OpKind.CONV)

    def test_counters_read_once_per_compiled_program(self, yolo11_64, yolo64,
                                                     resnet8_int8):
        from repro import obs
        cfg, r_params, r_x, r_scales = resnet8_int8
        fresh = [  # new graphs: their programs are traced in the recording
            (graphs.build_yolo11n_graph(YOLO11_64), *yolo11_64[1:]),
            (graphs.build_yolov8n_graph(YOLO64), *yolo64[1:]),
            (graphs.build_resnet_graph(cfg), r_params, r_x, r_scales)]
        read = []
        for g, params, x, scales in fresh:
            with obs.recording() as rec:
                for _ in range(2):          # the second call traces nothing
                    out = executor.execute(g, params, x[:1], mode="int8",
                                           act_scales=scales)
            assert np.isfinite(np.asarray(out)).all()
            read.append((rec.counters["quant.grouped_convs"],
                         rec.counters["execute.act_products"]))
            r = rec.rows()
            nodes = r["name"] == obs.SPAN_NAMES.index("node")
            assert nodes.sum() == len(g)    # every node ran under its span
        assert read == [(7, 2), (0, 0), (0, 0)]


class TestQuant:
    @given(st.integers(0, 1000), st.integers(1, 6), st.integers(1, 64))
    @example(seed=387, rows=6, cols=64)
    @example(seed=758, rows=6, cols=64)
    @settings(max_examples=30, deadline=None)
    def test_weight_roundtrip_error_bound(self, seed, rows, cols):
        key = jax.random.PRNGKey(seed)
        w = jax.random.normal(key, (rows * 4, cols)) * \
            jax.random.uniform(key, (1, cols), minval=0.1, maxval=10.0)
        qt = quant.quantize_weight(w, channel_axis=-1)
        back = quant.dequantize(qt, channel_axis=-1)
        # Per element, in exact (float64) arithmetic: half a step s / 2 from
        # rounding to the integer q, plus float32's rounding (unit roundoff
        # 2^-24) of the division w / s, at most 2^-24 |w|, and of the
        # product q * s, at most 2^-24 |q s|.  The two draws pinned above
        # exceed s / 2 alone, by 7.6e-8 and 7.5e-9.
        w64 = np.asarray(w, np.float64)
        s64 = np.asarray(qt.scale, np.float64)[None, :]
        qs = np.abs(np.asarray(qt.q, np.float64)) * s64
        err = np.abs(np.asarray(back, np.float64) - w64)
        bound = 0.5 * s64 + 2.0 ** -24 * (np.abs(w64) + qs)
        assert (err <= bound).all(), (err - bound).max()

    @given(st.integers(0, 1000))
    @settings(max_examples=20, deadline=None)
    def test_int8_matmul_exactness(self, seed):
        """Integer accumulate is exact: matches float64 computation of the
        same quantized integers."""
        key = jax.random.PRNGKey(seed)
        k1, k2 = jax.random.split(key)
        qx = jax.random.randint(k1, (8, 32), -127, 128, dtype=jnp.int32)
        qw = jax.random.randint(k2, (32, 16), -127, 128, dtype=jnp.int32)
        acc = quant.int8_matmul_acc(qx.astype(jnp.int8), qw.astype(jnp.int8))
        ref = np.asarray(qx, np.int64) @ np.asarray(qw, np.int64)
        np.testing.assert_array_equal(np.asarray(acc, np.int64), ref)

    def test_quantized_conv_close(self):
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (2, 16, 16, 8))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 8, 16)) * 0.2
        b = jnp.zeros((16,))
        ref = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        got = quant.quantized_conv2d(x, w, b)
        rel = jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref)
        assert rel < 0.05

    def test_grouped_conv_accumulates_each_group_exactly(self):
        # a depthwise int8 conv is, channel by channel, the dense int8 conv
        # of that channel alone
        k1, k2 = jax.random.split(jax.random.PRNGKey(3))
        qx = jax.random.randint(k1, (2, 9, 9, 6), -127, 128).astype(jnp.int8)
        qw = jax.random.randint(k2, (3, 3, 1, 6), -127, 128).astype(jnp.int8)
        acc = quant.int8_conv_acc(qx, qw, 2, "SAME", feature_group_count=6)
        per = [quant.int8_conv_acc(qx[..., c:c + 1], qw[..., c:c + 1], 2,
                                   "SAME") for c in range(6)]
        np.testing.assert_array_equal(np.asarray(acc),
                                      np.asarray(jnp.concatenate(per, -1)))

    def test_quantized_grouped_conv_close(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 16, 8))
        w = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 2, 12)) * 0.2
        b = jnp.zeros((12,))
        ref = L.conv2d({"w": w, "b": b}, x, groups=4)
        got = quant.quantized_conv2d(x, w, b, feature_group_count=4)
        assert quant.weight_scale(w).shape == (12,)    # per output channel
        rel = jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref)
        assert rel < 0.05

    def test_aimc_noise_hook(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
        w = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
        clean = quant.quantized_matmul(x, w)
        noisy = quant.quantized_matmul(x, w, noise_std=5.0,
                                       key=jax.random.PRNGKey(2))
        assert not jnp.allclose(clean, noisy)

    @pytest.mark.parametrize("model", ["resnet8", "yolov8n"])
    def test_calibrate_graph_covers_every_weighted_node(self, request, model):
        from repro import obs
        if model == "resnet8":
            cfg, params, x, _ = request.getfixturevalue("resnet8_int8")
            g = graphs.build_resnet_graph(cfg)
        else:
            g, params, x, _ = request.getfixturevalue("yolo64")
        with obs.recording() as rec:
            scales = quant.calibrate_graph(g, params, x, block=1)
        weighted = {n.name for n in g.nodes.values()
                    if n.kind in (OpKind.CONV, OpKind.MVM)
                    and n.meta.get("param") is not None}
        assert set(scales) == weighted
        assert "dfl.conv" not in scales         # fixed weights: not scaled
        assert all(s > 0 for s in scales.values())
        assert rec.counters["calibrate.scales"] == len(weighted)
        r = rec.rows()
        cal = np.flatnonzero(r["name"] == obs.SPAN_NAMES.index("calibrate"))
        assert len(cal) == 1
        assert rec.label(r["node"][cal[0]]) == g.name
        assert rec.label(r["kind"][cal[0]]) == f"{len(weighted)} nodes"
        assert r["batch"][cal[0]] == len(x)

    def test_calibrate_graph_in_blocks_takes_the_largest(self, yolo64):
        g, params, x, whole = yolo64
        one = [quant.calibrate_graph(g, params, x[i:i + 1]) for i in (0, 1)]
        for name, s in quant.calibrate_graph(g, params, x, block=1).items():
            assert s == max(one[0][name], one[1][name])
            assert s == pytest.approx(whole[name], rel=1e-5)

    @staticmethod
    def _replayed_scales(params, x):
        """The oracle: each conv and dense input's scale from an eager
        replay of ``resnet.forward``, written out block by block."""
        scales = {}

        def rec(name, t):
            scales[name] = float(quant.act_scale(t))

        rec("stem", x)
        h = L.conv2d(params["stem"], x, stride=1, act="relu")
        for si, blocks in enumerate(params["stages"]):
            for bi, block in enumerate(blocks):
                stride = 2 if (si > 0 and bi == 0) else 1
                identity = h
                rec(f"s{si}b{bi}.conv1", h)
                y = L.conv2d(block["conv1"], h, stride=stride, act="relu")
                rec(f"s{si}b{bi}.conv2", y)
                y = L.conv2d(block["conv2"], y, stride=1, act=None)
                if "down" in block:
                    rec(f"s{si}b{bi}.down", identity)
                    identity = L.conv2d(block["down"], identity,
                                        stride=stride, act=None)
                h = jax.nn.relu(y + identity)
        rec("fc", jnp.mean(h, axis=(1, 2)))
        return scales

    def test_calibrate_graph_matches_calibrate_resnet(self, resnet8_int8,
                                                      resnet18_int8):
        # calibrate_resnet is calibrate_graph on the ResNet's deployment
        # graph: the same magnitudes as the replay, from the graph's jitted
        # float program; XLA rounds the two differently in the last bit
        # (one scale of ResNet-8's ten on the CPU).
        for cfg, params, x, scales in (resnet8_int8, resnet18_int8):
            replay = self._replayed_scales(params, x)
            assert set(scales) == set(replay)
            for name, s in replay.items():
                assert scales[name] == pytest.approx(s, rel=1e-6), name

    def test_calibration_scales_cover_layers(self):
        cfg = resnet.RESNET8
        params = resnet.init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))
        scales = quant.calibrate_resnet(params, x, cfg)
        g = graphs.build_resnet_graph(cfg)
        conv_names = {n.name for n in g.nodes.values()
                      if n.kind in (OpKind.CONV, OpKind.MVM)}
        assert conv_names <= set(scales)
        assert all(s > 0 for s in scales.values())
