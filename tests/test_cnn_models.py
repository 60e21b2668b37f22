"""CNN workloads: node/param counts vs the paper, executor numerics
parity, INT8 quantization properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers import given, settings, st

from repro.core.graph import OpKind, PUType
from repro.models import quant
from repro.models.cnn import executor, graphs, resnet, yolo
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

    def test_yolo_forward_shapes(self):
        params = yolo.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 64, 3))
        out = yolo.forward(params, x)
        assert out.shape == (1, 8 * 8 + 4 * 4 + 2 * 2, 4 + yolo.NC)
        assert jnp.isfinite(out).all()
        raw = yolo.forward(params, x, decode=False)
        assert [r.shape for r in raw] == [
            (1, 8, 8, 144), (1, 4, 4, 144), (1, 2, 2, 144)]


@pytest.fixture(scope="module")
def resnet8_int8():
    cfg = resnet.RESNET8
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


class TestCompiledExecutor:
    """One jitted program per graph, mode and set of scaled nodes."""

    def test_second_call_traces_nothing(self, resnet8_int8):
        cfg, params, x, scales = resnet8_int8
        g = graphs.build_resnet_graph(cfg)
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
        # host numpy frames of a warm shape reuse the program
        assert _traced(lambda: executor.execute(
            g, params, np.asarray(x3), mode="int8", act_scales=scales))[2] == 0

    @pytest.mark.parametrize("mutation", ["node", "edge"])
    def test_graph_mutation_traces_again(self, resnet8_int8, mutation):
        cfg, params, x, scales = resnet8_int8
        g = graphs.build_resnet_graph(cfg)
        run = lambda: executor.execute(g, params, x, mode="int8",  # noqa: E731
                                       act_scales=scales)
        before, _, _ = _traced(run)
        sink = g.topo_order()[-1]
        if mutation == "node":
            g.add("out2", OpKind.OUTPUT, deps=[sink])   # a new sink, same logits
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

    def test_inside_an_outer_jit_twice_leaks_no_tracer(self, resnet8_int8):
        cfg, params, x, scales = resnet8_int8
        g = graphs.build_resnet_graph(cfg)      # the scale memo is made inside
        fn = jax.jit(lambda p, x: executor.execute(g, p, x, mode="int8",
                                                   act_scales=scales))
        a = np.asarray(fn(params, x))
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


class TestQuant:
    @given(st.integers(0, 1000), st.integers(1, 6), st.integers(1, 64))
    @settings(max_examples=30, deadline=None)
    def test_weight_roundtrip_error_bound(self, seed, rows, cols):
        key = jax.random.PRNGKey(seed)
        w = jax.random.normal(key, (rows * 4, cols)) * \
            jax.random.uniform(key, (1, cols), minval=0.1, maxval=10.0)
        qt = quant.quantize_weight(w, channel_axis=-1)
        back = quant.dequantize(qt, channel_axis=-1)
        # per-channel error bounded by scale/2 per element
        err = jnp.abs(back - w)
        bound = qt.scale[None, :] * 0.5 + 1e-7
        assert bool(jnp.all(err <= bound))

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

    def test_aimc_noise_hook(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
        w = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
        clean = quant.quantized_matmul(x, w)
        noisy = quant.quantized_matmul(x, w, noise_std=5.0,
                                       key=jax.random.PRNGKey(2))
        assert not jnp.allclose(clean, noisy)

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
