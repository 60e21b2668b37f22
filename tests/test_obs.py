"""Program spans and counters (``repro.obs``): off by default, and when on,
the span tree of one int8 ``execute`` of ResNet-8: the graph walk's spans
in the call that traces the compiled program, the call's span alone after."""

import gc

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.graph import OpKind
from repro.models import quant
from repro.models.cnn import executor, graphs, resnet

PHASES = ("quant.act", "quant.weight", "int8.acc", "dequant")
NAME = {n: i for i, n in enumerate(obs.SPAN_NAMES)}


@pytest.fixture(scope="module")
def resnet8():
    cfg = resnet.RESNET8
    params = resnet.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    scales = quant.calibrate_resnet(params, x, cfg)
    g = graphs.build_resnet_graph(cfg)

    def run(graph=g):
        return executor.execute(graph, params, x, mode="int8",
                                act_scales=scales)

    return g, run


@pytest.fixture(scope="module")
def recorded(resnet8):
    g, run = resnet8
    with obs.recording() as rec:            # the first call on g: the trace
        out = run()
    return g, rec, rec.rows(), out


def test_off_records_nothing_and_returns_the_shared_null_context():
    assert obs._active is None
    first = obs.span("node", node="stem", kind="CONV")
    assert first is obs.span("execute", batch=4) is obs._NULL
    with first:
        obs.count("quant.weight.tensors")
    assert obs._active is None


def test_on_only_inside_a_recording():
    # the executor counts its bytes only when this says a recording is on
    assert not obs.on()
    with obs.recording():
        assert obs.on()
    assert not obs.on()


def test_off_allocates_nothing_per_span():
    def spans(n):
        for _ in range(n):
            with obs.span("quant.act"):
                obs.count("quant.weight.tensors")

    spans(10)
    gc.collect()
    before = gc.get_count()[0]
    spans(10_000)
    # no object the collector tracks was made (and left) per span
    assert gc.get_count()[0] - before < 10


def test_nested_spans_carry_parent_call_and_self_time():
    with obs.recording() as rec:
        with obs.span("execute", kind="int8", batch=3):
            with obs.span("node", node="a", kind="CONV"):
                with obs.span("quant.act"):
                    pass
                with obs.span("dequant"):
                    pass
            with obs.span("node", node="b", kind="ADD"):
                pass
        with obs.span("node", node="c"):
            pass
    r = rec.rows()
    names = [obs.SPAN_NAMES[k] for k in r["name"]]
    assert names == ["execute", "node", "quant.act", "dequant", "node", "node"]
    assert list(r["parent"]) == [-1, 0, 1, 1, 0, -1]
    assert list(r["call"]) == [0, 0, 0, 0, 0, -1]
    assert [rec.label(k) for k in r["node"]] == [None, "a", "a", "a", "b", "c"]
    assert rec.label(r["kind"][0]) == "int8" and r["batch"][0] == 3
    assert np.all(r["t1"] >= r["t0"])
    # thread-CPU time is kept for the call only
    assert r["cpu"][0] >= 0 and np.isnan(r["cpu"][1:]).all()
    assert r["self"][0] == pytest.approx(r["dur"][0] - r["dur"][1] - r["dur"][4])
    assert r["self"][1] == pytest.approx(r["dur"][1] - r["dur"][2] - r["dur"][3])
    assert r["self"][2] == r["dur"][2]
    assert obs._active is None


def test_recording_keeps_every_span_and_refuses_nesting():
    n = 5000
    with obs.recording() as rec:
        for i in range(n):
            with obs.span("node", node=f"n{i % 3}"):
                pass
        with pytest.raises(RuntimeError):
            with obs.recording():
                pass
    assert rec.n == n
    r = rec.rows()
    assert np.all(r["dur"] >= 0) and np.all(r["t0"][1:] >= r["t1"][:-1])
    assert [rec.label(k) for k in r["node"][-3:]] == \
        [f"n{i % 3}" for i in range(n - 3, n)]


def test_unknown_span_name_is_refused():
    with obs.recording():
        with pytest.raises(ValueError):
            with obs.span("no_such_span"):
                pass


def test_int8_execute_records_one_call(recorded):
    g, rec, r, _ = recorded
    names = np.array(obs.SPAN_NAMES)[r["name"]]
    assert (names == "execute").sum() == 1
    assert r["batch"][0] == 2 and rec.label(r["kind"][0]) == "int8"
    assert np.all(r["call"] == 0)
    assert rec.counters["execute.frames"] == 2
    assert rec.counters["execute.traces"] == 1


def test_warm_call_records_its_execute_span_alone(resnet8, recorded):
    _, run = resnet8
    with obs.recording() as rec:
        run()
    r = rec.rows()
    assert [obs.SPAN_NAMES[k] for k in r["name"]] == ["execute"]
    assert rec.counters == {"execute.frames": 2, "execute.traces": 0,
                            "quant.weight.tensors": 0, "calibrate.scales": 0,
                            "execute.bytes_in": 2 * 32 * 32 * 3 * 4,
                            "execute.bytes_out": 2 * 10 * 4,
                            "execute.flat_puts": 0, "execute.planar_puts": 0,
                            "quant.grouped_convs": 0,
                            "execute.act_products": 0}


def test_bytes_in_and_out_counted_from_shapes_per_call(resnet8, recorded):
    # float32 frames in and logits out, counted in the call that traces
    # and in warm calls alike, without waiting for the device
    _, rec, _, out = recorded
    assert rec.counters["execute.bytes_in"] == 2 * 32 * 32 * 3 * 4
    assert rec.counters["execute.bytes_out"] == out.nbytes == 2 * 10 * 4
    _, run = resnet8
    with obs.recording() as rec3:
        for _ in range(3):
            run()
    assert rec3.counters["execute.bytes_in"] == 3 * 2 * 32 * 32 * 3 * 4
    assert rec3.counters["execute.bytes_out"] == 3 * 2 * 10 * 4


def test_calibrate_records_its_span_and_scales():
    cfg = resnet.RESNET8
    params = resnet.init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    g = graphs.build_resnet_graph(cfg)
    with obs.recording() as rec:
        scales = quant.calibrate_graph(g, params, x, block=2)
    r = rec.rows()
    top = r["parent"] == -1
    assert [obs.SPAN_NAMES[k] for k in r["name"][top]] == ["calibrate"]
    assert rec.label(r["node"][top][0]) == g.name == "resnet8"
    assert rec.label(r["kind"][top][0]) == "10 nodes"
    assert r["batch"][top][0] == 4
    assert rec.counters["calibrate.scales"] == len(scales) == 10
    assert rec.counters["execute.frames"] == 0   # no execute call


def test_one_node_span_per_graph_node_in_topological_order(recorded):
    g, rec, r, _ = recorded
    nodes = r["name"] == NAME["node"]
    assert nodes.sum() == len(g.nodes) == 14
    assert [rec.label(k) for k in r["node"][nodes]] == \
        [g.nodes[n].name for n in g.topo_order()]
    assert [rec.label(k) for k in r["kind"][nodes]] == \
        [g.nodes[n].kind.name for n in g.topo_order()]
    assert np.all(r["parent"][nodes] == 0)


def test_four_phases_under_each_conv_and_dense_node(recorded):
    g, rec, r, _ = recorded
    compute = [g.nodes[n].name for n in g.topo_order()
               if g.nodes[n].kind in (OpKind.CONV, OpKind.MVM)]
    assert len(compute) == 10
    for i in np.flatnonzero(r["name"] == NAME["node"]):
        children = [obs.SPAN_NAMES[k] for k in r["name"][r["parent"] == i]]
        name = rec.label(r["node"][i])
        assert children == (list(PHASES) if name in compute else []), name
        # each phase is tagged with its node
        assert all(rec.label(k) == name for k in r["node"][r["parent"] == i])


def test_weight_tensors_counted_once_per_call(resnet8, recorded):
    # once per call that traces the program: warm calls quantise no tensor
    # on the host, their re-quantisation runs inside the compiled program
    _, rec, _, _ = recorded
    assert rec.counters["quant.weight.tensors"] == 10
    _, run = resnet8
    with obs.recording() as rec3:
        for _ in range(3):
            run()
    assert rec3.counters["quant.weight.tensors"] == 0
    assert rec3.counters["execute.traces"] == 0
    assert rec3.counters["execute.frames"] == 6
    assert (rec3.rows()["name"] == NAME["execute"]).sum() == 3


def test_logits_bit_identical_with_recording_on_and_off(resnet8, recorded):
    _, run = resnet8
    # traced with recording off, on a graph of its own
    off = np.asarray(run(graphs.build_resnet_graph(resnet.RESNET8)))
    np.testing.assert_array_equal(np.asarray(recorded[3]), off)
    np.testing.assert_array_equal(np.asarray(run()), off)
