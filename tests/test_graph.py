"""Graph IR: topo order, longest path, parallelism — unit + property tests
(the longest-path oracle is networkx)."""

import pytest

try:
    import networkx as nx
except ModuleNotFoundError:  # minimal-deps leg: oracle tests skip below
    nx = None

from repro.core.cost import CostModel
from repro.core.graph import Graph, GraphError, Node, OpKind, PUType

from helpers import build_random_graph, given, random_graph_st, settings

requires_nx = pytest.mark.skipif(nx is None, reason="networkx not installed")


def to_networkx(g: Graph, cm: CostModel) -> "nx.DiGraph":
    ng = nx.DiGraph()
    for nid, node in g.nodes.items():
        t = cm.time(node) if not node.is_free() else 0.0
        ng.add_node(nid, t=t)
    for s, d in g.edges():
        ng.add_edge(s, d)
    return ng


class TestBasics:
    def test_duplicate_id_rejected(self):
        g = Graph()
        g.add_node(Node(1, "a", OpKind.CONV))
        with pytest.raises(GraphError):
            g.add_node(Node(1, "b", OpKind.ADD))

    def test_cycle_detected(self):
        g = Graph()
        g.add_node(Node(1, "a", OpKind.CONV))
        g.add_node(Node(2, "b", OpKind.CONV))
        g.add_edge(1, 2)
        g.add_edge(2, 1)
        with pytest.raises(GraphError):
            g.topo_order()

    def test_default_pu_types(self):
        assert Node(1, "c", OpKind.CONV).pu_type is PUType.IMC
        assert Node(2, "m", OpKind.MVM).pu_type is PUType.IMC
        assert Node(3, "a", OpKind.ADD).pu_type is PUType.DPU
        assert Node(4, "p", OpKind.POOL_MAX).pu_type is PUType.DPU
        # two activations multiplied: no stationary weights for a crossbar
        assert Node(5, "qk", OpKind.PRODUCT).pu_type is PUType.DPU

    def test_activation_product_priced_by_its_macs(self):
        cm = CostModel()
        p = cm.profile
        # attention scores of 2 heads over 400 tokens: 400 x 400 x 2
        # elements, each a sum of 32 products
        node = Node(1, "qk", OpKind.PRODUCT, flops=2.0 * 10_240_000,
                    out_elems=320_000.0)
        assert cm.time(node) == pytest.approx(
            2.0 * 10_240_000 / p.dpu_mac_rate + p.dpu_setup)
        assert cm.time(node) > 320_000.0 / p.dpu_elem_rate + p.dpu_setup
        assert cm.time(node, PUType.IMC) == float("inf")

    def test_json_roundtrip(self):
        g = build_random_graph(12, 0.3, seed=7)
        g2 = Graph.from_json(g.to_json())
        assert sorted(g2.nodes) == sorted(g.nodes)
        assert sorted(g2.edges()) == sorted(g.edges())
        for nid in g.nodes:
            assert g2.nodes[nid].kind == g.nodes[nid].kind
            assert g2.nodes[nid].weight_bytes == g.nodes[nid].weight_bytes


class TestProperties:
    @given(random_graph_st)
    @settings(max_examples=60, deadline=None)
    def test_topo_order_respects_edges(self, g: Graph):
        order = g.topo_order()
        pos = {n: i for i, n in enumerate(order)}
        assert len(order) == len(g.nodes)
        for s, d in g.edges():
            assert pos[s] < pos[d]

    @requires_nx
    @given(random_graph_st)
    @settings(max_examples=40, deadline=None)
    def test_longest_path_matches_networkx(self, g: Graph):
        cm = CostModel()
        lp = g.longest_path(lambda n: cm.time(n))
        # path must be a real path
        for a, b in zip(lp, lp[1:]):
            assert b in g.successors(a)
        my_len = sum(cm.time(g.nodes[n]) for n in lp if not g.nodes[n].is_free())

        # networkx oracle: put node weight on incoming edges + source handling
        ng = to_networkx(g, cm)
        best = 0.0
        topo = list(nx.topological_sort(ng))
        dist = {}
        for n in topo:
            t = ng.nodes[n]["t"]
            dist[n] = t + max((dist[p] for p in ng.predecessors(n)), default=0.0)
            best = max(best, dist[n])
        assert my_len == pytest.approx(best, rel=1e-9)

    @requires_nx
    @given(random_graph_st)
    @settings(max_examples=40, deadline=None)
    def test_is_parallel_matches_reachability(self, g: Graph):
        ng = nx.DiGraph(list(g.edges()))
        ng.add_nodes_from(g.nodes)
        ids = sorted(g.nodes)
        import itertools
        reach = {n: nx.descendants(ng, n) for n in ids}
        for a, b in itertools.combinations(ids[:12], 2):
            expected = (b not in reach[a]) and (a not in reach[b])
            assert g.is_parallel(a, b) == expected
            assert g.is_parallel(b, a) == expected

    @given(random_graph_st)
    @settings(max_examples=30, deadline=None)
    def test_levels_monotone_on_edges(self, g: Graph):
        lvl = g.depth_levels()
        for s, d in g.edges():
            assert lvl[d] > lvl[s]
