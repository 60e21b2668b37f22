"""Shared test utilities: random deployment-graph strategies.

``hypothesis`` is an optional test dependency (``pip install .[test]``).
When it is absent the property tests must not break collection, so this
module exports drop-in ``given`` / ``settings`` / ``st`` shims: the
decorated tests are collected normally and skip with a clear reason.
Deterministic tests built on :func:`build_random_graph` run either way.
"""

from __future__ import annotations

import random

import pytest

try:
    from hypothesis import example, given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:  # degrade gracefully: collect, then skip
    HAVE_HYPOTHESIS = False
    SKIP_REASON = ("hypothesis is not installed — property test skipped "
                   "(install the [test] extra: pip install .[test])")

    class _StubStrategy:
        """Placeholder for strategy objects built at import time; supports
        arbitrary chaining (``st.tuples(...).map(...)``) but never runs."""

        def __call__(self, *a, **kw):
            return self

        def __getattr__(self, name):
            return self

    st = _StubStrategy()  # type: ignore[assignment]

    def given(*_args, **_kwargs):
        def deco(fn):
            # signature (*a, **kw) on purpose: pytest must not treat the
            # hypothesis-bound parameters as fixtures.
            def skipper(*a, **kw):
                pytest.skip(SKIP_REASON)

            skipper.__name__ = fn.__name__
            skipper.__doc__ = fn.__doc__
            return skipper

        return deco

    def settings(*_args, **_kwargs):
        def deco(fn):
            return fn

        return deco

    example = settings


from repro.core.graph import Graph, OpKind

__all__ = ["example", "given", "settings", "st", "HAVE_HYPOTHESIS",
           "build_random_graph", "random_graph_st"]

IMC_OPS = [OpKind.CONV, OpKind.MVM]
DPU_OPS = [OpKind.ADD, OpKind.POOL_MAX, OpKind.POOL_AVG, OpKind.CONCAT,
           OpKind.RESHAPE, OpKind.SOFTMAX]


def build_random_graph(n_nodes: int, edge_density: float, seed: int,
                       imc_fraction: float = 0.6) -> Graph:
    """Random connected-ish DAG with mixed IMC/DPU nodes.

    Edges only go from lower to higher ids (guarantees acyclicity); every
    non-source node gets at least one predecessor so the graph is a single
    weakly-connected component rooted at node 1.
    """
    rng = random.Random(seed)
    g = Graph(f"rand-{seed}")
    for i in range(n_nodes):
        if rng.random() < imc_fraction:
            kind = rng.choice(IMC_OPS)
            weight = rng.uniform(1e3, 300e3)
            meta = {
                "cin_kk": rng.choice([27, 64, 144, 288, 576, 1152]),
                "cout": rng.choice([16, 32, 64, 128, 256]),
                "n_vectors": rng.choice([1, 64, 256, 1024, 4096]),
            }
        else:
            kind = rng.choice(DPU_OPS)
            weight = 0.0
            meta = {}
        g.add(
            f"n{i+1}", kind,
            flops=rng.uniform(1e5, 5e7),
            weight_bytes=weight,
            out_bytes=rng.uniform(1e3, 64e3),
            out_elems=rng.uniform(1e3, 64e3),
            meta=meta,
        )
    ids = sorted(g.nodes)
    for j_idx, j in enumerate(ids[1:], start=1):
        preds = [i for i in ids[:j_idx] if rng.random() < edge_density]
        if not preds:
            preds = [rng.choice(ids[:j_idx])]
        for p in preds:
            g.add_edge(p, j)
    g.validate()
    return g


random_graph_st = st.builds(
    build_random_graph,
    n_nodes=st.integers(min_value=2, max_value=24),
    edge_density=st.floats(min_value=0.05, max_value=0.5),
    seed=st.integers(min_value=0, max_value=10_000),
    imc_fraction=st.floats(min_value=0.2, max_value=0.9),
)
