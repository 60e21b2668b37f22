"""Execution-time cost models for the hybrid IMC/DPU platform.

The paper schedules on *measured* per-node execution times from the FPGA
IMCE.  Those measurements are not public, so we model them analytically
from the node's tensor shapes and the emulated hardware's documented
behaviour, and expose the model behind the same interface a measurement
table would use (``CostModel.time(node, pu_spec)``).  The paper's claims
are about *relative* behaviour (orderings, ratios, convergence), which an
analytic model reproduces; EXPERIMENTS.md §Paper-validation checks those
claims, not absolute milliseconds.

IMC PU model (weight-stationary crossbar, paper §III / NeuroSoC)
----------------------------------------------------------------
A conv/MVM node of weight shape (Cout, Cin*K*K) is tiled onto R x C
crossbars: ``tiles = ceil(Cin*K*K / R) * ceil(Cout / C)``.  Every output
position issues one analog MVM per row-tile; column tiles run in
parallel across the crossbars *within* the PU up to ``xbars_per_pu``;
beyond that they serialize.  Fused ReLU/SiLU is free (in the PU's
datapath).

    t_imc(node) = n_vectors * serial_tiles * t_mvm + t_setup
    n_vectors   = H_out * W_out          (1 for an MVM/linear node)
    serial_tiles= ceil(row_tiles * col_tiles / xbars_per_pu)

DPU model (digital elementwise/pool/move engine)
------------------------------------------------
    t_dpu(node) = out_elems / elem_rate + t_setup
conv/MVM *can* run on a DPU at ``dpu_mac_rate`` MAC/s (paper: "functions
similar to IMC-PUs are also supported but with lower performance").  A
PRODUCT of two activations (attention) is MAC work with no stationary
weights: priced the same way from its ``flops``, on a DPU only.

Transfers (compute-and-forward over shared DRAM / IPI)
------------------------------------------------------
    t_xfer(bytes) = bytes / dram_bw + t_ipi      (0 if same PU)

All constants live in a named ``HardwareProfile`` so experiments can swap
calibrations; ``IMCE_DEFAULT`` approximates the NeuroSoC-class emulator
(INT8, 512x512 crossbars).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Dict, List, Optional

from .graph import Graph, Node, OpKind, PUType


@dataclass(frozen=True)
class HardwareProfile:
    """Calibration constants for one emulated hardware generation."""

    name: str = "imce-default"
    # IMC side
    xbar_rows: int = 512
    xbar_cols: int = 512
    xbars_per_pu: int = 4
    t_mvm: float = 250e-9          # s per crossbar MVM issue
    imc_setup: float = 2e-6        # s fixed per node invocation
    #: stationary-weight capacity of one IMC PU (INT8 bytes).  Calibrated so
    #: ResNet18-CIFAR (2.8M params) on 8 IMC PUs reproduces Table I's
    #: "weights area" scale (several PUs near 100%).
    pu_weight_capacity: float = 700e3
    # DPU side
    dpu_elem_rate: float = 2.0e9   # elementwise ops / s
    dpu_mac_rate: float = 0.5e9    # MAC/s when conv/MVM falls back to DPU
    dpu_setup: float = 2e-6
    # interconnect (shared DRAM + inter-processor interrupts)
    dram_bw: float = 8.0e9         # bytes/s effective
    t_ipi: float = 3e-6            # s per forwarded tensor hand-off


IMCE_DEFAULT = HardwareProfile()

#: A faster-interconnect profile used in sensitivity studies.
IMCE_FAST_LINK = replace(IMCE_DEFAULT, name="imce-fast-link", dram_bw=32e9, t_ipi=1e-6)


@dataclass(frozen=True)
class PUSpec:
    """One physical processing unit instance."""

    pu_id: int
    pu_type: PUType
    #: relative speed factor (1.0 = profile nominal); lets experiments model
    #: heterogeneous-capacity fleets and degraded/straggler units.
    speed: float = 1.0
    weight_capacity: Optional[float] = None  # bytes; None -> profile default

    def capacity(self, prof: HardwareProfile) -> float:
        if self.weight_capacity is not None:
            return self.weight_capacity
        return prof.pu_weight_capacity if self.pu_type is PUType.IMC else math.inf


def make_pus(n_imc: int, n_dpu: int, profile: HardwareProfile = IMCE_DEFAULT,
             ) -> List[PUSpec]:
    """Standard fleet: ``n_imc`` IMC PUs then ``n_dpu`` DPU PUs, ids 1-based."""
    pus = [PUSpec(pu_id=i + 1, pu_type=PUType.IMC) for i in range(n_imc)]
    pus += [PUSpec(pu_id=n_imc + i + 1, pu_type=PUType.DPU) for i in range(n_dpu)]
    return pus


class CostModel:
    """Analytic per-node execution/transfer times on a hardware profile."""

    def __init__(self, profile: HardwareProfile = IMCE_DEFAULT) -> None:
        self.profile = profile
        self._cache: Dict[tuple, float] = {}

    # -- node execution ----------------------------------------------------
    def time(self, node: Node, pu_type: Optional[PUType] = None,
             speed: float = 1.0) -> float:
        """Execution time of ``node`` on a PU of ``pu_type`` (default: the
        node's preferred type)."""
        pu_type = pu_type or node.pu_type
        # Fast path: a per-node side table (attached to the node object,
        # so it can never alias across nodes) keyed by the profile
        # *object* plus the call args.  Node cost inputs are set at
        # construction time, so the entry stays valid for the node's
        # lifetime; a different profile simply misses into the slow path.
        tc = node.__dict__.get("_time_cache")
        if tc is not None and tc[0] is self.profile:
            t = tc[1].get((pu_type, speed))
            if t is not None:
                return t
        # Memoize on the cost-relevant content, never on object identity:
        # an id()-based key aliases when a dead node's address is reused by
        # a new graph, handing back a stale time (a CostModel routinely
        # outlives the graphs it prices, e.g. across benchmark sweeps).
        meta = node.meta
        key = (node.kind, pu_type, speed, node.flops, node.out_elems,
               meta.get("cin_kk"), meta.get("cout"), meta.get("n_vectors"))
        t = self._cache.get(key)
        if t is None:
            t = self._time_uncached(node, pu_type) / max(speed, 1e-12)
            self._cache[key] = t
        if tc is None or tc[0] is not self.profile:
            tc = (self.profile, {})
            node.__dict__["_time_cache"] = tc
        tc[1][(pu_type, speed)] = t
        return t

    def _time_uncached(self, node: Node, pu_type: PUType) -> float:
        p = self.profile
        if node.is_free():
            return 0.0
        if node.kind in (OpKind.CONV, OpKind.MVM):
            if pu_type is PUType.IMC:
                return self._imc_time(node)
            # digital fallback
            return node.flops / p.dpu_mac_rate + p.dpu_setup
        # digital ops; IMC PUs cannot run them at all.
        if pu_type is PUType.IMC:
            return math.inf
        if node.kind is OpKind.PRODUCT:
            return node.flops / p.dpu_mac_rate + p.dpu_setup
        return node.out_elems / p.dpu_elem_rate + p.dpu_setup

    def frame_time(self, node: Node, pu_type: Optional[PUType] = None,
                   speed: float = 1.0) -> float:
        """Per-frame amortized execution time (LRMP accounting).

        A node replicated ``k``-way serves every k-th frame round-robin, so
        each replica contributes ``time/k`` to its PU's steady-state
        per-frame load; the max-per-PU sum of these is the pipeline
        interval bound.  Identical to :meth:`time` for unreplicated nodes.
        """
        return self.time(node, pu_type, speed) / node.replica_count

    def _imc_time(self, node: Node) -> float:
        p = self.profile
        meta = node.meta
        cin_kk = meta.get("cin_kk")
        cout = meta.get("cout")
        n_vectors = meta.get("n_vectors")
        if cin_kk is None or cout is None or n_vectors is None:
            # Fallback purely from flops: flops = n_vectors * cin_kk * cout.
            # Assume a square-ish MVM the size of one crossbar.
            n_vectors = max(1.0, node.flops / (p.xbar_rows * p.xbar_cols))
            cin_kk, cout = p.xbar_rows, p.xbar_cols
        row_tiles = math.ceil(cin_kk / p.xbar_rows)
        col_tiles = math.ceil(cout / p.xbar_cols)
        serial = math.ceil(row_tiles * col_tiles / p.xbars_per_pu)
        return n_vectors * serial * p.t_mvm + p.imc_setup

    # -- transfers -----------------------------------------------------------
    def transfer(self, src: Node, same_pu: bool) -> float:
        if same_pu or src.out_bytes == 0:
            return 0.0
        p = self.profile
        return src.out_bytes / p.dram_bw + p.t_ipi

    # -- aggregates ------------------------------------------------------------
    def graph_times(self, g: Graph) -> Dict[int, float]:
        return {nid: self.time(n) for nid, n in g.nodes.items()}

    def longest_path(self, g: Graph) -> List[int]:
        return g.longest_path(lambda n: self.time(n))

    def table(self, g: Graph) -> str:
        """Debug: per-node cost table."""
        rows = ["id  name                          kind      pu    time_us  weightKB"]
        for nid in g.topo_order():
            n = g.nodes[nid]
            rows.append(
                f"{nid:<3d} {n.name:<28s} {n.kind.value:<9s} {n.pu_type.value:<5s}"
                f" {self.time(n)*1e6:8.1f} {n.weight_bytes/1e3:8.1f}"
            )
        return "\n".join(rows)
