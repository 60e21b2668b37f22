"""Smoke run of the deployment path on one TPU chip.

ResNet-18-CIFAR (30 nodes, full width, seeded random weights) is placed
with LBLP on 8 IMC + 4 DPU PUs and its rate predicted by the simulator on
the host.  The graph executor then serves seeded frames on the chip in
float and int8 mode, and the run checks:

* the float executor against ``resnet.forward`` (rtol/atol 1e-5) and the
  int8 executor against it (top-1 agreement >= 0.75, relative L2 error
  < 0.25), both at ``highest`` matmul precision, i.e. true float32;
* the int32 accumulator of the int8 conv against a NumPy int64 oracle,
  bit for bit, on operands whose sums pass 2**24;
* the Pallas kernels ``imc_mvm`` and ``imc_conv2d`` compiled for the chip
  (``interpret=False``) against ``repro.kernels.ref``.

    python3 chip_smoke.py

Every phase prints one line.  The first failed check raises, so the run
exits non-zero and prints no result.  Without a TPU (for example under
``JAX_PLATFORMS=cpu``) it stops at the device guard.  The last line of a
passing run is ``{"ok": true, "device": {...}}``.  Wall times are one-off
timings of this run, not benchmark numbers.
"""

from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import device  # noqa: E402
from repro.core import (CostModel, IMCESimulator, get_scheduler,  # noqa: E402
                        make_pus)
from repro.core.graph import OpKind  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.conv2d import imc_conv2d  # noqa: E402
from repro.kernels.imc_mvm import imc_mvm  # noqa: E402
from repro.models import quant  # noqa: E402
from repro.models.cnn import executor, graphs, resnet  # noqa: E402

CFG = resnet.RESNET18_CIFAR
SEED = 0              # weights, frames and kernel operands
N_SINGLE = 4          # batch-1 requests, then one request of BATCH frames
BATCH = 8


class CheckFailed(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def report(phase: str, t0: float, **fields) -> None:
    kv = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {kv} wall_s={time.perf_counter() - t0:.3f}", flush=True)


def cache_entries(cache_dir: str) -> int:
    return sum(len(files) for _, _, files in os.walk(cache_dir))


def schedule(graph):
    cm = CostModel()
    fleet = make_pus(8, 4)
    assignment = get_scheduler("lblp", cm).schedule(graph, fleet)
    return fleet, IMCESimulator(graph, cm).run(assignment, frames=96)


def serve(graph, params, frames):
    """N_SINGLE batch-1 requests then one batch of BATCH, in each mode.

    Returns ``{mode: (outputs, per-request seconds)}``; the first request
    at each batch size includes compilation.
    """
    requests = [frames[i:i + 1] for i in range(N_SINGLE)]
    requests.append(frames[N_SINGLE:N_SINGLE + BATCH])
    served = {}
    for mode in ("float", "int8"):
        fn = jax.jit(lambda p, x, mode=mode: executor.execute(
            graph, p, x, mode=mode))
        outs, secs = [], []
        for x in requests:
            t = time.perf_counter()
            outs.append(jax.block_until_ready(fn(params, x)))
            secs.append(time.perf_counter() - t)
        served[mode] = (outs, secs)
    return requests, served


def int64_conv(qx: np.ndarray, qw: np.ndarray) -> np.ndarray:
    """SAME-padded stride-1 NHWC x HWIO conv in NumPy int64."""
    k = qw.shape[0]
    x = np.pad(qx.astype(np.int64), ((0, 0), (k // 2, k // 2),
                                     (k // 2, k // 2), (0, 0)))
    w = qw.astype(np.int64)
    h, wd = qx.shape[1:3]
    out = np.zeros(qx.shape[:3] + (qw.shape[-1],), np.int64)
    for di in range(k):
        for dj in range(k):
            out += np.einsum("bhwc,co->bhwo", x[:, di:di + h, dj:dj + wd],
                             w[di, dj])
    return out


def conv_layers(graph):
    """Distinct (H, Cin, Cout, K, stride) of the graph's conv nodes."""
    layers = set()
    for node in graph.nodes.values():
        if node.kind == OpKind.CONV:
            m = node.meta
            layers.add((m["out_hw"][0] * m["stride"],
                        m["cin_kk"] // m["k"] ** 2, m["cout"], m["k"],
                        m["stride"]))
    return sorted(layers)


def check_outputs(served, want: np.ndarray) -> dict:
    """Float executor == reference; int8 executor close to it."""
    got_f = np.concatenate([np.asarray(y) for y in served["float"][0]])
    got_q = np.concatenate([np.asarray(y) for y in served["int8"][0]])
    float_err = float(np.max(np.abs(got_f - want)))
    check(np.allclose(got_f, want, rtol=1e-5, atol=1e-5),
          f"float executor differs from resnet.forward by {float_err}")
    check(bool(np.isfinite(got_q).all()), "int8 outputs not finite")
    agree = float(np.mean(np.argmax(got_q, -1) == np.argmax(want, -1)))
    rel = float(np.linalg.norm(got_q - want) / np.linalg.norm(want))
    check(agree >= 0.75, f"int8 top-1 agreement {agree} < 0.75")
    check(rel < 0.25, f"int8 relative L2 error {rel} >= 0.25")
    return {"float_max_abs_err": f"{float_err:.3e}",
            "int8_top1_agreement": agree, "int8_rel_l2_err": f"{rel:.5f}"}


def int8(rng, shape) -> np.ndarray:
    return rng.integers(-127, 128, shape, dtype=np.int8)


def check_accumulator(rng) -> int:
    """The executor's int32 conv on the widest ResNet-18 conv (4x4x256 ->
    256, 3x3) == the int64 oracle.  Sample 1 and output channel 0 take
    operands in [64, 127], so that their sums pass 2**24, where a float
    lowering would round."""
    qx, qw = int8(rng, (2, 4, 4, 256)), int8(rng, (3, 3, 256, 256))
    qx[1] = rng.integers(64, 128, qx[1].shape, dtype=np.int8)
    qw[..., 0] = rng.integers(64, 128, qw[..., 0].shape, dtype=np.int8)
    acc = np.asarray(jax.jit(quant.int8_conv_acc)(qx, qw)).astype(np.int64)
    want = int64_conv(qx, qw)
    peak = int(np.max(np.abs(want)))
    check(peak > 2 ** 24, f"accumulator test too weak: max |acc| {peak}")
    check(np.array_equal(acc, want),
          f"int32 accumulator differs from int64 oracle at "
          f"{int(np.sum(acc != want))} outputs")
    return peak


def check_kernels(rng, layers):
    """imc_mvm (fc at BATCH, 1024^3) and imc_conv2d (each ResNet-18 conv
    shape at BATCH) compiled for the chip == ``repro.kernels.ref``.
    Returns the case names and the largest absolute difference."""
    def epilogue(n, sw_max):
        return (rng.uniform(1e-3, sw_max, n).astype(np.float32),
                rng.standard_normal(n).astype(np.float32))

    cases = []
    for m, k, n in [(BATCH, 256, 10), (1024, 1024, 1024)]:
        qx, qw, sx = int8(rng, (m, k)), int8(rng, (k, n)), np.float32(0.02)
        sw, b = epilogue(n, 0.2)
        cases.append((f"mvm{m}x{k}x{n}", 1e-5,
                      imc_mvm(qx, qw, sx, sw, b, interpret=False),
                      ref.imc_mvm_ref(qx, qw, sx, sw, b)))
    for h, cin, n, k, s in layers:
        qx, qw = int8(rng, (BATCH, h, h, cin)), int8(rng, (k, k, cin, n))
        sx = np.float32(0.04)
        sw, b = epilogue(n, 0.1)
        cases.append((f"conv{h}x{h}x{cin}->{n}k{k}s{s}", 1e-4,
                      imc_conv2d(qx, qw, sx, sw, b, stride=s,
                                 interpret=False),
                      ref.conv2d_ref(qx, qw, sx, sw, b, stride=s)))
    worst = 0.0
    for name, tol, got, want in cases:
        got, want = np.asarray(got), np.asarray(want)
        check(got.shape == want.shape, f"kernel {name}: shape {got.shape}")
        err = float(np.max(np.abs(got - want)))
        check(np.allclose(got, want, rtol=tol, atol=tol),
              f"kernel {name} differs from ref by {err}")
        worst = max(worst, err)
    return [c[0] for c in cases], worst


def main() -> None:
    t_start = t0 = time.perf_counter()
    devices = device.require_tpu()
    dev = devices[0]
    report("device", t0, platform=dev.platform, kind=repr(dev.device_kind),
           count=len(devices))

    t0 = time.perf_counter()
    cache_dir = device.use_compile_cache()
    entries_before = cache_entries(cache_dir)
    report("cache", t0, dir=cache_dir, entries=entries_before)

    t0 = time.perf_counter()
    graph = graphs.build_resnet_graph(CFG)
    fleet, sim = schedule(graph)
    check(len(graph) == 30, f"ResNet-18 graph has {len(graph)} nodes, not 30")
    report("schedule", t0, model=graph.name, nodes=len(graph),
           pus=f"{len(fleet)}(8imc+4dpu)", scheduler="lblp",
           sim_predicted_rate_fps=f"{sim.rate:.1f}",
           sim_predicted_latency_ms=f"{sim.latency * 1e3:.4f}")

    k_params, k_frames = jax.random.split(jax.random.PRNGKey(SEED))
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        params = resnet.init(k_params, CFG)
        frames = jax.random.normal(k_frames, (N_SINGLE + BATCH, 32, 32, 3))
        requests, served = serve(graph, params, frames)
        fields = {}
        for mode, (_, secs) in served.items():
            ms = [f"{s * 1e3:.3f}" for s in secs]
            fields[f"{mode}_b1_ms"] = "[" + ",".join(ms[:N_SINGLE]) + "]"
            fields[f"{mode}_b{BATCH}_ms"] = ms[N_SINGLE]
        report("serve", t0, frames=N_SINGLE + BATCH, **fields,
               note="first_b1_and_b8_include_compile")

        t0 = time.perf_counter()
        forward = jax.jit(lambda p, x: resnet.forward(p, x, CFG))
        want = np.concatenate([np.asarray(forward(params, x))
                               for x in requests])
    report("check_outputs", t0, reference="resnet.forward@highest",
           **check_outputs(served, want))

    # operands come from NumPy: no device program per random draw
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    report("accumulator", t0, layer="4x4x256->256_k3",
           max_abs_acc=check_accumulator(rng), exact=True)

    t0 = time.perf_counter()
    names, err = check_kernels(rng, conv_layers(graph))
    report("kernels", t0, interpret=False, checked=len(names),
           max_abs_err=f"{err:.3e}", cases=",".join(names))

    report("done", t_start, cache_entries_before=entries_before,
           cache_entries_after=cache_entries(cache_dir))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
