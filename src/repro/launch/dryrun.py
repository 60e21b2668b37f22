"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
on the production meshes, record memory/cost/collective analyses.

Run as a script, it appends ``--xla_force_host_platform_device_count=512``
to ``XLA_FLAGS`` before JAX's backend starts: the production meshes need
512 placeholder host devices.  Importing the module (the tests import
``parse_collectives``) changes no flag.

Per cell this produces (artifacts/dryrun/<arch>__<shape>__<mesh>.json):
  * compile success + wall time,
  * memory_analysis (per-device argument/output/temp bytes),
  * exact FLOPs / bytes via E/B scan-decomposition (XLA cost analysis
    counts a while-loop body once, so we compile an all-segments-at-1
    base and per-segment at-2 variants:
    corrected = f(all=1) + sum_seg (n_seg - 1) * B_seg, x n_microbatches
    for train — cross-validated against full-unroll compiles and
    first-principles analytics),
  * per-collective byte totals parsed from the optimized HLO, corrected
    the same way.

Usage:
  python -m repro.launch.dryrun --arch stablelm-1.6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""

import argparse
import dataclasses
import json
import os
import re
import time
import traceback
from typing import Dict, Optional, Tuple

import jax

from repro.configs import SHAPES, all_archs, get_config
from repro.configs.base import LMConfig, ShapeSpec, shape_supported
from repro.launch.mesh import make_production_mesh
from repro.models.lm import model, sharding

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "artifacts", "dryrun")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4,
    "u16": 2, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"=\s*(\w+)\[([\d,]*)\]\S*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")


def parse_collectives(hlo_text: str) -> Dict[str, float]:
    """Sum result-shape bytes per collective kind from optimized HLO."""
    out: Dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        size = _DTYPE_BYTES.get(dtype, 4)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        out[kind] = out.get(kind, 0.0) + size * n
    return out


# ---------------------------------------------------------------------------
# cell construction
# ---------------------------------------------------------------------------

def _variant(cfg: LMConfig, seg_counts: Dict[int, int],
             enc_counts: Optional[Dict[int, int]] = None) -> LMConfig:
    """Config with per-segment layer counts overridden."""
    segs = tuple(dataclasses.replace(s, n=seg_counts.get(i, 0))
                 for i, s in enumerate(cfg.segments))
    encs = cfg.enc_segments
    if encs:
        enc_counts = enc_counts or {}
        encs = tuple(dataclasses.replace(s, n=enc_counts.get(i, 0))
                     for i, s in enumerate(encs))
    return dataclasses.replace(cfg, segments=segs, enc_segments=encs)


def build_step(cfg: LMConfig, shape: ShapeSpec, mesh,
               single_microbatch: bool = False):
    """Returns (jitted_fn, abstract_args) for the cell."""
    rep = sharding.replicated(mesh)
    aparams = model.abstract_params(cfg)
    ps = sharding.param_shardings(cfg, mesh, aparams)

    if shape.mode == "train":
        eff_shape = shape
        if single_microbatch:
            eff_shape = dataclasses.replace(
                shape, global_batch=min(cfg.microbatch, shape.global_batch))
        aopt = model.abstract_opt_state(cfg)
        batch_spec = model.make_batch_spec(cfg, eff_shape)
        os_ = sharding.opt_shardings(cfg, mesh, aopt, aparams)
        bs = sharding.batch_shardings(mesh, batch_spec)
        step = model.make_train_step(cfg, mesh=mesh)
        met = {"loss": rep, "grad_norm": rep, "lr": rep}
        fn = jax.jit(step, in_shardings=(ps, os_, bs),
                     out_shardings=(ps, os_, met), donate_argnums=(0, 1))
        return fn, (aparams, aopt, batch_spec)

    if shape.mode == "prefill":
        batch_spec = model.make_batch_spec(cfg, shape)
        bs = sharding.batch_shardings(mesh, batch_spec)
        acache = model.init_cache_spec(cfg, shape)
        cs = sharding.cache_shardings(mesh, acache)
        step = model.make_prefill_step(cfg, s_max=shape.seq_len)
        fn = jax.jit(step, in_shardings=(ps, bs),
                     out_shardings=(rep, cs))
        return fn, (aparams, batch_spec)

    # decode
    batch_spec = model.make_batch_spec(cfg, shape)
    bs = sharding.batch_shardings(mesh, batch_spec)
    acache = model.init_cache_spec(cfg, shape)
    cs = sharding.cache_shardings(mesh, acache)
    step = model.make_decode_step(cfg)
    fn = jax.jit(step, in_shardings=(ps, bs["token"], cs),
                 out_shardings=(rep, cs), donate_argnums=(2,))
    return fn, (aparams, batch_spec["token"], acache)


def compile_cell(cfg: LMConfig, shape: ShapeSpec, mesh,
                 single_microbatch: bool = False):
    fn, args = build_step(cfg, shape, mesh, single_microbatch)
    # activation sharding constraints apply while tracing
    with sharding.activation_mesh(mesh):
        lowered = fn.lower(*args)
    compiled = lowered.compile()
    return compiled


def cost_of(compiled) -> Tuple[float, float, Dict[str, float]]:
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    coll = parse_collectives(compiled.as_text())
    return flops, byts, coll


def corrected_costs(cfg: LMConfig, shape: ShapeSpec, mesh) -> Dict:
    """E/B decomposition: exact flops/bytes/collectives despite rolled
    scans.

    E = 0-layer program; B_i = f(only segment i at 1 layer) - E;
    total = E + sum_i n_i * B_i  (x n_microbatches for train).

    jax emits a while loop even for scan length 1, so every variant
    counts each scan body exactly once — the decomposition is exact for
    FLOPs and was validated against a full-unroll compile and hand
    analytics (stablelm train: 5.80e13 vs 6.3e13 unrolled, the gap being
    unroll-mode fusion double-counting).  Bytes/collective deltas are
    clamped at >= 0: XLA:CPU fusion noise can make a 1-layer program
    report marginally fewer pre-fusion bytes than the 0-layer one.
    """
    n_mb = 1
    if shape.mode == "train":
        n_mb = max(shape.global_batch // min(cfg.microbatch,
                                             shape.global_batch), 1)

    cfg = dataclasses.replace(cfg, chunk_scan=False)  # exact chunk flops
    zero = _variant(cfg, {}, {})
    e_flops, e_bytes, e_coll = cost_of(
        compile_cell(zero, shape, mesh, single_microbatch=True))

    flops, byts = e_flops, e_bytes
    coll = dict(e_coll)
    per_seg = []

    def add_segment(kind_label, n_layers, one_cfg):
        nonlocal flops, byts, coll
        f1, b1, c1 = cost_of(
            compile_cell(one_cfg, shape, mesh, single_microbatch=True))
        bf = max(f1 - e_flops, 0.0)
        bb = max(b1 - e_bytes, 0.0)
        per_seg.append({"kind": kind_label, "n": n_layers,
                        "body_flops": bf, "body_bytes": bb})
        flops += n_layers * bf
        byts += n_layers * bb
        for k in set(c1) | set(coll):
            delta = max(c1.get(k, 0.0) - e_coll.get(k, 0.0), 0.0)
            coll[k] = coll.get(k, 0.0) + n_layers * delta

    for i, seg in enumerate(cfg.segments):
        add_segment(seg.kind, seg.n, _variant(cfg, {i: 1}, {}))
    for i, seg in enumerate(cfg.enc_segments):
        add_segment("enc:" + seg.kind, seg.n, _variant(cfg, {}, {i: 1}))

    return {
        "n_microbatches": n_mb,
        "flops_per_device": flops * n_mb,
        "bytes_per_device": byts * n_mb,
        "collective_bytes_per_device": {k: v * n_mb for k, v in coll.items()},
        "segments": per_seg,
    }


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             with_cost: bool = True) -> Dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    ok, reason = shape_supported(cfg, shape)
    result: Dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not ok:
        result.update(status="skipped", reason=reason)
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    compiled = compile_cell(cfg, shape, mesh)
    compile_s = time.time() - t0
    ma = compiled.memory_analysis()
    result.update(
        status="ok",
        compile_seconds=compile_s,
        devices=mesh.devices.size,
        memory={
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_estimate_bytes": int(ma.argument_size_in_bytes
                                       + ma.output_size_in_bytes
                                       + ma.temp_size_in_bytes
                                       - ma.alias_size_in_bytes),
        },
    )
    if with_cost:
        t1 = time.time()
        result["cost"] = corrected_costs(cfg, shape, mesh)
        result["cost_seconds"] = time.time() - t1
    return result


def cell_path(arch: str, shape_name: str, mesh_name: str) -> str:
    os.makedirs(ART_DIR, exist_ok=True)
    return os.path.join(ART_DIR, f"{arch}__{shape_name}__{mesh_name}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-cost", action="store_true",
                    help="compile-only (multi-pod pass)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = all_archs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]

    failures = []
    for multi_pod in meshes:
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
        for arch in archs:
            for shape_name in shapes:
                path = cell_path(arch, shape_name, mesh_name)
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {arch} x {shape_name} x {mesh_name}")
                    continue
                print(f"[run   ] {arch} x {shape_name} x {mesh_name} ...",
                      flush=True)
                try:
                    res = run_cell(arch, shape_name, multi_pod,
                                   with_cost=not args.no_cost)
                except Exception as e:  # record failures as data
                    res = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "failed",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                    failures.append((arch, shape_name, mesh_name))
                with open(path, "w") as f:
                    json.dump(res, f, indent=2)
                status = res["status"]
                extra = ""
                if status == "ok":
                    mem = res["memory"]["peak_estimate_bytes"] / 2**30
                    extra = (f" compile={res['compile_seconds']:.1f}s "
                             f"peak/device={mem:.2f}GiB")
                print(f"[{status:7s}] {arch} x {shape_name} x {mesh_name}"
                      f"{extra}", flush=True)
    if failures:
        print(f"\nFAILED cells: {failures}")
        raise SystemExit(1)
    print("\nall requested cells done")


if __name__ == "__main__":
    # read when the backend starts, which no import above does
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512"
                               ).strip()
    main()
