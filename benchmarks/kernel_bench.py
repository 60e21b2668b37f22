"""Oracle micro-benchmarks: wall time of the pure-jnp references in
``repro.kernels.ref`` (not the Pallas kernels) on whatever device JAX
picks.  Every result is labelled with that device's platform and kind;
none is a TPU number unless the label says ``tpu``."""

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref

from .common import csv_line, dump


def _time(fn, *args, iters=3):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def main() -> dict:
    dev = jax.devices()[0]
    device = f"{dev.platform}:{dev.device_kind}"
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())}}
    key = jax.random.PRNGKey(0)
    print(f"oracle wall times on {device}")
    print("oracle          M/B   K/S   N/hd  us_per_call")
    for (M, K, N) in [(256, 512, 512), (1024, 1024, 1024),
                      (128, 4096, 4096)]:
        k1, k2 = jax.random.split(key)
        qx = jax.random.randint(k1, (M, K), -127, 128, dtype=jnp.int8)
        qw = jax.random.randint(k2, (K, N), -127, 128, dtype=jnp.int8)
        sw = jnp.full((N,), 0.01, jnp.float32)
        us = _time(lambda a, b: ref.imc_mvm_ref(a, b, jnp.float32(0.1), sw),
                   qx, qw)
        name = f"imc_mvm_ref.{M}x{K}x{N}"
        print(f"imc_mvm_ref {M:6d} {K:5d} {N:5d} {us:12.1f}")
        csv_line(name, us, f"device={device}")
        out[name] = {"oracle_us": us}

    for (B, H, S, hd) in [(2, 8, 1024, 128), (1, 8, 4096, 128)]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, H, S, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, H, S, hd), jnp.float32)
        us = _time(lambda a, b, c: ref.flash_attention_ref(a, b, c), q, k, v)
        name = f"flash_ref.{B}x{H}x{S}x{hd}"
        print(f"flash_ref  {B:3d}x{H}  {S:5d} {hd:5d} {us:12.1f}")
        csv_line(name, us, f"device={device}")
        out[name] = {"oracle_us": us}

    path = dump("kernel_bench", out)
    print(f"artifact: {path}")
    return out


if __name__ == "__main__":
    main()
