"""Kernel micro-benchmarks.

Times the jnp reference implementations (XLA-compiled on this host) and
validates the Pallas kernels against them (interpret mode — Python
execution, so its wall time is NOT a TPU predictor; the TPU-side roofline
for each kernel is derived analytically below from BlockSpec tiling).

Run: PYTHONPATH=src python -m benchmarks.kernels
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.core.network import deliver_remote_ref
from repro.kernels import ops, ref

PEAK = 197e12
HBM = 819e9


def bench(fn, *args, iters=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    key = jax.random.PRNGKey(0)
    print("name,us_per_call,derived")

    # synapse_matmul at paper shape (per-device tile: 36 cols x 1240^2)
    c, n = 36, 1240
    k1, k2 = jax.random.split(key)
    spikes = (jax.random.uniform(k1, (c, n)) < 0.005).astype(jnp.float32)
    w = jax.random.normal(k2, (c, n, n))
    jref = jax.jit(ref.synapse_matmul_ref)
    t = bench(jref, spikes, w)
    flops = 2 * c * n * n
    tpu_t = max(flops / PEAK, (2 * c * n * n) / HBM)  # bf16 weights
    print(f"synapse_matmul_ref_cpu,{t*1e6:.0f},"
          f"{flops/t/1e9:.1f}GFLOP/s_host")
    print(f"synapse_matmul_tpu_roofline,{tpu_t*1e6:.1f},"
          f"memory-bound@{2*c*n*n/1e6:.0f}MB_weights")
    got = ops.synapse_matmul(spikes[:4, :256], w[:4, :256, :256])
    want = jref(spikes[:4, :256], w[:4, :256, :256])
    assert jnp.allclose(got, want, atol=1e-4), "pallas mismatch"

    # remote ELL gather (the XLA gather every impl uses) at paper shape
    kk = 248
    o = 20
    t_tbl = o * n
    s = (jax.random.uniform(k1, (c, t_tbl)) < 0.005).astype(jnp.float32)
    idx = jax.random.randint(k2, (c, n, kk), 0, t_tbl)
    wr = jax.random.normal(k1, (c, n, kk))
    jref2 = jax.jit(deliver_remote_ref)
    t = bench(jref2, s, idx, wr)
    bytes_moved = c * n * kk * (4 + 4 + 4)
    print(f"ell_gather_ref_cpu,{t*1e6:.0f},"
          f"{bytes_moved/t/1e9:.1f}GB/s_host")
    print(f"ell_gather_tpu_roofline,{bytes_moved/HBM*1e6:.1f},"
          f"gather-bandwidth-bound")

    # lif_step
    from repro.configs.base import NeuronConfig
    cfg = NeuronConfig()
    v = jax.random.uniform(k1, (c, n), maxval=21)
    cc = jax.random.uniform(k2, (c, n), maxval=2)
    r = jnp.zeros((c, n), jnp.int32)
    cur = jax.random.normal(k1, (c, n))

    def jref3(v, cc, r, cur):
        import math
        return ref.lif_step_ref(
            v, cc, r, cur,
            decay_v=math.exp(-1 / 20), decay_c=math.exp(-1 / 300),
            gain=(1 - math.exp(-1 / 20)) * 20,
            g_c=cfg.g_c, alpha_c=cfg.alpha_c, v_rest=0.0, v_reset=10.0,
            v_threshold=20.0, arp_steps=2)

    jref3 = jax.jit(jref3)
    t = bench(jref3, v, cc, r, cur)
    sbytes = c * n * 4 * 8
    print(f"lif_step_ref_cpu,{t*1e6:.0f},{sbytes/t/1e9:.1f}GB/s_host")
    print(f"lif_step_tpu_roofline,{sbytes/HBM*1e6:.2f},"
          f"fused-elementwise(8x4B/neuron)")


if __name__ == "__main__":
    main()
