#!/usr/bin/env python
"""Measure the chip's achievable matmul FLOP/s — the MFU denominator check.

A dense bf16 matmul large enough to saturate the MXU runs within a few
percent of the hardware's true peak; whatever ceiling this probe observes is
the honest denominator for every MFU number the bench reports. Motivated by
r05: the bench table listed "TPU v5 lite" (v5e) at 394 TFLOP/s, which is the
chip's *int8* rate — its bf16 rate is 197 TFLOP/s (the 394 entry was
inconsistent with the same table's bf16 entries for v4/275, v5p/459,
v6e/918). This probe exists so the table can never silently drift from
hardware again.

Prints one JSON line: {"device", "results": [{m,n,k,dtype,tflops}...],
"best_tflops"}.
"""
import json
import sys
import time

import jax
import jax.numpy as jnp


def measure(m, n, k, dtype, iters=20):
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, (m, k)).astype(dtype)
    b = jax.random.normal(jax.random.fold_in(key, 1), (k, n)).astype(dtype)

    @jax.jit
    def chain(x, b):
        # 4 dependent matmuls per call amortize dispatch;
        # the 1/sqrt(k) rescale keeps magnitudes stable across iterations
        # (it fuses into the matmul epilogue — no extra HBM pass)
        for _ in range(4):
            x = jax.lax.dot(x, b, preferred_element_type=dtype) * (k ** -0.5)
        return x

    # every dispatch consumes the previous output, so the chain is
    # serialized on the device
    x = chain(x0, b)
    float(x[0, 0])     # compile + warm; fetching bytes is the barrier
    t0 = time.perf_counter()
    for _ in range(iters):
        x = chain(x, b)
    float(x[0, 0])
    dt = time.perf_counter() - t0
    flops = iters * 4 * 2 * m * n * k
    return flops / dt


def main():
    dev = jax.devices()[0]
    print(f"[peak] {dev.platform} {getattr(dev, 'device_kind', '?')}",
          file=sys.stderr, flush=True)
    # n == k so the 4-matmul chain composes shape-wise
    shapes = [(4096, 4096, 4096), (8192, 8192, 8192), (16384, 8192, 8192)]
    results = []
    for m, n, k in shapes:
        for dtype in (jnp.bfloat16,):
            tf = measure(m, n, k, dtype) / 1e12
            print(f"[peak] {m}x{k}x{n} {jnp.dtype(dtype).name}: "
                  f"{tf:.1f} TFLOP/s", file=sys.stderr, flush=True)
            results.append({"m": m, "n": n, "k": k,
                            "dtype": jnp.dtype(dtype).name,
                            "tflops": round(tf, 1)})
    print(json.dumps({
        "device": str(getattr(dev, "device_kind", dev.platform)),
        "results": results,
        "best_tflops": max(r["tflops"] for r in results),
    }), flush=True)


if __name__ == "__main__":
    main()
