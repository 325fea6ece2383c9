#!/usr/bin/env python
"""Flash-attention kernel efficiency sweep + LAMB step timing.

Prints one JSON line per configuration:
  * per-length flash fwd / fwd+bwd time, achieved TF/s, and kernel MXU
    efficiency = achieved / the device's bf16 peak (mx.inspect's table,
    keyed by device_kind)
  * fused-LAMB apply_flat wall time at BERT-base scale

One process, one chip. Needs a TPU; exits non-zero without one. Every
timed region ends in a host scalar fetch of its last result.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def fence(x):
    import numpy as np
    return float(np.asarray(x).ravel()[0])


def attn_flops(B, H, L, D, causal):
    # fwd: QK^T (2*B*H*L*L*D) + PV (2*B*H*L*L*D); bwd adds ~2.5x fwd
    f = 4 * B * H * L * L * D
    return f / 2 if causal else f


def main():
    from benchmarks import _provenance
    prov = _provenance.start()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.pallas_ops.flash_attention import flash_attention
    from mxnet_tpu import config, parallel
    from mxnet_tpu import inspect as mxinspect

    # one device even on a four-chip host: the fused-LAMB kernels engage
    # only on a single-device step
    parallel.make_mesh(devices=jax.devices()[:1])
    rows = []

    def emit(row):
        row.update(prov)
        rows.append(row)
        print(json.dumps(row), flush=True)

    peak = mxinspect.peak_flops_per_chip()

    B, H, D = 8, 12, 64
    config.set("pallas_bwd_min_len", 1)   # always the Pallas backward
    for L in (512, 1024, 2048, 4096, 8192):
        rng = np.random.RandomState(0)
        q, k, v = [jnp.asarray(rng.randn(B, H, L, D), jnp.bfloat16)
                   for _ in range(3)]
        for causal in (False, True):
            fwd = jax.jit(lambda q, k, v: flash_attention(
                q, k, v, causal=causal))
            grad = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
                flash_attention(q, k, v, causal=causal)
                .astype(jnp.float32)), argnums=(0, 1, 2)))
            fence(fwd(q, k, v)[:1, :1, :1, :1].astype(jnp.float32))
            reps = max(2, 4096 // (L // 512))
            t0 = time.perf_counter()
            for _ in range(reps):
                o = fwd(q, k, v)
            fence(o[:1, :1, :1, :1].astype(jnp.float32))
            t_fwd = (time.perf_counter() - t0) / reps
            g = grad(q, k, v)
            fence(g[0][:1, :1, :1, :1].astype(jnp.float32))
            t0 = time.perf_counter()
            for _ in range(max(2, reps // 3)):
                g = grad(q, k, v)
            fence(g[0][:1, :1, :1, :1].astype(jnp.float32))
            t_fb = (time.perf_counter() - t0) / max(2, reps // 3)
            f_fwd = attn_flops(B, H, L, D, causal)
            emit({
                "config": f"L={L}{'c' if causal else ''}",
                "fwd_ms": round(t_fwd * 1e3, 2),
                "fwdbwd_ms": round(t_fb * 1e3, 2),
                "fwd_tflops": round(f_fwd / t_fwd / 1e12, 1),
                "fwd_mxu_eff": round(f_fwd / t_fwd / peak, 3),
                "fwdbwd_mxu_eff": round(3.5 * f_fwd / t_fb / peak, 3),
            })

    # fused LAMB at BERT-base scale
    from mxnet_tpu.parallel.fused_lamb import FusedLamb
    shapes = [(1024, 1024)] * 84 + [(30522, 768), (768,)] * 2
    fl = FusedLamb(shapes, [jnp.float32] * len(shapes),
                   [0.01] * len(shapes), 0.9, 0.999, 1e-6, True, 1.0,
                   -1.0, -1.0, -1.0)
    N = fl.total
    w = jnp.zeros(N)
    gbuf = jnp.ones(N) * 1e-3
    m = jnp.zeros(N)
    vv = jnp.zeros(N)
    step = jax.jit(fl.apply_flat, donate_argnums=(0, 2, 3))
    t = jnp.asarray(1.0)
    lr = jnp.asarray(1e-3)
    w2, m2, v2 = step(w, gbuf, m, vv, t, lr)
    fence(w2[:1])
    reps = 20
    t0 = time.perf_counter()
    for _ in range(reps):
        w2, m2, v2 = step(w2, gbuf, m2, v2, t, lr)
    fence(w2[:1])
    dt = (time.perf_counter() - t0) / reps
    emit({
        "lamb_apply_ms": round(dt * 1e3, 2),
        "lamb_n_params_M": round(N / 1e6, 1),
        "lamb_eff_gbps": round(10 * N * 4 / dt / 1e9, 1),
    })
    _provenance.ledger_append("bench_attention", rows)


if __name__ == "__main__":
    main()
