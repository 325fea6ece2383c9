#!/usr/bin/env python
"""mx.kernels pallas-vs-XLA sweep: for each kernel in pallas_ops/
(int8 serving matmul, fused Adam update, fused-LAMB passes, MoE
dispatch/combine) time the Pallas path against the XLA-native fallback
at the SAME shapes and record mx.inspect's roofline verdict for both —
the before/after evidence that owning the kernel moved a memory-bound
executable.

One JSON line per kernel, paired across runs by `metric`
(tools/bench_diff.py; `speedup` is registered higher-better,
`pallas_ms`/`xla_ms` lower-better):

  {"metric": "kernel_int8_matmul", "pallas_ms": ..., "xla_ms": ...,
   "speedup": ..., "roofline_xla": ..., "roofline_pallas": ...,
   "shape": ..., "platform": ..., "device_kind": ..., "devices": ...,
   "smoke_mode": false}

One process, one chip (a one-device mesh even on a four-chip host: the
global-view kernels engage only on a single-device step). Needs a TPU;
exits non-zero without one — interpreter time is not kernel time."""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _time_ms(fn, reps):
    import jax
    fn()                                     # warm (compile)
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e3


def _roofline(name, jitted, args):
    """mx.inspect roofline verdict for one jitted path."""
    from mxnet_tpu import inspect as mxi
    was = mxi.enabled()
    mxi.enable()
    try:
        rec = mxi.analyze_jit(name, f"bench_kernels:{name}", jitted, *args)
        return rec.roofline() if rec is not None else None
    finally:
        if not was:
            mxi.disable()


def main():
    from benchmarks import _provenance
    provenance = _provenance.start()

    import numpy as np
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import parallel
    parallel.make_mesh(devices=jax.devices()[:1])

    import importlib
    from mxnet_tpu import config
    im = importlib.import_module("mxnet_tpu.pallas_ops.int8_matmul")
    fu = importlib.import_module("mxnet_tpu.pallas_ops.fused_update")
    mk = importlib.import_module("mxnet_tpu.pallas_ops.moe_kernels")
    pa = importlib.import_module("mxnet_tpu.pallas_ops.paged_attention")

    reps = 20
    config.set("kernels_min_elements", 1)
    rng = np.random.RandomState(0)
    rows = []

    def emit(name, shape, xla_fn, xla_args, pallas_fn, pallas_args):
        config.set("kernels", "off")
        jx = jax.jit(xla_fn)
        xla_ms = _time_ms(lambda: jx(*xla_args), reps)
        roof_x = _roofline(f"{name}_xla", jx, xla_args)
        config.set("kernels", "on")
        jp = jax.jit(pallas_fn)
        pallas_ms = _time_ms(lambda: jp(*pallas_args), reps)
        roof_p = _roofline(f"{name}_pallas", jp, pallas_args)
        config.set("kernels", "off")
        row = {
            "metric": f"kernel_{name}",
            "pallas_ms": round(pallas_ms, 3),
            "xla_ms": round(xla_ms, 3),
            "speedup": round(xla_ms / pallas_ms, 3) if pallas_ms else None,
            "roofline_xla": roof_x,
            "roofline_pallas": roof_p,
            "shape": shape,
        }
        row.update(provenance)
        rows.append(row)
        print(json.dumps(row), flush=True)

    # -- int8 serving matmul ------------------------------------------
    M, K, O = 1024, 1024, 4096
    xq = jnp.asarray(rng.randint(-127, 128, (M, K)), jnp.int8)
    wq = jnp.asarray(rng.randint(-127, 128, (K, O)), jnp.int8)
    ws = jnp.asarray(rng.rand(O).astype(np.float32) * 0.1)
    bias = jnp.asarray(rng.randn(O).astype(np.float32))
    emit("int8_matmul", f"{M}x{K}x{O}",
         functools.partial(im.int8_matmul_reference, relu=True),
         (xq, wq, jnp.float32(0.02), ws, bias),
         functools.partial(im.int8_matmul, relu=True),
         (xq, wq, jnp.float32(0.02), ws, bias))

    # -- fused Adam update --------------------------------------------
    n = 8 << 20
    w = jnp.asarray(rng.randn(n).astype(np.float32))
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    m = jnp.zeros(n, jnp.float32)
    v = jnp.zeros(n, jnp.float32)
    upd_args = (w, g, m, v, jnp.float32(1e-3))
    emit("fused_adam", f"{n}",
         functools.partial(fu.adam_update_reference, beta1=0.9,
                           beta2=0.999, epsilon=1e-8, wd=0.01,
                           rescale_grad=1.0, clip_gradient=1.0),
         upd_args,
         functools.partial(fu.adam_update, wd=0.01, clip_gradient=1.0),
         upd_args)

    # -- fused MoE dispatch/combine -----------------------------------
    # both MoE kernels hold a whole operand in one VMEM block, so the
    # shape stays under the 16 MB scoped-VMEM limit (README, "Kernel
    # library": N8192 D2048 E16 is refused at compile time)
    N, D, E = 1024, 512, 8
    C = max(N // E, 1)
    x = jnp.asarray(rng.randn(N, D).astype(np.float32))
    expert = jnp.asarray(rng.randint(0, E, N), jnp.int32)
    # realistic positions: slot within the chosen expert's buffer
    pos = np.zeros(N, np.int32)
    counts = {}
    for i, e in enumerate(np.asarray(expert)):
        pos[i] = counts.get(int(e), 0)
        counts[int(e)] = pos[i] + 1
    pos = jnp.asarray(pos)
    gate = jnp.asarray(rng.rand(N).astype(np.float32))

    def roundtrip_ref(x_, expert_, pos_, gate_):
        buf = mk.dispatch_reference(x_, expert_, pos_, E, C)
        return mk.combine_reference(buf, expert_, pos_, gate_)

    def roundtrip_pallas(x_, expert_, pos_, gate_):
        buf = mk.dispatch_to_experts(x_, expert_, pos_, E, C)
        return mk.combine_from_experts(buf, expert_, pos_, gate_)

    emit("moe_dispatch_combine", f"N{N}xD{D}xE{E}xC{C}",
         roundtrip_ref, (x, expert, pos, gate),
         roundtrip_pallas, (x, expert, pos, gate))

    # -- paged decode attention (mx.pages serving hot loop) ------------
    B, H, D, ps, n_pg = 32, 16, 128, 16, 128
    P = B * n_pg + 1
    q = jnp.asarray(rng.randn(B, H, 1, D).astype(np.float32))
    k_pg = jnp.asarray(rng.randn(P, H, ps, D).astype(np.float32))
    v_pg = jnp.asarray(rng.randn(P, H, ps, D).astype(np.float32))
    tables = jnp.asarray(
        rng.permutation(P - 1)[: B * n_pg].reshape(B, n_pg) + 1,
        jnp.int32)
    t = jnp.asarray(rng.randint(0, n_pg * ps, B), jnp.int32)
    emit("paged_attention", f"B{B}xH{H}xD{D}xps{ps}xn{n_pg}",
         pa.paged_attention_reference, (q, k_pg, v_pg, tables, t),
         pa.paged_attention, (q, k_pg, v_pg, tables, t))
    _provenance.ledger_append("bench_kernels", rows)


if __name__ == "__main__":
    main()
