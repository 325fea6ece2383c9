#!/usr/bin/env python
"""Collective-communication bandwidth benchmark (reference:
tools/bandwidth/ — the kvstore comm benchmarking scripts; here the
measured primitives are the XLA collectives that replace the reference's
transports: psum, all_gather, reduce_scatter, ppermute over a device
mesh's axis).

On real multi-chip hardware the numbers reflect ICI; on the virtual CPU
mesh (XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu)
they validate the harness only.

  python tools/comm_bench.py --size-mb 64 --axis dp
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                os.pardir)))


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--size-mb", type=float, default=64.0,
                   help="payload per device, MB")
    p.add_argument("--reps", type=int, default=10)
    p.add_argument("--axis", default="dp")
    p.add_argument("--dtype", default="float32")
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from mxnet_tpu import parallel

    mesh = parallel.make_mesh(**{args.axis: -1})
    n = mesh.shape[args.axis]
    if n < 2:
        print(f"# axis '{args.axis}' has size {n}; nothing to measure")
        return
    elems = int(args.size_mb * 1e6 / jnp.dtype(args.dtype).itemsize)
    elems -= elems % (n * n)   # reduce_scatter shards each shard n ways
    x = jnp.ones((elems,), args.dtype)

    # the package's one shard_map import site (the mx.check
    # `shard-map-import` AST rule keeps every caller on it)
    from mxnet_tpu.parallel._compat import shard_map

    def bench(name, fn, bytes_moved):
        f = jax.jit(shard_map(fn, mesh=mesh, in_specs=P(args.axis),
                              out_specs=P(args.axis)))
        r = f(x)
        float(np.asarray(r)[0])          # compile + fence
        t0 = time.perf_counter()
        for _ in range(args.reps):
            r = f(r if r.shape == x.shape else x)
        float(np.asarray(r)[0])
        dt = (time.perf_counter() - t0) / args.reps
        print(f"{name:16s} {dt * 1e3:8.2f} ms   "
              f"{bytes_moved / dt / 1e9:8.2f} GB/s algo-bw")

    per_dev = elems // n * jnp.dtype(args.dtype).itemsize
    print(f"# devices={n} axis={args.axis} payload/dev="
          f"{per_dev / 1e6:.1f}MB dtype={args.dtype}")
    # algorithmic bandwidth conventions: ring allreduce moves 2(n-1)/n of
    # the buffer, gather/scatter (n-1)/n, permute the full shard
    bench("psum", lambda a: jax.lax.psum(a, args.axis),
          2 * (n - 1) / n * per_dev * n)
    bench("all_gather",
          lambda a: jax.lax.all_gather(a, args.axis, tiled=True),
          (n - 1) / n * per_dev * n)
    bench("reduce_scatter",
          lambda a: jax.lax.psum_scatter(a, args.axis, tiled=True),
          (n - 1) / n * per_dev * n)
    bench("ppermute",
          lambda a: jax.lax.ppermute(
              a, args.axis, [(i, (i + 1) % n) for i in range(n)]),
          per_dev * n)


if __name__ == "__main__":
    main()
