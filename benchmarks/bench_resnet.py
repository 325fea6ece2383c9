#!/usr/bin/env python
"""ResNet-50 training throughput, images/sec/chip — the second
BASELINE.json metric (GluonCV ResNet-50). Same shape as bench.py: one
jitted sharded train step, bf16 compute, SGD+momentum, synthetic ImageNet
batches, one process driving every chip of the host. Prints ONE JSON line
carrying the device it ran on (benchmarks/_provenance.py); appends a run
record to the mx.ledger when `ledger_dir` is armed. Needs a TPU; exits
non-zero without one.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def main():
    from benchmarks import _provenance
    prov = _provenance.start()

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.models import resnet as resnet_mod

    n_dev = len(jax.devices())
    parallel.make_mesh(dp=-1)
    batch, size, steps, warmup = 128, 224, 20, 4

    net = resnet_mod.resnet50_v1(classes=1000)
    mx.random.seed(0)
    net.initialize()
    net.cast("bfloat16")
    lfn = gloss.SoftmaxCrossEntropyLoss()
    trainer = parallel.ShardedTrainer(
        net, lambda out, label: lfn(out, label), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})

    rng = np.random.RandomState(0)
    dtype = np.float32
    x = nd.array(rng.randn(batch, 3, size, size).astype(dtype))
    y = nd.array(rng.randint(0, 1000, batch).astype(np.float32))

    for _ in range(warmup):
        loss = trainer.step([x], [y])
    float(loss.asscalar())  # host fetch fences the warmup

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step([x], [y])
    loss_val = float(loss.asscalar())
    dt = time.perf_counter() - t0

    per_chip = batch * steps / dt / n_dev
    print(f"# devices={n_dev} batch={batch} size={size} "
          f"steps={steps} time={dt:.2f}s loss={loss_val:.3f}",
          file=sys.stderr)

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                           "BASELINE.json")) as f:
        baseline = json.load(f).get("published", {}) \
            .get("resnet50_images_per_sec_per_chip")
    row = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/s/chip",
        "vs_baseline": round(per_chip / baseline, 4) if baseline else 1.0,
    }
    row.update(prov)
    print(json.dumps(row))
    _provenance.ledger_append("bench_resnet", [row])


if __name__ == "__main__":
    main()
