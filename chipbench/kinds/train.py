"""kind `train`: a pretraining job through the normal entry points.

make_mesh -> model -> parallel.ShardedTrainer -> dataflow.prefetch_to_mesh
-> step_async, as chip_smoke.py proved it on the chip (PR 21). Weights come
from `--seed` on the device; the input is a cycle of seeded host batches
staged by the prefetcher, so staging is real. The harness keeps the device
at most `fence_lag` steps ahead of the host, so the window holds whole
steps and the clock stops on `block_until_ready` of the last loss.
"""
import itertools
import time

import numpy as np


def synthetic_batch(vocab, batch, seq_len, masked, seed):
    """One seeded pretraining batch as host arrays, (data, labels) in the
    order BERTForPretraining and bert_pretrain_loss take them. Copied from
    models.bert.make_synthetic_batch (the program's may change)."""
    rng = np.random.RandomState(seed)
    input_ids = rng.randint(0, vocab, (batch, seq_len)).astype(np.int32)
    token_types = (rng.rand(batch, seq_len) > 0.5).astype(np.int32)
    valid_length = np.full((batch,), seq_len, np.int32)
    positions = np.stack([rng.choice(seq_len, masked, replace=False)
                          for _ in range(batch)]).astype(np.int32)
    mlm_labels = rng.randint(0, vocab, (batch, masked)).astype(np.int32)
    mlm_weights = np.ones((batch, masked), np.float32)
    nsp_labels = rng.randint(0, 2, (batch,)).astype(np.int32)
    return ([input_ids, token_types, valid_length, positions],
            [mlm_labels, mlm_weights, nsp_labels])


def build(ctx):
    """(trainer, model config) on a mesh over the cell's devices."""
    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.models import bert

    model_cfg = dict(ctx.config["model"])
    family = model_cfg.pop("family")
    if family != "bert":
        raise ValueError(f"kind train knows the family 'bert', not {family!r}")
    cfg = bert.bert_base_config(**model_cfg)
    mesh = parallel.make_mesh(devices=ctx.devices, **ctx.traffic["mesh"])
    ctx.say(f"mesh {dict(mesh.shape)} on {[str(d) for d in ctx.devices]}")
    model = bert.BERTForPretraining(cfg)
    mx.random.seed(ctx.seed)
    model.initialize()
    job = ctx.config["job"]
    trainer = parallel.ShardedTrainer(
        model, bert.bert_pretrain_loss, job["optimizer"],
        dict(job["optimizer_params"]))
    return trainer, cfg


def run(ctx):
    import jax
    from jax.profiler import TraceAnnotation

    from chipbench import window
    from mxnet_tpu import dataflow

    job, traffic = ctx.config["job"], ctx.traffic
    chips = len(ctx.devices)
    batch = traffic["per_chip_batch"] * chips
    seq_len, masked = job["seq_len"], job["masked"]
    t_build = time.perf_counter()
    trainer, cfg = build(ctx)
    t_model = time.perf_counter()
    host = [synthetic_batch(cfg["vocab_size"], batch, seq_len, masked,
                            ctx.seed + i)
            for i in range(traffic["host_batches"])]
    ctx.say(f"{ctx.cell['name']}: {cfg['num_layers']}L/{cfg['units']} "
            f"{cfg['dtype']} global batch {batch} seq {seq_len} masked "
            f"{masked} {job['optimizer']} {job['optimizer_params']}; "
            f"{len(host)} host batches in a cycle")

    losses, waits = [], []
    lag = traffic["fence_lag"]

    with dataflow.prefetch_to_mesh(itertools.cycle(host), trainer,
                                   depth=traffic["prefetch_depth"]) as pf:
        feed = iter(pf)

        def one_step():
            """The one call site of the step: warm-up, window and traced
            stretch all trace and run through here (a Mosaic kernel's
            cache key holds the Python stack that traced it)."""
            t = time.perf_counter()
            with TraceAnnotation("bench.input"):
                data, labels = next(feed)
            waits.append(time.perf_counter() - t)
            with TraceAnnotation("bench.step"):
                losses.append(trainer.step_async(data, labels)._data)
            if len(losses) > lag:
                jax.block_until_ready(losses[-1 - lag])

        win = window.measure(
            ctx, one_step, lambda: jax.block_until_ready(losses[-1]),
            traffic["warmup_steps"], traffic["trace_steps"])

    n_warm, steps, elapsed = traffic["warmup_steps"], win.steps, win.t1 - win.t0
    ctx.say(f"set-up {win.setup_s:.1f}s: imports and device "
            f"{t_build - ctx.t_start:.1f}, model and trainer "
            f"{t_model - t_build:.1f}, host batches and {n_warm} warm-up "
            f"steps {win.t0 - t_model:.1f}")
    values = [float(x) for x in losses]
    bad = [i for i, v in enumerate(values) if not np.isfinite(v)]
    fell = float(np.mean(values[-5:])) < values[0]
    tokens_per_s = steps * batch * seq_len / elapsed
    ctx.say(f"loss first {values[0]:.4f}, mean of last five "
            f"{np.mean(values[-5:]):.4f}; {steps} steps in {elapsed:.3f}s")
    if ctx.peaks:
        n_params = trainer.param_count
        mfu = 6 * n_params * tokens_per_s / (
            chips * ctx.peaks["bf16_flops_per_s"])
        ctx.say(f"model FLOP/s utilization 6*N*tokens/s over peak: "
                f"{100 * mfu:.2f}% (N={n_params}, {chips} chip(s) at "
                f"{ctx.peaks['bf16_flops_per_s']:.3g} FLOP/s)")
    return {
        "correct": not bad and fell,
        "attempted": len(values), "failed": len(bad),
        "checks": {"losses_not_finite": [len(bad), 0],
                   "loss_last_five_over_first": [
                       float(np.mean(values[-5:])) / values[0], 1.0]},
        "end_to_end": {
            "train_tokens_per_s_per_chip": tokens_per_s / chips,
            "setup_s": win.setup_s},
        "spans": {"bench.input": waits[n_warm:n_warm + steps]},
        "counters": {},
        "shapes": {"batch_per_chip": traffic["per_chip_batch"],
                   "heads": cfg["num_heads"], "seq_len": seq_len,
                   "head_dim": cfg["units"] // cfg["num_heads"],
                   "layers": cfg["num_layers"], "units": cfg["units"],
                   "hidden": cfg["hidden_size"], "vocab": cfg["vocab_size"],
                   "masked": ctx.config["job"]["masked"],
                   "itemsize": jax.numpy.dtype(cfg["dtype"]).itemsize},
        "peaks": ctx.peaks,
        **window.trace_result(win),
    }
