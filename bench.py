#!/usr/bin/env python
"""Benchmark: BERT-base pretraining throughput, tokens/sec/chip.

The BASELINE.json headline metric (GluonNLP BERT tokens/sec/chip). Runs the
flagship path: one jitted train step (forward+loss+backward+LAMB), bf16
compute / f32 optimizer state, flash-attention Pallas kernel — in ONE
process, on the device JAX gives it.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} with
the device it ran on. Every number in it is a chip number: without a TPU,
on a device missing from mx.inspect's peak table, or on any failure, the
script exits non-zero and prints no row. `python bench.py ROW...` picks the
rows (default: all of bert_base, bert_large, resnet50), each run in this
same process one after the other.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmarks import _provenance  # noqa: E402

METRIC = "bert_base_pretrain_tokens_per_sec_per_chip"


def bench_bert_base():
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import check as mxcheck
    from mxnet_tpu import diagnostics, memsafe, nd, parallel, telemetry
    from mxnet_tpu import goodput as mxgoodput
    from mxnet_tpu import inspect as mxinspect
    from mxnet_tpu import trace as mxtrace
    from mxnet_tpu.models import bert as bert_mod

    # telemetry rides along (compile accounting happens during warmup, so
    # enable BEFORE the first step): the JSON line gets compile_time_s and
    # recompile_count so compile cost is separable from steady-state tok/s.
    # Trade-off: with telemetry on, ShardedTrainer.step fences each step
    # (block_until_ready), which trims host/device overlap.
    # mx.inspect rides along too: each warmup compile is analyzed once
    # (cost/memory analysis; warm via the persistent compile cache) so the
    # JSON line reports hardware-terms efficiency (mfu, achieved_tflops,
    # peak_device_bytes, comm_bytes_per_step), not just wall-clock
    telemetry.enable()
    mxinspect.enable()
    # mx.memsafe rides along too: each compile's pre-flight budget check
    # records predicted peak vs capacity, so the JSON line reports real
    # memory headroom
    memsafe.enable()
    # mx.check rides along in warn mode (one trace-only lint per compile):
    # the JSON line's check_findings field records whether the headline
    # configuration's graph is CLEAN — a perf trajectory whose findings
    # count creeps up caught a hazard before it cost a recompile or an OOM
    mxcheck.enable("warn")
    # mx.trace rides along (in-memory spans, no trace_dir): the JSON line
    # gets measured step-arrival skew and this rank's dominant span — the
    # gang-timeline trajectory next to the throughput one. Sampled steps
    # fence, but telemetry above already fences every step.
    mxtrace.enable()
    # mx.goodput rides along (memory-only, no goodput_dir): the JSON line
    # gets the run's goodput fraction (productive step seconds over the
    # armed wall-clock — compile/warmup drags it below 1.0 on a cold run)
    # and its top badput cause, so the ledger trajectory catches a
    # regression in where the bench's wall-clock WENT, not just how fast
    # the steady-state loop was
    mxgoodput.enable()

    backend = jax.default_backend()
    n_dev = len(jax.devices())
    parallel.make_mesh(dp=-1)

    batch, seq_len, masked = 32, 512, 76
    cfg = bert_mod.bert_base_config(dtype="bfloat16")
    steps, warmup = 20, 4

    model = bert_mod.BERTForPretraining(cfg)
    mx.random.seed(0)
    model.initialize()
    trainer = parallel.ShardedTrainer(
        model, bert_mod.bert_pretrain_loss, "lamb",
        {"learning_rate": 1e-3, "wd": 0.01})

    b = bert_mod.make_synthetic_batch(cfg, batch, seq_len, masked, seed=0)
    data = [nd.array(b[k]) for k in
            ("input_ids", "token_types", "valid_length", "masked_positions")]
    labels = [nd.array(b[k]) for k in ("mlm_labels", "mlm_weights", "nsp_labels")]

    # sync via scalar host fetch: the final loss depends on every prior
    # step's params, so one fetch fences the whole timed region
    for _ in range(warmup):
        loss = trainer.step(data, labels)
    float(loss.asscalar())

    # timed loop rides the overlapped pipeline (prefetch_to_mesh staging +
    # async dispatch) so the recorded tokens/s/chip reflects what training
    # actually achieves, not serialized H2D; MXNET_TPU_BENCH_PREFETCH=0
    # reverts to the serialized sync path for A/B runs
    use_prefetch = os.environ.get("MXNET_TPU_BENCH_PREFETCH", "1") != "0"
    t0 = time.perf_counter()
    if use_prefetch:
        from mxnet_tpu import dataflow
        with dataflow.prefetch_to_mesh(
                ((data, labels) for _ in range(steps)), trainer,
                depth=2) as pf:
            for d, l in pf:
                loss = trainer.step_async(d, l)
    else:
        for _ in range(steps):
            loss = trainer.step(data, labels)
    loss_val = float(loss.asscalar())
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq_len * steps / dt
    per_chip = tokens_per_sec / n_dev

    # rough MFU: BERT fwd+bwd ≈ 6 * params * tokens FLOPs. This IGNORES the
    # attention quadratic term (~9% extra at seq 512), i.e. est_mfu is a
    # slight UNDERestimate; stated here and in the JSON so the artifact is
    # self-interpreting.
    n_params = trainer.param_count
    flops_per_token = 6 * n_params
    # bf16 peak by device_kind, from mx.inspect's table (_provenance
    # already refused a device that is not in it)
    mfu = per_chip * flops_per_token / mxinspect.peak_flops_per_chip()

    print(f"# backend={backend} devices={n_dev} params={n_params/1e6:.1f}M "
          f"batch={batch} seq={seq_len} steps={steps} time={dt:.2f}s "
          f"loss={loss_val:.3f} est_mfu={mfu:.3f}", file=sys.stderr)

    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "BASELINE.json")) as f:
        baseline = json.load(f).get("published", {}).get(
            "bert_base_tokens_per_sec_per_chip")
    vs = per_chip / baseline if baseline else 1.0

    out = {
        "metric": METRIC,
        "value": round(per_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs, 4),
        # steady state should show recompile_count == 0: every recompile in
        # the timed loop is shape churn eating the reported throughput
        "compile_time_s": round(telemetry.histogram("compile_seconds").sum, 3),
        "recompile_count": int(telemetry.counter("recompile_total").value),
        # tail latency + memory trajectory: a p99 far above p50 means the
        # run stutters (recompiles, input stalls, host interference) even
        # when mean throughput looks healthy; RSS creep across rounds is
        # the host-side leak detector
        "step_p99_ms": round(
            (telemetry.histogram("trainer_step_seconds").percentile(99)
             or 0.0) * 1e3, 3),
        "peak_host_rss_mb": round(diagnostics.host_peak_rss_mb(), 1),
        # the overlap story in two numbers: how much of the run the
        # consumer spent starved for input (host batch + H2D staging wait
        # vs device step time), and how many compiles the persistent
        # cache served warm (0 on a cold first run; the whole point is the
        # NEXT run)
        "input_stall_fraction": _input_stall_fraction(telemetry),
        "compile_cache_hit": int(
            telemetry.counter("compile_cache_hits_total").value),
        "prefetch": bool(use_prefetch),
    }
    # mx.goodput wall-clock accounting: what fraction of the armed run
    # produced new kept progress, and where the rest went (a cold run
    # says "compile"; a stall regression flips it to "input_stall")
    _gp = mxgoodput.snapshot()
    out["goodput_fraction"] = _gp.get("goodput_fraction")
    out["badput_top_cause"] = _gp.get("top_badput_cause")
    # XLA-cost-model efficiency of the train-step executable (mx.inspect):
    # all four fields always present, null when the backend withheld the
    # input (single device -> comm_bytes_per_step null). Unlike
    # est_mfu_nominal_peak below (6*N*T paper arithmetic), "mfu" divides
    # XLA's own flop count for the compiled program by measured step time
    # and the per-chip peak table
    insp = mxinspect.summary()
    rnd = lambda v, n: round(v, n) if isinstance(v, (int, float)) else None
    out["mfu"] = rnd(insp.get("mfu"), 4)
    out["achieved_tflops"] = rnd(insp.get("achieved_tflops"), 4)
    out["peak_device_bytes"] = insp.get("peak_device_bytes")
    out["comm_bytes_per_step"] = insp.get("comm_bytes_per_step")
    # memory-safety fields (mx.memsafe): headroom from the last pre-flight
    # check, the effective remat policy the timed model ran under, and how
    # many OOMs the degradation ladder survived (0 on a healthy fit)
    out["memory_headroom_bytes"] = memsafe.last_headroom_bytes()
    out["remat_policy"] = memsafe.policy_marker(model)
    out["oom_recoveries"] = int(
        telemetry.counter("oom_recoveries_total").value)
    # mx.zero provenance: whether the headline trainer sharded its
    # optimizer state across the data axes, and the PER-DEVICE resident
    # opt-state bytes (sharded arrays count their shard) — the number the (D-1)/D memory win shows up in
    # when compared across zero on/off rows on the same mesh
    out["zero_enabled"] = bool(getattr(trainer, "_zero", False))
    # fused LAMB keeps its fp32 flat master in trainer.params — it IS
    # optimizer state (the README memory table's 12 bytes/param counts
    # master+m+v), so include it or the field under-reports by a third
    _opt_tree = (trainer.opt_state,
                 trainer.params if getattr(trainer, "_fused", False) else ())
    out["opt_state_bytes_per_device"] = int(memsafe.resident_bytes(
        _opt_tree)) if getattr(trainer, "_ready", False) else None
    # mx.check: graph + concurrency findings for the benched
    # configuration (0 = lint-clean; the trajectory should stay 0)
    out["check_findings"] = len(mxcheck.findings()) \
        + len(mxcheck.thread_findings())
    # mx.trace gang-timeline fields: p99 of the measured multi-rank
    # step-arrival spread at the collective boundary (null below 2
    # participants — a lone process cannot measure gang skew), and this
    # rank's dominant span as the local leg of the critical path (null on
    # 1 device, where there is no gang to attribute)
    out["step_skew_p99_ms"] = mxtrace.skew_p99_ms()
    out["critical_path"] = mxtrace.critical_path() if n_dev > 1 else None
    # 6*N*tokens model flops, attention quadratic term EXCLUDED
    # (~9% underestimate at seq 512)
    out["est_mfu_nominal_peak"] = round(mfu, 4)
    # the observers rode this row only: later rows run without them
    for mod in (mxgoodput, mxtrace, mxcheck, memsafe, mxinspect, telemetry):
        mod.disable()
    return out


def _input_stall_fraction(telemetry):
    """Share of (input wait + step) time the consumer spent blocked on the
    input pipeline. With prefetch_to_mesh staging, the host DataLoader is
    consumed by the worker thread (overlapped) — only the staging wait
    stalls the train loop; without it, host batch wait is the stall."""
    dev = telemetry.histogram("device_prefetch_wait_seconds")
    wait = dev.sum if dev.count \
        else telemetry.histogram("dataloader_wait_seconds").sum
    step = telemetry.histogram("trainer_step_seconds").sum
    denom = wait + step
    return round(wait / denom, 4) if denom > 0 else 0.0


def bench_bert_large(batch=32, seq_len=512, masked=76, steps=8,
                     warmup=2):
    """BERT-large (24L/1024/16H), per-layer remat active (cfg default),
    bf16 — the BASELINE.json north-star config.

    Batch 32 matches the BERT-base headline (at batch 8 the fixed cost of
    the 335M-param LAMB apply is spread over only 4096 tokens).  HBM at
    b32: 24 layer-boundary activations (32x512x1024 bf16 = 33.5 MB each,
    0.8 GB) + 335M params x 14 B of train state (~4.7 GB) against v5e's
    16 GB."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.models import bert as bert_mod

    n_dev = len(jax.devices())
    parallel.make_mesh(dp=-1)
    cfg = bert_mod.bert_large_config(dtype="bfloat16")
    model = bert_mod.BERTForPretraining(cfg)
    mx.random.seed(0)
    model.initialize()
    trainer = parallel.ShardedTrainer(
        model, bert_mod.bert_pretrain_loss, "lamb",
        {"learning_rate": 1e-3, "wd": 0.01})
    b = bert_mod.make_synthetic_batch(cfg, batch, seq_len, masked, seed=0)
    data = [nd.array(b[k]) for k in
            ("input_ids", "token_types", "valid_length", "masked_positions")]
    labels = [nd.array(b[k]) for k in
              ("mlm_labels", "mlm_weights", "nsp_labels")]
    for _ in range(warmup):
        loss = trainer.step(data, labels)
    float(loss.asscalar())
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(data, labels)
    float(loss.asscalar())
    dt = time.perf_counter() - t0
    per_chip = batch * seq_len * steps / dt / n_dev
    res = {"bert_large_tokens_per_sec_per_chip": round(per_chip, 2),
           "bert_large_batch": batch}
    print(f"# bert_large batch={batch} seq={seq_len} steps={steps} "
          f"time={dt:.2f}s tok/s/chip={per_chip:.0f}", file=sys.stderr)
    return res


def bench_resnet50(batch=128, size=224, steps=10, warmup=3):
    """ResNet-50 v1 train step, bf16, SGD+momentum (BASELINE.json second
    published metric; full config in benchmarks/bench_resnet.py)."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.models import resnet as resnet_mod

    n_dev = len(jax.devices())
    parallel.make_mesh(dp=-1)
    net = resnet_mod.resnet50_v1(classes=1000)
    mx.random.seed(0)
    net.initialize()
    net.cast("bfloat16")
    lfn = gloss.SoftmaxCrossEntropyLoss()
    trainer = parallel.ShardedTrainer(
        net, lambda out, label: lfn(out, label), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})
    rng = np.random.RandomState(0)
    x = nd.array(rng.randn(batch, 3, size, size).astype(np.float32))
    y = nd.array(rng.randint(0, 1000, batch).astype(np.float32))
    for _ in range(warmup):
        loss = trainer.step([x], [y])
    float(loss.asscalar())
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step([x], [y])
    float(loss.asscalar())
    dt = time.perf_counter() - t0
    per_chip = batch * steps / dt / n_dev
    print(f"# resnet50 batch={batch} steps={steps} time={dt:.2f}s "
          f"img/s/chip={per_chip:.0f}", file=sys.stderr)
    return {"resnet50_images_per_sec_per_chip": round(per_chip, 2)}


ROWS = {"bert_base": bench_bert_base, "bert_large": bench_bert_large,
        "resnet50": bench_resnet50}


def main():
    rows = sys.argv[1:] or list(ROWS)
    unknown = [r for r in rows if r not in ROWS]
    if unknown:
        raise SystemExit(f"unknown row(s) {unknown}; choose from {list(ROWS)}")
    out = {}
    for row in rows:
        out.update(ROWS[row]())
    out.update(_provenance.device_fields())
    print(json.dumps(out), flush=True)
    _provenance.ledger_append("bench.py", [out])


if __name__ == "__main__":
    _provenance.start()
    main()
