#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that mxnet_tpu still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of the models the repo supports, with random weights from a
seed, in ONE process that holds the chip(s) for the whole run:

  train_one_chip    BERT-base bf16, batch 32, sequence 512, LAMB:
                    make_mesh -> BERTForPretraining -> ShardedTrainer ->
                    step, then prefetch_to_mesh + step_async. Loss finite on
                    every step and lower after the last than the first; the
                    flash and both fused-LAMB kernels in the executable.
  train_four_chips  the same model and global batch over make_mesh(dp=-1) on
                    four devices: flash in the executable (under shard_map;
                    the fused-update kernels stay off on a multi-device step
                    by design), shards on four distinct devices, every
                    device's memory in use, a different dropout mask on every
                    shard, first-step loss (dropout 0) agreeing with one
                    chip. Reported as NOT RUN on a machine with fewer than
                    four devices.
  serve_one_chip    GPT-2 345M bf16 through serve.Server(slots=8,
                    page_size=16) on a one-device mesh: mixed-length prompts,
                    drain(), every request DONE with the tokens it asked
                    for; the paged-attention kernel in the executable.
  kernels           tools/tpu_validate.py: flash (fwd + grads; plain, mask,
                    causal, dropout oracle), paged attention at GPT-2
                    shapes, one fused-LAMB step kernels=auto vs off, int8
                    matmul at GPT-2 widths — each against its reference.

Nothing is caught: a phase that raises, or whose check fails, ends the run
with a traceback and a non-zero exit code. Without a TPU (or with
MXNET_TPU_PALLAS_INTERPRET=1) the script stops at once, naming what it
found. On success the last line of stdout is one JSON object,
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}.

`--rehearsal` runs the same phases on the CPU at tiny sizes through the
Pallas interpreter (four virtual devices) to debug the script itself. It
prints REHEARSAL, claims nothing about a device and prints no result line.
"""
import gc
import json
import os
import sys
import time

REHEARSAL = "--rehearsal" in sys.argv[1:]
if REHEARSAL:
    # before jax is imported: the CPU platform, four virtual devices, and
    # every kernel through the interpreter
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") \
        + " --xla_force_host_platform_device_count=4"
    os.environ["MXNET_TPU_PALLAS_INTERPRET"] = "1"

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import dataflow, nd, parallel, serve  # noqa: E402
from mxnet_tpu.models import bert as bert_mod  # noqa: E402
from mxnet_tpu.models import gpt as gpt_mod  # noqa: E402
from tools import tpu_validate  # noqa: E402

# persistent-compile-cache traffic, counted from jax's own events (the
# telemetry mirror in mx.dataflow only counts while telemetry is on)
CACHE = {"hits": 0, "misses": 0}


def _on_cache_event(event, **_kw):
    if event == "/jax/compilation_cache/cache_hits":
        CACHE["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        CACHE["misses"] += 1


def say(msg):
    print(msg, flush=True)


def require(ok, what):
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def timed(fn):
    """(result, seconds), the clock stopped after block_until_ready."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def phase(name):
    say(f"\n== {name} ==" + (" [REHEARSAL: CPU, interpreter, tiny sizes]"
                             if REHEARSAL else ""))
    return dict(CACHE), time.perf_counter()


def phase_end(name, start):
    cache0, t0 = start
    say(f"-- {name}: {time.perf_counter() - t0:.1f}s, compile cache "
        f"{CACHE['hits'] - cache0['hits']} hits / "
        f"{CACHE['misses'] - cache0['misses']} misses")


def setup():
    """Print what JAX found and where compiles are cached; stop unless it
    is a TPU running compiled kernels."""
    cache_dir = dataflow.ensure_compile_cache()
    jax.monitoring.register_event_listener(_on_cache_event)
    dev = jax.devices()[0]
    say(f"jax {jax.__version__}  platform={dev.platform}  "
        f"device_kind={dev.device_kind!r}  count={len(jax.devices())}")
    say("compile cache: " + cache_dir
        + ("  (JAX_COMPILATION_CACHE_DIR)"
           if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "  (default)"))
    if REHEARSAL:
        say("REHEARSAL: nothing below is a statement about a device")
        return
    if os.environ.get("MXNET_TPU_PALLAS_INTERPRET") == "1":
        sys.exit("chip_smoke: MXNET_TPU_PALLAS_INTERPRET=1 would run every "
                 "kernel through the interpreter; unset it on the chip")
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: jax found platform {dev.platform!r}, not "
                 f"'tpu' (JAX_PLATFORMS="
                 f"{os.environ.get('JAX_PLATFORMS')!r}); nothing was run")


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

def bert_case(**overrides):
    """(cfg, batch, seq_len, masked)."""
    if REHEARSAL:
        return bert_mod.bert_tiny_config(max_length=128, **overrides), \
            8, 128, 10
    return bert_mod.bert_base_config(dtype="bfloat16", **overrides), \
        32, 512, 76


def bert_batch(cfg, batch, seq_len, masked):
    b = bert_mod.make_synthetic_batch(cfg, batch, seq_len, masked, seed=0)
    data = [nd.array(b[k]) for k in
            ("input_ids", "token_types", "valid_length", "masked_positions")]
    labels = [nd.array(b[k]) for k in
              ("mlm_labels", "mlm_weights", "nsp_labels")]
    return data, labels


def bert_trainer(cfg):
    model = bert_mod.BERTForPretraining(cfg)
    mx.random.seed(0)
    model.initialize()
    return parallel.ShardedTrainer(
        model, bert_mod.bert_pretrain_loss, "lamb",
        {"learning_rate": 1e-3, "wd": 0.01})


# ---------------------------------------------------------------------------
# trainer
# ---------------------------------------------------------------------------

def train(devices, want_kernels):
    """A few BERT steps on a mesh of `devices`: three `step`s (the first
    compiles), twelve more through prefetch_to_mesh + step_async. Returns the
    trainer and the last staged batch for the caller's placement checks."""
    mesh = parallel.make_mesh(dp=-1, devices=devices)
    cfg, batch, seq_len, masked = bert_case()
    say(f"mesh {dict(mesh.shape)} on {[str(d) for d in devices]}")
    say(f"BERT {cfg['num_layers']}L/{cfg['units']} {cfg['dtype']} "
        f"batch={batch} seq={seq_len} masked={masked} "
        f"dropout={cfg['dropout']} optimizer=lamb")
    trainer = bert_trainer(cfg)
    data, labels = bert_batch(cfg, batch, seq_len, masked)

    found = tpu_validate.pallas_kernels(trainer.lower_step(data, labels))
    say(f"Pallas kernels in the lowered step: {found or 'none'}")
    if not REHEARSAL:     # the interpreter leaves no custom call to count
        for name in want_kernels:
            require(found.get(name, 0) >= 1,
                    f"kernel {name!r} missing from the "
                    f"{len(devices)}-device step: {found}")
        say("fused-update kernels: "
            + ("lamb_pass1 + lamb_pass2 engaged" if "lamb_pass1" in found
               else "off (multi-device step, by design: a global-view "
                    "pallas_call cannot be partitioned)"))

    losses, secs = [], []
    for _ in range(3):
        loss, dt = timed(lambda: trainer.step(data, labels)._data)
        losses.append(loss)
        secs.append(dt)
    staged = None
    with dataflow.prefetch_to_mesh(
            ((data, labels) for _ in range(12)), trainer, depth=2) as pf:
        t0 = time.perf_counter()
        for d, l in pf:
            staged = d
            losses.append(trainer.step_async(d, l)._data)
        jax.block_until_ready(losses[-1])
        async_s = (time.perf_counter() - t0) / 12
    loss_dev = {s.device for s in losses[-1].addressable_shards}
    losses = [float(x) for x in losses]
    say(f"first step {secs[0]:.1f}s (compile ~"
        f"{secs[0] - min(secs[1:]):.1f}s + run); step "
        f"{min(secs[1:]) * 1e3:.1f} ms; prefetch+step_async "
        f"{async_s * 1e3:.1f} ms/step")
    say("loss per step: " + " ".join(f"{x:.4f}" for x in losses))
    require(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    require(losses[-1] < losses[0],
            f"loss did not fall on a fixed batch: {losses}")
    require(loss_dev <= set(devices),
            f"loss lives on {loss_dev}, outside the mesh {devices}")
    return trainer, staged


def first_loss(devices, cfg, case):
    """First-step loss of a fresh trainer (same seed, same batch) on a
    mesh of `devices`."""
    parallel.make_mesh(dp=-1, devices=devices)
    trainer = bert_trainer(cfg)
    data, labels = bert_batch(cfg, *case)
    return float(trainer.step(data, labels)._data)


def train_one_chip():
    start = phase("train_one_chip")
    train(jax.devices()[:1], ("flash_fwd", "flash_dq", "flash_dkv",
                              "lamb_pass1", "lamb_pass2"))
    gc.collect()
    phase_end("train_one_chip", start)


def train_four_chips():
    if len(jax.devices()) < 4:
        say(f"\n== train_four_chips ==\ntrain_four_chips: NOT RUN "
            f"(jax sees {len(jax.devices())} device(s), the phase needs 4)")
        return False
    start = phase("train_four_chips")
    devices = jax.devices()[:4]
    trainer, staged = train(devices, ("flash_fwd", "flash_dq", "flash_dkv"))

    # is the work really spread?
    leaves = jax.tree.leaves(trainer.params)
    param_dev = {s.device for s in leaves[0].addressable_shards}
    batch_dev = {s.device for s in staged[0]._data.addressable_shards}
    require(len(param_dev) == 4, f"parameters sit on {param_dev}")
    require(len(batch_dev) == 4, f"the staged batch sits on {batch_dev}")
    shard = staged[0]._data.addressable_shards[0].data.shape
    say(f"parameters on {len(param_dev)} devices, batch on "
        f"{len(batch_dev)} (per-device input_ids {shard})")
    in_use = {str(d): (d.memory_stats() or {}).get("bytes_in_use")
              for d in devices}
    say(f"bytes_in_use: {in_use}")
    if not REHEARSAL:          # the CPU backend reports no memory stats
        require(all(v for v in in_use.values()),
                f"a device holds nothing: {in_use}")
    del trainer, staged
    gc.collect()

    # a different dropout mask on every shard: with one batch row per
    # device, every shard's kernel sees local row 0 — the masks differ
    # only because each shard folds its mesh position into the key.
    # Independent 0.7-keep masks agree on 0.7^2 + 0.3^2 = 0.58 of their
    # entries; identical masks on all of them.
    if not REHEARSAL:          # the interpreter cannot draw the TPU PRNG
        masks = tpu_validate.keep_masks(4, 2, 256, 0.3, jax.random.key(7))
        agree = [float((masks[0] == masks[b]).mean()) for b in (1, 2, 3)]
        say("dropout masks, shard 0 vs shards 1-3 agree on "
            + " ".join(f"{a:.3f}" for a in agree) + " of entries")
        require(all(a < 0.7 for a in agree),
                f"shards drew the same dropout mask: {agree}")

    # one chip vs four, dropout off for this comparison only. Tolerance:
    # same weights, same batch, bf16 forward; the per-device batch is 8
    # rows instead of 32, so XLA may tile the matmuls differently and the
    # mean over the batch is summed in another order — both bounded by a
    # bf16 ulp (2^-8) of an O(10) loss, far inside 1e-2 relative.
    cfg, *case = bert_case(dropout=0.0, attn_dropout=0.0)
    one = first_loss(devices[:1], cfg, case)
    gc.collect()
    four = first_loss(devices, cfg, case)
    gc.collect()
    say(f"first-step loss, dropout 0: one chip {one:.5f}  "
        f"four chips {four:.5f}  rel diff {abs(one - four) / abs(one):.2e}")
    require(abs(one - four) <= 1e-2 * abs(one),
            f"four-chip loss {four} disagrees with one-chip {one}")
    phase_end("train_four_chips", start)
    return True


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

def serve_one_chip():
    start = phase("serve_one_chip")
    # the server places nothing itself and the paged kernel reads the
    # installed mesh: one device, whatever the host holds
    device = jax.devices()[0]
    parallel.make_mesh(devices=[device])
    if REHEARSAL:
        cfg = gpt_mod.gpt_tiny_config()
        slots, page, buckets = 4, 8, [32, 64]
        lens, new = [5, 11, 20, 40], 6
    else:
        cfg = gpt_mod.gpt2_345m_config(dtype="bfloat16")
        slots, page, buckets = 8, 16, [128, 512]
        lens, new = [24, 57, 90, 130, 200, 310, 470], 24
    say(f"GPT {cfg['num_layers']}L/{cfg['units']} {cfg['dtype']} "
        f"scan_layers={cfg['scan_layers']} on {device}; slots={slots} "
        f"page_size={page} buckets={buckets}")
    model = gpt_mod.GPTForCausalLM(cfg)
    mx.random.seed(0)
    model.initialize()
    srv = serve.Server(model, slots=slots, page_size=page, buckets=buckets)

    for bucket in buckets:
        lowered = srv.lower_step(bucket)
        found = tpu_validate.pallas_kernels(lowered)
        say(f"bucket {bucket}: Pallas kernels in the lowered step of the "
            f"top rung ({srv._wide()} virtual rows): {found or 'none'}")
        if not REHEARSAL:
            # once through the layers: each kernel once a layer, in no loop
            for kernel in ("paged_attention", "kv_page_write"):
                require(found.get(kernel, 0) == cfg["num_layers"],
                        f"{kernel} x{cfg['num_layers']} expected in the "
                        f"bucket-{bucket} step: {found}")
            require("stablehlo.while" not in lowered.as_text(),
                    f"a loop in the bucket-{bucket} step, which feeds "
                    "its tokens through the layers once")

    rng = np.random.RandomState(0)
    for wave in ("first wave (compiles included)", "second wave (warm)"):
        reqs = [srv.submit(rng.randint(0, cfg["vocab_size"], (n,))
                           .astype(np.int32), max_new_tokens=new)
                for n in lens]
        t0 = time.perf_counter()
        srv.drain()
        dt = time.perf_counter() - t0
        say(f"{wave}: prompts {lens} + {new} new tokens each drained in "
            f"{dt:.1f}s")
        for r in reqs:
            say(f"  {r!r}")
            require(r.state == serve.DONE, f"request not DONE: {r!r}")
            require(len(r.tokens) == new,
                    f"asked for {new} tokens, got {len(r.tokens)}: {r!r}")
            require(all(0 <= tok < cfg["vocab_size"] for tok in r.tokens),
                    f"token outside the vocabulary: {r!r}")
    st = srv.stats()
    say(f"{st['completed']} requests, {st['tokens']} tokens, "
        f"{st['steps']} scheduler steps; dispatches by width "
        f"{st['width_dispatches']}, {st['rows_fed']} tokens fed in "
        f"{st['rows_dispatched']} virtual rows; executables "
        f"{st['executables']}")
    require(st["chunk_steps"] > 0 and st["token_steps"] > 0
            and set(st["width_dispatches"]) <= set(srv._rungs),
            f"the `slots` rung and a wider one of {srv._rungs} should have "
            f"run: {st['width_dispatches']}")
    srv.stop()
    if REHEARSAL:
        # one request through two page classes (window layers beside full)
        from mxnet_tpu.models import laguna
        model = laguna.LagunaForCausalLM(laguna.laguna_tiny_config())
        model.initialize()
        srv = serve.Server(model, slots=2, page_size=4, buckets=[64],
                           pool_pages=32, prefill_chunk=4)
        req = srv.submit(rng.randint(0, 96, (30,)), max_new_tokens=6)
        srv.drain()
        st = srv.stats()
        say(f"Laguna 5L tiny, window 12: {req!r}; window pages returned "
            f"{st['window_pages_freed']}, in use {st['pages_in_use']}")
        require(req.state == serve.DONE and st["window_pages_freed"] > 0,
                f"a request through both page classes: {req!r}, {st}")
        srv.stop()
        # one request through a latent cache with no indexer (every row
        # attends its whole context through paged_latent_attention)
        from mxnet_tpu.models import deepseek
        model = deepseek.DeepseekForCausalLM(deepseek.deepseek_tiny_config())
        model.initialize()
        srv = serve.Server(model, slots=2, page_size=4, buckets=[64],
                           pool_pages=32, prefill_chunk=4)
        req = srv.submit(rng.randint(0, 96, (30,)), max_new_tokens=6)
        srv.drain()
        st = srv.stats()
        say(f"DeepSeek-V2 3L tiny, 16 experts in 8 groups: {req!r}; rows "
            f"fed {st['attn_tokens']}, keys they saw {st['attn_ctx_tokens']}")
        require(req.state == serve.DONE
                and st["attn_sel_tokens"] == st["attn_ctx_tokens"],
                f"a request through the latent cache: {req!r}, {st}")
        srv.stop()
    del srv, model
    gc.collect()
    phase_end("serve_one_chip", start)


# ---------------------------------------------------------------------------
# kernels against their references
# ---------------------------------------------------------------------------

def kernels():
    start = phase("kernels")
    parallel.make_mesh(devices=jax.devices()[:1])
    if REHEARSAL:
        tpu_validate.flash_parity(B=1, H=2, L=256, D=64)
        say("  REHEARSAL: dropout checks skipped (the interpreter cannot "
            "draw the TPU PRNG)")
        tpu_validate.paged_parity(B=2, H=4, D=16, page_size=8, n_pg=4,
                                  dtype=jnp.float32, expect_kernel=False)
        tpu_validate.kv_write_parity(B=4, H=4, D=16, page_size=8, n_pages=24,
                                     dtype=jnp.float32, expect_kernel=False)
        tpu_validate.kv_write_parity(B=16, H=4, D=16, page_size=8,
                                     n_pages=48, dtype=jnp.float32,
                                     expect_kernel=False)
        tpu_validate.lamb_parity([(64, 64)] * 4 + [(64,)],
                                 expect_kernel=False)
        tpu_validate.int8_parity(M=8, K=128, O=256)
    else:
        tpu_validate.flash_parity()
        tpu_validate.flash_dropout_stats()
        tpu_validate.flash_dropout_oracle()
        # GPT-2 345M's decode shapes: 16 heads of 64, page 16, a 512 bucket
        tpu_validate.paged_parity(B=8, H=16, D=64, page_size=16, n_pg=32)
        # the benchmark's serving cell: 32 slots over a pool of 2,080
        # pages; then its passes of 64 and of 128 virtual rows
        tpu_validate.kv_write_parity()
        tpu_validate.kv_write_parity(B=64)
        tpu_validate.kv_write_parity(B=128)
        # BERT-base's own parameter layout (110M)
        cfg = bert_case()[0]
        model = bert_mod.BERTForPretraining(cfg)
        model.initialize()
        tpu_validate.lamb_parity(
            [tuple(p.shape) for p in model.collect_params().values()
             if p.grad_req != "null"])
        del model
        # GPT-2 widths: the MLP up-projection and the vocabulary head
        tpu_validate.int8_parity(M=8, K=768, O=3072)
        tpu_validate.int8_parity(M=8, K=768, O=50257)
    gc.collect()
    phase_end("kernels", start)


def main():
    unknown = [a for a in sys.argv[1:] if a != "--rehearsal"]
    if unknown:
        sys.exit(f"chip_smoke: unknown argument(s) {unknown}")
    t0 = time.perf_counter()
    setup()
    train_one_chip()
    ran_four = train_four_chips()
    serve_one_chip()
    kernels()
    say(f"\ntotal {time.perf_counter() - t0:.1f}s; compile cache "
        f"{CACHE['hits']} hits / {CACHE['misses']} misses; "
        "train_four_chips " + ("ran" if ran_four else "NOT RUN"))
    if REHEARSAL:
        say("REHEARSAL complete: every phase ran on the CPU; no device "
            "result")
        return
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
