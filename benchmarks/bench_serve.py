#!/usr/bin/env python
"""mx.serve load benchmark: a Poisson OPEN-LOOP generator (arrivals do
not wait for completions — the honest way to measure an overloadable
server) against the continuous-batching scheduler.

One JSON line:
  {"tokens_per_sec": ..., "requests_per_sec": ..., "ttft_p50_ms": ...,
   "ttft_p99_ms": ..., "tbt_p99_ms": ..., "queue_share": ...,
   "slo_violations": ..., "requests": ..., "completed": ..., "rejected":
   ..., "shed": ..., "deadline_missed": ..., "cancelled": ...,
   "degraded": ..., "requeues": ..., "slots": ..., "queue_depth": ...,
   "offered_rps": ..., "platform": ..., "device_kind": ..., "devices":
   ..., "smoke_mode": false}

tokens_per_sec counts GENERATED tokens over the
span from first submit to last completion; ttft is submit-to-first-
token. Knobs via env: MXNET_TPU_BENCH_SERVE_REQUESTS / _RATE (req/s) /
_DEADLINE_MS. GPT-2 117m bf16, one process, one chip: the server places
nothing itself, so the bench installs a one-device mesh (on a four-chip
host a wider mesh would silently turn the paged-attention kernel off
while the model sits on the first device). Needs a TPU; exits non-zero
without one. Rides the persistent compile cache like every bench.

mx.slo journals the measured window (MXNET_TPU_BENCH_SERVE_SLO=0 opts
out; the three slo fields are then null): tbt_p99_ms is the p99 gap
between consecutive generated tokens, queue_share the fraction of the
per-phase budget (queue/prefill/decode/stream) spent waiting for a
slot — mx.pages' future >=2x-TTFT gate reads its baseline from here —
and slo_violations the objective violations under the armed slo_*
knobs (all off by default: at the bench's low offered load the row
contract asserts zero). MXNET_TPU_SLO_DIR persists the journal tail
for tools/slo_report.py.

`--int8` (or MXNET_TPU_BENCH_SERVE_INT8=1) additionally drives the SAME
offered load through an int8-quantized copy of the model
(contrib.quantization.quantize_block -> the pallas_ops.int8_matmul
decode path) and reports int8_tokens_per_sec / int8_ttft_p99_ms in the
same row, so tools/bench_diff.py can compare the fp and int8 paths
(both fields are registered direction-aware there).

`--pages` (or MXNET_TPU_BENCH_SERVE_PAGES=1) re-drives the same
offered load through a pages=on server (mx.pages paged KV, chunked
prefill, and — unless MXNET_TPU_BENCH_SERVE_DRAFT=0 — self-draft
speculative decoding) and reports pages_tokens_per_sec /
pages_ttft_p50_ms / pages_ttft_p99_ms / prefix_hit_rate /
accepted_draft_rate plus pages_speedup (pages-vs-dense tokens/s) in
the same row. `--prefix` (or MXNET_TPU_BENCH_SERVE_PREFIX=1) switches
BOTH passes to the shared-prefix workload — every prompt opens with
one common system prefix and diverges in a short tail, the traffic
shape the prefix tree exists for ('workload' records which shape the
row measured).
"""
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    idx = min(len(sorted_vals) - 1,
              int(round(q / 100.0 * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def main():
    from benchmarks import _provenance
    prov = _provenance.start()

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import parallel, serve, slo
    from mxnet_tpu.models import gpt as gpt_mod

    slo_on = os.environ.get("MXNET_TPU_BENCH_SERVE_SLO", "1") == "1"

    parallel.make_mesh(devices=jax.devices()[:1])
    cfg = gpt_mod.gpt2_117m_config(dtype="bfloat16")
    n_requests, rate, slots = 64, 8.0, 8
    lp_range, new_range = (16, 64), (16, 64)
    prefix_mode = "--prefix" in sys.argv[1:] \
        or os.environ.get("MXNET_TPU_BENCH_SERVE_PREFIX") == "1"
    if prefix_mode:
        # the prefix workload is a CAPACITY comparison (pages-vs-dense
        # tokens/s): offer load well past dense capacity so tokens/s
        # measures the server, not the arrival process
        n_requests, rate = 64, 32.0
    n_requests = int(os.environ.get("MXNET_TPU_BENCH_SERVE_REQUESTS",
                                    n_requests))
    rate = float(os.environ.get("MXNET_TPU_BENCH_SERVE_RATE", rate))
    deadline_ms = float(os.environ.get("MXNET_TPU_BENCH_SERVE_DEADLINE_MS",
                                       30_000.0))

    model = gpt_mod.GPTForCausalLM(cfg)
    mx.random.seed(0)
    model.initialize()
    rng = np.random.RandomState(0)

    page_size = 16

    # pre-drawn offered load, shared by every pass: Poisson interarrivals
    # so arrivals are independent of how the server keeps up
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
    if prefix_mode:
        # shared-prefix shape: one common system prefix (a whole number
        # of pages so the prefix tree can match it block-for-block) and
        # a short unique tail per request
        pre_len = page_size * 6
        tail_range = (4, 16)
        shared = rng.randint(0, cfg["vocab_size"],
                             (pre_len,)).astype(np.int32)
        prompts = [np.concatenate(
            [shared,
             rng.randint(0, cfg["vocab_size"],
                         (rng.randint(*tail_range),)).astype(np.int32)])
            for _ in range(n_requests)]
    else:
        prompts = [rng.randint(0, cfg["vocab_size"],
                               (rng.randint(*lp_range),)).astype(np.int32)
                   for _ in range(n_requests)]
    news = [int(rng.randint(*new_range)) for _ in range(n_requests)]
    # one warm (prompt_len, max_new) pair per distinct total length:
    # warming covers EVERY bucket the pre-drawn load will touch, so the
    # measured window is steady-state for all passes — a single-length
    # warmup leaves the other buckets' jit compiles inside the window
    warm_pairs = {}
    for p, n in zip(prompts, news):
        warm_pairs.setdefault(len(p) + n, (len(p), n))

    def run_load(mdl, **srv_kw):
        srv = serve.Server(mdl, slots=slots, **srv_kw)
        warms = [srv.submit(rng.randint(0, cfg["vocab_size"],
                                        (lp,)).astype(np.int32),
                            max_new_tokens=n)
                 for lp, n in warm_pairs.values()]
        srv.drain()
        assert all(w.state == serve.DONE for w in warms)
        if slo_on:
            # arm AFTER the warmup so the journaled window is the
            # measured steady state, not the one-off compile; a fresh
            # tracker per pass keeps fp and int8 rows independent
            slo.disable()
            slo.reset()
            slo.enable()

        srv.start()
        reqs = []
        t0 = time.perf_counter()
        for i in range(n_requests):
            delay = arrivals[i] - (time.perf_counter() - t0)
            if delay > 0:
                time.sleep(delay)
            reqs.append(srv.submit(prompts[i], max_new_tokens=news[i],
                                   deadline_ms=deadline_ms))
        # a consumer per request: streams drain concurrently (and honor
        # any injected slow_client fault) without blocking the scheduler
        threads = [threading.Thread(target=lambda r=r: list(r.stream()))
                   for r in reqs]
        for th in threads:
            th.start()
        for r in reqs:
            r.result(timeout=600)
        wall = time.perf_counter() - t0
        for th in threads:
            th.join(timeout=60)
        srv.stop()

        st = srv.stats()
        snap = None
        if slo_on:
            snap = slo.snapshot()
            slo.disable()       # appends the summary when SLO_DIR is set
        ttfts = sorted(r.ttft_s * 1e3 for r in reqs
                       if r.ttft_s is not None)
        done = [r for r in reqs if r.state == serve.DONE]
        tokens = sum(len(r.tokens) for r in reqs)
        return srv, {
            "tokens_per_sec": round(tokens / wall, 1),
            "requests_per_sec": round(len(done) / wall, 2),
            "ttft_p50_ms": round(_percentile(ttfts, 50), 2)
            if ttfts else None,
            "ttft_p99_ms": round(_percentile(ttfts, 99), 2)
            if ttfts else None,
            "tbt_p99_ms": snap["tbt_p99_ms"] if snap else None,
            "queue_share": (snap["phase_share"]["queue"]
                            if snap else None),
            "slo_violations": (sum(snap["violations"].values())
                               if snap else None),
            "completed": len(done),
            "rejected": st["rejected"],
            "shed": st["shed"],
            "deadline_missed": st["expired"],
            "cancelled": st["cancelled"],
            "degraded": st["degraded"],
            "requeues": st["requeues"],
            "prefix_hit_rate": st.get("prefix_hit_rate"),
            "accepted_draft_rate": st.get("accepted_draft_rate"),
        }

    srv, stats = run_load(model)
    row = dict(stats)
    row.update({
        "requests": n_requests,
        "slots": slots,
        "queue_depth": srv._queue_depth,
        "offered_rps": round(rate, 2),
        "workload": "shared_prefix" if prefix_mode else "random",
    })
    row.update(prov)

    int8 = "--int8" in sys.argv[1:] \
        or os.environ.get("MXNET_TPU_BENCH_SERVE_INT8") == "1"
    if int8:
        # the quantized decode path (pallas_ops.int8_matmul via
        # QuantizedDense) under the SAME pre-drawn offered load, so
        # fp-vs-int8 tokens/s is an apples-to-apples pairing in one row
        from mxnet_tpu.contrib import quantization as _quant
        qmodel = gpt_mod.GPTForCausalLM(cfg)
        mx.random.seed(0)
        qmodel.initialize()
        _quant.quantize_block(qmodel)
        _, qstats = run_load(qmodel)
        row.update({
            "int8_tokens_per_sec": qstats["tokens_per_sec"],
            "int8_requests_per_sec": qstats["requests_per_sec"],
            "int8_ttft_p50_ms": qstats["ttft_p50_ms"],
            "int8_ttft_p99_ms": qstats["ttft_p99_ms"],
            "int8_completed": qstats["completed"],
        })

    pages = "--pages" in sys.argv[1:] \
        or os.environ.get("MXNET_TPU_BENCH_SERVE_PAGES") == "1"
    if pages:
        # the paged path (block-granular KV pool + prefix tree + chunked
        # prefill) under the SAME pre-drawn offered load, so pages-vs-
        # dense tokens/s and TTFT are an apples-to-apples pairing at
        # equal memory budget (pool defaults to slots * max_len pages).
        # Self-draft speculative decoding exercises the spec path with
        # ~full acceptance; MXNET_TPU_BENCH_SERVE_DRAFT=0 disables it.
        drafter = model \
            if os.environ.get("MXNET_TPU_BENCH_SERVE_DRAFT", "1") != "0" \
            else None
        _, pstats = run_load(model, pages="on", page_size=page_size,
                             drafter=drafter)
        base_tps = row["tokens_per_sec"] or 0.0
        row.update({
            "pages_tokens_per_sec": pstats["tokens_per_sec"],
            "pages_requests_per_sec": pstats["requests_per_sec"],
            "pages_ttft_p50_ms": pstats["ttft_p50_ms"],
            "pages_ttft_p99_ms": pstats["ttft_p99_ms"],
            "pages_completed": pstats["completed"],
            "prefix_hit_rate": pstats["prefix_hit_rate"],
            "accepted_draft_rate": pstats["accepted_draft_rate"],
            "pages_speedup": round(pstats["tokens_per_sec"] / base_tps, 2)
            if base_tps else None,
        })
    print(json.dumps(row), flush=True)
    _provenance.ledger_append("bench_serve", [row])


if __name__ == "__main__":
    main()
