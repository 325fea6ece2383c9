#!/usr/bin/env python
"""Autoregressive generation throughput: on-device whole-generation
program vs host-driven single-token stepping (the GPT `generate`
surface). The interesting number is the gap — every host-loop token pays
a host round trip, the on-device scan pays one.

One JSON line per row:
  {"path": "on_device"|"host_loop", "tokens_per_sec": ..., "ms_per_dispatch":
   ..., "dispatches": ..., "batch": B, "prompt": Lp, "new": N,
   "platform": ..., "device_kind": ..., "devices": ..., "smoke_mode": false}

tokens_per_sec is END-TO-END (prompt ingestion + N new tokens) so the two
rows are directly comparable; dispatches makes the mechanism visible —
the host loop pays Lp+N round trips (sequential one-token prefill +
generation), the on-device program pays 1.

GPT-2 117m bf16, one process, one chip (a one-device mesh even on a
four-chip host: the model sits on the first device). Needs a TPU; exits
non-zero without one. `generate` returns host arrays, so each timed call
ends in a device->host fetch.
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
    from mxnet_tpu import parallel
    from mxnet_tpu.models import gpt as gpt_mod

    parallel.make_mesh(devices=jax.devices()[:1])
    cfg = gpt_mod.gpt2_117m_config(dtype="bfloat16")
    B, Lp, N, reps = 8, 64, 64, 3

    model = gpt_mod.GPTForCausalLM(cfg)
    mx.random.seed(0)
    model.initialize()
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg["vocab_size"], (B, Lp)).astype(np.int32)

    rows = []
    for path, on_device in (("on_device", True), ("host_loop", False)):
        model.generate(prompt, max_new_tokens=N, on_device=on_device)  # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            out = model.generate(prompt, max_new_tokens=N,
                                 on_device=on_device)
        dt = (time.perf_counter() - t0) / reps
        assert out.shape == (B, N)
        dispatches = 1 if on_device else Lp + N
        row = {
            "path": path,
            "tokens_per_sec": round(B * N / dt, 1),
            "ms_per_dispatch": round(dt / dispatches * 1e3, 3),
            "dispatches": dispatches,
            "batch": B, "prompt": Lp, "new": N,
        }
        row.update(prov)
        rows.append(row)
        print(json.dumps(row), flush=True)
    _provenance.ledger_append("bench_generate", rows)


if __name__ == "__main__":
    main()
