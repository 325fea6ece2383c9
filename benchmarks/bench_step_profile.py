#!/usr/bin/env python
"""Per-phase breakdown of the full BERT-base train step (VERDICT r4 #7):
the next perf lever after attention should be chosen from data.

Phases, each jitted + timed INDEPENDENTLY at the bench shapes
(b32/seq512 BERT-base), in one process on ONE chip (a one-device mesh
even on a four-chip host, so the standalone phases and the full step see
the same device). Needs a TPU; exits non-zero without one:

  embed_fwd/fwdbwd  token+type+position embedding + LN      (BERTEmbedStage;
                    fwdbwd includes the table scatter-add gradient)
  attn_fwdbwd     one encoder layer's self-attention        (BERTAttention)
  layer_fwdbwd    one FULL encoder layer (attn + FFN + LNs) (BERTEncoderLayer)
  heads_fwdbwd    MLM gather/decode + NSP heads             (num_layers=0 model
                                                             minus embed_fwdbwd)
  lamb_apply      fused-LAMB optimizer pass at BERT-base N
  full_step       the real ShardedTrainer step (the bench.py number)

Prints ONE JSON line per phase: {"phase", "ms", "frac_of_step"} plus a
final {"phase": "unattributed"} row = full − (embed + L·layer + heads +
lamb); a large positive residual means inter-phase fusion/overhead is the
lever, a negative one means standalone compilation is slower than the fused
step (XLA fusing across phase boundaries — also informative).

Every timed region ends in a host scalar fetch of its last result.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def fence(x):
    import numpy as np
    return float(np.asarray(x).ravel()[0].astype("float32"))


def timeit(fn, args, reps):
    out = fn(*args)           # compile + warm
    fence(_first_leaf(out))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    fence(_first_leaf(out))
    return (time.perf_counter() - t0) / reps


def _first_leaf(out):
    import jax
    leaves = jax.tree_util.tree_leaves(out)
    x = leaves[0]
    return x.ravel()[:1] if hasattr(x, "ravel") else x


def main():
    from benchmarks import _provenance
    prov = _provenance.start()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon import functional_call
    from mxnet_tpu.models import bert as bert_mod

    parallel.make_mesh(devices=jax.devices()[:1])
    B, L, masked = 32, 512, 76
    cfg = bert_mod.bert_base_config(dtype="bfloat16")
    reps = 20
    nl = cfg["num_layers"]
    rows = []

    def row(phase, ms):
        rows.append({"phase": phase, "ms": round(ms, 3)})

    mx.random.seed(0)
    rng = np.random.RandomState(0)

    # ---- embed (fwd AND fwd+bwd: the 30522x768 table's scatter-add
    # gradient is a real cost that must land in THIS row, not "heads") ----
    embed = bert_mod.BERTEmbedStage(cfg)
    embed.initialize()
    efn, egp, eap = functional_call(embed, train=True)
    ep = [p.data()._data for _, p in egp]
    ea = [p.data()._data for _, p in eap]
    toks = jnp.asarray(rng.randint(0, cfg["vocab_size"], (B, L)), jnp.int32)
    f_embed = jax.jit(lambda p, t: efn(p, ea, jax.random.key(0), t)[0])
    t_embed_fwd = timeit(f_embed, (ep, toks), reps)
    row("embed_fwd", t_embed_fwd * 1e3)

    def eloss(params, t):
        out, _ = efn(params, ea, jax.random.key(0), t)
        while isinstance(out, (list, tuple)):
            out = out[0]
        return jnp.sum(out.astype(jnp.float32))

    ge = jax.jit(jax.grad(eloss))
    t_embed = timeit(ge, (ep, toks), reps)      # used for attribution below
    row("embed_fwdbwd", t_embed * 1e3)

    # ---- one attention / one full layer, fwd+bwd ----
    h = jnp.asarray(rng.randn(B, L, cfg["units"]), cfg["dtype"])
    for phase, blk in (
            ("attn", bert_mod.BERTAttention(cfg["units"], cfg["num_heads"],
                                            0.0, cfg["dtype"])),
            ("layer", bert_mod.BERTEncoderLayer(
                cfg["units"], cfg["hidden_size"], cfg["num_heads"], 0.0,
                cfg["dtype"]))):
        blk.initialize()
        bfn, bgp, bap = functional_call(blk, train=True)
        bp = [p.data()._data for _, p in bgp]
        ba = [p.data()._data for _, p in bap]

        def loss(params, x, _f=bfn, _a=ba):
            out, _ = _f(params, _a, jax.random.key(0), x)
            while isinstance(out, (list, tuple)):
                out = out[0]
            return jnp.sum(out.astype(jnp.float32))

        g = jax.jit(jax.grad(loss))
        t = timeit(g, (bp, h), reps)
        row(f"{phase}_fwdbwd", t * 1e3)

    # ---- heads (MLM gather/decode + NSP): num_layers=0 model − embed ----
    cfg0 = dict(cfg, num_layers=0)
    m0 = bert_mod.BERTForPretraining(cfg0)
    m0.initialize()
    b = bert_mod.make_synthetic_batch(cfg, B, L, masked, seed=0)
    hfn, hgp, hap = functional_call(m0, train=True)
    hp = [p.data()._data for _, p in hgp]
    ha = [p.data()._data for _, p in hap]
    args0 = tuple(jnp.asarray(b[k]) for k in
                  ("input_ids", "token_types", "valid_length",
                   "masked_positions"))

    def loss0(params, *inp):
        (mlm, nsp), _ = hfn(params, ha, jax.random.key(0), *inp)
        return (jnp.sum(mlm.astype(jnp.float32))
                + jnp.sum(nsp.astype(jnp.float32)))

    g0 = jax.jit(jax.grad(loss0))
    t_l0 = timeit(g0, (hp,) + args0, reps)
    t_heads = max(t_l0 - t_embed, 0.0)
    row("heads_fwdbwd", t_heads * 1e3)

    # ---- fused LAMB at BERT-base param count ----
    from mxnet_tpu.parallel.fused_lamb import FusedLamb
    shapes = [(1024, 1024)] * 84 + [(30522, 768), (768,)] * 2
    fl = FusedLamb(shapes, [jnp.float32] * len(shapes),
                   [0.01] * len(shapes), 0.9, 0.999, 1e-6, True, 1.0,
                   -1.0, -1.0, -1.0)
    N = fl.total
    step = jax.jit(fl.apply_flat)
    largs = (jnp.zeros(N), jnp.ones(N) * 1e-3, jnp.zeros(N), jnp.zeros(N),
             jnp.asarray(1.0), jnp.asarray(1e-3))
    t_lamb = timeit(lambda *a: step(*a)[0], largs, reps)
    row("lamb_apply", t_lamb * 1e3)

    # same pass with bf16 moment storage (config lamb_moments_dtype):
    # the bandwidth-bound apply should drop ~30% with the state bytes
    fl16 = FusedLamb(shapes, [jnp.float32] * len(shapes),
                     [0.01] * len(shapes), 0.9, 0.999, 1e-6, True, 1.0,
                     -1.0, -1.0, -1.0, moments_dtype=jnp.bfloat16)
    step16 = jax.jit(fl16.apply_flat)
    largs16 = (jnp.zeros(N), jnp.ones(N) * 1e-3,
               jnp.zeros(N, jnp.bfloat16), jnp.zeros(N, jnp.bfloat16),
               jnp.asarray(1.0), jnp.asarray(1e-3))
    t_lamb16 = timeit(lambda *a: step16(*a)[0], largs16, reps)
    row("lamb_apply_bf16_moments", t_lamb16 * 1e3)

    # ---- the real full step ----
    model = bert_mod.BERTForPretraining(cfg)
    mx.random.seed(0)
    model.initialize()
    trainer = parallel.ShardedTrainer(
        model, bert_mod.bert_pretrain_loss, "lamb",
        {"learning_rate": 1e-3, "wd": 0.01})
    data = [nd.array(b[k]) for k in
            ("input_ids", "token_types", "valid_length", "masked_positions")]
    labels = [nd.array(b[k]) for k in ("mlm_labels", "mlm_weights",
                                       "nsp_labels")]
    loss = trainer.step(data, labels)
    float(loss.asscalar())
    t0 = time.perf_counter()
    for _ in range(reps):
        loss = trainer.step(data, labels)
    float(loss.asscalar())
    t_full = (time.perf_counter() - t0) / reps
    row("full_step", t_full * 1e3)

    attributed = t_embed + nl * [r for r in rows
                                 if r["phase"] == "layer_fwdbwd"][0]["ms"] \
        / 1e3 + t_heads + t_lamb
    row("unattributed", (t_full - attributed) * 1e3)

    for r in rows:
        r["frac_of_step"] = round(
            r["ms"] * (nl if r["phase"] in ("attn_fwdbwd", "layer_fwdbwd")
                       else 1) / (t_full * 1e3), 3)
        r.update(prov)
        if r["phase"] in ("attn_fwdbwd", "layer_fwdbwd"):
            r["note"] = f"x{nl} layers -> frac_of_step"
        print(json.dumps(r), flush=True)
    _provenance.ledger_append("bench_step_profile", rows)


if __name__ == "__main__":
    main()
