"""BERT — the flagship workload (BASELINE.json: GluonNLP BERT pretraining).

Reference: GluonNLP's BERTModel/BERTEncoder over mxnet's fused attention ops
(`src/operator/contrib/transformer.cc`). TPU-first re-design:
  * attention = Pallas flash kernel (mxnet_tpu.pallas_ops), bf16 in/f32 acc
  * one jitted train step via parallel.ShardedTrainer (LAMB, weight-update
    sharding); tp rules shard QKV/FFN Megatron-style; sp rules enable ring
    attention for long sequences
  * MLM gathers masked positions before the vocab projection so the big
    (B,P,V) logits tensor — not (B,L,V) — hits the MXU

Pretraining objective matches GluonNLP: MLM over masked positions + NSP.
"""
from __future__ import annotations

import math

import numpy as np

from ..gluon import nn, HybridBlock, loss as gloss
from ..gluon.parameter import Parameter
from ..ndarray import NDArray
from ..ndarray import ndarray as F


def bert_base_config(**overrides):
    cfg = dict(vocab_size=30522, units=768, hidden_size=3072, num_layers=12,
               num_heads=12, max_length=512, type_vocab_size=2, dropout=0.1,
               attn_dropout=None, seq_parallel=False, dtype="float32",
               remat=False, scan_layers=False)
    cfg.update(overrides)
    return cfg


def bert_large_config(**overrides):
    # remat by default at large depth: recompute each encoder layer in the
    # backward pass (jax.checkpoint) so activation memory scales O(1) in
    # depth instead of O(num_layers) — the FLOPs-for-HBM trade that makes
    # BERT-large batch sizes fit (SURVEY §7.4 item 4).  scan_layers
    # compiles the layer body ONCE via lax.scan instead of unrolling 24
    # copies: >25 min cold compile down to ~BERT-base compile time.
    cfg = bert_base_config(units=1024, hidden_size=4096, num_layers=24,
                           num_heads=16, remat=True, scan_layers=True)
    cfg.update(overrides)
    return cfg


def bert_long_config(**overrides):
    """Long-context pretraining config: sequence sharded over the mesh's
    `sp` axis (ring attention — SURVEY §5.7 north-star). Attention-
    probability dropout must be 0 under the ring (hidden dropout stays)."""
    cfg = bert_base_config(max_length=8192, seq_parallel=True,
                           attn_dropout=0.0, remat=True)
    cfg.update(overrides)
    return cfg


def bert_tiny_config(**overrides):
    """Test-scale config."""
    cfg = bert_base_config(vocab_size=128, units=64, hidden_size=128,
                           num_layers=2, num_heads=4, max_length=64, dropout=0.0)
    cfg.update(overrides)
    return cfg


class BERTAttention(HybridBlock):
    """Self-attention with fused QKV and the flash kernel (or ring attention
    over the `sp` mesh axis when seq_parallel is set). `causal=True` makes
    it the decoder-side block (GPT family) — same kernel, causal mask."""

    def __init__(self, units, num_heads, dropout=0.0, dtype="float32",
                 seq_parallel=False, causal=False, **kwargs):
        super().__init__(**kwargs)
        if seq_parallel and dropout > 0.0:
            raise ValueError(
                "attention-probability dropout is not supported under ring "
                "sequence parallelism; pass attn_dropout=0 in the config")
        self._units = units
        self._num_heads = num_heads
        self.qkv = nn.Dense(3 * units, in_units=units, flatten=False, dtype=dtype,
                            weight_initializer="xavier")
        self.proj = nn.Dense(units, in_units=units, flatten=False, dtype=dtype,
                             weight_initializer="xavier")
        self._dropout = dropout
        self._seq_parallel = seq_parallel
        self._causal = causal

    def forward(self, x, mask=None):
        # x: (B, L, E); mask: (B, L) 1=valid
        qkv = self.qkv(x)  # (B, L, 3E)
        out = F.fused_self_attention(qkv, mask, num_heads=self._num_heads,
                                     dropout=self._dropout,
                                     causal=self._causal,
                                     seq_parallel=self._seq_parallel)
        return self.proj(out)


class BERTEncoderLayer(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 dtype="float32", attn_dropout=None, seq_parallel=False,
                 **kwargs):
        super().__init__(**kwargs)
        self.attention = BERTAttention(
            units, num_heads,
            dropout if attn_dropout is None else attn_dropout, dtype,
            seq_parallel=seq_parallel)
        self.attn_ln = nn.LayerNorm(in_channels=units)
        self.ffn_in = nn.Dense(hidden_size, in_units=units, flatten=False,
                               dtype=dtype, weight_initializer="xavier")
        self.ffn_out = nn.Dense(units, in_units=hidden_size, flatten=False,
                                dtype=dtype, weight_initializer="xavier")
        self.ffn_ln = nn.LayerNorm(in_channels=units)
        self.dropout = nn.Dropout(dropout) if dropout else None

    def forward(self, x, mask=None):
        attn = self.attention(x, mask)
        if self.dropout:
            attn = self.dropout(attn)
        x = self.attn_ln(x + attn)
        h = F.Activation(self.ffn_in(x), act_type="gelu")
        h = self.ffn_out(h)
        if self.dropout:
            h = self.dropout(h)
        return self.ffn_ln(x + h)


def _remat_call(layer, x, mask, policy="layers"):
    """Apply one encoder layer under jax.checkpoint: the backward pass
    recomputes the layer's internals from its (x, mask) boundary instead of
    stashing every intermediate. Layer parameters ride in as closure
    constants (under functional_call they are the substituted tracers).
    `policy` picks WHAT survives inside the layer (mx.memsafe graduated
    remat): "layers"/"full" save nothing, "dots_saveable" keeps matmul
    outputs so only the cheap elementwise work recomputes."""
    import jax

    from .. import memsafe as _memsafe

    def f(xd, *md):
        out = layer(NDArray(xd), NDArray(md[0]) if md else None)
        return out._data

    args = (x._data,) + (() if mask is None else (mask._data,))
    return NDArray(
        jax.checkpoint(f, policy=_memsafe.jax_policy(policy))(*args))


def _full_remat_stack(layers, x, mask):
    """policy='full', unrolled path: per-layer checkpoints INSIDE one
    checkpoint around the whole stack — only the stack's (x, mask) inputs
    survive the forward pass; backward re-runs the stack (itself
    re-checkpointed per layer, so the recompute stays O(1) in depth)."""
    import jax

    def f(xd, *md):
        out = NDArray(xd)
        m = NDArray(md[0]) if md else None
        for layer in layers:
            out = _remat_call(layer, out, m, "full")
        return out._data

    args = (x._data,) + (() if mask is None else (mask._data,))
    return NDArray(jax.checkpoint(f)(*args))


def _stack_call(layers, x, mask, policy):
    """Apply an encoder stack unrolled, under one remat policy (mx.memsafe:
    "none" | "dots_saveable" | "layers" | "full")."""
    if policy == "full":
        return _full_remat_stack(layers, x, mask)
    for layer in layers:
        if policy != "none":
            x = _remat_call(layer, x, mask, policy)
        else:
            x = layer(x, mask)
    return x


def _scan_layers_call(layers, x, mask, policy):
    """Apply an identical-structure encoder stack as ONE `lax.scan` over
    stacked per-layer parameters: the layer body is traced and compiled
    once instead of `num_layers` times.  This is what makes BERT-large
    (24 layers) compile in roughly the time BERT-base does.

    Mechanics: each layer's parameter tensors (identical pytree structure
    by construction) are stacked on a new leading axis *inside the trace*,
    so under `functional_call` the stack consumes the substituted per-layer
    tracers and gradients flow back to the individual parameters through
    the stack — the Block/Trainer/optimizer machinery is untouched.  The
    body runs layer 0's `forward` with its parameters swapped for the
    scanned slices (the same substitution trick `_make_pure_fn` uses).

    RNG: `next_key()` folds a PYTHON-side counter, which advances once at
    trace time — inside scan every iteration would replay identical
    dropout masks.  Each iteration therefore enters a fresh `key_scope`
    folding the layer index into one base key.

    `policy` (mx.memsafe graduated remat) wraps the body in
    `jax.checkpoint`: "layers" saves only the carry between iterations
    (activation memory O(1) in depth — the canonical scan-over-remat
    pairing), "dots_saveable" additionally keeps matmul outputs inside
    the body, and "full" puts one more checkpoint around the whole scan
    so only the stack inputs survive the forward pass."""
    import jax
    import jax.numpy as jnp

    from .. import memsafe as _memsafe
    from .. import random as _random

    if not isinstance(policy, str):
        # legacy use_remat boolean callers
        policy = "layers" if policy else "none"

    layer0 = layers[0]
    gp0, aux0 = layer0._param_lists()
    if aux0:
        raise ValueError("scan_layers requires encoder layers without "
                         "aux (grad_req='null') parameters")
    params0 = [p for _, p in gp0]
    per_layer = []
    for layer in layers:
        gp, aux = layer._param_lists()
        assert not aux and len(gp) == len(gp0)
        per_layer.append([p._data._data for _, p in gp])
    stacked = [jnp.stack(vals) for vals in zip(*per_layer)]
    base_key = _random.next_key()
    mask_d = None if mask is None else mask._data

    def body(carry, xs):
        idx, leaves = xs[0], xs[1:]
        saved = []
        for p, d in zip(params0, leaves):
            saved.append(p._data._data)
            p._data._data = d
        try:
            with _random.key_scope(jax.random.fold_in(base_key, idx)):
                out = layer0(NDArray(carry),
                             None if mask_d is None else NDArray(mask_d))
        finally:
            for p, d in zip(params0, saved):
                p._data._data = d
        return out._data, None

    if policy != "none":
        body = jax.checkpoint(body, policy=_memsafe.jax_policy(policy))

    def run_scan(x_d, *stk):
        xs = (jnp.arange(len(layers)),) + tuple(stk)
        y, _ = jax.lax.scan(body, x_d, xs)
        return y

    if policy == "full":
        run_scan = jax.checkpoint(run_scan)
    return NDArray(run_scan(x._data, *stacked))


def _positions(position_embed, L, sp_manual):
    """Slice L position embeddings. Inside a shard_map stage controlling
    `sp`, this device holds tokens [off, off+L) of the global sequence —
    slice ITS positions, not [0, L). The GLOBAL length is validated here:
    dynamic_slice clamps out-of-range starts, which would otherwise
    silently reuse shard 0's positions on every shard."""
    import jax
    max_len = position_embed.shape[0]
    if sp_manual:
        n = jax.lax.psum(1, "sp")       # static: axis size
        if L * n > max_len:
            raise ValueError(
                f"global sequence length {L * n} (local {L} x sp={n}) "
                f"exceeds max_length {max_len}")
        off = jax.lax.axis_index("sp") * L
        return NDArray(jax.lax.dynamic_slice_in_dim(
            position_embed.data()._data, off, L, 0))
    if L > max_len:
        raise ValueError(f"sequence length {L} exceeds max_length {max_len}")
    return NDArray(position_embed.data()._data[:L])


class BERTModel(HybridBlock):
    """Embeddings + encoder stack + pooler (reference: gluonnlp BERTModel)."""

    # remat policies route here (HybridBlock.remat / the remat_policy
    # knob): the encoder stack checkpoints per layer / per scan body
    # instead of wrapping the whole block (mx.memsafe graduated remat).
    # The legacy `remat=True` config flag stays the "layers" alias.
    _remat_handles_policy = True

    def __init__(self, vocab_size, units, hidden_size, num_layers, num_heads,
                 max_length=512, type_vocab_size=2, dropout=0.1,
                 attn_dropout=None, seq_parallel=False,
                 dtype="float32", remat=False, scan_layers=False, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._remat = remat
        self._scan_layers = scan_layers
        self._seq_parallel = seq_parallel
        self.word_embed = nn.Embedding(vocab_size, units, dtype=dtype,
                                       weight_initializer="xavier")
        self.token_type_embed = nn.Embedding(type_vocab_size, units, dtype=dtype,
                                             weight_initializer="xavier")
        self.position_embed = Parameter("position_weight", shape=(max_length, units),
                                        dtype=dtype, init="xavier")
        # sliced [:L] along dim 0 each step — keep that dim unsharded
        self.position_embed.shard_hint = "embedding"
        self.embed_ln = nn.LayerNorm(in_channels=units)
        self.embed_dropout = nn.Dropout(dropout) if dropout else None
        self.layers = nn.HybridSequential()
        for _ in range(num_layers):
            self.layers.add(BERTEncoderLayer(units, hidden_size, num_heads,
                                             dropout, dtype,
                                             attn_dropout=attn_dropout,
                                             seq_parallel=seq_parallel))
        self.pooler = nn.Dense(units, in_units=units, flatten=False,
                               activation="tanh", dtype=dtype,
                               weight_initializer="xavier")

    def forward(self, inputs, token_types=None, valid_length=None):
        B, L = inputs.shape
        max_len = self.position_embed.shape[0]
        if L > max_len:
            raise ValueError(
                f"sequence length {L} exceeds max_length {max_len}")
        from ..parallel import in_manual
        sp_manual = self._seq_parallel and in_manual("sp")
        x = self.word_embed(inputs)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = x + _positions(self.position_embed, L, sp_manual).expand_dims(axis=0)
        x = self.embed_ln(x)
        if self.embed_dropout:
            x = self.embed_dropout(x)
        mask = None
        if valid_length is not None:
            import jax
            import jax.numpy as jnp
            vl = valid_length._data if isinstance(valid_length, NDArray) else valid_length
            idx = jnp.arange(L)
            if sp_manual:
                idx = idx + jax.lax.axis_index("sp") * L
            mask = NDArray(idx[None, :] < vl[:, None].astype(jnp.int32))
        if self._seq_parallel and not sp_manual:
            # anchor the sequence sharding early so GSPMD keeps (B, L, E)
            # activations sp-sharded between the attention shard_maps
            from ..ndarray import apply_op
            from ..parallel import specs as _sp
            x = apply_op(_sp.constrain_seq, x)
        from .. import _engine
        from .. import memsafe as _memsafe
        # remat only where it means something: inside a jit trace (the
        # eager tape stores activations per-op; jax.checkpoint there would
        # just break recording)
        policy = _memsafe.effective_policy(
            getattr(self, "_remat_policy", None), self._remat)
        if _engine.is_recording():
            policy = "none"
        if self._scan_layers and not _engine.is_recording():
            x = _scan_layers_call(list(self.layers), x, mask, policy)
        else:
            x = _stack_call(list(self.layers), x, mask, policy)
        # pin the encoder output (and via transpose its cotangent) to batch
        # sharding: the MLM gather and pooler-slice backward paths otherwise
        # propagate conflicting feature shardings from fsdp-sharded head
        # weights onto d(seq), which GSPMD resolves by full remat
        from ..ndarray import apply_op
        from ..parallel import specs as _specs
        x = apply_op(_specs.constrain_batch, x)
        pooled = self.pooler(F.slice_axis(x, axis=1, begin=0, end=1).squeeze(axis=1))
        return x, pooled


class BERTEmbedStage(HybridBlock):
    """BERT embeddings as pipeline stage 0 (word + type + position + LN).
    sp-aware like BERTModel: under a shard_map that controls `sp` it embeds
    this device's sequence shard with the correct global positions.

    `token_types` is optional: the pipeline activation carrier moves a
    single tensor between stages, so segment-free LM pretraining passes
    tokens only — but two-segment pretraining CAN pass token_types and get
    the same embedding sum as BERTModel."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        units, dtype = cfg["units"], cfg["dtype"]
        self._seq_parallel = cfg.get("seq_parallel", False)
        self.word_embed = nn.Embedding(cfg["vocab_size"], units, dtype=dtype,
                                       weight_initializer="xavier")
        self.token_type_embed = nn.Embedding(
            cfg.get("type_vocab_size", 2), units, dtype=dtype,
            weight_initializer="xavier")
        self.position_embed = Parameter(
            "position_weight", shape=(cfg["max_length"], units), dtype=dtype,
            init="xavier")
        self.position_embed.shard_hint = "embedding"
        self.embed_ln = nn.LayerNorm(in_channels=units)

    def forward(self, inputs, token_types=None):
        from ..parallel import in_manual
        L = inputs.shape[1]
        sp_manual = self._seq_parallel and in_manual("sp")
        x = self.word_embed(inputs)
        if token_types is not None:
            x = x + self.token_type_embed(token_types)
        x = x + _positions(self.position_embed, L, sp_manual).expand_dims(axis=0)
        return self.embed_ln(x)


def bert_pipeline_stages(cfg, num_stages):
    """Split a BERT encoder into pipeline stage blocks: stage 0 =
    embeddings, stages 1..S-1 = equal groups of encoder layers. Padding
    masks don't travel the activation carrier, so stages attend over the
    full (micro)batch sequence.

    Use with the hetero PipelineTrainer only on sp=1 meshes. For sequence
    parallelism, build homogeneous stages (BERTEmbedStage + identical
    BERTEncoderLayer stages) for SeqPipelineTrainer instead — ring
    attention's collectives cannot live inside the hetero stage switch."""
    layers_per = cfg["num_layers"] // (num_stages - 1)
    if layers_per * (num_stages - 1) != cfg["num_layers"]:
        raise ValueError(
            f"num_layers {cfg['num_layers']} not divisible into "
            f"{num_stages - 1} encoder stages")
    stages = [BERTEmbedStage(cfg)]
    for _ in range(num_stages - 1):
        seq = nn.HybridSequential()
        for _ in range(layers_per):
            seq.add(BERTEncoderLayer(
                cfg["units"], cfg["hidden_size"], cfg["num_heads"],
                cfg["dropout"], cfg["dtype"],
                attn_dropout=cfg.get("attn_dropout"),
                seq_parallel=cfg.get("seq_parallel", False)))
        stages.append(seq)
    return stages


class BERTForPretraining(HybridBlock):
    """MLM + NSP heads (reference: gluonnlp BERTForPretrain)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        units, vocab = cfg["units"], cfg["vocab_size"]
        self.bert = BERTModel(**cfg)
        self.mlm_transform = nn.Dense(units, in_units=units, flatten=False,
                                      activation=None, dtype=cfg["dtype"],
                                      weight_initializer="xavier")
        self.mlm_ln = nn.LayerNorm(in_channels=units)
        # decoder weight tied to word embedding; separate bias
        self.mlm_bias = Parameter("mlm_bias", shape=(vocab,), init="zeros")
        self.nsp = nn.Dense(2, in_units=units, dtype=cfg["dtype"],
                            weight_initializer="xavier")

    def forward(self, inputs, token_types, valid_length, masked_positions):
        """Returns (mlm_scores (B,P,V), nsp_scores (B,2))."""
        import jax.numpy as jnp
        from ..ndarray import apply_op
        from ..parallel import specs as _specs
        seq, pooled = self.bert(inputs, token_types, valid_length)
        # gather masked positions before the vocab matmul: (B, P, E).
        # constrain_batch pins the gather output (and, via transpose, the
        # scatter cotangent into seq) to batch sharding so fsdp weight
        # shardings downstream can't force a GSPMD full-remat reshard.
        gathered = apply_op(
            lambda s, p: _specs.constrain_batch(
                jnp.take_along_axis(s, p.astype(jnp.int32)[..., None], 1)),
            seq, masked_positions)
        h = self.mlm_transform(gathered)
        h = F.Activation(h, act_type="gelu")
        h = self.mlm_ln(h)
        scores = apply_op(
            lambda hh, w, b: jnp.matmul(hh, w.T) + b,
            h, self.bert.word_embed.weight.data(), self.mlm_bias.data())
        return scores, self.nsp(pooled)


class BERTForQuestionAnswering(HybridBlock):
    """SQuAD-style span-extraction head (reference: gluonnlp
    BertForQA, scripts/bert/finetune_squad.py — the BASELINE SQuAD-F1
    quality-gate workload): a single Dense projects each token to
    (start, end) logits."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        self.bert = BERTModel(**cfg)
        self.span = nn.Dense(2, in_units=cfg["units"], flatten=False,
                             dtype=cfg["dtype"], weight_initializer="xavier")

    def forward(self, inputs, token_types, valid_length=None):
        """Returns (start_logits (B, L), end_logits (B, L)); positions past
        valid_length are masked to -inf so softmax ignores padding."""
        import jax.numpy as jnp
        from ..ndarray import apply_op
        seq, _ = self.bert(inputs, token_types, valid_length)
        logits = self.span(seq)                      # (B, L, 2)

        def split_mask(lg, vl=None):
            start, end = lg[..., 0], lg[..., 1]
            if vl is not None:
                L = lg.shape[1]
                live = jnp.arange(L)[None, :] < vl[:, None].astype(jnp.int32)
                start = jnp.where(live, start, -1e9)
                end = jnp.where(live, end, -1e9)
            return start, end

        if valid_length is None:
            return apply_op(split_mask, logits)
        return apply_op(split_mask, logits, valid_length)


def bert_qa_loss(start_logits, end_logits, start_positions, end_positions):
    """Mean cross-entropy of the gold start/end positions (reference:
    finetune_squad.py loss)."""
    import jax
    import jax.numpy as jnp
    from ..ndarray import apply_op

    def one(lg, pos):
        logp = jax.nn.log_softmax(lg, axis=-1)
        return -jnp.take_along_axis(
            logp, pos.astype(jnp.int32)[:, None], 1).mean()

    a = apply_op(one, start_logits, start_positions)
    b = apply_op(one, end_logits, end_positions)
    return (a + b) / 2


class BERTClassifier(HybridBlock):
    """Sentence(-pair) classification head over the pooled output
    (reference: gluonnlp BERTClassifier, finetune_classifier.py)."""

    def __init__(self, cfg, num_classes=2, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        self.bert = BERTModel(**cfg)
        self.dropout = nn.Dropout(dropout) if dropout else None
        self.classifier = nn.Dense(num_classes, in_units=cfg["units"],
                                   dtype=cfg["dtype"],
                                   weight_initializer="xavier")

    def forward(self, inputs, token_types, valid_length=None):
        _, pooled = self.bert(inputs, token_types, valid_length)
        if self.dropout is not None:
            pooled = self.dropout(pooled)
        return self.classifier(pooled)


def bert_pretrain_loss(mlm_scores, nsp_scores, mlm_labels, mlm_weights, nsp_labels):
    """Pretraining loss on NDArrays (ShardedTrainer loss_fn AND eager
    autograd compatible). mlm_scores (B,P,V), mlm_labels (B,P),
    mlm_weights (B,P) 1 for real masked positions, nsp_labels (B,).
    """
    import jax
    import jax.numpy as jnp
    from ..ndarray import apply_op

    def compute(ms, ns, lbl, w, nl):
        logp = jax.nn.log_softmax(ms.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, lbl.astype(jnp.int32)[..., None], -1)[..., 0]
        w = w.astype(jnp.float32)
        mlm_loss = jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)
        nlogp = jax.nn.log_softmax(ns.astype(jnp.float32), -1)
        nsp_loss = -jnp.mean(
            jnp.take_along_axis(nlogp, nl.astype(jnp.int32)[:, None], -1))
        return mlm_loss + nsp_loss

    return apply_op(compute, mlm_scores, nsp_scores, mlm_labels, mlm_weights,
                    nsp_labels)


def tp_rules(tp_axis="tp"):
    """Megatron sharding for BERT params (apply via parallel.apply_tp_rules):
    QKV and FFN-in split over heads/hidden (dim 0 of (out,in) weights),
    proj and FFN-out split on input dim; word embedding split on the FEATURE
    dim (not vocab: a vocab-sharded gather forces GSPMD full
    rematerialization; feature sharding partitions the gather trivially and
    the tied MLM decoder contracts over the sharded dim with a psum)."""
    from jax.sharding import PartitionSpec as P
    return [
        (r"\.qkv\.weight$", P(tp_axis, None)),
        (r"\.qkv\.bias$", P(tp_axis)),
        (r"\.ffn_in\.weight$", P(tp_axis, None)),
        (r"\.ffn_in\.bias$", P(tp_axis)),
        (r"\.proj\.weight$", P(None, tp_axis)),
        (r"\.ffn_out\.weight$", P(None, tp_axis)),
        (r"word_embed\.weight$", P(None, tp_axis)),
    ]


def make_synthetic_batch(cfg, batch_size, seq_len, num_masked=20, seed=0):
    """Deterministic synthetic pretraining batch (zero-egress environments)."""
    rng = np.random.RandomState(seed)
    V = cfg["vocab_size"]
    data = dict(
        input_ids=rng.randint(0, V, (batch_size, seq_len)).astype(np.int32),
        token_types=(rng.rand(batch_size, seq_len) > 0.5).astype(np.int32),
        valid_length=np.full((batch_size,), seq_len, np.int32),
        masked_positions=np.stack(
            [rng.choice(seq_len, num_masked, replace=False)
             for _ in range(batch_size)]).astype(np.int32),
        mlm_labels=rng.randint(0, V, (batch_size, num_masked)).astype(np.int32),
        mlm_weights=np.ones((batch_size, num_masked), np.float32),
        nsp_labels=rng.randint(0, 2, (batch_size,)).astype(np.int32),
    )
    return data
