"""GPT-2-style decoder-only causal language model.

Reference surface: the GluonNLP model zoo's text-generation family
(`gpt2_117m`/`gpt2_345m`, upstream gluon-nlp `scripts/text_generation/`,
model code `gluonnlp/model/transformer.py` GPT-2 variant) — the
reference ecosystem's causal-LM counterpart to BERT.  TPU-first build:
pre-LN blocks over the same fused-QKV flash attention as BERT but
`causal=True`, composing with every parallel axis this framework has —
dp/fsdp via ShardedTrainer, tp via `tp_rules`, ring/Ulysses sequence
parallelism for long context (`gpt_long_config`, SURVEY §5.7), and
`scan_layers` compile-once depth scaling shared with BERT.

The LM head ties the token embedding (GPT-2 has no separate output
matrix and no head bias).
"""
import numpy as np

from ..gluon import nn, HybridBlock
from ..gluon.parameter import Parameter
from ..ndarray import NDArray
from ..ndarray import ndarray as F
from ._decode import (ServingSpec, layer_call, paged_write_targets,
                      virtual_rows)
from .bert import BERTAttention, _positions, _scan_layers_call
from .bert import tp_rules as _bert_tp_rules


def gpt2_117m_config(**overrides):
    cfg = dict(vocab_size=50257, units=768, hidden_size=3072, num_layers=12,
               num_heads=12, max_length=1024, dropout=0.1, attn_dropout=0.0,
               seq_parallel=False, dtype="float32", remat=False,
               scan_layers=False)
    cfg.update(overrides)
    return cfg


def gpt2_345m_config(**overrides):
    # medium: same scan-once + remat depth treatment as bert_large
    cfg = gpt2_117m_config(units=1024, hidden_size=4096, num_layers=24,
                           num_heads=16, remat=True, scan_layers=True)
    cfg.update(overrides)
    return cfg


def gpt_long_config(**overrides):
    """Long-context causal pretraining: sequence sharded over the mesh's
    `sp` axis with CAUSAL ring attention (SURVEY §5.7)."""
    cfg = gpt2_117m_config(max_length=8192, seq_parallel=True, remat=True,
                           scan_layers=True)
    cfg.update(overrides)
    return cfg


def gpt_tiny_config(**overrides):
    cfg = gpt2_117m_config(vocab_size=128, units=64, hidden_size=128,
                           num_layers=2, num_heads=4, max_length=64,
                           dropout=0.0)
    cfg.update(overrides)
    return cfg


class GPTBlock(HybridBlock):
    """Pre-LN decoder block (GPT-2 ordering: LN -> attn -> +res,
    LN -> MLP -> +res)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 dtype="float32", attn_dropout=0.0, seq_parallel=False,
                 **kwargs):
        super().__init__(**kwargs)
        self.ln1 = nn.LayerNorm(in_channels=units)
        self.attn = BERTAttention(units, num_heads, attn_dropout, dtype,
                                  seq_parallel=seq_parallel, causal=True)
        self.ln2 = nn.LayerNorm(in_channels=units)
        self.ffn_in = nn.Dense(hidden_size, in_units=units, flatten=False,
                               dtype=dtype, weight_initializer="xavier")
        self.ffn_out = nn.Dense(units, in_units=hidden_size, flatten=False,
                                dtype=dtype, weight_initializer="xavier")
        self.dropout = nn.Dropout(dropout) if dropout else None

    def forward(self, x, mask=None):
        a = self.attn(self.ln1(x), mask)
        if self.dropout:
            a = self.dropout(a)
        x = x + a
        h = self.ffn_out(F.Activation(self.ffn_in(self.ln2(x)),
                                      act_type="gelu"))
        if self.dropout:
            h = self.dropout(h)
        return x + h

    def prefill(self, x, k_cache, v_cache):
        """Full-prompt forward that ALSO writes K/V[0:Lp] into the caches:
        on-device prefill is one batched (flash-attention) pass instead of
        Lp sequential one-token steps. x (B, Lp, E); caches (B,H,Lmax,D).
        Returns (y, new_k, new_v)."""
        import jax.numpy as jnp
        from jax import lax
        from ..ndarray import apply_op

        attn = self.attn
        H = attn._num_heads
        qkv = attn.qkv(self.ln1(x))             # (B, Lp, 3E)
        B, Lp, E3 = qkv.shape
        D = E3 // 3 // H

        def split_write(qkv_d, kc, vc):
            r = qkv_d.reshape(B, Lp, 3, H, D)
            q = r[:, :, 0].transpose(0, 2, 1, 3)
            k = r[:, :, 1].transpose(0, 2, 1, 3)
            v = r[:, :, 2].transpose(0, 2, 1, 3)
            kc = lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                          (0, 0, 0, 0))
            vc = lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                          (0, 0, 0, 0))
            return q, k, v, kc, vc

        q, k, v, k_cache, v_cache = apply_op(split_write, qkv, k_cache,
                                             v_cache)
        o = F.flash_attention(q, k, v, None, causal=True)   # (B,H,Lp,D)
        o = o.transpose(axes=(0, 2, 1, 3)).reshape(shape=(B, Lp, H * D))
        x = x + attn.proj(o)
        h = self.ffn_out(F.Activation(self.ffn_in(self.ln2(x)),
                                      act_type="gelu"))
        return x + h, k_cache, v_cache

    def _token_step(self, x, attend):
        """One token through the block, the cache access left to
        `attend(q, k_new, v_new) -> (o (B,1,E), new_k, new_v)`: the body
        `step` and `step_slots_paged` share. x (B,1,E)."""
        from ..ndarray import apply_op

        attn = self.attn
        H = attn._num_heads
        qkv = attn.qkv(self.ln1(x))             # (B, 1, 3E)
        B, _, E3 = qkv.shape
        D = E3 // 3 // H

        def split(qkv_d):
            r = qkv_d.reshape(B, 1, 3, H, D)
            return (r[:, :, 0].transpose(0, 2, 1, 3),
                    r[:, :, 1].transpose(0, 2, 1, 3),
                    r[:, :, 2].transpose(0, 2, 1, 3))   # (B,H,1,D) each

        q, k_new, v_new = apply_op(split, qkv)
        o, k_cache, v_cache = attend(q, k_new, v_new)
        x = x + attn.proj(o)
        h2 = self.ffn_out(F.Activation(self.ffn_in(self.ln2(x)),
                                       act_type="gelu"))
        return x + h2, k_cache, v_cache

    def step(self, x, k_cache, v_cache, t):
        """One-token incremental step against a static-shape KV cache
        (inference; same scheme as transformer.TransformerLayer.step).
        x (B,1,E); caches (B,H,Lmax,D); t traced scalar — one compile
        serves every position."""
        from ._decode import cached_self_attention_step
        return self._token_step(
            x, lambda q, k, v: cached_self_attention_step(
                q, k, v, k_cache, v_cache, t))

    def step_slots_paged(self, x, k_pages, v_pages, tables, wp, wo, t):
        """`step` with PER-ROW positions t (B,) against an mx.pages
        block-table cache — mx.serve's continuous-batching step: each
        batch row is an independent request at its own position, its K/V
        write lands in page wp[b] offset wo[b], and attention gathers
        through tables (B,n_pg). Row math is `step`'s, so a row's output
        never depends on its neighbours."""
        from ._decode import paged_attention_step
        return self._token_step(
            x, lambda q, k, v: paged_attention_step(
                q, k, v, k_pages, v_pages, tables, wp, wo, t))


class GPTModel(HybridBlock):
    """Token+position embeddings -> pre-LN block stack -> final LN.
    Returns hidden states (B, L, E)."""

    # remat policies route here (see BERTModel): per-layer / scan-body
    # checkpointing per the mx.memsafe graduated policy; the legacy
    # `remat=True` config flag stays the "layers" alias
    _remat_handles_policy = True

    def __init__(self, vocab_size, units, hidden_size, num_layers, num_heads,
                 max_length=1024, dropout=0.1, attn_dropout=0.0,
                 seq_parallel=False, dtype="float32", remat=False,
                 scan_layers=False, **kwargs):
        super().__init__(**kwargs)
        self._remat = remat
        self._scan_layers = scan_layers
        self._seq_parallel = seq_parallel
        self.word_embed = nn.Embedding(vocab_size, units, dtype=dtype,
                                       weight_initializer="xavier")
        self.position_embed = Parameter(
            "position_weight", shape=(max_length, units), dtype=dtype,
            init="xavier")
        self.position_embed.shard_hint = "embedding"
        self.embed_dropout = nn.Dropout(dropout) if dropout else None
        self.layers = nn.HybridSequential()
        for _ in range(num_layers):
            self.layers.add(GPTBlock(units, hidden_size, num_heads, dropout,
                                     dtype, attn_dropout=attn_dropout,
                                     seq_parallel=seq_parallel))
        self.ln_f = nn.LayerNorm(in_channels=units)

    def forward(self, inputs, valid_length=None):
        B, L = inputs.shape
        from ..parallel import in_manual
        sp_manual = self._seq_parallel and in_manual("sp")
        x = self.word_embed(inputs)
        x = x + _positions(self.position_embed, L, sp_manual).expand_dims(
            axis=0)
        if self.embed_dropout:
            x = self.embed_dropout(x)
        mask = None
        if valid_length is not None:
            import jax
            import jax.numpy as jnp
            vl = valid_length._data if isinstance(valid_length, NDArray) \
                else valid_length
            idx = jnp.arange(L)
            if sp_manual:
                idx = idx + jax.lax.axis_index("sp") * L
            mask = NDArray(idx[None, :] < vl[:, None].astype(jnp.int32))
        if self._seq_parallel and not sp_manual:
            from ..ndarray import apply_op
            from ..parallel import specs as _sp
            x = apply_op(_sp.constrain_seq, x)
        from .. import _engine
        from .. import memsafe as _memsafe
        policy = _memsafe.effective_policy(
            getattr(self, "_remat_policy", None), self._remat)
        if _engine.is_recording():
            policy = "none"
        if self._scan_layers and not _engine.is_recording():
            x = _scan_layers_call(list(self.layers), x, mask, policy)
        else:
            from .bert import _stack_call
            x = _stack_call(list(self.layers), x, mask, policy)
        # pin to batch sharding before the tied-embedding head: same
        # rationale as BERTModel — the head matmul against fsdp-sharded
        # word_embed weights otherwise propagates conflicting feature
        # shardings onto d(hidden), which GSPMD resolves by full remat
        from ..ndarray import apply_op
        from ..parallel import specs as _specs
        x = apply_op(_specs.constrain_batch, x)
        return self.ln_f(x)


class GPTForCausalLM(HybridBlock):
    """Hidden states -> tied-embedding logits (B, L, V)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        self.gpt = GPTModel(**cfg)

    def forward(self, inputs, valid_length=None):
        import jax.numpy as jnp
        from ..ndarray import apply_op

        h = self.gpt(inputs, valid_length)
        return apply_op(lambda hh, w: jnp.matmul(hh, w.T.astype(hh.dtype)),
                        h, self.gpt.word_embed.weight.data())

    # -- incremental generation (static-shape KV cache) -------------------
    def decode_step(self, tok, t, self_k, self_v):
        """One incremental step: tok (B,) int32, t traced scalar position;
        returns (logits (B,V), new_self_k, new_self_v). Same scheme as
        transformer.TransformerNMT.decode_step — one compile serves every
        position, including the prompt prefill."""
        import jax.numpy as jnp
        from jax import lax
        from ..ndarray import apply_op

        g = self.gpt
        x = g.word_embed(tok.reshape(shape=(-1, 1)))
        pos = apply_op(
            lambda pe, tt: lax.dynamic_slice(
                pe, (tt.astype(jnp.int32), 0), (1, pe.shape[1]))[None],
            NDArray(g.position_embed.data()._data), t)
        x = x + pos
        new_k, new_v = [], []
        for i, layer in enumerate(g.layers):
            x, k, v = layer.step(x, self_k[i], self_v[i], t)
            new_k.append(k)
            new_v.append(v)
        x = g.ln_f(x)
        logits = apply_op(
            lambda hh, w: jnp.matmul(hh, w.T.astype(hh.dtype)),
            x, g.word_embed.weight.data())
        return logits.reshape(shape=(tok.shape[0], -1)), new_k, new_v

    # -- paged decode (mx.pages block-table cache) -------------------------
    def _paged_token_step(self, tok_d, pos_d, tb_d, wp_d, wo_d, ks, vs,
                          head_rows=None):
        """Raw-jax one-token paged step (the whole of the chunk program,
        the lax.scan body of the draft chain): `decode_step` with per-row
        positions — embed + pe[pos] + layer stack + ln_f + tied logits —
        with the layers' cache access routed through `step_slots_paged`.
        A row is one fed token with the page-table row it goes through
        (tb_d (B,n_pg)); the batch is whatever the caller packed. Takes
        and returns raw arrays; ks/vs are tuples of the pooled (P,H,ps,D)
        page arrays per layer. `head_rows` (R,) int32: the rows whose
        logits are wanted (all B where None).

        Returns (f32 logits (R|B, V), new_ks, new_vs)."""
        import jax
        import jax.numpy as jnp
        from ..ndarray import apply_op

        g = self.gpt
        tok = NDArray(tok_d)
        t = NDArray(pos_d)
        x = g.word_embed(tok.reshape(shape=(-1, 1)))
        pos = apply_op(
            lambda pe, tt: pe[tt.astype(jnp.int32)][:, None, :],
            NDArray(g.position_embed.data()._data), t)
        x = x + pos
        nk, nv = [], []
        layers = list(g.layers)
        for i in range(len(layers)):
            x, k, v = layer_call(
                layers, i, "step_slots_paged",
                x, NDArray(ks[i]), NDArray(vs[i]), NDArray(tb_d),
                NDArray(wp_d), NDArray(wo_d), t)
            nk.append(k._data)
            nv.append(v._data)
        if head_rows is not None:
            x = NDArray(x._data[head_rows])
        x = g.ln_f(x)
        with jax.named_scope("lm_head"):
            logits = apply_op(
                lambda hh, w: jnp.matmul(hh, w.T.astype(hh.dtype)),
                x, g.word_embed.weight.data())
            lg = logits.reshape(shape=(x.shape[0], -1))._data \
                .astype(jnp.float32)
        return lg, tuple(nk), tuple(nv)

    def _paged_write_targets(self, pos_d, active_d, tb_d, page_size):
        return paged_write_targets(pos_d, active_d, tb_d, page_size)

    def serving_spec(self):
        """What `serve.Server` asks of a model (`_decode.ServingSpec`)."""
        g = self.gpt
        n_l = len(g.layers)
        heads = g.layers[0].attn._num_heads
        vocab, units = g.word_embed.weight.shape
        dtype = g.word_embed.weight.data()._data.dtype
        kv = [(heads, units // heads, dtype)] * (2 * n_l)
        return ServingSpec(
            vocab_size=int(vocab), max_length=int(g.position_embed.shape[0]),
            streams=kv, index_topk=None,
            chunk_step=self.decode_paged_chunk,
            draft_step=self.decode_paged_draft)

    def decode_paged_chunk(self, toks, pos, slot, last, tables, flat,
                           page_size, full=False):
        """The serving step (jit_flat_step step_fn): ONE pass over the
        step's tokens. The scheduler hands them as W virtual rows — row w
        is token toks[w] at position pos[w] of the request in slot
        slot[w], whose page-table row tables[slot[w]] it reads and writes
        through; rows that pad the pass to its width have pos = -1 (they
        walk no page and write their slot's scratch page). A decoding
        request is one row, a request inside its prompt several, at
        consecutive positions: the one-token body (`_paged_token_step`)
        runs once at batch W, and within a layer every row's key and
        value is written before any row attends, so a prompt token sees
        the pass's earlier tokens of its own request and — attending
        positions <= its own — nothing later.

        The head runs on row last[s] for each slot s, the slot's last fed
        token (any row where the slot fed nothing: its logits are not
        read), or on all W rows when `full`, the speculative verify
        surface. Logits agree with feeding the same tokens one dispatch at
        a time to rounding (a matmul at batch W rounds like a matmul at
        batch W), not bit for bit.

        toks/pos/slot (W,) int32; last (slots,) int32; tables (slots,
        n_pg) int32; flat = 2*n_l pooled page arrays (K per layer, then
        V). Returns (f32 logits (slots,V), or (W,V) when `full`; the new
        pool arrays)."""
        import jax.numpy as jnp

        n_l = len(self.gpt.layers)
        pos_d = pos._data.astype(jnp.int32)
        slot_d = slot._data.astype(jnp.int32)
        flat_d = [f._data for f in flat]
        rows, wp, wo = virtual_rows(pos_d, slot_d, tables._data, page_size)
        lg, ks, vs = self._paged_token_step(
            toks._data.astype(jnp.int32), pos_d, rows, wp, wo,
            tuple(flat_d[:n_l]), tuple(flat_d[n_l:]),
            head_rows=None if full else last._data.astype(jnp.int32))
        return NDArray(lg), [NDArray(a) for a in list(ks) + list(vs)]

    def decode_paged_draft(self, tok0, t0, active, tables, flat, page_size,
                           n_draft):
        """Greedy draft chain (jit_flat_step step_fn on the DRAFTER
        model): feed tok0[b] at position t0[b], take the argmax as the
        next token, repeat — n_draft proposals in one dispatch. The
        drafter writes its own pooled page arrays (`flat`, the pool's
        'draft' stream) through the SAME page tables as the target, so a
        prefix-tree hit skips drafter prefill too.

        Inactive rows (active[b] False — row not in a speculative round)
        run fully masked into scratch. Proposals feed exact-acceptance
        verification (arxiv 2302.01318): the target checks them in one
        chunked step and keeps the longest agreeing prefix, so a wrong
        draft costs speed, never correctness.

        tok0/t0 (B,) int32; active (B,) bool; tables (B,n_pg) int32.
        Returns (drafts (B, n_draft) int32, new draft-pool arrays)."""
        import jax
        import jax.numpy as jnp

        n_l = len(self.gpt.layers)
        tok0_d, t0_d, act_d, tb_d = (tok0._data, t0._data, active._data,
                                     tables._data)
        flat_d = [f._data for f in flat]

        def tok_step(carry, i):
            ks, vs, tok = carry
            pos = (t0_d + i).astype(jnp.int32)
            wp, wo = paged_write_targets(pos, act_d, tb_d, page_size)
            lg, ks, vs = self._paged_token_step(tok, pos, tb_d, wp, wo,
                                                ks, vs)
            nxt = jnp.argmax(lg, -1).astype(jnp.int32)
            return (ks, vs, nxt), nxt

        (ks, vs, _), drafts = jax.lax.scan(
            tok_step, (tuple(flat_d[:n_l]), tuple(flat_d[n_l:]),
                       tok0_d.astype(jnp.int32)),
            jnp.arange(n_draft))
        return NDArray(drafts.T), [NDArray(a) for a in list(ks) + list(vs)]

    def _init_generate(self, B, max_len):
        """Allocate caches and jit the step (shape-keyed — the reference
        analog is gluonnlp's SequenceSampler over a hybridized decoder)."""
        import jax
        import jax.numpy as jnp

        n_l = len(self.gpt.layers)
        caches = self._alloc_caches(B, max_len)
        self_k, self_v = caches[:n_l], caches[n_l:]

        key = (B, max_len)
        if not hasattr(self, "_gen_cache"):
            self._gen_cache = {}
        if key not in self._gen_cache:
            from ._decode import jit_flat_step

            def step(tok, t, flat):
                logits, nk, nv = self.decode_step(
                    tok, t, flat[:n_l], flat[n_l:])
                return logits, nk + nv

            # the K/V caches are threaded through every step: donate them
            # (old cache buffers die into the new ones instead of
            # double-buffering 2*n_l full-length caches per token —
            # mx.check `donation-miss`)
            run_flat = jit_flat_step(self, step, 2 * n_l,
                                     donate_state=2 * n_l)

            def run(tok, t, sk, sv):
                logits, state = run_flat(tok, t, sk + sv)
                return logits, state[:n_l], state[n_l:]

            self._gen_cache[key] = run
        return self._gen_cache[key], self_k, self_v

    def _prefill_body(self, prompt_d, lp_d, flat):
        """Raw-jax batched prefill: embed + per-layer flash pass writing
        K/V[0:Lp]; returns (f32 logits at the last REAL prompt position
        (B, V), ks, vs). Shared by the on-device generation program and
        the beam-search prefill program."""
        import jax
        import jax.numpy as jnp

        g = self.gpt
        Lp_b = prompt_d.shape[1]
        n_l = len(g.layers)
        x = g.word_embed(NDArray(prompt_d))
        x = x + NDArray(
            g.position_embed.data()._data[:Lp_b]).expand_dims(axis=0)
        ks, vs = list(flat[:n_l]), list(flat[n_l:])
        for i, layer in enumerate(g.layers):
            x, k, v = layer.prefill(x, NDArray(ks[i]), NDArray(vs[i]))
            ks[i], vs[i] = k._data, v._data
        h = g.ln_f(x)._data
        h_last = jax.lax.dynamic_index_in_dim(
            h, (lp_d - 1).astype(jnp.int32), axis=1, keepdims=False)
        w = g.word_embed.weight.data()._data
        logits = jnp.matmul(h_last, w.T.astype(h_last.dtype)) \
            .astype(jnp.float32)
        return logits, ks, vs

    def _init_prefill(self, B, Lp_b, max_len):
        """Jitted prefill-only program: ONE dispatch fills the caches and
        returns the first-expansion logits (beam search's prefill)."""
        n_l = len(self.gpt.layers)
        key = ("prefill", B, Lp_b, max_len)
        if not hasattr(self, "_gen_cache"):
            self._gen_cache = {}
        if key not in self._gen_cache:
            from ._decode import jit_flat_step

            def pre(prompt_nd, lp_nd, flat):
                logits, ks, vs = self._prefill_body(
                    prompt_nd._data, lp_nd._data, [f._data for f in flat])
                return logits, ks + vs

            # the zeroed caches passed in alias straight into the filled
            # ones coming out (donated: no transient double allocation of
            # the full-length K/V at prefill)
            self._gen_cache[key] = jit_flat_step(self, pre, 2 * n_l,
                                                 donate_state=2 * n_l)
        return self._gen_cache[key]

    def _alloc_caches(self, B, max_len):
        """Zeroed per-layer K+V caches (the single source of cache
        geometry for both generation paths)."""
        import jax.numpy as jnp

        g = self.gpt
        n_l = len(g.layers)
        H = g.layers[0].attn._num_heads
        D = g.word_embed.weight.shape[1] // H
        dt = g.word_embed.weight.data()._data.dtype
        return [jnp.zeros((B, H, max_len, D), dt) for _ in range(2 * n_l)]

    def _generate_on_device(self, prompt, max_new, eos, temperature, top_k,
                            seed, max_len):
        """Whole-generation as ONE jitted program: a batched flash
        prefill fills the K/V caches, then a generation lax.scan samples
        inside the trace — one host<->device round trip total instead of
        one per token.

        The prompt right-pads to a bucket so one compile serves a range
        of prompt lengths; temperature/eos/seed are traced scalars so
        sweeping them reuses the compile (top_k and max_new are
        structural: static). Pad-slot cache pollution is harmless:
        prefill attention is causal (real positions never see pad slots)
        and each generated step overwrites its slot before attending."""
        import jax
        import jax.numpy as jnp

        B, Lp = prompt.shape
        Lp_b = 16
        while Lp_b < Lp:
            Lp_b *= 2
        Lp_b = min(Lp_b, max_len - 1)
        pad = np.zeros((B, Lp_b - Lp), np.int32)
        prompt_pad = np.concatenate([prompt, pad], axis=1)

        n_l = len(self.gpt.layers)
        do_sample = bool(temperature and temperature > 0.0)
        key = ("dev", B, Lp_b, max_new, max_len, do_sample, int(top_k),
               eos is not None)
        if not hasattr(self, "_gen_cache"):
            self._gen_cache = {}
        if key not in self._gen_cache:
            from ._decode import jit_flat_step
            model = self

            def whole(prompt_d, lp_d, seed_d, temp_d, eos_d, flat):
                # jit_flat_step hands us NDArray-wrapped tracers; this
                # body speaks raw jax (lax.scan carries), so unwrap here
                prompt_d, lp_d, seed_d, temp_d, eos_d = (
                    prompt_d._data, lp_d._data, seed_d._data, temp_d._data,
                    eos_d._data)
                flat = [f._data for f in flat]

                def wrap(d):
                    return NDArray(d)

                logits, ks, vs = model._prefill_body(prompt_d, lp_d, flat)

                rngk = jax.random.fold_in(
                    jax.random.key(0), seed_d.astype(jnp.int32))

                def gen_t(carry, i):
                    logits, ks, vs, finished, rngk = carry
                    lg = logits
                    if do_sample:
                        if top_k:
                            kth = jax.lax.top_k(lg, top_k)[0][:, -1:]
                            lg = jnp.where(lg < kth, -jnp.inf, lg)
                        rngk, sub = jax.random.split(rngk)
                        nxt = jax.random.categorical(
                            sub, lg / temp_d, axis=-1).astype(jnp.int32)
                    else:
                        nxt = jnp.argmax(lg, -1).astype(jnp.int32)
                    if eos is not None:
                        nxt = jnp.where(finished, eos_d.astype(jnp.int32),
                                        nxt)
                        finished = finished | (nxt == eos_d)
                    t = lp_d + i
                    lg2, nk, nv = model.decode_step(
                        wrap(nxt), wrap(t),
                        [wrap(k) for k in ks], [wrap(v) for v in vs])
                    return (lg2._data.astype(jnp.float32),
                            tuple(k._data for k in nk),
                            tuple(v._data for v in nv),
                            finished, rngk), nxt

                finished0 = jnp.zeros((B,), bool)
                (_, _, _, _, _), toks = jax.lax.scan(
                    gen_t, (logits, tuple(ks), tuple(vs), finished0, rngk),
                    jnp.arange(max_new))
                return toks.T, []       # (B, max_new)

            run = jit_flat_step(self, whole, 2 * n_l)
            self._gen_cache[key] = run
        run = self._gen_cache[key]
        toks, _ = run(jnp.asarray(prompt_pad), jnp.asarray(Lp, jnp.int32),
                      jnp.asarray(seed, jnp.int32),
                      jnp.asarray(float(temperature or 1.0), jnp.float32),
                      jnp.asarray(-1 if eos is None else eos, jnp.int32),
                      self._alloc_caches(B, max_len))
        out = np.asarray(toks, np.int32)
        if eos is not None:
            # trim trailing columns after every row finished (host-loop
            # semantics: the step where the last row emits eos is kept)
            allf = np.all(np.cumsum(out == eos, axis=1) >= 1, axis=0)
            if allf.any():
                out = out[:, :int(np.argmax(allf)) + 1]
        return out

    def _generate_beam(self, prompt, max_new, eos, num_beams, alpha,
                       max_len, return_scores):
        """Beam search over the KV cache (the gluonnlp BeamSearchSampler
        surface): device steps + host top-k bookkeeping + on-device cache
        reorder gathers — the same shared driver as TransformerNMT."""
        import jax.numpy as jnp

        from ._decode import beam_search_loop

        B, Lp = prompt.shape
        # ONE batched-prefill dispatch at batch B (beams are identical
        # copies until the first expansion), then tile the caches: row
        # b*beam+j is beam j of batch b — exactly the layout reorder's
        # gather indices expect. Prompt right-pads to a bucket (pad-slot
        # pollution is harmless — see _generate_on_device).
        Lp_b = 16
        while Lp_b < Lp:
            Lp_b *= 2
        Lp_b = min(Lp_b, max_len - 1)
        prompt_pad = np.concatenate(
            [prompt, np.zeros((B, Lp_b - Lp), np.int32)], axis=1)
        pre = self._init_prefill(B, Lp_b, max_len)
        n_l = len(self.gpt.layers)
        logits0, caches = pre(jnp.asarray(prompt_pad),
                              jnp.asarray(Lp, jnp.int32),
                              self._alloc_caches(B, max_len))
        pk, pv = caches[:n_l], caches[n_l:]
        run, _, _ = self._init_generate(B * num_beams, max_len)
        state = {"k": [jnp.repeat(c, num_beams, axis=0) for c in pk],
                 "v": [jnp.repeat(c, num_beams, axis=0) for c in pv]}
        logits0 = jnp.repeat(jnp.asarray(logits0), num_beams, axis=0)

        def dev_step(tok, t):
            logits, state["k"], state["v"] = run(
                jnp.asarray(tok), jnp.asarray(t, jnp.int32),
                state["k"], state["v"])
            return logits

        def reorder(gather):
            g = jnp.asarray(gather)
            state["k"] = [jnp.take(c, g, axis=0) for c in state["k"]]
            state["v"] = [jnp.take(c, g, axis=0) for c in state["v"]]

        out, scores = beam_search_loop(
            logits0, lambda tok, i: dev_step(tok, Lp + i), reorder,
            B, num_beams, eos, max_new, alpha=alpha)
        return (out, scores) if return_scores else out

    def generate(self, prompt, max_new_tokens=32, eos=None, temperature=0.0,
                 top_k=0, seed=0, on_device=True, num_beams=1, alpha=0.6,
                 return_scores=False):
        """Autoregressive generation from int prompt tokens (B, Lp):
        greedy when temperature == 0, else softmax sampling at the given
        temperature (optionally truncated to the top_k logits) — the
        gluonnlp text_generation sampler surface. Returns (B, <=
        max_new_tokens) numpy tokens (rows stop growing at `eos`).

        on_device=True (default) runs prefill + the whole generation loop
        as one jitted program (lax.scan, sampling in-trace) — a single
        dispatch instead of one per token. on_device=False single-steps
        through the same jitted one-token step from the host (useful for
        debugging; identical greedy results, different sample streams).

        num_beams > 1 switches to beam search (requires `eos`; Sockeye
        length norm with `alpha`; `return_scores` adds per-batch scores).
        """
        import jax.numpy as jnp

        prompt = np.asarray(prompt, np.int32)
        B, Lp = prompt.shape
        need = Lp + max_new_tokens
        limit = self.gpt.position_embed.shape[0]
        if need > limit:
            raise ValueError(
                f"prompt {Lp} + max_new_tokens {max_new_tokens} exceeds "
                f"max_length {limit}")
        if Lp == 0 or max_new_tokens <= 0:
            return np.zeros((B, 0), np.int32)
        # bucket the cache length (next power of two, capped at the
        # position table) so one compile serves every prompt length —
        # t is traced, only the cache SHAPE keys the jit
        max_len = 16
        while max_len < need:
            max_len *= 2
        max_len = min(max_len, limit)
        if num_beams > 1:
            if eos is None:
                raise ValueError("beam search needs an `eos` id (scoring "
                                 "terminates beams on it)")
            if (temperature and temperature > 0.0) or top_k:
                raise ValueError("num_beams > 1 is deterministic beam "
                                 "search — temperature/top_k do not apply")
            return self._generate_beam(prompt, max_new_tokens, eos,
                                       num_beams, alpha, max_len,
                                       return_scores)
        if on_device:
            return self._generate_on_device(
                prompt, max_new_tokens, eos, temperature, top_k, seed,
                max_len)
        run, self_k, self_v = self._init_generate(B, max_len)
        rng = np.random.RandomState(seed)
        logits = None
        for t in range(Lp):
            logits, self_k, self_v = run(
                jnp.asarray(prompt[:, t]), jnp.asarray(t, jnp.int32),
                self_k, self_v)
        out = []
        finished = np.zeros(B, bool)
        for i in range(max_new_tokens):
            lg = np.asarray(logits, np.float32)
            if temperature and temperature > 0.0:
                if top_k:
                    kth = np.partition(lg, -top_k, axis=-1)[:, -top_k][:, None]
                    lg = np.where(lg < kth, -np.inf, lg)
                lg = lg / temperature
                p = np.exp(lg - lg.max(-1, keepdims=True))
                p /= p.sum(-1, keepdims=True)
                nxt = np.stack([rng.choice(p.shape[1], p=p[b])
                                for b in range(B)]).astype(np.int32)
            else:
                nxt = lg.argmax(-1).astype(np.int32)
            if eos is not None:
                nxt = np.where(finished, eos, nxt)
                finished |= nxt == eos
            out.append(nxt)
            if eos is not None and finished.all():
                break
            if i < max_new_tokens - 1:
                logits, self_k, self_v = run(
                    jnp.asarray(nxt), jnp.asarray(Lp + i, jnp.int32),
                    self_k, self_v)
        return np.stack(out, axis=1)


def gpt_lm_loss(logits, labels, weights):
    """Next-token cross entropy on NDArrays (ShardedTrainer loss_fn and
    eager compatible). logits (B, L, V) at input positions, labels (B, L)
    the NEXT token at each position (pre-shifted by the data pipeline so
    sequence-parallel shards stay self-contained), weights (B, L) 0/1."""
    import jax
    import jax.numpy as jnp
    from ..ndarray import apply_op

    def compute(lg, lb, w):
        logp = jax.nn.log_softmax(lg.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(
            logp, lb.astype(jnp.int32)[..., None], -1)[..., 0]
        w = w.astype(jnp.float32)
        return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)

    return apply_op(compute, logits, labels, weights)


def make_synthetic_batch(cfg, batch_size, seq_len, seed=0):
    """Tokens + pre-shifted next-token labels + weights, numpy."""
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, cfg["vocab_size"],
                       (batch_size, seq_len + 1)).astype(np.int32)
    return {
        "input_ids": toks[:, :-1],
        "labels": toks[:, 1:],
        "weights": np.ones((batch_size, seq_len), np.float32),
        "valid_length": np.full((batch_size,), seq_len, np.int32),
    }


def tp_rules(tp_axis="tp"):
    """Megatron sharding for GPT params: bert.tp_rules verbatim (the block
    param names match by construction) plus the position table on its
    feature dim — the tied LM head then contracts over the sharded dim
    with a psum."""
    from jax.sharding import PartitionSpec as P
    return _bert_tp_rules(tp_axis) + [(r"position_weight$", P(None, tp_axis))]
