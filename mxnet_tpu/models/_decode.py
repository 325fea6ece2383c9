"""Shared incremental-decode scaffolding for the autoregressive models
(TransformerNMT beam/greedy decode, GPT generate).

One pattern, one place: wrap a model's `decode_step`-style function in a
throwaway HybridBlock taking flat positional state, functionalize it
(`gluon.functional_call`), `jax.jit` it, and return a runner that re-reads
the model's parameters on every call — parameters are jit ARGUMENTS, not
baked constants, so decoding stays correct after further training."""
import collections

from ..gluon import HybridBlock

# What `serve.Server` asks of a model (`model.serving_spec()`), so that it
# reaches into no model's attributes:
#   vocab_size, max_length   ids the logits cover; longest position served
#   streams                  one `(*lead, width, dtype)` per paged arena, in
#                            the order of the chunk step's flat state: the
#                            pool allocates `(pages, *lead, page_size,
#                            width)` for each (GPT: `(heads, head_dim, dt)`,
#                            K per layer then V; a latent cache: `(width,
#                            dt)`, no head axis)
#   index_topk               tokens a learned sparse attention keeps, None
#                            for full attention (the `sparse_tokens` counter)
#   windows                  None, or one entry per stream: the stream's
#                            page CLASS, named by its window. None keeps
#                            every row of a request; an int W is a sliding-
#                            window layer's cache, whose pages the server
#                            returns once every row lies more than W behind
#                            the request's position. Streams of one window
#                            share an allocator and a page table; with more
#                            than one class the step's `tables` are
#                            (classes, slots, n_pg), None's first
#   chunk_step               `decode_paged_chunk(toks, pos, slot, last,
#                            tables, flat, page_size, full=)`: one pass over
#                            the step's tokens as virtual rows (see
#                            `GPTForCausalLM.decode_paged_chunk`)
#   draft_step               `decode_paged_draft(...)`, None: cannot draft
ServingSpec = collections.namedtuple(
    "ServingSpec", "vocab_size max_length streams index_topk chunk_step "
    "draft_step windows", defaults=(None,))


def paged_write_targets(pos_d, active_d, tb_d, page_size, scratch=None):
    """Write page/offset of each row of a paged step. Row b is one fed
    token at position pos_d[b], `tb_d[b]` the page-table row it goes
    through: it writes page tb_d[b, pos//ps] at offset pos%ps. Rows that
    are not `active_d` (padding, a masked lane of the draft chain) write
    offset 0 of the scratch page `scratch[b]` instead (mx.pages reserves
    pages 0..slots-1 as per-slot scratch; the row's own index where
    `scratch` is None), so a batched step never pollutes a real page of
    an inactive request. Positions past the table's range also divert to
    scratch: a speculative round that starts near the bucket's last
    position feeds its fixed k+1 tokens past the end, and clipping those
    writes back into the last real page would corrupt positions the row
    still attends. Nothing reads a scratch page, so rows may share a
    scratch cell; every other cell is named by one row."""
    import jax.numpy as jnp

    B, n_pg = tb_d.shape
    idx = jnp.clip(pos_d // page_size, 0, n_pg - 1)
    real = jnp.take_along_axis(tb_d, idx[:, None], axis=1)[:, 0]
    if scratch is None:
        scratch = jnp.arange(B, dtype=jnp.int32)
    ok = active_d & (pos_d >= 0) & (pos_d < n_pg * page_size)
    wp = jnp.where(ok, real.astype(jnp.int32), scratch.astype(jnp.int32))
    wo = jnp.where(ok, pos_d % page_size, 0).astype(jnp.int32)
    return wp, wo


def virtual_rows(pos_d, slot_d, tb_d, page_size):
    """What the one-token body needs of a step's W virtual rows (each a
    fed token: position pos_d[w] >= 0 of the request in slot slot_d[w],
    or padding at position -1): the page-table row each goes through
    (W, n_pg) and its write target. Padding writes its slot's scratch
    page. Returns (rows, wp, wo)."""
    rows = tb_d[slot_d]
    wp, wo = paged_write_targets(pos_d, pos_d >= 0, rows, page_size,
                                 scratch=slot_d)
    return rows, wp, wo


def cached_self_attention_step(q, k_new, v_new, k_cache, v_cache, t):
    """The one-token causal KV-cache attention inner shared by
    MultiHeadAttention.self_step (NMT) and GPTBlock.step: write this
    token's K/V at position t, attend q over positions <= t.

    q/k_new/v_new (B,H,1,D); caches (B,H,Lmax,D); t traced scalar.
    Returns (out (B,1,H*D), new_k, new_v). Score/softmax/PV math runs in
    float32 regardless of cache dtype (bf16 caches would otherwise give
    decode logits that diverge from the training forward's f32-accumulate
    flash kernel)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ..ndarray import apply_op

    def f(q_, kn, vn, kc, vc, tt):
        ti = tt.astype(jnp.int32)
        kc = lax.dynamic_update_slice(kc, kn.astype(kc.dtype), (0, 0, ti, 0))
        vc = lax.dynamic_update_slice(vc, vn.astype(vc.dtype), (0, 0, ti, 0))
        B, H, _, D = q_.shape
        s = jnp.einsum("bhqd,bhkd->bhqk", q_.astype(jnp.float32),
                       kc.astype(jnp.float32)) / (D ** 0.5)
        valid = jnp.arange(kc.shape[2])[None, None, None, :] <= ti
        s = jnp.where(valid, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p,
                       vc.astype(jnp.float32)).astype(q_.dtype)
        return o.transpose(0, 2, 1, 3).reshape(B, 1, H * D), kc, vc

    return apply_op(f, q, k_new, v_new, k_cache, v_cache, t)


def paged_attention_step(q, k_new, v_new, k_pages, v_pages, tables, wp, wo,
                         t):
    """`cached_self_attention_step` with PER-ROW positions over an
    mx.pages block-table cache — the continuous-batching variant
    mx.serve's steps need. A row is one fed token: row b writes its K/V
    into page wp[b] at in-page offset wo[b] and attends over positions <=
    t[b] gathered through ITS page-table row tables[b]. Several rows may
    be one request's tokens at consecutive positions (the same table row
    repeated): every row's write lands before any row attends, and a row
    at position p attends positions <= p, so a prompt token sees the same
    pass's earlier tokens of its own request and nothing later —
    causality inside a pass comes from the cache. A row at t = -1 is
    padding: it attends nothing and its output is not read. The attention
    math is `pallas_ops.paged_attention`, whose XLA fallback is the
    scalar-t version's f32 score/softmax/PV expression at the gathered
    (B,H,L,D) shapes, so a request's logits do not depend on what the
    other slots are doing — the property mx.serve's
    same-under-load-as-alone guarantee rests on.

    The write is `pallas_ops.kv_page_write`: where the paged kernels run
    (one device) it replaces row wo[b] of page wp[b] in place, in the
    row-major layout the attention kernel reads and — the pool's head
    dimension being padded to the lane width there, `arena_head_dim` —
    the arenas rest in, so no arena is copied or re-laid around it;
    elsewhere it is the `.at[wp, :, wo, :].set` scatter, the pre-kernel
    expression. Rows may share a page (at different offsets); the cells
    are distinct by construction but for scratch cells
    (`paged_write_targets`), which nothing reads.

    q (B,H,1,D); k_new/v_new (B,Hkv,1,D), H a multiple of Hkv (grouped
    queries); k_pages/v_pages (P,Hkv,ps,Dp), Dp >= D (the lanes past D
    hold zeros); tables (B,n_pg) int32; wp/wo/t (B,) traced int. Returns
    (out (B,1,H*D), new_k_pages, new_v_pages). (A model whose layers
    differ in window or scope calls the two kernels itself:
    `models/laguna.py`.)"""
    import jax
    import jax.numpy as jnp

    from ..ndarray import apply_op
    from ..pallas_ops import kv_page_write as _kv_write
    from ..pallas_ops import paged_attention as _paged_attn

    def f(q_, kn, vn, kp, vp, tb, wp_, wo_, tt):
        # named scopes: the device trace knows an XLA operation by its
        # instruction number only; these names survive a refactor
        # (mx.trace.scope_map reads them back from the executable)
        with jax.named_scope("kv_arena_update"):
            kp, vp = _kv_write(kp, vp, kn, vn, wp_, wo_)
        B, H, _, D = q_.shape
        with jax.named_scope("page_gather"):
            o = _paged_attn(q_, kp, vp, tb.astype(jnp.int32),
                            tt.astype(jnp.int32))
        return o.transpose(0, 2, 1, 3).reshape(B, 1, H * D), kp, vp

    return apply_op(f, q, k_new, v_new, k_pages, v_pages, tables, wp, wo, t)


def layer_call(layers, i, method, *args):
    """`getattr(layers[i], method)(*args)` for a stack of layers built
    alike, traced ONCE a shape for the whole stack: the call goes through
    one `jax.jit` of layer 0's method with the layer's parameters as
    arguments, so every layer after the first finds the trace made, and
    the lowered module holds the layer once and a call a layer (XLA
    inlines them: the executable is the one a Python loop over the layers
    gives). A step of 24 layers traced and lowered layer by layer spends
    most of a second of set-up there, for every executable of every
    bucket (PERF.md section 6, PR 38). `args` and the returns are
    NDArrays (tuples of them); the layers hold the same parameters by
    name, and the method reads nothing else that differs between them.
    Runs while a step is traced, never on a step."""
    import jax

    from ..ndarray import NDArray

    proto = layers[0]
    shared = proto.__dict__.setdefault("_layer_calls", {})
    if method not in shared:
        names = [name for name, _ in proto._iter_params()]
        held = [p for _, p in proto._iter_params()]

        def layer(pdata, *data):
            saved = [p._data._data for p in held]
            for p, d in zip(held, pdata):
                p._data._data = d
            try:
                out = getattr(proto, method)(*[NDArray(d) for d in data])
            finally:
                for p, d in zip(held, saved):
                    p._data._data = d
            return tuple(o._data for o in out)

        shared[method] = names, jax.jit(layer)
    names, fn = shared[method]
    mine = list(layers[i]._iter_params())
    if [name for name, _ in mine] != names:
        raise ValueError(
            f"layer_call: layer {i} does not hold layer 0's parameters")
    out = fn([p.data()._data for _, p in mine], *[a._data for a in args])
    return tuple(NDArray(o) for o in out)


def beam_search_loop(logits0, step, reorder, B, beam, eos, max_steps,
                     alpha=0.6, seqs0=None, lengths0=1):
    """Host-side beam bookkeeping shared by TransformerNMT.beam_search and
    GPTForCausalLM.generate(num_beams>1): device emits logits, the host
    selects top-k continuations, and `reorder` gathers the KV caches by
    beam parent on-device.

    logits0: (B*beam, V) for the FIRST expansion (encoder bos step for
    NMT, prompt prefill for GPT) — only beam 0 is live so the expansion
    yields `beam` DISTINCT tokens, not copies of the argmax.
    step(tok_flat (B*beam,) int32, i) -> (B*beam, V) logits for expansion
    i+1.  reorder(gather (B*beam,) int32) reindexes the caches.
    Returns (seqs (B, <=max_steps [+ seqs0 cols]), scores (B,)) — the
    best beam per batch under Sockeye/GNMT length norm
    lp(l) = ((5+l)/6)^alpha."""
    import numpy as np

    if seqs0 is None:
        seqs = np.zeros((B, beam, 0), np.int32)
    else:
        seqs = np.asarray(seqs0, np.int32)
    cum = np.full((B, beam), -np.inf, np.float32)
    cum[:, 0] = 0.0
    finished = np.zeros((B, beam), bool)
    lengths = np.full((B, beam), lengths0, np.int32)
    batch_off = np.arange(B)[:, None] * beam
    logits = logits0

    for i in range(max_steps):
        lg = np.asarray(logits, np.float32)
        V = lg.shape[-1]
        m = lg.max(-1, keepdims=True)
        logp = lg - np.log(np.exp(lg - m).sum(-1, keepdims=True)) - m
        logp = logp.reshape(B, beam, V)
        # finished beams may only emit eos, at no additional cost
        fin_row = np.full((V,), -np.inf, np.float32)
        fin_row[eos] = 0.0
        logp = np.where(finished[:, :, None], fin_row[None, None, :], logp)
        flat = (cum[:, :, None] + logp).reshape(B, beam * V)
        top = np.argpartition(-flat, beam - 1, axis=1)[:, :beam]
        order = np.argsort(-np.take_along_axis(flat, top, 1), axis=1)
        top = np.take_along_axis(top, order, 1)              # sorted top-k
        parent = top // V                                    # (B, beam)
        tok = (top % V).astype(np.int32)
        cum = np.take_along_axis(flat, top, 1)
        finished = np.take_along_axis(finished, parent, 1)
        lengths = np.take_along_axis(lengths, parent, 1) + (~finished)
        seqs = np.take_along_axis(seqs, parent[:, :, None], 1)
        seqs = np.concatenate([seqs, tok[:, :, None]], axis=2)
        finished = finished | (tok == eos)
        reorder((batch_off + parent).reshape(-1).astype(np.int32))
        if finished.all():
            break
        if i < max_steps - 1:
            logits = step(tok.reshape(-1).astype(np.int32), i)

    lp = ((5.0 + lengths) / 6.0) ** alpha
    norm = cum / lp
    norm = np.where(np.isfinite(norm), norm, -np.inf)
    best = norm.argmax(axis=1)
    idx = np.arange(B)
    return seqs[idx, best], norm[idx, best]


def jit_flat_step(model, step_fn, n_state, donate_state=0, label=None):
    """step_fn(*leading, flat_state: list) -> (primary, new_state: list).

    `model` MUST be the block whose parameters step_fn uses: registering
    it as a child is what makes functional_call substitute its parameters
    as jit ARGUMENTS — without it they trace as closure CONSTANTS and
    decoding silently freezes at the weights of the first compile
    (pinned by tests/train/test_decode.py::test_decode_sees_updated_weights).

    `donate_state`: how many LEADING entries of the flat state are
    threaded through the call (passed in, returned as new state) and
    therefore DONATED to the executable. Without donation every decode
    step double-buffers the whole KV cache — the old buffers stay live
    while XLA allocates the new ones (the mx.check `donation-miss`
    finding that motivated this parameter). Callers must not touch a
    donated buffer after the call: thread the RETURNED state, as both
    decode loops already do. Read-only state entries (e.g. the NMT
    encoder K/V, re-passed every step) go AFTER the donated prefix and
    keep their buffers.

    Returns run(*leading_arrays, state_list) -> (primary, new_state) with
    everything jitted; `leading` are the per-call scalars/arrays before the
    flat state (token ids, step index, masks...). The runner also carries
    `run.lower(*leading_avals, state_avals)`, the `jax.stages.Lowered`
    form of a call at those (shape, dtype)s: nothing is dispatched and no
    batch transfers. It goes through the jit the calls use, so what its
    `compile()` builds is what a call at the same avals runs, and that
    call builds nothing (mx.serve builds a bucket's executables ahead of
    their first call so, and reads the admission budget's memory analysis
    from them: `Server._build`).

    `label`: hand the executable of the first call to
    `mx.trace.note_executable` under this name (the jit and the call's
    avals; nothing is lowered or read until a reader asks
    `mx.trace.scope_map()`), and add the first call's seconds, which are
    the compile's, to `mx.trace.setup()["compile_s"]`."""
    import time

    import jax

    from .. import check as _check
    from .. import serve as _serve
    from .. import trace as _trace
    from ..gluon.block import functional_call

    class _Step(HybridBlock):
        def __init__(self):
            super().__init__()
            self.model = model

        def forward(self, *args):
            leading, flat = args[:-n_state], list(args[-n_state:])
            primary, new_state = step_fn(*leading, flat)
            return tuple([primary] + list(new_state))

    whole, gp, aux = functional_call(_Step(), train=False)

    def pure(*args):
        # inference leaves auxiliary parameters as they are; returned, each
        # would be copied to an output buffer every call (a model whose
        # parameters carry no gradient holds ALL its weights there)
        return whole(*args)[0]

    rng = jax.random.key(0)
    # donate_argnums are positional, so there is one jit per leading arity
    # (fixed per call site in practice), made once: the calls and `lower`
    # go through it, so an executable lowered and compiled ahead of time
    # IS the one the call runs (jax keeps a jit's trace, lowering and
    # executable by its avals; nothing is built twice)
    jits = {}
    called = set()      # leading arities whose first call has been made

    def jit_of(n_leading):
        """(the jit, its donate_argnums) for this leading arity."""
        if n_leading not in jits:
            base = 3 + n_leading    # gp_data, aux_data, rng come first
            donate = tuple(range(base, base + int(donate_state)))
            jits[n_leading] = jax.jit(pure, donate_argnums=donate), donate
        return jits[n_leading]

    def run(*args):
        leading, state = args[:-1], list(args[-1])
        gp_data = [p.data()._data for _, p in gp]
        aux_data = [p.data()._data for _, p in aux]
        entry, donate = jit_of(len(leading))
        is_miss = len(leading) not in called
        if is_miss and _check._enabled:
            _check.check_jit(
                f"decode_step({type(model).__name__})",
                (len(leading), n_state,
                 tuple(tuple(getattr(s, "shape", ())) for s in state)),
                entry, (gp_data, aux_data, rng) + leading
                + tuple(state), donate_argnums=donate,
                can_donate=True)
        if is_miss:
            called.add(len(leading))    # after the lint: refused, it runs again
        if is_miss and label is not None:
            # before the call: the state buffers are donated to it
            _trace.note_executable(
                label, entry,
                (gp_data, aux_data, rng) + leading + tuple(state))
            t_compile = time.perf_counter()
        if _serve._enabled:
            t0 = time.perf_counter()
            outs = entry(gp_data, aux_data, rng, *leading, *state)
            _serve.note_dispatch(type(model).__name__, t0)
        else:
            outs = entry(gp_data, aux_data, rng, *leading, *state)
        if is_miss and label is not None:
            _trace.note_setup("compile_s", time.perf_counter() - t_compile)
        return outs[0], list(outs[1:])

    def lower(*args):
        """`jax.stages.Lowered` of a call with these (shape, dtype)
        arguments: jax.ShapeDtypeStructs, or arrays, of which the avals
        are kept with the sharding of those committed to a device
        (`trace.avals_of`: what makes the avals the call's). No dispatch,
        no transfer. Its `compile()` is the build the call with the same
        avals then finds done; that call still runs the mx.check lint."""
        leading, state = args[:-1], list(args[-1])
        gp_data = [p.data()._data for _, p in gp]
        aux_data = [p.data()._data for _, p in aux]
        return jit_of(len(leading))[0].lower(
            gp_data, aux_data, rng,
            *_trace.avals_of(tuple(leading) + tuple(state)))

    run.lower = lower
    return run
