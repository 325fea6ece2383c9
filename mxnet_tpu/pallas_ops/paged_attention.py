"""Paged decode attention: one-token attention over a block-table KV
cache (vLLM/PagedAttention, PAPERS.md 2309.06180).

mx.pages stores each sequence's K/V as a LIST of fixed-size pages in a
pooled (pages, H, page_size, D) array; a decode step must attend row b's
query over the positions <= t[b] scattered across its page table. XLA's
lowering of that gather (`k_pages[tables]` then a dense attention)
materializes the gathered (B, H, L, D) operand in HBM before the matmul
— an extra full-cache round-trip per token, on the executable mx.inspect
already flags memory-bound. This kernel walks the page table inside the
program instead: it reads the tables (scalar-prefetched) and DMAs the
pages they name from the pool, which stays in HBM, into VMEM a few at a
time, the next few in flight while these are reduced into online-softmax
state — the gathered operand never exists. A walk ends at the last
position it serves: a row at token 40 of a 256-token bucket brings and
reduces 3 pages, not 16.

A row is one fed TOKEN: a serving step hands the kernel every token it
feeds (`models/_decode`), several of one request at consecutive
positions through the same table row among them, and the rows that pad
the step to its width at position -1, which walk no page and come back
as zeros. Rows of one table row at consecutive positions are a RUN, and
a run is walked ONCE (since PR 40): a prompt chunk of 32 rows brings its
request's pages once, not 32 times, and reduces each wave of them
against a query block of its rows x heads on the MXU. A run of one row
(every decoding row) is reduced as it was before runs, page by page.

Fallback (`kernels=off`, non-TPU without the interpreter): the gather +
the per-row attention expression of a dense (B,H,L,D) cache
(`models/_decode.cached_self_attention_step`'s f32 score/softmax/PV
math with a position a row) — what the tests hold the served tokens to
`model.generate`'s with.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _common

__all__ = ["paged_attention", "paged_attention_reference"]

_NEG = -1e30


def paged_attention_reference(q, k_pages, v_pages, tables, t, window=None):
    """Pure-XLA paged decode attention (the pre-kernel lowering).

    q (B,H,1,D); k_pages/v_pages (P,Hkv,ps,Dp), Dp >= D (lanes past D are
    ignored), H a multiple of Hkv: query head h reads KV head
    h // (H / Hkv); tables (B,n_pg) int32 page ids; t (B,) traced int
    positions; `window` (static): row b sees positions t[b] - window <
    j <= t[b] only. Returns (B,H,1,D) in q.dtype.

    Gathers the pages into the dense (B,H,L,D) layout (L = n_pg*ps) and
    then runs VERBATIM the masked f32 score/softmax/PV expression of the
    dense-cache step (`models/_decode.cached_self_attention_step`, with
    a position a row) — identical reductions, so a paged cache whose
    tables enumerate a sequence's pages in order produces the logits of
    a dense cache of the same length."""
    ti = t.astype(jnp.int32)
    if k_pages.shape[3] != q.shape[3]:      # lane-padded arenas
        k_pages = k_pages[..., :q.shape[3]]
        v_pages = v_pages[..., :q.shape[3]]
    kc = k_pages[tables]                         # (B, n_pg, H, ps, D)
    B, n_pg, H, ps, D = kc.shape
    kc = kc.transpose(0, 2, 1, 3, 4).reshape(B, H, n_pg * ps, D)
    vc = v_pages[tables].transpose(0, 2, 1, 3, 4) \
        .reshape(B, H, n_pg * ps, D)
    group = q.shape[1] // H
    if group > 1:       # each KV head under the queries of its group
        kc, vc = jnp.repeat(kc, group, axis=1), jnp.repeat(vc, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) / (D ** 0.5)
    key = jnp.arange(kc.shape[2])[None, None, None, :]
    valid = key <= ti[:, None, None, None]
    if window is not None:
        valid &= key > ti[:, None, None, None] - window
    s = jnp.where(valid, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p,
                   vc.astype(jnp.float32)).astype(q.dtype)
    return o


# --------------------------------------------------------------------------
# pallas kernel
# --------------------------------------------------------------------------

# a wave is the pages of one walk that are in flight together: enough of
# them that their DMAs overlap each other, few enough that two waves of
# K and V stay a small part of VMEM. Worked out from shapes, not a knob
_WAVE_BYTES = 512 * 1024
# a run's rows are reduced in blocks of this many (the f32 sublane tile:
# the rows of the blocks' online-softmax state)
_BLOCK_ROWS = 8
# VMEM for the online-softmax state of one run's blocks: a run longer
# than the blocks it buys is cut into runs that fit
_STATE_BYTES = 8 * 1024 * 1024
# Mosaic's default scoped-VMEM limit; a call that needs more asks for it
_SCOPED_VMEM = 16 * 1024 * 1024


def _pages_per_wave(n_pg, page_bytes, wave_bytes):
    return max(1, min(n_pg, wave_bytes // page_bytes))


def _runs(tables, t, cap):
    """The runs of a call's rows, in XLA: (run, nxt, multi): run and nxt
    (B,) int32, multi (1,) int32.
    A run is rows at consecutive positions through EQUAL page-table rows
    (a request's fed tokens: `models/_decode.virtual_rows` hands each the
    table row of its slot), so every row of it reads the pages its first
    row's walk brings. `run[b]` is the run's length at its first row, 0
    at every later row and at padding (t = -1); a run of more than `cap`
    rows is cut into runs of `cap`. `nxt[b]` is the first row after b
    that starts a run (B: none). `multi[0]` is 1 where some run has more
    than one row (a call of decoding rows only has none)."""
    import jax.numpy as jnp
    from jax import lax
    B = t.shape[0]
    idx = jnp.arange(B, dtype=jnp.int32)
    live = t >= 0
    prev_t = jnp.concatenate([jnp.full((1,), -1, jnp.int32), t[:-1]])
    same = jnp.concatenate([jnp.zeros((1,), bool),
                            jnp.all(tables[1:] == tables[:-1], axis=1)])
    start = live & ((prev_t < 0) | (t != prev_t + 1) | ~same)
    first = lax.cummax(jnp.where(start, idx, 0))
    start = live & (start | ((idx - first) % cap == 0))

    def after(x):
        # min over rows j > b of x[j]
        return jnp.concatenate([lax.cummin(x, reverse=True)[1:],
                                jnp.full((1,), B, jnp.int32)])
    end = after(jnp.where(start | ~live, idx, B))
    run = jnp.where(start, end - idx, 0).astype(jnp.int32)
    return (run, after(jnp.where(start, idx, B)).astype(jnp.int32),
            jnp.any(run > 1).astype(jnp.int32).reshape(1))


def _kernel(tb_ref, t_ref, run_ref, nxt_ref, multi_ref, q_ref, k_hbm, v_hbm,
            o_ref,
            k_buf, v_buf, sem, wave0, *state, page_size, n_pg, wave,
            sm_scale, group, window, block, q_dtype):
    """One program a virtual row; the program of a run's FIRST row walks
    the run's pages, the programs of its later rows do nothing.

    A run (`run_ref`, `_runs`) is rows of one request at consecutive
    positions (one table row): a decoding row is a run of one, a prompt
    chunk of C rows one of C. The walk goes from the page the first row
    sees (0, or with `window` the page of t_first - window + 1: a table
    entry behind every row's window, which the pool may have freed, is
    never read; waves stay aligned to multiples of `wave` pages, so the
    first may be short at its front) to the page of the last row's
    position, `wave` pages at a time. K and V stay in HBM; each
    page tables[b, j] is one DMA into a double-buffered VMEM scratch
    (slot = parity of a wave counter that runs across walks, kept in
    SMEM). While a wave is reduced the next is in flight: the walk's own
    next wave, or after its last the FIRST wave of the next run
    (`nxt_ref`) — scratch and semaphores outlive a grid step, so only
    the first walk exposes its DMA latency. Pages past the last row's
    position are neither fetched nor reduced. A row at position -1
    (padding) walks nothing and writes zeros. q and the output are
    blocks of the whole call, so a run's program reads and writes all of
    its rows.

    A run of ONE row is reduced as before runs existed: page by page,
    the online-softmax state in registers. `group` (static) 1 where
    there is a KV head a query head: one query row a head against the
    page, on the VPU, in f32. Otherwise q holds `group` rows a KV head
    (the queries that share it, padded to 8) and a page is reduced
    against all of them: two small matrix products a KV head, the
    queries rounded to the pages' dtype.

    A longer run is reduced a wave at a time against a query BLOCK: its
    rows in blocks of `block` rows, each with its online-softmax state in
    VMEM scratch (`state`), row r masked to keys j <= t[r] (and j >
    t[r] - window). group 1: per head the block's (block x Dp) queries
    against the wave's keys, (block x wave*ps) scores, on the MXU: bf16
    operands where q and the pages are bf16, f32 accumulation, and p
    split into three bf16 terms for P.V, so that p keeps its f32 bits
    (f32 pages: f32 products at HIGHEST). group > 1: per KV head the
    (block*group x Dp) queries of its heads, at the per-row path's
    precision (p rounded to the pages' dtype for P.V). Slots of a wave
    that the walk did not fetch hold zeros or an earlier page (V is
    zeroed once a call that has such a run, `multi_ref`): their keys are
    masked for every row."""
    b = pl.program_id(0)
    B = pl.num_programs(0)

    def n_pages(pos):
        # positions -1 (no page), 0 .. page_size - 1 (one), ...
        return jnp.minimum(
            (jnp.maximum(pos, -1) + page_size) // page_size, n_pg)

    def n_walk(row):
        """The pages a walk from `row` takes: to its run's last row."""
        return n_pages(t_ref[row + jnp.maximum(run_ref[row], 1) - 1])

    def first_page(row):
        """The page of the first visible position: 0 without a window."""
        if window is None:
            return 0
        return jnp.maximum(t_ref[row] - (window - 1), 0) // page_size

    def first_wave(row):
        return 0 if window is None else first_page(row) // wave

    def buffer(j, slot):
        # page j of a walk lies in the slot of its wave at j % wave.
        # (Spelled with the remainder on purpose: handed `slot * wave -
        # w * wave + j` Mosaic schedules the pair of page steps below
        # 11 % slower, 226 against 203 us a call of full rows)
        return slot * wave + j % wave

    def wave_lo(row, w):
        """First page of wave w of `row` that the walk takes."""
        if window is None:
            return w * wave
        return jnp.maximum(w * wave, first_page(row))

    def wave_dmas(row, w, slot, n, go):
        """Start (or wait for) the DMAs of wave w of `row` into `slot`:
        one of K and one of V for each of its pages below n."""
        def page(j, _):
            for hbm, buf, kv in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                go(pltpu.make_async_copy(hbm.at[tb_ref[row, j]],
                                         buf.at[buffer(j, slot)],
                                         sem.at[slot, kv]))
        jax.lax.fori_loop(wave_lo(row, w), jnp.minimum((w + 1) * wave, n),
                          page, None)

    def start(row, w, slot, n):
        wave_dmas(row, w, slot, n, lambda dma: dma.start())

    @pl.when((b == 0) & (multi_ref[0] > 0))
    def _zero_v():
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)

    @pl.when(b == 0)
    def _first():
        wave0[0] = 0
        f = jnp.where(run_ref[0] > 0, 0, nxt_ref[0])
        row = jnp.minimum(f, B - 1)
        start(row, first_wave(row), 0, jnp.where(f < B, n_walk(row), 0))

    def walk(n, reduce_wave, carry):
        """Wait for and reduce this program's waves (pages first_page(b)
        .. n - 1), each while the next is in flight."""
        w_lo = first_wave(b)
        n_w = (n + wave - 1) // wave
        if window is not None:
            n_w = n_w - w_lo
        w0 = wave0[0]

        def wave_step(i, carry):
            w = i if window is None else w_lo + i
            slot = (w0 + i) % 2
            # the next wave in the order the grid runs: this walk's, or
            # the first of the next run (none after the last run's last)
            more = i + 1 < n_w
            nx = nxt_ref[b]
            row = jnp.where(more, b, jnp.minimum(nx, B - 1))
            start(row, jnp.where(more, w + 1, first_wave(row)), 1 - slot,
                  jnp.where(more | (nx < B), n_walk(row), 0))
            wave_dmas(b, w, slot, n, lambda dma: dma.wait())
            return reduce_wave(w, slot, carry)

        carry = jax.lax.fori_loop(0, n_w, wave_step, carry)
        wave0[0] = w0 + n_w
        return carry

    @pl.when(t_ref[b] < 0)
    def _padding():
        o_ref[b] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when(run_ref[b] == 1)
    def _row():
        if group == 1:
            q = q_ref[b].astype(jnp.float32)             # (H, Dp)
        else:
            q = q_ref[b].astype(k_buf.dtype)    # (Hkv * group, Dp)
        H, Dp = q.shape
        tb = t_ref[b]

        def visible(j, rows):
            pos = j * page_size + \
                jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 1)
            if window is None:
                return pos <= tb
            return (pos <= tb) & (pos > tb - window)

        def page_step(j, slot, carry):
            """One page's arithmetic, in page order."""
            m_prev, l_prev, acc = carry
            g = buffer(j, slot)
            if group == 1:
                k = k_buf[g].astype(jnp.float32)         # (H, ps, Dp)
                v = v_buf[g].astype(jnp.float32)
                # per-head single-query scores over this page's
                # positions. One query row per head is a batched
                # matrix-VECTOR product, which Mosaic's dot_general
                # refuses (no lhs non-contracting dim) — and the MXU
                # would idle on it anyway; multiply-and-reduce on the VPU
                s = jnp.sum(q[:, None, :] * k, axis=-1) * sm_scale
            else:
                # the group's queries against the page of their KV head
                s = jnp.concatenate([
                    jax.lax.dot_general(
                        q[h * group:(h + 1) * group], k_buf[g, h],
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    for h in range(H // group)], axis=0) * sm_scale
            s = jnp.where(visible(j, H), s, _NEG)        # (H, ps)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if group == 1:
                pv = jnp.sum(p[:, :, None] * v, axis=1)
            else:
                pv = jnp.concatenate([
                    jnp.dot(p[h * group:(h + 1) * group].astype(v_buf.dtype),
                            v_buf[g, h], preferred_element_type=jnp.float32)
                    for h in range(H // group)], axis=0)         # (H, Dp)
            return m_new, l_new, acc * alpha + pv

        def reduce_wave(w, slot, carry):
            # two pages a loop step, in order: the second page's scores
            # do not wait for the first page's softmax, so the two
            # overlap (a call at the benchmark's shapes: 88 -> 71 us;
            # four buy no more)
            lo = wave_lo(b, w)
            hi = jnp.minimum(w * wave + wave, n)
            pairs = (hi - lo) // 2

            def pair(i, c):
                c = page_step(lo + 2 * i, slot, c)
                return page_step(lo + 2 * i + 1, slot, c)

            carry = jax.lax.fori_loop(0, pairs, pair, carry)
            return jax.lax.fori_loop(
                lo + 2 * pairs, hi, lambda j, c: page_step(j, slot, c),
                carry)

        n = n_pages(tb)
        _, l, acc = walk(n, reduce_wave,
                         (jnp.full((H, 1), _NEG, jnp.float32),
                          jnp.zeros((H, 1), jnp.float32),
                          jnp.zeros((H, Dp), jnp.float32)))
        o_ref[b] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    @pl.when(run_ref[b] > 1)
    def _run():
        _run_walk(b, run_ref[b], t_ref[b], q_ref, o_ref, k_buf, v_buf,
                  state, walk, n_pages, page_size=page_size, wave=wave,
                  sm_scale=sm_scale, group=group, window=window, block=block,
                  q_dtype=q_dtype)


def _run_walk(b, L, t0, q_ref, o_ref, k_buf, v_buf, state, walk, n_pages, *,
              page_size, wave, sm_scale, group, window, block, q_dtype):
    """The program of a run's first row: rows b .. b + L - 1 at
    positions t0 .. t0 + L - 1, one walk, `block` rows a query block
    (`_kernel` says how)."""
    m_sc, l_sc, acc_sc = state
    n_heads, M, Dp = acc_sc.shape[1:]           # M = block * group
    Wk = wave * page_size
    n_blocks = (L + block - 1) // block

    def mm(a, b_, contract):
        """a (M, K) x b_ along `contract`, f32 accumulation; bf16
        operands where both are bf16, else f32 at HIGHEST."""
        dims = (((1,), (contract,)), ((), ()))
        if a.dtype == b_.dtype == jnp.bfloat16:
            return jax.lax.dot_general(a, b_, dims,
                                       preferred_element_type=jnp.float32)
        return jax.lax.dot_general(
            a.astype(jnp.float32), b_.astype(jnp.float32), dims,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def init(i, _):
        m_sc[i] = jnp.full(m_sc.shape[1:], _NEG, jnp.float32)
        l_sc[i] = jnp.zeros(l_sc.shape[1:], jnp.float32)
        acc_sc[i] = jnp.zeros(acc_sc.shape[1:], jnp.float32)

    jax.lax.fori_loop(0, n_blocks, init, None)

    def rows(r0, h):
        """Block r0's rows of head h (group 1) or of KV head h's queries:
        the index q and the output share."""
        if group == 1:
            return pl.ds(r0, block), h, slice(None)
        return pl.ds(r0, block), pl.ds(pl.multiple_of(h * group, group),
                                       group), slice(None)

    # heads are a loop, not unrolled: set-up traces the kernel once a
    # shape, and 16 heads unrolled (in Python, or as the loop is lowered)
    # cost `decode-closed` 3-6 s of `setup_s` on the chip, for a call
    # 1.3-1.6x as fast (PR 40)
    def reduce_wave(w, slot, carry):
        key = w * Wk + jax.lax.broadcasted_iota(jnp.int32, (M, Wk), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (M, Wk), 0) // group

        def one_block(i, _):
            r0 = b + i * block
            pos = t0 + i * block + row
            valid = key <= pos
            if window is not None:
                valid &= key > pos - window

            def head(h, _):
                # q's f32 block back in the dtype it came in (grouped
                # queries: already rounded to the pages')
                q = q_ref[rows(r0, h)].reshape(M, Dp).astype(q_dtype)
                k = k_buf[pl.ds(slot * wave, wave), h].reshape(Wk, Dp)
                v = v_buf[pl.ds(slot * wave, wave), h].reshape(Wk, Dp)
                s = jnp.where(valid, mm(q, k, 1) * sm_scale, _NEG)  # (M, Wk)
                m_prev = m_sc[i, h][:, :1]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m_prev - m_new)
                l_sc[i, h] = jnp.broadcast_to(
                    l_sc[i, h][:, :1] * alpha
                    + jnp.sum(p, axis=-1, keepdims=True), (M, 128))
                m_sc[i, h] = jnp.broadcast_to(m_new, (M, 128))
                if group > 1:
                    pv = jnp.dot(p.astype(v.dtype), v,
                                 preferred_element_type=jnp.float32)
                elif v.dtype == jnp.bfloat16:
                    # p as three bf16 terms, one product: p's f32 bits
                    hi = p.astype(jnp.bfloat16)
                    r = p - hi.astype(jnp.float32)
                    mid = r.astype(jnp.bfloat16)
                    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
                    y = jnp.dot(jnp.concatenate([hi, mid, lo], axis=0), v,
                                preferred_element_type=jnp.float32)
                    pv = y[:M] + y[M:2 * M] + y[2 * M:]
                else:
                    pv = mm(p, v, 0)
                acc_sc[i, h] = acc_sc[i, h] * alpha + pv

            jax.lax.fori_loop(0, n_heads, head, None)

        jax.lax.fori_loop(0, n_blocks, one_block, None)
        return carry

    walk(n_pages(t0 + L - 1), reduce_wave, None)

    def finish(i, _):
        r0 = b + i * block
        # rows past the run belong to later programs: left as they are
        shape = (block, Dp) if group == 1 else (block, group, Dp)
        live = i * block + jax.lax.broadcasted_iota(jnp.int32, shape, 0) < L

        def head(h, _):
            l = l_sc[i, h][:, :1]
            out = (acc_sc[i, h] / jnp.where(l == 0.0, 1.0, l)) \
                .reshape(shape).astype(o_ref.dtype)
            at = rows(r0, h)
            o_ref[at] = jnp.where(live, out, o_ref[at])

        jax.lax.fori_loop(0, n_heads, head, None)

    jax.lax.fori_loop(0, n_blocks, finish, None)


def _paged_attention_pallas(q, k_pages, v_pages, tables, t, window):
    return _paged_call(q, k_pages, v_pages, tables, t, window=window,
                       wave_bytes=_WAVE_BYTES, state_bytes=_STATE_BYTES,
                       interpret=_common.interpret())


# A step executable calls this once a layer with the same shapes: under
# an inlined jit the body is traced once a process and every call site
# still gets a `pallas_call` of its own; what the trace depends on
# besides shapes is a static argument.
@functools.partial(jax.jit,
                   static_argnames=("window", "wave_bytes", "state_bytes",
                                    "interpret"),
                   inline=True)
def _paged_call(q, k_pages, v_pages, tables, t, *, window=None, wave_bytes,
                state_bytes=_STATE_BYTES, interpret):
    B, H, _, D = q.shape
    Hkv, ps, Dp = k_pages.shape[1:]
    n_pg = tables.shape[1]
    # a page is DMAed whole, and Mosaic slices an HBM operand only where
    # its last dimension fills the lanes: arenas the pool allocated at
    # the lane width (kv_page_write.arena_head_dim, zeros past D) come
    # as they are; any other width is padded here, which copies the
    # arena (tools/tpu_validate's bare pools, never a served one). Zeros
    # in q's lanes past D leave every score as it is, and the output's
    # lanes there are dropped
    lanes = _common.round_up(Dp, 128) - Dp
    if lanes:
        pad = ((0, 0),) * 3 + ((0, lanes),)
        k_pages, v_pages, Dp = \
            jnp.pad(k_pages, pad), jnp.pad(v_pages, pad), Dp + lanes
    page_bytes = Hkv * ps * Dp * k_pages.dtype.itemsize
    wave = _pages_per_wave(n_pg, page_bytes, wave_bytes)
    if H == Hkv:
        # q and the output in f32 blocks (a head's rows of a query block
        # are then one row of an f32 tile each); the output is rounded to
        # q's dtype after the call, as it was inside it
        group, rows = 1, H
        q2 = jnp.pad(q.reshape(B, H, D).astype(jnp.float32),
                     ((0, 0), (0, 0), (0, Dp - D)))
    else:
        # the queries of a KV head side by side, each group padded to the
        # f32 sublane tile: q and the output are f32 blocks, so the kernel
        # slices whole tiles of 8 rows; the queries are rounded to the
        # pages' dtype for the products in the kernel, the output to q's
        # after it, and the rows of padding (zeros: a uniform softmax)
        # are dropped
        group = _common.round_up(H // Hkv, 8)
        rows = Hkv * group
        q2 = jnp.pad(q.reshape(B, Hkv, H // Hkv, D).astype(k_pages.dtype)
                     .astype(jnp.float32),
                     ((0, 0), (0, 0), (0, group - H // Hkv), (0, Dp - D))) \
            .reshape(B, rows, Dp)
    tables, t = tables.astype(jnp.int32), t.astype(jnp.int32)
    # a run's rows in query blocks: as many blocks as `state_bytes` holds
    # the online-softmax state of (at most the call's rows)
    block = _BLOCK_ROWS
    n_heads, M = (H, block) if group == 1 else (Hkv, block * group)
    per_block = n_heads * M * (2 * 128 + Dp) * 4
    n_blocks = max(1, min(-(-B // block), state_bytes // per_block))
    run, nxt, multi = _runs(tables, t, n_blocks * block)
    # a run's last block may reach past row B - 1
    B_out = B + block
    q2 = jnp.pad(q2, ((0, block),) + ((0, 0),) * 2)
    # q and the output are blocks of the whole call, one buffer each
    need = 2 * B_out * rows * Dp * q2.dtype.itemsize \
        + 4 * wave * page_bytes + n_blocks * per_block
    limit = None if need + (4 << 20) <= _SCOPED_VMEM \
        else _common.round_up(need + (8 << 20), 1 << 20)
    spec = functools.partial(pl.BlockSpec, (B_out, rows, Dp),
                             lambda b, *_: (0, 0, 0))
    if not interpret:
        spec = functools.partial(spec, pipeline_mode=pl.Buffered(1))
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=ps, n_pg=n_pg, wave=wave,
                          sm_scale=1.0 / (D ** 0.5), group=group,
                          window=window, block=block,
                          q_dtype=q.dtype if group == 1 else k_pages.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B,),
            in_specs=[
                spec(),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=spec(),
            scratch_shapes=[
                pltpu.VMEM((2 * wave, Hkv, ps, Dp), k_pages.dtype),
                pltpu.VMEM((2 * wave, Hkv, ps, Dp), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),     # (slot, K or V)
                pltpu.SMEM((1,), jnp.int32),         # waves before a walk
                # the online-softmax state of a run's blocks: m, l, acc
                pltpu.VMEM((n_blocks, n_heads, M, 128), jnp.float32),
                pltpu.VMEM((n_blocks, n_heads, M, 128), jnp.float32),
                pltpu.VMEM((n_blocks, n_heads, M, Dp), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B_out, rows, Dp), q2.dtype),
        # rows run in order: each walk starts the next one's first wave
        compiler_params=_common.compiler_params(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=limit),
        interpret=interpret,
        name="paged_attention",
    )(tables, t, run, nxt, multi, q2, k_pages, v_pages)[:B]
    if group == 1:
        return out[:, :, :D].reshape(B, H, 1, D).astype(q.dtype)
    return out.reshape(B, Hkv, group, Dp)[:, :, :H // Hkv, :D] \
        .reshape(B, H, 1, D).astype(q.dtype)


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------

def paged_attention(q, k_pages, v_pages, tables, t, window=None):
    """Single-query decode attention through a page table.

    Args:
      q: (B, H, 1, D) queries (model dtype).
      k_pages, v_pages: (P, Hkv, page_size, Dp) pooled KV pages (cache
        dtype), Dp >= D with zeros past D — page id p is physical row p.
        H is a multiple of Hkv (grouped queries): query head h reads KV
        head h // (H / Hkv), and a page is brought once for its group.
      tables: (B, n_pg) int32 page ids; row b's logical position range
        [0, n_pg*page_size) maps page-major onto its table entries.
      t: (B,) traced int — row b attends positions <= t[b]; -1 marks a
        padding row, which reads nothing (the kernel returns zeros for
        it, the reference a finite mean of whatever its table names:
        nobody reads either).
      window: static int or None — row b attends positions t[b] - window
        < j <= t[b] only, and the kernel walks no page behind them (a
        table entry there may name a page the pool has freed).

    Returns (B, H, 1, D) in q.dtype. `kernels=off` (or no
    TPU/interpreter) runs `paged_attention_reference` — the dense-cache
    attention expression at the gathered shapes. Like the
    fused-update kernels, the Pallas path is a global-view
    `pallas_call` with no GSPMD rule, so it engages only when the step
    sees a single device (serve's decode regime)."""
    if _common.use_pallas() and not _common.multi_device():
        _load_pallas()
        return _paged_attention_pallas(q, k_pages, v_pages, tables, t,
                                       window)
    return paged_attention_reference(q, k_pages, v_pages, tables, t, window)


# pallas binds lazily at first kernel engagement (shared logic in
# _common): this module sits on the serve decode hot path, and with
# kernels=off it must not drag jax.experimental.pallas into the process
# (ci sanity asserts it)
pl = None
pltpu = None


def _load_pallas():
    global pl, pltpu
    pl = _common.load_pallas()
    if pltpu is None:
        from jax.experimental.pallas import tpu as _pltpu
        pltpu = _pltpu
