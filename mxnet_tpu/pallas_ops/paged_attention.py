"""Paged decode attention: one-token attention over a block-table KV
cache (vLLM/PagedAttention, PAPERS.md 2309.06180).

mx.pages stores each sequence's K/V as a LIST of fixed-size pages in a
pooled (pages, H, page_size, D) array; a decode step must attend row b's
query over the positions <= t[b] scattered across its page table. XLA's
lowering of that gather (`k_pages[tables]` then a dense attention)
materializes the gathered (B, H, L, D) operand in HBM before the matmul
— an extra full-cache round-trip per token, on the executable mx.inspect
already flags memory-bound. This kernel walks the page table inside the
program instead: one grid program a batch row reads the row's table
(scalar-prefetched) and DMAs the pages it names from the pool, which
stays in HBM, into VMEM a few at a time, the next few in flight while
these are reduced into online-softmax state — the gathered operand never
exists. The walk ends at the row's position: a row at token 40 of a
256-token bucket brings and reduces 3 pages, not 16. A row is one fed
TOKEN: a serving step hands it every token it feeds, several of one
request at consecutive positions through the same table row among them
(`models/_decode`), and the rows that pad the step to its width at
position -1, which walk no page and come back as zeros.

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

# a wave is the pages of one row that are in flight together: enough of
# them that their DMAs overlap each other, few enough that two waves of
# K and V stay a small part of VMEM. Worked out from shapes, not a knob
_WAVE_BYTES = 512 * 1024


def _pages_per_wave(n_pg, page_bytes, wave_bytes):
    return max(1, min(n_pg, wave_bytes // page_bytes))


def _kernel(tb_ref, t_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem,
            wave0, *, page_size, n_pg, wave, sm_scale, group, window):
    """One batch row a program: walk the row's page table as far as its
    position, `wave` pages at a time, and reduce them with an online
    softmax whose state stays in registers.

    K and V stay in HBM; each page tables[b, j] is one DMA into a
    double-buffered VMEM scratch (slot = parity of a wave counter that
    runs across rows, kept in SMEM). While a wave is reduced the next is
    in flight: the row's own next wave, or after its last the FIRST wave
    of row b + 1 — scratch and semaphores outlive a grid step, so a row
    exposes no DMA latency but row 0's. Pages past t[b] are neither
    fetched nor reduced: they would contribute exact zeros (p = 0,
    alpha = 1), so no bit of the result depends on them. A row at
    position -1 (padding) has no page: it hands the next row's first
    wave on and writes zeros.

    `window` (static, None: everything): the walk starts at the page
    that holds position t[b] - window + 1 and that page is masked below
    it, so a table entry behind the window is never read (the pool has
    freed it). Waves stay aligned to multiples of `wave` pages; the first
    one of a row may be short at its front.

    `group` (static): 1 where there is a KV head a query head; the
    arithmetic is then one query row a head against the page, on the VPU.
    Otherwise q holds `group` rows a KV head (the queries that share it,
    padded to the sublane tile) and a page, brought once, is reduced
    against all of them: two small matrix products a KV head."""
    b = pl.program_id(0)
    B = pl.num_programs(0)

    def n_pages(row):
        # positions -1 (no page), 0 .. page_size - 1 (one), ...
        return jnp.minimum(
            (jnp.maximum(t_ref[row], -1) + page_size) // page_size, n_pg)

    def first_page(row):
        """The page of the first visible position: 0 without a window."""
        if window is None:
            return 0
        return jnp.maximum(t_ref[row] - (window - 1), 0) // page_size

    def first_wave(row):
        return 0 if window is None else first_page(row) // wave

    def buffer(j, slot):
        # page j of a row lies in the slot of its wave at j % wave.
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

    @pl.when(b == 0)
    def _first():
        wave0[0] = 0
        start(0, first_wave(0), 0, n_pages(0))

    n = n_pages(b)
    w_lo = first_wave(b)
    n_w = (n + wave - 1) // wave
    if window is not None:
        n_w = n_w - w_lo
    w0 = wave0[0]
    if group == 1:
        q = q_ref[0].astype(jnp.float32)                 # (H, Dp)
    else:
        q = q_ref[0]                            # (Hkv * group, Dp)
    H, Dp = q.shape

    def visible(j, rows):
        pos = j * page_size + \
            jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 1)
        if window is None:
            return pos <= t_ref[b]
        return (pos <= t_ref[b]) & (pos > t_ref[b] - window)

    def page_step(j, slot, carry):
        """Today's arithmetic of one page, in today's page order."""
        m_prev, l_prev, acc = carry
        g = buffer(j, slot)
        if group == 1:
            k = k_buf[g].astype(jnp.float32)             # (H, ps, Dp)
            v = v_buf[g].astype(jnp.float32)
            # per-head single-query scores over this page's positions.
            # One query row per head is a batched matrix-VECTOR product,
            # which Mosaic's dot_general refuses (no lhs non-contracting
            # dim) — and the MXU would idle on it anyway;
            # multiply-and-reduce on the VPU
            s = jnp.sum(q[:, None, :] * k, axis=-1) * sm_scale   # (H, ps)
        else:
            # the group's queries against the page of their KV head
            s = jnp.concatenate([
                jax.lax.dot_general(
                    q[h * group:(h + 1) * group], k_buf[g, h],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                for h in range(H // group)], axis=0) * sm_scale  # (H, ps)
        s = jnp.where(visible(j, H), s, _NEG)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                           # (H, ps)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if group == 1:
            pv = jnp.sum(p[:, :, None] * v, axis=1)
        else:
            pv = jnp.concatenate([
                jnp.dot(p[h * group:(h + 1) * group].astype(v_buf.dtype),
                        v_buf[g, h], preferred_element_type=jnp.float32)
                for h in range(H // group)], axis=0)             # (H, Dp)
        return m_new, l_new, acc * alpha + pv

    def wave_step(i, carry):
        w = i if window is None else w_lo + i
        slot = (w0 + i) % 2
        # the next wave in the order the grid runs: this row's, or the
        # first of the next row (none after the last row's last)
        more = i + 1 < n_w
        nxt = jnp.where(more, b, jnp.minimum(b + 1, B - 1))
        start(nxt, jnp.where(more, w + 1, first_wave(nxt)), 1 - slot,
              jnp.where(more | (b + 1 < B), n_pages(nxt), 0))
        wave_dmas(b, w, slot, n, lambda dma: dma.wait())
        # two pages a loop step, in order: the second page's scores do
        # not wait for the first page's softmax, so the two overlap (a
        # call at the benchmark's shapes: 88 -> 71 us; four buy no more)
        lo = wave_lo(b, w)
        hi = jnp.minimum(w * wave + wave, n)
        pairs = (hi - lo) // 2

        def pair(i, c):
            c = page_step(lo + 2 * i, slot, c)
            return page_step(lo + 2 * i + 1, slot, c)

        carry = jax.lax.fori_loop(0, pairs, pair, carry)
        return jax.lax.fori_loop(
            lo + 2 * pairs, hi, lambda j, c: page_step(j, slot, c), carry)

    @pl.when((n_w == 0) & (b + 1 < B))
    def _hand_on():
        # no wave of this row's runs to start the next row's first: it
        # goes where that row looks for it, the slot of wave counter w0
        start(b + 1, first_wave(b + 1), w0 % 2, n_pages(b + 1))

    _, l, acc = jax.lax.fori_loop(
        0, n_w, wave_step,
        (jnp.full((H, 1), _NEG, jnp.float32),
         jnp.zeros((H, 1), jnp.float32),
         jnp.zeros((H, Dp), jnp.float32)))
    wave0[0] = w0 + n_w
    # an empty walk leaves l = 0 and acc = 0: zeros, not 0 / 0
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pages, v_pages, tables, t, window):
    return _paged_call(q, k_pages, v_pages, tables, t, window=window,
                       wave_bytes=_WAVE_BYTES, interpret=_common.interpret())


# A step executable calls this once a layer with the same shapes: under
# an inlined jit the body is traced once a process and every call site
# still gets a `pallas_call` of its own; what the trace depends on
# besides shapes is a static argument.
@functools.partial(jax.jit,
                   static_argnames=("window", "wave_bytes", "interpret"),
                   inline=True)
def _paged_call(q, k_pages, v_pages, tables, t, *, window=None, wave_bytes,
                interpret):
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
    wave = _pages_per_wave(n_pg, Hkv * ps * Dp * k_pages.dtype.itemsize,
                           wave_bytes)
    if H == Hkv:
        group, rows = 1, H
        q2 = jnp.pad(q.reshape(B, H, D), ((0, 0), (0, 0), (0, Dp - D)))
    else:
        # the queries of a KV head side by side, each group padded to the
        # sublane tile of the arenas' dtype: the kernel slices whole tiles
        # and the rows of padding (zeros: a uniform softmax) are dropped
        group = _common.round_up(
            H // Hkv, 8 * 4 // k_pages.dtype.itemsize)
        rows = Hkv * group
        q2 = jnp.pad(q.reshape(B, Hkv, H // Hkv, D).astype(k_pages.dtype),
                     ((0, 0), (0, 0), (0, group - H // Hkv), (0, Dp - D))) \
            .reshape(B, rows, Dp)
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=ps, n_pg=n_pg, wave=wave,
                          sm_scale=1.0 / (D ** 0.5), group=group,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, rows, Dp), lambda b, tb, tt: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, rows, Dp),
                                   lambda b, tb, tt: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2 * wave, Hkv, ps, Dp), k_pages.dtype),
                pltpu.VMEM((2 * wave, Hkv, ps, Dp), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),     # (slot, K or V)
                pltpu.SMEM((1,), jnp.int32),         # waves before row b
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, rows, Dp), q.dtype),
        # rows run in order: each starts the next one's first wave
        compiler_params=_common.compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(tables.astype(jnp.int32), t.astype(jnp.int32), q2, k_pages, v_pages)
    if group == 1:
        return out[:, :, :D].reshape(B, H, 1, D)
    return out.reshape(B, Hkv, group, Dp)[:, :, :H // Hkv, :D] \
        .reshape(B, H, 1, D)


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
