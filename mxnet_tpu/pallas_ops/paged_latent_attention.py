"""Paged attention over a LATENT cache (multi-head latent attention in
absorbed form, DeepSeek-V2, PAPERS.md 2405.04434).

A latent cache keeps ONE row a token and layer, `[c (kv_lora_rank); k_pe
(qk_rope_head_dim)]`, with no head axis: it is the key of every head (all
of it) and the value of every head (its first `v_width` = kv_lora_rank
lanes). The per-head key and value up-projections are folded into the
query and applied after the weighted sum (`models/deepseek.py`), so a
decode step attends `q (B, H, W)` over the rows as they lie in the pool's
arena `(P, page_size, W)`.

`paged_attention` cannot read that arena (it wants a head axis and
separate K and V), and XLA's lowering (`lat_pages[tables]`, then a dense
attention) gathers the whole bucket through HBM for every row of the
step, padding rows included. This kernel walks the page table inside the
program, as `paged_attention` does: one grid program a VIRTUAL ROW reads
the row's table (scalar-prefetched) and DMAs the pages it names into VMEM
a wave at a time, the next wave in flight while this one is reduced into
online-softmax state. A wave's rows `(wave * page_size, W)` are brought
once and used twice, as the keys of all H heads (one matrix product
against the query tile) and as their values (a second one against the
probabilities): per cached row `W * itemsize` bytes and `2 * H * (W +
v_width)` operations, 242 operations a byte at DeepSeek-V2's 128 heads,
which is the v5e's ridge. The walk ends at the row's position; a padding
row (position -1) walks nothing and comes back as zeros.

Fallback (`kernels=off`, no TPU and no interpreter):
`paged_latent_attention_reference`, the gather and the float32 attention
expression, which is also what the tests hold the kernel to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _common

__all__ = ["paged_latent_attention", "paged_latent_attention_reference"]

_NEG = -1e30


def paged_latent_attention_reference(q, lat_pages, tables, t, scale,
                                     v_width):
    """Pure-XLA attention over a paged latent cache.

    q (B,H,Wq); lat_pages (P,ps,W), W >= Wq (lanes past Wq are ignored);
    tables (B,n_pg) int32 page ids; t (B,) traced int positions: row b
    sees the cached rows at positions <= t[b] of its table; `scale`
    multiplies the scores; the first `v_width` lanes of a cached row are
    its value. Returns (B,H,v_width) in q.dtype. Scores, softmax and the
    weighted sum run in float32 over the gathered (B, n_pg * ps, W) rows."""
    B, n_pg = tables.shape
    wq = q.shape[-1]
    rows = lat_pages[tables].reshape(B, n_pg * lat_pages.shape[1], -1) \
        .astype(jnp.float32)
    s = jnp.einsum("bhw,blw->bhl", q.astype(jnp.float32), rows[..., :wq]) \
        * scale
    seen = jnp.arange(rows.shape[1])[None, None, :] \
        <= t.astype(jnp.int32)[:, None, None]
    p = jax.nn.softmax(jnp.where(seen, s, _NEG), axis=-1)
    return jnp.einsum("bhl,blv->bhv", p, rows[..., :v_width]).astype(q.dtype)


# --------------------------------------------------------------------------
# pallas kernel
# --------------------------------------------------------------------------

# the pages of one row that are in flight together and reduced by one pair
# of matrix products: enough rows that the products fill the MXU and the
# online-softmax state is rescaled seldom, few enough that two waves and
# their scores stay a small part of VMEM. Worked out from shapes, not a knob
# (on the v5e at 128 heads, a call over full rows: 320 KB 42 % of the
# roofline, 640 KB 52 %, 1,280 KB 58 %, 2,560 KB 60 %, and a row's last
# wave is reduced whole, half of it wasted on average)
_WAVE_BYTES = 1280 * 1024


def _pages_per_wave(n_pg, page_bytes, wave_bytes):
    return max(1, min(n_pg, wave_bytes // page_bytes))


def _kernel(tb_ref, t_ref, q_ref, lat_hbm, o_ref, buf, sem, wave0, acc_ref,
            *, page_size, n_pg, wave, scale, v_width):
    """One virtual row a program: walk the row's page table as far as its
    position, `wave` pages at a time, each wave two matrix products for
    all heads, with the online softmax's sum of values in VMEM.

    The arena stays in HBM; page tables[b, j] is one DMA into a
    double-buffered VMEM scratch (slot = parity of a wave counter that
    runs across rows, kept in SMEM). While a wave is reduced the next is
    in flight: the row's own next wave, or after its last the FIRST wave
    of row b + 1, so a row exposes no DMA latency but row 0's. A wave is
    reduced whole: the rows of its pages past t[b] (not fetched: they hold
    what an earlier wave left, zeros at first) are masked, so they weigh
    exactly nothing. A row at position -1 (padding) has no page: it hands
    the next row's first wave on and writes zeros."""
    b = pl.program_id(0)
    B = pl.num_programs(0)
    rows = wave * page_size

    def n_pages(row):
        # positions -1 (no page), 0 .. page_size - 1 (one), ...
        return jnp.minimum(
            (jnp.maximum(t_ref[row], -1) + page_size) // page_size, n_pg)

    def wave_dmas(row, w, slot, n, go):
        """Start (or wait for) the DMAs of wave w of `row` into `slot`:
        one for each of its pages below n."""
        def page(j, _):
            go(pltpu.make_async_copy(
                lat_hbm.at[tb_ref[row, j]],
                buf.at[slot, pl.ds((j - w * wave) * page_size, page_size)],
                sem.at[slot]))
        jax.lax.fori_loop(w * wave, jnp.minimum((w + 1) * wave, n), page,
                          None)

    def start(row, w, slot, n):
        wave_dmas(row, w, slot, n, lambda dma: dma.start())

    @pl.when(b == 0)
    def _first():
        # what a masked row of a wave holds is multiplied by a weight of
        # exactly zero: it has to be finite
        buf[...] = jnp.zeros_like(buf)
        wave0[0] = 0
        start(0, 0, 0, n_pages(0))

    n = n_pages(b)
    n_w = (n + wave - 1) // wave
    w0 = wave0[0]
    q = q_ref[0]                                         # (H, W)
    H = q.shape[0]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def wave_step(w, carry):
        m_prev, l_prev = carry
        slot = (w0 + w) % 2
        # the next wave in the order the grid runs: this row's, or the
        # first of the next row (none after the last row's last)
        more = w + 1 < n_w
        nxt = jnp.where(more, b, jnp.minimum(b + 1, B - 1))
        start(nxt, jnp.where(more, w + 1, 0), 1 - slot,
              jnp.where(more | (b + 1 < B), n_pages(nxt), 0))
        wave_dmas(b, w, slot, n, lambda dma: dma.wait())
        kv = buf[slot]                                   # (rows, W)
        s = jax.lax.dot_general(
            q, kv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (H, rows)
        pos = w * rows + jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)
        s = jnp.where(pos <= t_ref[b], s, _NEG)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(kv.dtype), kv[:, :v_width],
            preferred_element_type=jnp.float32)
        return m_new, l_new

    @pl.when((n_w == 0) & (b + 1 < B))
    def _hand_on():
        # no wave of this row's runs to start the next row's first: it
        # goes where that row looks for it, the slot of wave counter w0
        start(b + 1, 0, w0 % 2, n_pages(b + 1))

    _, l = jax.lax.fori_loop(
        0, n_w, wave_step,
        (jnp.full((H, 1), _NEG, jnp.float32), jnp.zeros((H, 1), jnp.float32)))
    wave0[0] = w0 + n_w
    # an empty walk leaves l = 0 and acc = 0: zeros, not 0 / 0
    o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)) \
        .astype(o_ref.dtype)


# A step executable calls this once a layer with the same shapes: under an
# inlined jit the body is traced once a process and every call site still
# gets a `pallas_call` of its own; what the trace depends on besides shapes
# is a static argument (`paged_attention._paged_call` says why).
@functools.partial(jax.jit, static_argnames=("scale", "v_width",
                                             "wave_bytes", "interpret"),
                   inline=True)
def _paged_call(q, lat_pages, tables, t, *, scale, v_width, wave_bytes,
                interpret):
    B, H, wq = q.shape
    ps, W = lat_pages.shape[1:]
    n_pg = tables.shape[1]
    # a page is DMAed whole, and Mosaic slices an HBM operand only where
    # its last dimension fills the lanes: arenas the pool allocated at the
    # lane width (kv_page_write.arena_head_dim, zeros past the row) come
    # as they are; any other width is padded here, which copies the arena
    # (the tests' bare pools, never a served one)
    lanes = _common.round_up(W, 128) - W
    if lanes:
        lat_pages = jnp.pad(lat_pages, ((0, 0), (0, 0), (0, lanes)))
        W += lanes
    # zeros in q's lanes past its width leave every score as it is
    q = jnp.pad(q.astype(lat_pages.dtype), ((0, 0), (0, 0), (0, W - wq)))
    wave = _pages_per_wave(n_pg, ps * W * lat_pages.dtype.itemsize,
                           wave_bytes)
    return pl.pallas_call(
        functools.partial(_kernel, page_size=ps, n_pg=n_pg, wave=wave,
                          scale=scale, v_width=v_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, W), lambda b, tb, tt: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, v_width),
                                   lambda b, tb, tt: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, wave * ps, W), lat_pages.dtype),
                pltpu.SemaphoreType.DMA((2,)),       # a slot
                pltpu.SMEM((1,), jnp.int32),         # waves before row b
                pltpu.VMEM((H, v_width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, v_width), q.dtype),
        # rows run in order: each starts the next one's first wave
        compiler_params=_common.compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_latent_attention",
    )(tables.astype(jnp.int32), t.astype(jnp.int32), q, lat_pages)


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------

def paged_latent_attention(q, lat_pages, tables, t, scale, v_width):
    """Attention of B virtual rows over a paged latent cache.

    Args:
      q: (B, H, Wq) queries in absorbed form, `[W_uk^T q_nope; q_pe]` a
        head (model dtype).
      lat_pages: (P, page_size, W) pooled latent rows (cache dtype), W >=
        Wq with zeros past Wq: page id p is physical row p.
      tables: (B, n_pg) int32 page ids; row b's logical positions [0, n_pg
        * page_size) map page-major onto its table entries.
      t: (B,) traced int: row b attends positions <= t[b]; -1 marks a
        padding row, which reads nothing (the kernel returns zeros for
        it, the reference a finite mean of whatever its table names:
        nobody reads either).
      scale: static float on the scores (the model's softmax scale).
      v_width: static int, the lanes of a cached row that are its value.

    Returns (B, H, v_width) in q.dtype. `kernels=off` (or no
    TPU/interpreter) runs `paged_latent_attention_reference`. Like
    `paged_attention`, the Pallas path is a global-view `pallas_call`
    with no GSPMD rule, so it engages only when the step sees a single
    device (serve's decode regime)."""
    if _common.use_pallas() and not _common.multi_device():
        _load_pallas()
        return _paged_call(q, lat_pages, tables, t, scale=float(scale),
                           v_width=int(v_width), wave_bytes=_WAVE_BYTES,
                           interpret=_common.interpret())
    return paged_latent_attention_reference(q, lat_pages, tables, t, scale,
                                            v_width)


# pallas binds lazily at first kernel engagement (see paged_attention)
pl = None
pltpu = None


def _load_pallas():
    global pl, pltpu
    pl = _common.load_pallas()
    if pltpu is None:
        from jax.experimental.pallas import tpu as _pltpu
        pltpu = _pltpu
