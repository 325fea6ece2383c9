"""Paged decode attention: one-token attention over a block-table KV
cache (vLLM/PagedAttention, PAPERS.md 2309.06180).

mx.pages stores each sequence's K/V as a LIST of fixed-size pages in a
pooled (pages, H, page_size, D) array; a decode step must attend row b's
query over the positions <= t[b] scattered across its page table. XLA's
lowering of that gather (`k_pages[tables]` then a dense attention)
materializes the gathered (B, H, L, D) operand in HBM before the matmul
— an extra full-cache round-trip per token, on the executable mx.inspect
already flags memory-bound. This kernel walks the page table inside the
grid instead: scalar-prefetched block indices drive the BlockSpec
index_map, so each (batch, page) program DMAs exactly one page from the
pool into VMEM and accumulates online-softmax state — the gathered
operand never exists.

Fallback (`kernels=off`, non-TPU without the interpreter): the gather +
the EXACT dense per-row attention expression
(`models/_decode.batched_cached_attention_step`'s f32 score/softmax/PV
math) — when the page tables tile a contiguous [0, L) range this is
bit-identical to the dense slot cache path, which is what serve's
pages=on-vs-off bit-identity guarantee rests on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _common

__all__ = ["paged_attention", "paged_attention_reference"]

_NEG = -1e30


def paged_attention_reference(q, k_pages, v_pages, tables, t):
    """Pure-XLA paged decode attention (the pre-kernel lowering).

    q (B,H,1,D); k_pages/v_pages (P,H,ps,Dp), Dp >= D (lanes past D are
    ignored); tables (B,n_pg) int32 page ids; t (B,) traced int
    positions. Returns (B,H,1,D) in q.dtype.

    Gathers the pages into the dense (B,H,L,D) layout (L = n_pg*ps) and
    then runs VERBATIM the masked f32 score/softmax/PV expression of the
    dense slot-cache step — identical operand shapes, identical
    reductions, so a paged cache whose tables enumerate a sequence's
    pages in order produces bit-identical logits to the dense cache."""
    ti = t.astype(jnp.int32)
    if k_pages.shape[3] != q.shape[3]:      # lane-padded arenas
        k_pages = k_pages[..., :q.shape[3]]
        v_pages = v_pages[..., :q.shape[3]]
    kc = k_pages[tables]                         # (B, n_pg, H, ps, D)
    B, n_pg, H, ps, D = kc.shape
    kc = kc.transpose(0, 2, 1, 3, 4).reshape(B, H, n_pg * ps, D)
    vc = v_pages[tables].transpose(0, 2, 1, 3, 4) \
        .reshape(B, H, n_pg * ps, D)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) / (D ** 0.5)
    valid = jnp.arange(kc.shape[2])[None, None, None, :] \
        <= ti[:, None, None, None]
    s = jnp.where(valid, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p,
                   vc.astype(jnp.float32)).astype(q.dtype)
    return o


# --------------------------------------------------------------------------
# pallas kernel
# --------------------------------------------------------------------------

def _kernel(tb_ref, t_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *,
            page_size, sm_scale):
    """One (batch row, page) program: online-softmax accumulate this
    page's contribution to row b's single-query attention.

    The page-table gather happens OUTSIDE this body — the k/v BlockSpec
    index_map reads the scalar-prefetched table, so k_ref/v_ref already
    hold page tables[b, j] in VMEM. Scratch (m, l, acc) carries the
    running max / denominator / value-sum across the page ('arbitrary')
    grid dimension; lanes-broadcast (H, 128) carriers keep the row
    vectors in Mosaic-friendly tiles."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_pg = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, _NEG, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    q = q_ref[0].astype(jnp.float32)                     # (H, D)
    k = k_ref[0].astype(jnp.float32)                     # (H, ps, D)
    v = v_ref[0].astype(jnp.float32)
    H, ps, _ = k.shape
    # per-head single-query scores over this page's positions. One
    # query row per head is a batched matrix-VECTOR product, which
    # Mosaic's dot_general refuses (no lhs non-contracting dim) — and
    # the MXU would idle on it anyway; multiply-and-reduce on the VPU
    s = jnp.sum(q[:, None, :] * k, axis=-1) * sm_scale   # (H, ps)
    pos = j * page_size + \
        jax.lax.broadcasted_iota(jnp.int32, (H, ps), 1)
    s = jnp.where(pos <= t_ref[b], s, _NEG)

    m_prev = m_s[:, 0:1]                                 # (H, 1)
    l_prev = l_s[:, 0:1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                               # (H, ps)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc_s[...] * alpha + jnp.sum(p[:, :, None] * v, axis=1)
    m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[...] = jnp.broadcast_to(l_new, l_s.shape)
    acc_s[...] = acc

    @pl.when(j == n_pg - 1)
    def _write():
        o_ref[0] = (acc_s[...] / l_s[:, 0:1]).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pages, v_pages, tables, t):
    B, H, _, D = q.shape
    ps, Dp = k_pages.shape[2:]
    n_pg = tables.shape[1]
    # arenas wider than the heads (kv_page_write.arena_head_dim) hold
    # zeros past D: zeros in q's lanes there leave every score as it is,
    # and the output's lanes there are dropped
    q2 = jnp.pad(q.reshape(B, H, D), ((0, 0), (0, 0), (0, Dp - D)))
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=ps,
                          sm_scale=1.0 / (D ** 0.5)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_pg),
            in_specs=[
                pl.BlockSpec((1, H, Dp),
                             lambda b, j, tb, tt: (b, 0, 0)),
                pl.BlockSpec((1, H, ps, Dp),
                             lambda b, j, tb, tt: (tb[b, j], 0, 0, 0)),
                pl.BlockSpec((1, H, ps, Dp),
                             lambda b, j, tb, tt: (tb[b, j], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, Dp),
                                   lambda b, j, tb, tt: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, 128), jnp.float32),       # running max
                pltpu.VMEM((H, 128), jnp.float32),       # denominator
                pltpu.VMEM((H, Dp), jnp.float32),        # value acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Dp), q.dtype),
        compiler_params=_common.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_common.interpret(),
        name="paged_attention",
    )(tables.astype(jnp.int32), t.astype(jnp.int32), q2, k_pages, v_pages)
    return out[:, :, :D].reshape(B, H, 1, D)


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------

def paged_attention(q, k_pages, v_pages, tables, t):
    """Single-query decode attention through a page table.

    Args:
      q: (B, H, 1, D) queries (model dtype).
      k_pages, v_pages: (P, H, page_size, Dp) pooled KV pages (cache
        dtype), Dp >= D with zeros past D — page id p is physical row p.
      tables: (B, n_pg) int32 page ids; row b's logical position range
        [0, n_pg*page_size) maps page-major onto its table entries.
      t: (B,) traced int — row b attends positions <= t[b].

    Returns (B, H, 1, D) in q.dtype. `kernels=off` (or no
    TPU/interpreter) runs `paged_attention_reference` — bit-identical to
    the dense slot-cache attention at the same gathered shapes. Like the
    fused-update kernels, the Pallas path is a global-view
    `pallas_call` with no GSPMD rule, so it engages only when the step
    sees a single device (serve's decode regime)."""
    if _common.use_pallas() and not _common.multi_device():
        _load_pallas()
        return _paged_attention_pallas(q, k_pages, v_pages, tables, t)
    return paged_attention_reference(q, k_pages, v_pages, tables, t)


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
