"""In-place write of a step's new keys and values into the mx.pages
arenas (the cache side of `models/_decode.paged_attention_step`).

Row b of a paged step (a decoding request's token, or one of the prompt
tokens a request feeds in the same pass) stores its new (H, D) key and
value at in-page offset wo[b] of page wp[b] of the pooled (pages, H,
page_size, Dp) arenas. XLA's lowering of that write (`arena.at[wp, :, wo, :].set(new)`,
dimensions 0 and 2 indexed around a slice) is a scatter whose TPU form
wants the (H, D) window contiguous: it re-lays the whole arena out for
the scatter and copies it back for `paged_attention`, which reads the
row-major layout — once per layer, per token, whatever the batch. This
kernel writes in the layout the attention kernel reads: scalar-prefetched
(wp, wo) drive the BlockSpec index_map, a program brings its page of K
and of V into VMEM, replaces one row and puts the page back, and the
arenas are aliased to the outputs, so no other page moves.

Several rows of a call may name one page at different offsets (positions
p, p + 1, ... of one request), and THE ROWS OF ONE PAGE ARE CONSECUTIVE
ROWS OF THE CALL: that is the contract, and every caller packs so (a
request's tokens of a pass lie side by side in position order, padding
lies at the tail or beside its slot's rows). The first row of a run takes
the page as it came in, and each later one replaces its row in the OUTPUT
block, which stays in VMEM while consecutive programs name the same block
and goes back to HBM once, when the page changes. Rows that name one CELL
(padding rows share a scratch cell) leave the last of them there. A page
named again after another page came between loses the earlier run's rows
(the later run starts from the page as it came in).

`arena_head_dim` is the other half. A Mosaic kernel reads its operands
row-major, and row-major pads a last dimension under 128 to the lane
width. The TPU's own default layout for such an array avoids that padding
by another dimension order (for `bf16[P,16,16,64]`: pages minor-most,
`{0,3,2,1}`), so an arena of head dimension 64 is re-laid whole on entry
to every step executable and again on exit. The pool therefore allocates
the last dimension at the lane width where these kernels run — the same
bytes the row-major layout holds, said in the shape — and row-major IS
the default layout: parameters, scan carries, kernel operands and results
are one layout, and the donated buffers are written in place. (Pinning a
non-default layout on the unpadded shape, `jax.experimental.layout`, gives
the same executable but one that fails when loaded from the persistent
compile cache: PR 27, PERF.md.) Both kernels take arenas of any Dp >= D
and keep zeros in the lanes past D.

A page is named by one run of consecutive programs and by no program
after it, which is also what makes the pipeline's read-ahead of the next
page safe while this one is still on its way back.

Fallback (`kernels=off`, non-TPU without the interpreter, a multi-device
step): the `.at[].set` itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _common

__all__ = ["kv_page_write", "kv_page_write_reference", "arena_head_dim"]

_LANES = 128


def _engaged():
    """The gate `paged_attention` has: a global-view `pallas_call`, so
    only where a kernel can run and the step sees a single device."""
    return _common.use_pallas() and not _common.multi_device()


def kv_page_write_reference(k_pages, v_pages, k_new, v_new, wp, wo):
    """Pure-XLA arena write (the pre-kernel lowering): one scatter per
    arena. Shapes as `kv_page_write`."""
    wp = wp.astype(jnp.int32)
    wo = wo.astype(jnp.int32)
    D = k_new.shape[3]
    # the whole last dimension, unless the arena is wider than the heads
    lanes = slice(None) if k_pages.shape[3] == D else slice(0, D)
    k_pages = k_pages.at[wp, :, wo, lanes].set(
        k_new[:, :, 0, :].astype(k_pages.dtype))
    v_pages = v_pages.at[wp, :, wo, lanes].set(
        v_new[:, :, 0, :].astype(v_pages.dtype))
    return k_pages, v_pages


def _kernel(wp_ref, wo_ref, kn_ref, vn_ref, kp_ref, vp_ref, ko_ref, vo_ref):
    """Program b: pages wp[b] of K and V (the BlockSpec index_map read
    the prefetched wp) with row wo[b] replaced by row b's new (H, 1, Dp)
    vectors, broadcast over the page's rows. Where program b - 1 named
    the same page the output block is still in VMEM with that program's
    row in it, and is what this one starts from."""
    b = pl.program_id(0)
    row = jax.lax.broadcasted_iota(jnp.int32, kp_ref.shape[1:], 1) \
        == wo_ref[b]                                     # (H, ps, Dp)
    resident = (b > 0) & (wp_ref[b] == wp_ref[jnp.maximum(b - 1, 0)])

    @pl.when(resident)
    def _same_page():
        ko_ref[0] = jnp.where(row, kn_ref[0], ko_ref[0])
        vo_ref[0] = jnp.where(row, vn_ref[0], vo_ref[0])

    @pl.when(jnp.logical_not(resident))
    def _new_page():
        ko_ref[0] = jnp.where(row, kn_ref[0], kp_ref[0])
        vo_ref[0] = jnp.where(row, vn_ref[0], vp_ref[0])


def _kv_page_write_pallas(k_pages, v_pages, k_new, v_new, wp, wo):
    return _write_call(k_pages, v_pages, k_new, v_new, wp, wo,
                       interpret=_common.interpret())


# A step executable calls this once a layer with the same shapes: under an
# inlined jit the kernel is traced once a width (a trace is 46 ms, and each
# of a bucket's executables holds 24 calls) and every call site still gets a
# `pallas_call` of its own; the interpreter flag, which the trace reads
# besides shapes, is a static argument (as `paged_attention._paged_call`).
@functools.partial(jax.jit, static_argnames=("interpret",), inline=True)
def _write_call(k_pages, v_pages, k_new, v_new, wp, wo, *, interpret):
    B, H, _, D = k_new.shape
    ps, Dp = k_pages.shape[2:]
    new = pl.BlockSpec((1, H, 1, Dp), lambda b, wp_, wo_: (b, 0, 0, 0))
    page = pl.BlockSpec((1, H, ps, Dp),
                        lambda b, wp_, wo_: (wp_[b], 0, 0, 0))

    def padded(x, dtype):       # zeros in the lanes past D
        return jnp.pad(x.astype(dtype), ((0, 0),) * 3 + ((0, Dp - D),))

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[new, new, page, page],
            out_specs=[page, page],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)],
        # operands count the two prefetched scalars: arenas are 4 and 5
        input_output_aliases={4: 0, 5: 1},
        compiler_params=_common.compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="kv_page_write",
    )(wp.astype(jnp.int32), wo.astype(jnp.int32),
      padded(k_new, k_pages.dtype), padded(v_new, v_pages.dtype),
      k_pages, v_pages)


def kv_page_write(k_pages, v_pages, k_new, v_new, wp, wo):
    """Write row b's new key and value at (page wp[b], offset wo[b]).

    Args:
      k_pages, v_pages: (P, H, page_size, Dp) pooled KV pages, Dp >= D.
      k_new, v_new: (B, H, 1, D) this token's keys and values (cast to
        the arenas' dtype here).
      wp, wo: (B,) traced int page ids and in-page offsets. Rows may
        share a page; the rows of one page are consecutive (the module
        docstring's contract). Rows that share a (page, offset) cell
        leave the last of them there (scratch cells, which nothing
        reads).

    Returns (new_k_pages, new_v_pages): the same values, dtype and targets
    as `kv_page_write_reference`, bit for bit."""
    if _engaged():
        _load_pallas()
        return _kv_page_write_pallas(k_pages, v_pages, k_new, v_new, wp, wo)
    return kv_page_write_reference(k_pages, v_pages, k_new, v_new, wp, wo)


def arena_head_dim(head_dim):
    """The last dimension page arenas are allocated with for heads of
    `head_dim`: the next multiple of the lane width where the two paged
    kernels run (their gate), `head_dim` itself elsewhere. See the module
    docstring: padded in the shape, row-major is the default layout and
    nothing re-lays an arena."""
    if _engaged():
        return _common.round_up(int(head_dim), _LANES)
    return int(head_dim)


# pallas binds lazily at first kernel engagement (see paged_attention)
pl = None
pltpu = None


def _load_pallas():
    global pl, pltpu
    pl = _common.load_pallas()
    if pltpu is None:
        from jax.experimental.pallas import tpu as _pltpu
        pltpu = _pltpu
