"""Ring attention: sequence/context parallelism over a mesh axis.

Net-new capability vs the reference (SURVEY.md §5.7 — MXNet has nothing that
shards the sequence dimension). Design: the sequence is sharded over the
`sp` mesh axis; each device holds local Q/K/V blocks. K/V blocks rotate
around the ring via `lax.ppermute` (XLA lowers to ICI collective-permute)
while each device accumulates its queries' attention online with
flash-style log-sum-exp merging.

Memory is O(L_local), not O(L_local^2): the per-block-pair attention is the
SAME blockwise kernel as single-chip flash attention — on TPU the Pallas
flash forward/backward kernels run per KV block (`pallas_ops/
flash_attention._flash_fwd_pallas` / `_flash_bwd_pallas` with the globally
merged LSE), on CPU test meshes a chunked `lax.scan` computes at most a
(L_local, chunk) score tile at a time. The whole ring is a `jax.custom_vjp`:
the backward pass is a second ring rotation in which dK/dV accumulators
travel WITH their K/V blocks and arrive home after n hops, so no L×L tensor
and no all-gather ever materializes.

Use under `shard_map` with the `sp` axis (see `ring_self_attention` /
`sp_self_attention`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .mesh import current_mesh

__all__ = ["ring_attention", "ring_self_attention", "sp_self_attention"]

_NEG = -1e30
_DEFAULT_CHUNK = 512


def _fit_chunk(chunk, L):
    """Largest divisor of L that is <= chunk (scan needs equal chunks)."""
    c = max(1, min(int(chunk), int(L)))
    while L % c:
        c -= 1
    return c


# --------------------------------------------------------------------------
# inner per-block-pair kernels: (q_block x kv_block) -> normalized (o, lse)
# and the matching backward.  Two implementations, one contract:
#   fwd: (B,H,Lq,D)x(B,H,Lk,D) + bias (B,Lk) -> o (B,H,Lq,D) f32, lse (B,H,Lq) f32
#   bwd: given global (o, lse) and upstream g -> (dq, dk, dv) f32
# `causal` here means causal WITHIN the block pair (Lq == Lk, offset 0) —
# the only causal case the ring needs (the diagonal block src == my).
# --------------------------------------------------------------------------


def _chunked_fwd(q, k, v, bias, causal, sm_scale, chunk):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    C = _fit_chunk(chunk, Lk)
    nc = Lk // C
    q32 = q.astype(jnp.float32)
    kc = jnp.moveaxis(k.reshape(B, H, nc, C, D), 2, 0)
    vc = jnp.moveaxis(v.reshape(B, H, nc, C, D), 2, 0)
    bc = jnp.moveaxis(bias.reshape(B, nc, C), 1, 0)
    rows = jnp.arange(Lq)[:, None]

    def body(carry, blk):
        m, l, acc = carry
        kb, vb, bb, ci = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kb.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * sm_scale
        s = s + bb[:, None, None, :]
        if causal:
            cols = ci * C + jnp.arange(C)[None, :]
            s = jnp.where(cols <= rows, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p,
                                       vb.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Lq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Lq, 1), jnp.float32)
    a0 = jnp.zeros((B, H, Lq, D), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0),
                              (kc, vc, bc, jnp.arange(nc)))
    l = jnp.maximum(l, 1e-30)
    return acc / l, (m + jnp.log(l))[..., 0]


def _chunked_bwd(q, k, v, bias, g, lse, delta, causal, sm_scale, chunk):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    C = _fit_chunk(chunk, Lk)
    nc = Lk // C
    q32 = q.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    kc = jnp.moveaxis(k.reshape(B, H, nc, C, D), 2, 0)
    vc = jnp.moveaxis(v.reshape(B, H, nc, C, D), 2, 0)
    bc = jnp.moveaxis(bias.reshape(B, nc, C), 1, 0)
    rows = jnp.arange(Lq)[:, None]
    lse_c = lse[..., None]
    delta_c = delta[..., None]

    def body(dq, blk):
        kb, vb, bb, ci = blk
        kb32 = kb.astype(jnp.float32)
        vb32 = vb.astype(jnp.float32)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kb32,
                       preferred_element_type=jnp.float32) * sm_scale
        s = s + bb[:, None, None, :]
        if causal:
            cols = ci * C + jnp.arange(C)[None, :]
            s = jnp.where(cols <= rows, s, _NEG)
        p = jnp.exp(s - lse_c)                       # true probabilities
        dv_c = jnp.einsum("bhqk,bhqd->bhkd", p, g32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", g32, vb32)
        ds = p * (dp - delta_c) * sm_scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kb32)
        dk_c = jnp.einsum("bhqk,bhqd->bhkd", ds, q32)
        return dq, (dk_c, dv_c)

    dq0 = jnp.zeros((B, H, Lq, D), jnp.float32)
    dq, (dk_c, dv_c) = lax.scan(body, dq0, (kc, vc, bc, jnp.arange(nc)))
    dk = jnp.moveaxis(dk_c, 0, 2).reshape(B, H, Lk, D)
    dv = jnp.moveaxis(dv_c, 0, 2).reshape(B, H, Lk, D)
    return dq, dk, dv


def _use_pallas(q, k):
    from ..pallas_ops import _common
    from ..pallas_ops.flash_attention import load_pallas
    if not (q.shape[2] % 128 == 0 and k.shape[2] % 128 == 0
            and _common.use_pallas()):
        return False
    load_pallas()
    return True


def _inner_fwd(q, k, v, bias, causal, sm_scale, chunk, use_pallas):
    if use_pallas:
        from ..pallas_ops.flash_attention import (_fit_block,
                                                  _flash_fwd_pallas)
        bq = _fit_block(512, q.shape[2])
        bk = _fit_block(512, k.shape[2])
        seed = jnp.zeros((1,), jnp.int32)
        o, lse8 = _flash_fwd_pallas(q, k, v, bias, seed, causal, sm_scale,
                                    bq, bk, 0.0)
        B, H, L, _ = q.shape
        return o.astype(jnp.float32), lse8[:, 0, :].reshape(B, H, L)
    return _chunked_fwd(q, k, v, bias, causal, sm_scale, chunk)


def _inner_bwd(q, k, v, bias, g, o, lse, delta, causal, sm_scale, chunk,
               use_pallas):
    if use_pallas:
        from ..pallas_ops.flash_attention import (_fit_block,
                                                  _flash_bwd_pallas, _row8)
        B, H, L, _ = q.shape
        bq = _fit_block(512, q.shape[2])
        bk = _fit_block(512, k.shape[2])
        seed = jnp.zeros((1,), jnp.int32)
        lse8 = _row8(lse.reshape(B * H, L))
        dq, dk, dv = _flash_bwd_pallas(
            q, k, v, bias, seed, o.astype(q.dtype), lse8, g, causal,
            sm_scale, bq, bk, 0.0)
        return (dq.astype(jnp.float32), dk.astype(jnp.float32),
                dv.astype(jnp.float32))
    return _chunked_bwd(q, k, v, bias, g, lse, delta, causal, sm_scale, chunk)


# --------------------------------------------------------------------------
# the ring itself (custom_vjp; call inside shard_map)
# --------------------------------------------------------------------------


def _merge(o_acc, lse_acc, o_blk, lse_blk):
    """Merge two NORMALIZED partial attentions by their log-sum-exps."""
    m = jnp.maximum(lse_acc, lse_blk)
    wa = jnp.exp(lse_acc - m)
    wb = jnp.exp(lse_blk - m)
    w = wa + wb
    o = (o_acc * wa[..., None] + o_blk * wb[..., None]) / w[..., None]
    return o, m + jnp.log(w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ring(q, k, v, bias, axis_name, causal, sm_scale, chunk):
    out, _ = _ring_fwd(q, k, v, bias, axis_name, causal, sm_scale, chunk)
    return out


def _ring_fwd(q, k, v, bias, axis_name, causal, sm_scale, chunk):
    n = lax.psum(1, axis_name)          # static: axis size
    my = lax.axis_index(axis_name)
    use_pallas = _use_pallas(q, k)
    B, H, L, D = q.shape

    o_acc = jnp.zeros((B, H, L, D), jnp.float32)
    lse_acc = jnp.full((B, H, L), _NEG, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur, b_cur = k, v, bias

    def full_blk(kv):
        return _inner_fwd(q, kv[0], kv[1], kv[2], False, sm_scale, chunk,
                          use_pallas)

    def caus_blk(kv):
        return _inner_fwd(q, kv[0], kv[1], kv[2], True, sm_scale, chunk,
                          use_pallas)

    def masked_blk(kv):
        return (jnp.zeros((B, H, L, D), jnp.float32),
                jnp.full((B, H, L), _NEG, jnp.float32))

    # python loop of static length n: unrolled into the XLA program so each
    # ppermute overlaps the previous block's compute
    for step in range(n):
        src = (my - step) % n           # which shard's kv we currently hold
        if causal:
            # shard-level causality: src < my → full block; == → causal
            # within the block; > → entirely masked (selected at runtime —
            # src is traced — via lax.switch, so only ONE branch executes)
            idx = jnp.where(src == my, 1, jnp.where(src > my, 2, 0))
            o_blk, lse_blk = lax.switch(
                idx, [full_blk, caus_blk, masked_blk], (k_cur, v_cur, b_cur))
        else:
            o_blk, lse_blk = full_blk((k_cur, v_cur, b_cur))
        o_acc, lse_acc = _merge(o_acc, lse_acc, o_blk, lse_blk)
        if step < n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
            b_cur = lax.ppermute(b_cur, axis_name, perm)

    return o_acc.astype(q.dtype), (q, k, v, bias, o_acc, lse_acc)


def _ring_bwd(axis_name, causal, sm_scale, chunk, res, g):
    q, k, v, bias, o, lse = res
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    use_pallas = _use_pallas(q, k)
    B, H, L, D = q.shape
    delta = jnp.sum(g.astype(jnp.float32) * o, axis=-1)      # (B,H,L)

    dq = jnp.zeros((B, H, L, D), jnp.float32)
    dk_acc = jnp.zeros((B, H, L, D), jnp.float32)
    dv_acc = jnp.zeros((B, H, L, D), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    k_cur, v_cur, b_cur = k, v, bias

    def full_blk(kv):
        return _inner_bwd(q, kv[0], kv[1], kv[2], g, o, lse, delta, False,
                          sm_scale, chunk, use_pallas)

    def caus_blk(kv):
        return _inner_bwd(q, kv[0], kv[1], kv[2], g, o, lse, delta, True,
                          sm_scale, chunk, use_pallas)

    def masked_blk(kv):
        z = jnp.zeros((B, H, L, D), jnp.float32)
        return z, z, z

    # second ring pass: dK/dV accumulators TRAVEL WITH their K/V blocks —
    # after n hops (note: n, not n-1; the kv blocks themselves only need
    # n-1) each accumulator has collected every device's contribution and
    # is back on the device that owns that sequence shard
    for step in range(n):
        src = (my - step) % n
        if causal:
            idx = jnp.where(src == my, 1, jnp.where(src > my, 2, 0))
            dq_b, dk_b, dv_b = lax.switch(
                idx, [full_blk, caus_blk, masked_blk], (k_cur, v_cur, b_cur))
        else:
            dq_b, dk_b, dv_b = full_blk((k_cur, v_cur, b_cur))
        dq = dq + dq_b
        dk_acc = dk_acc + dk_b
        dv_acc = dv_acc + dv_b
        dk_acc = lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = lax.ppermute(dv_acc, axis_name, perm)
        if step < n - 1:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
            b_cur = lax.ppermute(b_cur, axis_name, perm)

    return (dq.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype), jnp.zeros_like(bias))


_ring.defvjp(_ring_fwd, _ring_bwd)


def ring_attention(q, k, v, axis_name, mask=None, causal=False, sm_scale=None,
                   chunk=_DEFAULT_CHUNK):
    """Attention over a ring: call INSIDE shard_map with seq sharded on
    `axis_name`. q,k,v: (B, H, L_local, D) per device; mask: (B, L_local)
    local padding mask (True = attend). Differentiable (custom VJP; the
    backward is a second ring pass). Attention-probability dropout is not
    supported under the ring (the reference fused attention it replaces is
    a single-chip op; see `pallas_ops.flash_attention` for that)."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if mask is not None:
        bias = jnp.where(mask.astype(bool), 0.0, _NEG).astype(jnp.float32)
    else:
        bias = jnp.zeros((q.shape[0], k.shape[2]), jnp.float32)
    return _ring(q, k, v, bias, axis_name, causal, float(sm_scale),
                 int(chunk))


def ring_self_attention(q, k, v, mask=None, causal=False, mesh=None,
                        axis_name="sp"):
    """Convenience wrapper: shard_map over the mesh's `sp` axis with
    (B, H, L, D) global tensors; L is sharded."""
    from ._compat import shard_map

    mesh = mesh or current_mesh()
    qspec = P(None, None, axis_name, None)
    mspec = P(None, axis_name)

    if mask is not None:
        fn = shard_map(
            lambda q_, k_, v_, m_: ring_attention(
                q_, k_, v_, axis_name, mask=m_, causal=causal),
            mesh=mesh, in_specs=(qspec, qspec, qspec, mspec), out_specs=qspec,
            check_vma=False)
        return fn(q, k, v, mask)
    fn = shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, axis_name, causal=causal),
        mesh=mesh, in_specs=(qspec, qspec, qspec), out_specs=qspec,
        check_vma=False)
    return fn(q, k, v)


def sp_self_attention(q, k, v, mask=None, causal=False, mesh=None,
                      axis_name="sp", inner=None):
    """Ring attention inside a FULL training mesh: shard_map over every mesh
    axis with batch kept on the data axes, heads on `tp` (when divisible)
    and the sequence on `axis_name`, so it composes with dp/fsdp/tp GSPMD
    sharding in a jitted train step (the flagship sp path — SURVEY §5.7).

    q,k,v: GLOBAL (B, H, L, D); mask: global (B, L).
    inner: the per-shard attention (q, k, v, axis_name, mask=, causal=) —
    defaults to `ring_attention`; pass `ulysses.ulysses_attention` for the
    all-to-all head↔sequence reshard instead of the ring."""
    from ._compat import shard_map

    mesh = mesh or current_mesh()
    B, H, L, D = q.shape
    if L % mesh.shape.get(axis_name, 1):
        raise ValueError(
            f"sequence length {L} not divisible by {axis_name} axis size "
            f"{mesh.shape.get(axis_name, 1)}")
    from .specs import attention_axes
    bspec, hspec = attention_axes(mesh, B, H)
    qspec = P(bspec, hspec, axis_name, None)
    mspec = P(bspec, axis_name)
    attn = inner or ring_attention

    if mask is not None:
        fn = shard_map(
            lambda q_, k_, v_, m_: attn(
                q_, k_, v_, axis_name, mask=m_, causal=causal),
            mesh=mesh, in_specs=(qspec, qspec, qspec, mspec), out_specs=qspec,
            check_vma=False)
        return fn(q, k, v, mask)
    fn = shard_map(
        lambda q_, k_, v_: attn(q_, k_, v_, axis_name, causal=causal),
        mesh=mesh, in_specs=(qspec, qspec, qspec), out_specs=qspec,
        check_vma=False)
    return fn(q, k, v)
