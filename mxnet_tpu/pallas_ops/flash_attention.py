"""Flash attention for TPU (Pallas).

Replaces the reference's fused attention ops
(`src/operator/contrib/transformer.cc` `_contrib_interleaved_matmul_selfatt_*`)
with a blockwise online-softmax kernel: O(L) memory instead of the L×L score
matrix, MXU-sized tiles, f32 accumulation over bf16 inputs.

Layout convention here: (batch, heads, seq, head_dim).

Forward is a Pallas kernel that also emits the row-wise log-sum-exp
residual. Backward is selected by sequence length: below
`_PALLAS_BWD_MIN_LEN` XLA's fused L×L formulation (reusing the saved LSE)
is faster; at long context the blockwise Pallas dq/dkv kernels win on both
memory and bandwidth. CPU test meshes use the pure-jnp reference so the
whole framework tests under `--xla_force_host_platform_device_count`.

TPU layout note: row-vector arrays (LSE, delta, padding bias) are carried as
(rows, 8, L) with (1, 8, block) BlockSpecs — Mosaic requires the last two
block dims be (8k, 128k) or span the array, and a blocked spec (unlike a
full-array output spec with a constant index map) is also what keeps each
grid program's writes disjoint, which matters when the batch×head grid dim
is declared "parallel" and megacore TPUs split it across TensorCores.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import _common

_NEG = -1e30


def mha_reference(q, k, v, bias=None, causal=False, sm_scale=None,
                  dropout=0.0, dropout_key=None):
    """Pure-XLA multi-head attention. q,k,v: (B, H, L, D); bias: (B, 1|H, 1|Lq, Lk).

    dropout is applied to the attention probabilities (inverted scaling),
    matching the reference's attention-dropout in
    `src/operator/contrib/transformer.cc` consumers (gluonnlp BERT)."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * sm_scale
    if bias is not None:
        s = s + bias
    if causal:
        Lq, Lk = s.shape[-2], s.shape[-1]
        row = jnp.arange(Lq)[:, None] + (Lk - Lq)
        col = jnp.arange(Lk)[None, :]
        s = jnp.where(col <= row, s, _NEG)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    if dropout > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), jnp.zeros((), p.dtype))
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


# pallas binds lazily at first kernel engagement (shared logic in
# _common): this module is reachable from hot paths via the pallas_ops
# package, and a kernels=off / CPU process must keep
# jax.experimental.pallas out of sys.modules (ci/run.sh sanity asserts it)
pl = None
pltpu = None


def load_pallas():
    global pl, pltpu
    pl = _common.load_pallas()
    if pltpu is None:
        from jax.experimental.pallas import tpu as _pltpu
        pltpu = _pltpu


# --------------------------------------------------------------------------
# shared block math — the ONE definition of the masked score tile, used by
# forward and both backward kernels so fwd/bwd can never drift apart
# --------------------------------------------------------------------------

def _score_block(q, k, bias_row, qi, kb, causal, causal_off, block_q,
                 block_k, sm_scale):
    """Scaled masked scores for one (q block, k block) tile.

    q (block_q, D), k (block_k, D) in the MODEL dtype — bf16 operands hit
    the MXU's native bf16 x bf16 -> f32 mode; upcasting them first would
    force the (4x slower) f32 systolic path. bias_row (1, block_k) f32
    additive. Returns s (block_q, block_k) f32.
    """
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = s + bias_row
    if causal:
        row = qi * block_q + causal_off + \
            jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        col = kb * block_k + \
            jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(col <= row, s, _NEG)
    return s


def _keep_tile(seed_ref, b, qi, kb, num_qb, num_kb, block_q, block_k, dropout):
    """Attention-dropout keep mask for score tile (b, qi, kb).

    The per-core PRNG is re-seeded from (step seed, flat tile id) before
    every tile, so the forward, dq, and dkv kernels regenerate bit-identical
    masks regardless of their different grid/loop iteration orders. Mosaic
    caps prng_seed at two values, hence the flat id."""
    tile = (b * num_qb + qi) * num_kb + kb
    pltpu.prng_seed(seed_ref[0], tile)
    bits = pltpu.bitcast(pltpu.prng_random_bits((block_q, block_k)),
                         jnp.uint32)
    cutoff = np.uint32(min(int(round(dropout * 2.0 ** 32)), 0xFFFFFFFF))
    return bits >= cutoff


# --------------------------------------------------------------------------
# pallas forward (emits out + row LSE)
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, o_ref, lse_ref, *,
                sm_scale, causal, block_q, block_k, kv_len, dropout):
    qi = pl.program_id(1)
    q = q_ref[0]                                         # (block_q, D)
    num_kb = kv_len // block_k
    q_len = pl.num_programs(1) * block_q
    causal_off = kv_len - q_len  # align last query with last key (as reference)
    if causal:
        hi = jax.lax.div((qi + 1) * block_q + causal_off + block_k - 1, block_k)
        hi = jnp.clip(hi, 1, num_kb)
    else:
        hi = num_kb

    m0 = jnp.full((block_q, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        bias_row = bias_ref[0, 0, pl.ds(kb * block_k, block_k)] \
            .reshape(1, block_k)
        s = _score_block(q, k, bias_row, qi, kb, causal, causal_off,
                         block_q, block_k, sm_scale)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        # l (the softmax denominator) sums the UNDROPPED p; dropout only
        # thins what reaches the value accumulation
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if dropout > 0.0:
            keep = _keep_tile(seed_ref, pl.program_id(0), qi, kb,
                              pl.num_programs(1), num_kb, block_q, block_k,
                              dropout)
            p = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout))
        # p rounds to the model dtype for the value matmul: bf16 x bf16 ->
        # f32-accumulate is the MXU's full-rate mode, and p in [0, 1/keep]
        # loses ~3 mantissa-decimal at bf16 — the standard flash trade
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, hi, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    # log-sum-exp residual, broadcast over the 8-sublane carrier dim
    lse = (m + jnp.log(l)).reshape(1, 1, block_q)
    lse_ref[...] = jnp.broadcast_to(lse, (1, 8, block_q))


def _row8(x):
    """(R, L) -> (R, 8, L): 8-sublane carrier layout (see module docstring)."""
    return jnp.broadcast_to(x[:, None, :], (x.shape[0], 8, x.shape[1]))


def _flash_fwd_pallas(q, k, v, bias, seed, causal, sm_scale, block_q, block_k,
                      dropout):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    qr = q.reshape(B * H, Lq, D)
    kr = k.reshape(B * H, Lk, D)
    vr = v.reshape(B * H, Lk, D)
    bias8 = _row8(bias)                                   # (B, 8, Lk)
    grid = (B * H, Lq // block_q)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, kv_len=Lk, dropout=dropout),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 8, Lk), lambda b, i, H=H: (b // H, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Lq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 8, Lq), jnp.float32),
        ],
        compiler_params=_common.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_common.interpret(),
        name="flash_fwd",
    )(qr, kr, vr, bias8, seed)
    return out.reshape(B, H, Lq, D), lse


# --------------------------------------------------------------------------
# pallas backward: dq kernel (grid over q blocks) + dkv kernel (over k blocks)
# --------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, bias_ref, g_ref, lse_ref, delta_ref,
               seed_ref, dq_ref, *, sm_scale, causal, block_q, block_k,
               kv_len, dropout):
    qi = pl.program_id(1)
    q = q_ref[0]
    g = g_ref[0]
    lse_c = lse_ref[0, 0, :].reshape(block_q, 1)
    delta_c = delta_ref[0, 0, :].reshape(block_q, 1)
    num_kb = kv_len // block_k
    q_len = pl.num_programs(1) * block_q
    causal_off = kv_len - q_len
    if causal:
        hi = jax.lax.div((qi + 1) * block_q + causal_off + block_k - 1,
                         block_k)
        hi = jnp.clip(hi, 1, num_kb)
    else:
        hi = num_kb

    acc0 = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)

    def body(kb, acc):
        k = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v = v_ref[0, pl.ds(kb * block_k, block_k), :]
        bias_row = bias_ref[0, 0, pl.ds(kb * block_k, block_k)] \
            .reshape(1, block_k)
        s = _score_block(q, k, bias_row, qi, kb, causal, causal_off,
                         block_q, block_k, sm_scale)
        p = jnp.exp(s - lse_c)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout > 0.0:
            keep = _keep_tile(seed_ref, pl.program_id(0), qi, kb,
                              pl.num_programs(1), num_kb, block_q, block_k,
                              dropout)
            dp = jnp.where(keep, dp, 0.0) * (1.0 / (1.0 - dropout))
        ds = (p * (dp - delta_c) * sm_scale).astype(k.dtype)
        return acc + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    dq_ref[0] = jax.lax.fori_loop(0, hi, body, acc0).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, bias_ref, g_ref, lse_ref, delta_ref,
                seed_ref, dk_ref, dv_ref, *, sm_scale, causal, block_q,
                block_k, q_len, kv_len, dropout):
    kb = pl.program_id(1)
    k = k_ref[0]                                           # (block_k, D)
    v = v_ref[0]
    bias_row = bias_ref[0, 0, pl.ds(kb * block_k, block_k)] \
        .reshape(1, block_k)
    num_qb = q_len // block_q
    causal_off = kv_len - q_len
    if causal:
        lo = jax.lax.div(kb * block_k - causal_off, block_q)
        lo = jnp.clip(lo, 0, num_qb)
    else:
        lo = 0

    dk0 = jnp.zeros((k.shape[0], k.shape[1]), jnp.float32)
    dv0 = jnp.zeros((v.shape[0], v.shape[1]), jnp.float32)

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q), :]
        g = g_ref[0, pl.ds(qi * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q)].reshape(block_q, 1)
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q)] \
            .reshape(block_q, 1)
        s = _score_block(q, k, bias_row, qi, kb, causal, causal_off,
                         block_q, block_k, sm_scale)
        p = jnp.exp(s - lse)                               # (bq, bk)
        dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        pv = p
        if dropout > 0.0:
            keep = _keep_tile(seed_ref, pl.program_id(0), qi, kb,
                              q_len // block_q, pl.num_programs(1),
                              block_q, block_k, dropout)
            inv = 1.0 / (1.0 - dropout)
            pv = jnp.where(keep, p, 0.0) * inv
            dp = jnp.where(keep, dp, 0.0) * inv
        dv = dv + jax.lax.dot_general(pv.astype(g.dtype), g,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(lo, num_qb, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, bias, seed, out, lse, g, causal, sm_scale,
                      block_q, block_k, dropout):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    qr = q.reshape(B * H, Lq, D)
    kr = k.reshape(B * H, Lk, D)
    vr = v.reshape(B * H, Lk, D)
    gr = g.reshape(B * H, Lq, D)
    bias8 = _row8(bias)                                    # (B, 8, Lk)
    # delta = rowsum(dO * O): one fused elementwise+reduce, no L×L tensor
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(B * H, Lq)
    delta8 = _row8(delta)                                  # (BH, 8, Lq)
    # lse already arrives in (BH, 8, Lq) carrier layout from the forward

    bias_spec = pl.BlockSpec((1, 8, Lk), lambda b, i, H=H: (b // H, 0, 0))
    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, kv_len=Lk,
                          dropout=dropout),
        grid=(B * H, Lq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Lk, D), lambda b, i: (b, 0, 0)),
            bias_spec,
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 8, block_q), lambda b, i: (b, 0, i)),
            seed_spec,
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Lq, D), q.dtype),
        compiler_params=_common.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_common.interpret(),
        name="flash_dq",
    )(qr, kr, vr, bias8, gr, lse, delta8, seed)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          q_len=Lq, kv_len=Lk, dropout=dropout),
        grid=(B * H, Lk // block_k),
        in_specs=[
            pl.BlockSpec((1, Lq, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            bias_spec,
            pl.BlockSpec((1, Lq, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 8, Lq), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 8, Lq), lambda b, j: (b, 0, 0)),
            seed_spec,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Lk, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Lk, D), v.dtype),
        ],
        compiler_params=_common.compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_common.interpret(),
        name="flash_dkv",
    )(qr, kr, vr, bias8, gr, lse, delta8, seed)

    return (dq.reshape(B, H, Lq, D), dk.reshape(B, H, Lk, D),
            dv.reshape(B, H, Lk, D))


def _flash_bwd_xla(q, k, v, bias, out, lse, g, causal, sm_scale):
    """Materialized backward, reusing the saved LSE (same score convention
    as `_score_block`, whole-matrix form). At short sequence lengths XLA's
    fused L×L formulation beats the blockwise kernels; the Pallas path
    exists for the long-context regime where the L×L buffer is the
    problem."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    s = s + bias[:, None, None, :]
    if causal:
        row = jnp.arange(Lq)[:, None] + (Lk - Lq)
        col = jnp.arange(Lk)[None, :]
        s = jnp.where(col <= row, s, _NEG)
    lse_rows = lse[:, 0, :].reshape(B, H, Lq, 1)
    p = jnp.exp(s - lse_rows)
    g32 = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, g32)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g32, v.astype(jnp.float32))
    delta = jnp.sum(g32 * out.astype(jnp.float32), axis=-1, keepdims=True)
    ds = p * (dp - delta) * sm_scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# Above this many kv positions the blockwise Pallas backward wins; below it
# XLA's fused L×L backward is faster. Measured with 512x512 blocks at
# BERT-base shapes: Pallas fwd+bwd 5.3ms vs Pallas-fwd+XLA-bwd 6.6ms at
# L=512, 1.47x at L=4096 — so the crossover sits at 512. With attention
# dropout the Pallas backward is used at every length: only it can
# regenerate the kernel-PRNG masks.
# Knob: config 'pallas_bwd_min_len' / MXNET_TPU_PALLAS_BWD_MIN_LEN.


def _pallas_bwd_min_len():
    from .. import config
    return config.get("pallas_bwd_min_len")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, bias, seed, causal, sm_scale, block_q, block_k, dropout):
    out, _ = _flash_fwd_pallas(q, k, v, bias, seed, causal, sm_scale,
                               block_q, block_k, dropout)
    return out


def _flash_fwd(q, k, v, bias, seed, causal, sm_scale, block_q, block_k,
               dropout):
    out, lse = _flash_fwd_pallas(q, k, v, bias, seed, causal, sm_scale,
                                 block_q, block_k, dropout)
    return out, (q, k, v, bias, seed, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, dropout, res, g):
    q, k, v, bias, seed, out, lse = res
    if dropout > 0.0 or k.shape[2] >= _pallas_bwd_min_len():
        dq, dk, dv = _flash_bwd_pallas(q, k, v, bias, seed, out, lse, g,
                                       causal, sm_scale, block_q, block_k,
                                       dropout)
    else:
        dq, dk, dv = _flash_bwd_xla(q, k, v, bias, out, lse, g, causal,
                                    sm_scale)
    return (dq, dk, dv, jnp.zeros_like(bias),
            np.zeros(seed.shape, jax.dtypes.float0))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _round_up(x, m):
    return (x + m - 1) // m * m


def _fit_block(b, L):
    """Largest 128-multiple <= b that divides the lane-padded length, so a
    big default block never forces padding beyond round_up(L, 128) (e.g.
    L=768 runs at 384 blocks unpadded instead of padding to 1024).
    Arbitrary caller values are clamped into the 128-multiple grid first;
    128 always divides Lp, so the loop terminates."""
    Lp = _round_up(L, 128)
    b = max(128, min(b, Lp) // 128 * 128)
    while Lp % b:
        b -= 128
    return b


def flash_attention(q, k, v, mask=None, causal=False, sm_scale=None,
                    block_q=512, block_k=512, dropout=0.0, dropout_key=None):
    """Multi-head attention, flash-style.

    Args:
      q, k, v: (batch, heads, seq, head_dim). bf16 or f32.
      mask: optional (batch, kv_seq) — True/1 where attendable (padding mask).
      causal: apply causal masking.
      dropout: attention-probability dropout rate (training). Requires
        dropout_key (a jax PRNG key); silently 0 when the key is absent so
        inference code never pays for RNG plumbing.
    Returns (batch, heads, q_seq, head_dim), q.dtype.

    Engagement follows the `kernels` knob like every other kernel
    (`_common.use_pallas`): off, or a non-TPU backend without the
    interpreter, runs `mha_reference`. On a mesh of more than one device
    the kernel runs per device under `shard_map` — batch on the data
    axes, heads on `tp` when divisible, sequence whole (jit refuses to
    partition a Mosaic kernel by itself) — and each shard folds its mesh
    position into the dropout key so no two shards draw the same masks.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    dropout = float(dropout)
    if dropout_key is None or dropout == 0.0:
        dropout, dropout_key = 0.0, None

    if not _common.use_pallas():
        bias = None
        if mask is not None:
            bias = jnp.where(mask.astype(bool), 0.0, _NEG)[:, None, None, :]
        return mha_reference(q, k, v, bias=bias, causal=causal,
                             sm_scale=sm_scale, dropout=dropout,
                             dropout_key=dropout_key)
    load_pallas()

    def local(q, k, v, mask, key):
        return _flash_local(q, k, v, mask, key, causal, sm_scale, block_q,
                            block_k, dropout)

    mesh = _common.installed_mesh()
    if mesh is None or mesh.size == 1 or _common.in_shard_map():
        return local(q, k, v, mask, dropout_key)

    from jax.sharding import PartitionSpec as P
    from ..parallel._compat import shard_map
    from ..parallel.specs import attention_axes
    bspec, hspec = attention_axes(mesh, q.shape[0], q.shape[1])
    axes = (bspec or ()) + ((hspec,) if hspec else ())
    qspec = P(bspec, hspec, None, None)
    # optional operands ride as a dict so one body serves every
    # (mask, dropout) combination
    opt, opt_specs = {}, {}
    if mask is not None:
        opt["mask"], opt_specs["mask"] = mask, P(bspec, None)
    if dropout_key is not None:
        opt["key"], opt_specs["key"] = dropout_key, P()

    def body(q, k, v, opt):
        key = opt.get("key")
        if key is not None and axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(axes))
        return local(q, k, v, opt.get("mask"), key)

    return shard_map(body, mesh=mesh,
                     in_specs=(qspec, qspec, qspec, opt_specs),
                     out_specs=qspec, check_vma=False)(q, k, v, opt)


def _flash_local(q, k, v, mask, dropout_key, causal, sm_scale, block_q,
                 block_k, dropout):
    """The kernel call on one device's (B, H, L, D) operands: fit the
    blocks, pad to them, derive the kernel PRNG seed."""
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    block_q = _fit_block(block_q, Lq)
    block_k = _fit_block(block_k, Lk)
    Lq_p, Lk_p = _round_up(Lq, block_q), _round_up(Lk, block_k)
    if mask is not None:
        bias = jnp.where(mask.astype(bool), 0.0, _NEG).astype(jnp.float32)
    else:
        bias = jnp.zeros((B, Lk), jnp.float32)
    if Lk_p != Lk:
        bias = jnp.pad(bias, ((0, 0), (0, Lk_p - Lk)), constant_values=_NEG)
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Lk_p - Lk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Lk_p - Lk), (0, 0)))
    if Lq_p != Lq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Lq_p - Lq), (0, 0)))
    if dropout_key is not None:
        seed = jax.lax.bitcast_convert_type(
            jax.random.bits(dropout_key, (1,), jnp.uint32), jnp.int32)
    else:
        seed = jnp.zeros((1,), jnp.int32)
    out = _flash(q, k, v, bias, seed, causal, sm_scale, block_q, block_k,
                 dropout)
    if Lq_p != Lq:
        out = out[:, :, :Lq]
    return out
