"""Fused MoE dispatch/combine: gather-by-expert + scatter-back with
capacity masking, without the (N, E, C) one-hot tensor.

`parallel/moe.py`'s dense-dispatch formulation materializes a
(tokens, experts, capacity) float dispatch tensor in HBM and einsums
against it twice — O(N*E*C) memory traffic for what is logically a
permutation. mx.inspect's roofline classifies those einsums
memory-bound. These kernels keep the selection one-hot in VMEM, built
on the fly from compact (N,) routing vectors via iota compares, and
express the gather/scatter as MXU matmuls per expert tile:

  dispatch:  buf[e, c]  = sum_n [expert_n == e][pos_n == c] * x[n]
  combine :  y[n]       = gate_n * buf[expert_n, pos_n]

HBM traffic drops from O(N*E*C + N*D + E*C*D) to O(N*D + E*C*D); the
(C, n_block) selection tile lives and dies in VMEM.

Both ops are differentiable where the training path needs them —
dispatch in x, combine in (buf, gate) — and the VJPs are each other:
d(dispatch)/dx is a combine with unit gate; d(combine)/dbuf is a
dispatch of the gate-scaled cotangent. The routing ints carry
`float0` tangents (the flash-attention seed convention).

These run INSIDE `shard_map` (per-device manual code), so unlike the
fused-update kernels they engage on any mesh. Fallback
(`kernels=off` / no TPU / no interpreter): the same one-hot einsum
formulation moe.py always used — bit-identical.

Routing convention: `expert` (N,) int32 in [0, E); `pos` (N,) int32 is
the token's slot within its expert's capacity buffer, with OVERFLOW AND
INVALID TOKENS CARRYING pos >= capacity or pos < 0 (they dispatch
nowhere and combine to zero — the Switch-style capacity drop).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import _common

__all__ = ["dispatch_to_experts", "combine_from_experts",
           "dispatch_reference", "combine_reference", "engaged"]

_LANE = 128


def engaged():
    """Trace-time gate (shard_map-safe: no device-count restriction)."""
    return _common.use_pallas()


# --------------------------------------------------------------------------
# references (the pre-kernel einsum formulation, and the VJP oracle)
# --------------------------------------------------------------------------

def _one_hot_dispatch(expert, pos, num_experts, capacity):
    """(N, E, C) f32 selection tensor from compact routing — exactly the
    `dispatch` moe.moe_dispatch builds (pos >= capacity or < 0 drops)."""
    e_oh = jax.nn.one_hot(expert, num_experts, dtype=jnp.float32)
    valid = (pos >= 0) & (pos < capacity)
    p_oh = jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity,
                          dtype=jnp.float32)
    return e_oh[:, :, None] * p_oh[:, None, :] \
        * valid[:, None, None].astype(jnp.float32)


def dispatch_reference(x, expert, pos, num_experts, capacity):
    d = _one_hot_dispatch(expert, pos, num_experts, capacity)
    return jnp.einsum("nec,nd->ecd", d, x.astype(jnp.float32))


def combine_reference(buf, expert, pos, gate):
    E, C, _ = buf.shape
    d = _one_hot_dispatch(expert, pos, E, C) * gate[:, None, None]
    return jnp.einsum("nec,ecd->nd", d, buf)


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

_row8 = _common.row8
_round_up = _common.round_up


def _dispatch_kernel(x_ref, exp_ref, pos_ref, buf_ref, *, block_n, n_nb,
                     capacity):
    """Grid over experts: program e accumulates its (C, D) buffer as
    sel(C, block_n) @ x(block_n, D) over token blocks — the selection
    tile is built in VMEM from iota compares, never written to HBM."""
    e = pl.program_id(0)
    C = buf_ref.shape[1]
    D = x_ref.shape[1]
    acc0 = jnp.zeros((C, D), jnp.float32)

    def body(nb, acc):
        xs = x_ref[pl.ds(nb * block_n, block_n), :]
        er = exp_ref[0:1, pl.ds(nb * block_n, block_n)]       # (1, bn)
        pr = pos_ref[0:1, pl.ds(nb * block_n, block_n)]
        c_iota = jax.lax.broadcasted_iota(jnp.int32, (C, block_n), 0)
        sel = ((er == e) & (pr == c_iota)
               & (pr >= 0) & (pr < capacity)).astype(jnp.float32)
        return acc + jax.lax.dot_general(
            sel, xs, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    buf_ref[0] = jax.lax.fori_loop(0, n_nb, body, acc0)


def _combine_kernel(buf_ref, exp_ref, pos_ref, gate_ref, y_ref, *,
                    num_experts, capacity):
    """Grid over token blocks: program i gathers its (block_n, D) rows
    as sel(block_n, C) @ buf[e](C, D) summed over experts, then scales
    by the gate column."""
    i = pl.program_id(0)
    bn = y_ref.shape[0]
    D = y_ref.shape[1]
    C = buf_ref.shape[1]
    er = exp_ref[0:1, pl.ds(i * bn, bn)]                      # (1, bn)
    pr = pos_ref[0:1, pl.ds(i * bn, bn)]
    gr = gate_ref[0:1, pl.ds(i * bn, bn)]
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (bn, C), 1)
    pcol = pr.reshape(bn, 1)
    ecol = er.reshape(bn, 1)
    valid = (pcol >= 0) & (pcol < capacity)

    def body(e, acc):
        sel = ((ecol == e) & (pcol == c_iota) & valid).astype(jnp.float32)
        be = buf_ref[pl.ds(e, 1)][0]                          # (C, D)
        return acc + jax.lax.dot_general(
            sel, be, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(0, num_experts, body,
                            jnp.zeros((bn, D), jnp.float32))
    y_ref[...] = acc * gr.reshape(bn, 1)


def _pad_tokens(x, expert, pos, gate=None):
    """Pad the token dim to a lane multiple; padding tokens route
    nowhere (expert -1, pos -1)."""
    N = x.shape[0]
    Np = _round_up(max(N, _LANE), _LANE)
    if Np != N:
        x = jnp.pad(x, ((0, Np - N), (0, 0)))
        expert = jnp.pad(expert, (0, Np - N), constant_values=-1)
        pos = jnp.pad(pos, (0, Np - N), constant_values=-1)
        if gate is not None:
            gate = jnp.pad(gate, (0, Np - N))
    return x, expert, pos, gate, N, Np


def _dispatch_pallas(x, expert, pos, num_experts, capacity):
    _load_pallas()
    x = x.astype(jnp.float32)
    x, expert, pos, _, N, Np = _pad_tokens(x, expert, pos)
    D = x.shape[1]
    Dp = _round_up(D, _LANE)
    Cp = _round_up(capacity, 8)
    if Dp != D:
        x = jnp.pad(x, ((0, 0), (0, Dp - D)))
    block_n = min(512, Np)
    while Np % block_n:
        block_n -= _LANE
    buf = pl.pallas_call(
        functools.partial(_dispatch_kernel, block_n=block_n,
                          n_nb=Np // block_n, capacity=capacity),
        grid=(num_experts,),
        in_specs=[
            pl.BlockSpec((Np, Dp), lambda e: (0, 0)),
            pl.BlockSpec((8, Np), lambda e: (0, 0)),
            pl.BlockSpec((8, Np), lambda e: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Cp, Dp), lambda e: (e, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_experts, Cp, Dp),
                                       jnp.float32),
        compiler_params=_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=_common.interpret(),
        name="moe_dispatch",
    )(x, _row8(expert.astype(jnp.int32)), _row8(pos.astype(jnp.int32)))
    return buf[:, :capacity, :D]


def _combine_pallas(buf, expert, pos, gate):
    _load_pallas()
    E, C, D = buf.shape
    Cp = _round_up(C, 8)
    Dp = _round_up(D, _LANE)
    if (Cp, Dp) != (C, D):
        buf = jnp.pad(buf, ((0, 0), (0, Cp - C), (0, Dp - D)))
    xdummy = jnp.zeros((expert.shape[0], 1), jnp.float32)
    _, expert, pos, gate, N, Np = _pad_tokens(xdummy, expert, pos, gate)
    block_n = min(512, Np)
    while Np % block_n:
        block_n -= _LANE
    y = pl.pallas_call(
        functools.partial(_combine_kernel, num_experts=E, capacity=C),
        grid=(Np // block_n,),
        in_specs=[
            pl.BlockSpec((E, Cp, Dp), lambda i: (0, 0, 0)),
            pl.BlockSpec((8, Np), lambda i: (0, 0)),
            pl.BlockSpec((8, Np), lambda i: (0, 0)),
            pl.BlockSpec((8, Np), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, Dp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np, Dp), jnp.float32),
        compiler_params=_compiler_params(
            dimension_semantics=("arbitrary",)),
        interpret=_common.interpret(),
        name="moe_combine",
    )(buf.astype(jnp.float32), _row8(expert.astype(jnp.int32)),
      _row8(pos.astype(jnp.int32)), _row8(gate.astype(jnp.float32)))
    return y[:N, :D]


_compiler_params = _common.compiler_params


# --------------------------------------------------------------------------
# differentiable entry points
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch(x, expert, pos, num_experts, capacity):
    return _dispatch_pallas(x, expert, pos, num_experts, capacity)


def _dispatch_fwd(x, expert, pos, num_experts, capacity):
    return (_dispatch_pallas(x, expert, pos, num_experts, capacity),
            (expert, pos))


def _dispatch_bwd(num_experts, capacity, res, dbuf):
    expert, pos = res
    ones = jnp.ones(expert.shape, jnp.float32)
    dx = _combine_pallas(dbuf, expert, pos, ones)
    z = np.zeros(expert.shape, jax.dtypes.float0)
    return dx, z, np.zeros(pos.shape, jax.dtypes.float0)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(buf, expert, pos, gate):
    return _combine_pallas(buf, expert, pos, gate)


def _combine_fwd(buf, expert, pos, gate):
    return _combine_pallas(buf, expert, pos, gate), (buf, expert, pos,
                                                     gate)


def _combine_bwd(res, dy):
    buf, expert, pos, gate = res
    E, C, _ = buf.shape
    dbuf = _dispatch_pallas(dy * gate[:, None], expert, pos, E, C)
    gathered = _combine_pallas(buf, expert, pos,
                               jnp.ones(gate.shape, jnp.float32))
    dgate = jnp.sum(dy * gathered, axis=-1)
    return (dbuf, np.zeros(expert.shape, jax.dtypes.float0),
            np.zeros(pos.shape, jax.dtypes.float0),
            dgate.astype(gate.dtype))


_combine.defvjp(_combine_fwd, _combine_bwd)


def dispatch_to_experts(x, expert, pos, num_experts, capacity):
    """Gather tokens into per-expert capacity buffers: (N, D) ->
    (E, C, D) f32. Differentiable in `x`; `expert`/`pos` are routing
    ints (see module docstring for the overflow convention). Falls back
    to the one-hot einsum under kernels=off / no TPU."""
    if engaged():
        return _dispatch(x, expert, pos, num_experts, capacity)
    return dispatch_reference(x, expert, pos, num_experts, capacity)


def combine_from_experts(buf, expert, pos, gate):
    """Scatter expert outputs back to token order, gate-weighted:
    (E, C, D) -> (N, D) f32. Differentiable in `buf` and `gate`;
    dropped tokens (pos outside capacity) combine to zero and pass
    through the residual upstream."""
    if engaged():
        return _combine(buf, expert, pos, gate)
    return combine_reference(buf, expert, pos, gate)


# pallas binds lazily at first kernel engagement (shared logic in
# _common): this module sits on the moe_ffn hot path, and with
# kernels=off it must not drag jax.experimental.pallas into the
# process (ci sanity asserts it)
pl = None


def _load_pallas():
    global pl
    pl = _common.load_pallas()
