"""Int8 serving matmul: int8 x int8 -> int32 on the MXU with the
per-channel rescale fused into the same kernel.

The quantized serving path (contrib/quantization.py QuantizedDense, the
mx.serve decode step) computes `dot(x_q, w_q) -> int32` followed by one
elementwise `acc * (x_scale * w_scale[o]) (+ bias) (relu)`. XLA lowers
that as matmul + a separate elementwise pass — an extra HBM round-trip
over the (M, O) accumulator, which is exactly what mx.inspect's roofline
flags on the memory-bound decode executables. This kernel keeps the
int32 accumulator in VMEM and applies scale/bias/relu before the single
write-back, and guarantees the int8 operands actually hit the MXU's
native int8 path (no silent dequantize-then-fp-matmul).

Fallback (`kernels=off`, non-TPU without the interpreter): the exact
XLA expression the quantized layers always used — bit-identical to a
build without this package. The op is not differentiable (integer
inputs); it exists for inference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _common

__all__ = ["int8_matmul", "int8_matmul_reference"]


def int8_matmul_reference(x_q, w_q_t, x_scale, w_scale, bias=None,
                          relu=False):
    """The XLA-native lowering (the pre-kernel serving path, verbatim):
    int8 x int8 -> int32 `dot_general` (XLA maps it onto the MXU's int8
    mode on TPU), one rescale to f32, optional bias/relu."""
    acc = jax.lax.dot_general(
        x_q, w_q_t, (((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * (x_scale * w_scale)
    if bias is not None:
        out = out + bias
    if relu:
        out = jnp.maximum(out, 0.0)
    return out


# --------------------------------------------------------------------------
# pallas kernel
# --------------------------------------------------------------------------

def _kernel(x_ref, w_ref, s_ref, b_ref, o_ref, *, block_k, n_kb, relu):
    """One (block_m, block_n) output tile: int32-accumulate over K in
    VMEM, then scale+bias+relu fused before the single f32 write-back.

    x (block_m, K) int8; w (K, block_n) int8; s/b (8, block_n) f32
    carriers (combined scale `x_scale * w_scale`, bias or zeros)."""
    acc0 = jnp.zeros((x_ref.shape[0], o_ref.shape[1]), jnp.int32)

    def body(kb, acc):
        xk = x_ref[:, pl.ds(kb * block_k, block_k)]
        wk = w_ref[pl.ds(kb * block_k, block_k), :]
        # int8 x int8 -> int32: the MXU's native low-precision path
        return acc + jax.lax.dot_general(
            xk, wk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    acc = jax.lax.fori_loop(0, n_kb, body, acc0)
    out = acc.astype(jnp.float32) * s_ref[0:1, :] + b_ref[0:1, :]
    if relu:
        out = jnp.maximum(out, 0.0)
    o_ref[...] = out


_round_up = _common.round_up
_row8 = _common.row8


def _int8_matmul_pallas(x_q, w_q_t, x_scale, w_scale, bias, relu):
    lead = x_q.shape[:-1]
    K = x_q.shape[-1]
    O = w_q_t.shape[1]
    M = 1
    for d in lead:
        M *= int(d)
    x2 = x_q.reshape(M, K)

    # pad every dim to the MXU grid; int8 operand tiles need 32-sublane
    # alignment, the f32 output tile 8 — 128 covers both lanes-wise
    Mp, Kp, Op = _round_up(M, 128), _round_up(K, 128), _round_up(O, 128)
    if Mp != M:
        x2 = jnp.pad(x2, ((0, Mp - M), (0, 0)))
    if Kp != K:
        x2 = jnp.pad(x2, ((0, 0), (0, Kp - K)))
        w_q_t = jnp.pad(w_q_t, ((0, Kp - K), (0, 0)))
    if Op != O:
        w_q_t = jnp.pad(w_q_t, ((0, 0), (0, Op - O)))
    # the combined per-channel rescale: padding channels scale by 0 so
    # their (zero) accumulators stay zero through bias-less lanes
    s = (jnp.asarray(x_scale, jnp.float32)
         * w_scale.astype(jnp.float32)).reshape(-1)
    if s.shape[0] == 1 and O > 1:                   # per-tensor caller
        s = jnp.broadcast_to(s, (O,))
    b = jnp.zeros((O,), jnp.float32) if bias is None \
        else bias.astype(jnp.float32).reshape(-1)
    if Op != O:
        s = jnp.pad(s, (0, Op - O))
        b = jnp.pad(b, (0, Op - O))

    block_m = min(256, Mp)
    block_n = min(256, Op)
    block_k = min(512, Kp)
    while Mp % block_m:
        block_m -= 128
    while Op % block_n:
        block_n -= 128
    while Kp % block_k:
        block_k -= 128

    out = pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, n_kb=Kp // block_k,
                          relu=relu),
        grid=(Mp // block_m, Op // block_n),
        in_specs=[
            pl.BlockSpec((block_m, Kp), lambda i, j: (i, 0)),
            pl.BlockSpec((Kp, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((8, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((8, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Op), jnp.float32),
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel")),
        interpret=_common.interpret(),
        name="int8_matmul",
    )(x2, w_q_t, _row8(s), _row8(b))
    return out[:M, :O].reshape(lead + (O,))


_compiler_params = _common.compiler_params


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------

def int8_matmul(x_q, w_q_t, x_scale, w_scale, bias=None, relu=False):
    """Quantized matmul with fused per-channel rescale.

    Args:
      x_q: (..., K) int8 activations (already quantized).
      w_q_t: (K, O) int8 weight, pre-transposed (QuantizedDense layout).
      x_scale: scalar f32 activation scale (traced or concrete).
      w_scale: (O,) f32 per-output-channel weight scales (a scalar /
        (1,) per-tensor scale is broadcast).
      bias: optional (O,) f32, fused into the kernel epilogue.
      relu: fuse a relu into the epilogue.

    Returns (..., O) f32. `kernels=off` (or no TPU/interpreter) runs
    `int8_matmul_reference` — bit-identical to the pre-kernel path.
    """
    if x_q.dtype != jnp.int8 or w_q_t.dtype != jnp.int8:
        raise TypeError(
            f"int8_matmul needs int8 operands, got {x_q.dtype} x "
            f"{w_q_t.dtype} (quantize first; the fp path is nn.Dense)")
    if _common.use_pallas():
        _load_pallas()
        return _int8_matmul_pallas(x_q, w_q_t, x_scale,
                                   jnp.asarray(w_scale, jnp.float32),
                                   bias, relu)
    return int8_matmul_reference(x_q, w_q_t, x_scale, w_scale,
                                 bias=bias, relu=relu)


# pallas binds lazily at first kernel engagement (shared logic in
# _common): this module sits on the QuantizedDense/serve hot path, and
# with kernels=off it must not drag jax.experimental.pallas into the
# process (ci sanity asserts it)
pl = None


def _load_pallas():
    global pl
    pl = _common.load_pallas()
