"""Fused optimizer update: grad-scale + moment update + weight apply in
one VMEM pass.

The optimizer tail of a train step is a chain of elementwise HLOs
(scale, clip, two moment EMAs, rsqrt, the weight apply) over every
parameter — mx.inspect's roofline classifies it memory-bound: each HLO
XLA fails to fuse is another full HBM round-trip over state that is
read-once/write-once. These kernels do the whole update per (rows, 128)
tile while it sits in VMEM, with `input_output_aliases` so w/m/v update
in place (donation-safe — the mx.check lint on the traced form stays
quiet).

Two surfaces:
  * `adam_update` — Adam / AdamW (decoupled_wd) per-parameter update,
    wired into `parallel/functional_opt.FunctionalOptimizer`. The math
    is EXACTLY `ops.optimizer_ops.adam_update`/`adamw_update` (the
    fallback calls them, so `kernels=off` is bit-identical to main).
  * `lamb_pass1` / `lamb_pass2` — the two elementwise passes of
    `parallel/fused_lamb.FusedLamb.apply_flat` over the flat fp32
    master layout: pass 1 produces the new moments plus the per-row
    sums of squares the trust-ratio norms need; the tiny per-segment
    scatter + trust ratio stays in XLA (R elements); pass 2 applies the
    trust-scaled update. The two-kernel split IS apply_flat's
    optimization_barrier structure: the update temp is never written to
    HBM, it is recomputed in pass 2.

The per-shard math composes with mx.zero: the kernels see only a flat
(rows, lane) view, so applying them per flat shard is bit-exact against
the whole-vector application (pinned by test_kernels.py). Engagement is
trace-time only (`engaged()`): kernels=off|non-TPU runs the reference,
and multi-device SPMD steps keep the XLA lowering (`pl.pallas_call` has
no GSPMD rule — see pallas_ops/_common.py).

Not differentiable by design: optimizer updates run outside autodiff
(no gradient flows through a weight apply), so no custom_vjp is
defined.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import _common
from ..ops import OPS as _OPS

__all__ = ["adam_update", "lamb_pass1", "lamb_pass2", "engaged",
           "adam_update_reference"]

_LANE = 128


def engaged(n_elements):
    """Trace-time gate for the fused-update kernels: the knob asks, the
    backend can, the buffer clears kernels_min_elements (kernel launch
    overhead beats one fused pass on tiny LayerNorm/bias state), and
    the step is not a multi-device SPMD program. The interpreter
    overrides the SPMD gate: interpreted kernels lower to ordinary XLA
    ops the partitioner handles, and the gate would otherwise leave the
    kernel CODE untested on the 8-device CPU test mesh."""
    return (int(n_elements) >= _common.min_elements()
            and _common.use_pallas()
            and (_common.interpret() or not _common.multi_device()))


# --------------------------------------------------------------------------
# Adam / AdamW
# --------------------------------------------------------------------------

def adam_update_reference(w, g, m, v, lr, beta1, beta2, epsilon, wd,
                          rescale_grad, clip_gradient, decoupled_wd=False,
                          eta=1.0):
    """The XLA-native lowering — literally the registered optimizer ops
    the functional path always used, so the fallback cannot drift."""
    if decoupled_wd:
        return _OPS["adamw_update"](
            w, g, m, v, lr, eta=eta, beta1=beta1, beta2=beta2,
            epsilon=epsilon, wd=wd, rescale_grad=rescale_grad,
            clip_gradient=clip_gradient)
    return _OPS["adam_update"](
        w, g, m, v, lr, beta1=beta1, beta2=beta2, epsilon=epsilon,
        wd=wd, rescale_grad=rescale_grad, clip_gradient=clip_gradient)


def _adam_kernel(lr_ref, w_ref, g_ref, m_ref, v_ref, wo_ref, mo_ref,
                 vo_ref, *, beta1, beta2, epsilon, wd, rescale_grad,
                 clip_gradient, decoupled_wd, eta):
    """One (rows, 128) tile: the full Adam/AdamW update in VMEM."""
    w32 = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32) * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    if not decoupled_wd:
        g = g + wd * w32                    # Adam: wd folds into the grad
    new_m = beta1 * m_ref[...].astype(jnp.float32) + (1 - beta1) * g
    new_v = beta2 * v_ref[...].astype(jnp.float32) + (1 - beta2) \
        * jnp.square(g)
    lr = lr_ref[0]
    step = lr * new_m / (jnp.sqrt(new_v) + epsilon)
    if decoupled_wd:                        # AdamW: wd decoupled, eta-scaled
        step = eta * (step + wd * w32)
    wo_ref[...] = (w32 - step).astype(wo_ref.dtype)
    mo_ref[...] = new_m.astype(mo_ref.dtype)
    vo_ref[...] = new_v.astype(vo_ref.dtype)


def _pad_rows(flat, rows_mult=16):
    """1-D -> (R, 128) with R padded to a sublane multiple (16 covers
    the bf16 min tile; f32's 8 divides it); returns (view, n, R). Zero
    padding is self-consistent: a zero w/g/m/v lane produces a zero
    update (epsilon keeps the rsqrt finite)."""
    n = flat.shape[0]
    per = _LANE * rows_mult
    np_ = (n + per - 1) // per * per
    if np_ != n:
        flat = jnp.pad(flat, (0, np_ - n))
    return flat.reshape(np_ // _LANE, _LANE), n, np_ // _LANE


def adam_update(w, g, m, v, lr, beta1=0.9, beta2=0.999, epsilon=1e-8,
                wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                decoupled_wd=False, eta=1.0):
    """Fused Adam/AdamW update; returns (new_w, new_m, new_v) with the
    input dtypes. Hyperparameters are trace-time constants (they key the
    step cache upstream); `lr` may be traced (the in-jit scheduler)."""
    if not engaged(w.size):
        return adam_update_reference(
            w, g, m, v, lr, beta1, beta2, epsilon, wd, rescale_grad,
            clip_gradient, decoupled_wd=decoupled_wd, eta=eta)

    _load_pallas()
    shape = w.shape
    w2, n, R = _pad_rows(w.reshape(-1))
    g2, _, _ = _pad_rows(g.reshape(-1))
    m2, _, _ = _pad_rows(m.reshape(-1))
    v2, _, _ = _pad_rows(v.reshape(-1))
    block_r = min(512, R)
    while R % block_r:
        block_r -= 16
    lr1 = jnp.asarray(lr, jnp.float32).reshape(1)

    row_spec = pl.BlockSpec((block_r, _LANE), lambda i: (i, 0))
    new_w, new_m, new_v = pl.pallas_call(
        functools.partial(
            _adam_kernel, beta1=float(beta1), beta2=float(beta2),
            epsilon=float(epsilon), wd=float(wd),
            rescale_grad=float(rescale_grad),
            clip_gradient=float(clip_gradient), decoupled_wd=decoupled_wd,
            eta=float(eta)),
        grid=(R // block_r,),
        in_specs=[pl.BlockSpec(memory_space=_smem()),
                  row_spec, row_spec, row_spec, row_spec],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((R, _LANE), w.dtype),
            jax.ShapeDtypeStruct((R, _LANE), m.dtype),
            jax.ShapeDtypeStruct((R, _LANE), v.dtype),
        ],
        input_output_aliases={1: 0, 3: 1, 4: 2},   # w/m/v update in place
        compiler_params=_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=_common.interpret(),
        name="adam_update",
    )(lr1, w2, g2, m2, v2)
    return (new_w.reshape(-1)[:n].reshape(shape),
            new_m.reshape(-1)[:n].reshape(shape),
            new_v.reshape(-1)[:n].reshape(shape))


# --------------------------------------------------------------------------
# fused-LAMB passes (flat (rows, 512) master layout)
# --------------------------------------------------------------------------

def _lamb1_kernel(sc_ref, w_ref, g_ref, m_ref, v_ref, wd_ref, mo_ref,
                  vo_ref, rw_ref, ru_ref, *, beta1, beta2, epsilon,
                  rescale_grad, clip_gradient, bias_correction,
                  moments_f32):
    """Pass 1: moment EMA (+ the storage-dtype round-trip) and the
    per-row sums of squares feeding the trust-ratio norms. sc = (c1, c2)
    bias-correction denominators (traced: they depend on t)."""
    W = w_ref[...].astype(jnp.float32)
    G = g_ref[...].astype(jnp.float32) * rescale_grad
    if clip_gradient is not None and clip_gradient > 0:
        G = jnp.clip(G, -clip_gradient, clip_gradient)
    new_m = beta1 * m_ref[...].astype(jnp.float32) + (1 - beta1) * G
    new_v = beta2 * v_ref[...].astype(jnp.float32) + (1 - beta2) \
        * jnp.square(G)
    if not moments_f32:
        # reduced-precision moment storage: round-trip through the
        # storage dtype BEFORE the norms (fused_lamb.py's invariant —
        # trust must see what is stored)
        new_m = new_m.astype(mo_ref.dtype).astype(jnp.float32)
        new_v = new_v.astype(vo_ref.dtype).astype(jnp.float32)
    m_hat, v_hat = new_m, new_v
    if bias_correction:
        m_hat = new_m / sc_ref[0]
        v_hat = new_v / sc_ref[1]
    upd = m_hat / (jnp.sqrt(v_hat) + epsilon) + wd_ref[...] * W
    mo_ref[...] = new_m.astype(mo_ref.dtype)
    vo_ref[...] = new_v.astype(vo_ref.dtype)
    rw_ref[...] = jnp.sum(jnp.square(W), axis=1, keepdims=True)
    ru_ref[...] = jnp.sum(jnp.square(upd), axis=1, keepdims=True)


def _lamb2_kernel(sc_ref, w_ref, m_ref, v_ref, wd_ref, tr_ref, wo_ref, *,
                  beta1, beta2, epsilon, bias_correction):
    """Pass 2: recompute the update from the stored moments (the
    recompute IS apply_flat's optimization barrier — pure FLOPs traded
    for never writing the update temp to HBM) and apply the trust-scaled
    step. sc = (c1, c2, lr)."""
    W = w_ref[...].astype(jnp.float32)
    new_m = m_ref[...].astype(jnp.float32)
    new_v = v_ref[...].astype(jnp.float32)
    m_hat, v_hat = new_m, new_v
    if bias_correction:
        m_hat = new_m / sc_ref[0]
        v_hat = new_v / sc_ref[1]
    upd = m_hat / (jnp.sqrt(v_hat) + epsilon) + wd_ref[...] * W
    wo_ref[...] = W - sc_ref[2] * tr_ref[...] * upd


def _lamb_specs(R, C, block_r):
    row = pl.BlockSpec((block_r, C), lambda i: (i, 0))
    col = pl.BlockSpec((block_r, 1), lambda i: (i, 0))
    return row, col


def _lamb_block(R):
    # 16-row granularity: the moment buffers may store bf16
    # (lamb_moments_dtype), whose min sublane tile is 16
    block_r = min(256, R)
    while R % block_r:
        block_r -= 16
    return block_r


def _pad_rc(x2, Rp):
    R = x2.shape[0]
    return jnp.pad(x2, ((0, Rp - R), (0, 0))) if Rp != R else x2


def lamb_pass1(W, G, m, v, wd_rows, c1, c2, *, beta1, beta2, epsilon,
               rescale_grad, clip_gradient, bias_correction,
               moments_dtype=jnp.float32):
    """Fused-LAMB pass 1 over the flat (R, 512) layout. Returns
    (new_m (Rp, C), new_v (Rp, C), rowsq_w (R,), rowsq_upd (R,)): the
    moments stay ROW-PADDED for `lamb_pass2` to consume as-is (slice
    their [:R] prefix only when keeping them); the row sums feed
    FusedLamb's per-segment scatter-add norms (kept in XLA: R elements,
    off the hot path). Caller guarantees `engaged(W.size)`."""
    _load_pallas()
    R, C = W.shape
    Rp = (R + 15) // 16 * 16
    block_r = _lamb_block(Rp)
    row, col = _lamb_specs(Rp, C, block_r)
    mdt = jnp.dtype(moments_dtype)
    sc = jnp.stack([jnp.asarray(c1, jnp.float32),
                    jnp.asarray(c2, jnp.float32)])
    new_m, new_v, rw, ru = pl.pallas_call(
        functools.partial(
            _lamb1_kernel, beta1=float(beta1), beta2=float(beta2),
            epsilon=float(epsilon), rescale_grad=float(rescale_grad),
            clip_gradient=(float(clip_gradient) if clip_gradient
                           else None),
            bias_correction=bool(bias_correction),
            moments_f32=mdt == jnp.float32),
        grid=(Rp // block_r,),
        in_specs=[pl.BlockSpec(memory_space=_smem()),
                  row, row, row, row, col],
        out_specs=[row, row, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((Rp, C), mdt),
            jax.ShapeDtypeStruct((Rp, C), mdt),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
            jax.ShapeDtypeStruct((Rp, 1), jnp.float32),
        ],
        input_output_aliases={3: 0, 4: 1},          # moments update in place
        compiler_params=_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=_common.interpret(),
        name="lamb_pass1",
    )(sc, _pad_rc(W, Rp), _pad_rc(G, Rp),
      _pad_rc(m.reshape(R, C), Rp), _pad_rc(v.reshape(R, C), Rp),
      _pad_rc(wd_rows.reshape(R, 1), Rp))
    # moments return PADDED (Rp, C): pass 2 consumes them at the same
    # padding (its _pad_rc no-ops), so XLA never pays a pad(slice(x))
    # round-trip over the full moment buffers between passes — the
    # caller slices [:R] only on the values it keeps
    return (new_m, new_v, rw[:R, 0], ru[:R, 0])


def lamb_pass2(W, new_m, new_v, wd_rows, trust_rows, c1, c2, lr, *,
               beta1, beta2, epsilon, bias_correction):
    """Fused-LAMB pass 2: the trust-scaled weight apply. Returns the new
    flat (R, 512) f32 master."""
    _load_pallas()
    R, C = W.shape
    Rp = (R + 15) // 16 * 16
    block_r = _lamb_block(Rp)
    row, col = _lamb_specs(Rp, C, block_r)
    sc = jnp.stack([jnp.asarray(c1, jnp.float32),
                    jnp.asarray(c2, jnp.float32),
                    jnp.asarray(lr, jnp.float32)])
    mrow = pl.BlockSpec((block_r, C), lambda i: (i, 0))
    new_w = pl.pallas_call(
        functools.partial(
            _lamb2_kernel, beta1=float(beta1), beta2=float(beta2),
            epsilon=float(epsilon),
            bias_correction=bool(bias_correction)),
        grid=(Rp // block_r,),
        in_specs=[pl.BlockSpec(memory_space=_smem()),
                  row, mrow, mrow, col, col],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((Rp, C), jnp.float32),
        input_output_aliases={1: 0},                # master updates in place
        compiler_params=_compiler_params(
            dimension_semantics=("parallel",)),
        interpret=_common.interpret(),
        name="lamb_pass2",
    )(sc, _pad_rc(W, Rp), _pad_rc(new_m, Rp), _pad_rc(new_v, Rp),
      _pad_rc(wd_rows.reshape(R, 1), Rp),
      _pad_rc(trust_rows.reshape(R, 1), Rp))
    return new_w[:R]


_smem = _common.smem
_compiler_params = _common.compiler_params


# pallas binds lazily at first kernel engagement (shared logic in
# _common): this module sits on the optimizer hot path, and with
# kernels=off it must not drag jax.experimental.pallas into the
# process (ci sanity asserts it)
pl = None


def _load_pallas():
    global pl
    pl = _common.load_pallas()
