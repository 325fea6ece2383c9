"""Pallas flash-attention KERNEL parity via the Pallas interpreter.

Until now the kernel code itself (not the jnp fallback) only ran on a real
TPU; MXNET_TPU_PALLAS_INTERPRET=1 routes `flash_attention` through
`pallas_call(interpret=True)` on CPU, so forward AND both backward kernels
are pinned against `mha_reference` in CI — including the bf16 path the
MXU-rate change (bf16 operands kept until the f32 accumulate) touches.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import importlib

# the package re-exports the flash_attention FUNCTION under the module's
# name, so a plain import binds the function; resolve the module itself
fa = importlib.import_module("mxnet_tpu.pallas_ops.flash_attention")


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    """Kernels through the interpreter, and no mesh left installed by an
    earlier test: on a multi-device mesh flash_attention runs under
    shard_map, which the sharded tests below ask for by name."""
    from mxnet_tpu import parallel
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    parallel.set_mesh(None)
    yield
    parallel.set_mesh(None)


def _qkv(B=1, H=2, L=256, D=64, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(B, H, L, D), dtype) for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
def test_interpret_fwd_parity_f32(causal):
    q, k, v = _qkv()
    mask = jnp.asarray(np.arange(256)[None, :] < 200)
    got = fa.flash_attention(q, k, v, mask=mask, causal=causal,
                             block_q=128, block_k=128)
    bias = jnp.where(mask, 0.0, -1e30)[:, None, None, :]
    ref = fa.mha_reference(q, k, v, bias=bias, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_interpret_fwd_parity_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    got = fa.flash_attention(q, k, v, block_q=128, block_k=128)
    ref = fa.mha_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.02)


@pytest.mark.parametrize("causal", [False, True])
def test_interpret_bwd_parity(causal):
    # force the Pallas backward (not the XLA fallback) regardless of length
    from mxnet_tpu import config
    q, k, v = _qkv(L=256)
    old = config.get("pallas_bwd_min_len")
    config.set("pallas_bwd_min_len", 1)
    try:
        def loss(q, k, v):
            o = fa.flash_attention(q, k, v, causal=causal,
                                   block_q=128, block_k=128)
            return jnp.sum(jnp.sin(o))

        def loss_ref(q, k, v):
            o = fa.mha_reference(q, k, v, causal=causal)
            return jnp.sum(jnp.sin(o))

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=2e-4, atol=2e-5)
    finally:
        config.set("pallas_bwd_min_len", old)


@pytest.mark.slow  # ~11s interpret-mode kernel; ci unittest stage runs it by name
def test_interpret_ring_pallas_inner():
    """Ring attention's Pallas inner (per-KV-block flash fwd + bwd with the
    globally merged LSE) against the dense reference — the TPU code path
    of ring_attention, exercised via the interpreter inside shard_map."""
    from mxnet_tpu import parallel

    B, H, L, D = 1, 2, 256, 32          # L/sp = 128: kernel-eligible
    rng = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rng.randn(B, H, L, D).astype(np.float32))
               for _ in range(3)]
    try:
        parallel.make_mesh(sp=2, devices=jax.devices()[:2])

        def loss(q, k, v):
            o = parallel.ring_self_attention(q, k, v, causal=True)
            return jnp.sum(jnp.sin(o))

        def loss_ref(q, k, v):
            o = fa.mha_reference(q, k, v, causal=True)
            return jnp.sum(jnp.sin(o))

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(got, ref):
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=2e-4, atol=2e-5)
    finally:
        parallel.set_mesh(None)


@pytest.mark.parametrize("axes", [dict(dp=4), dict(dp=2, tp=2)],
                         ids=["dp4", "dp2xtp2"])
def test_interpret_sharded_matches_unsharded(axes):
    """On a multi-device mesh the kernel runs per device under shard_map
    (batch on the data axes, heads on tp): output and all three grads
    must equal the single-device kernel's, and come back sharded."""
    from mxnet_tpu import parallel

    rng = np.random.RandomState(0)
    B, H, L, D = 4, 4, 256, 32
    q, k, v = [jnp.asarray(rng.randn(B, H, L, D), jnp.float32)
               for _ in range(3)]
    mask = jnp.asarray(rng.rand(B, L) > 0.2)

    def attn(q, k, v):
        return fa.flash_attention(q, k, v, mask=mask, block_q=128,
                                  block_k=128)

    def loss(q, k, v):
        return jnp.sum(jnp.sin(attn(q, k, v)))

    ref = attn(q, k, v)                               # no mesh: one device
    gref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    parallel.make_mesh(devices=jax.devices()[:4], **axes)
    got = jax.jit(attn)(q, k, v)
    assert len({s.device for s in got.addressable_shards}) == 4
    assert got.addressable_shards[0].data.shape == (
        B // axes["dp"], H // axes.get("tp", 1), L, D)
    # same kernel, same math; jit may fuse the pad/mask prologue
    # differently from the eager reference, hence an ulp of slack
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    for g, r in zip(jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v),
                    gref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("axes", [dict(dp=4), dict(dp=2, tp=2)],
                         ids=["dp4", "dp2xtp2"])
def test_sharded_dropout_seed_differs_per_shard(axes, monkeypatch):
    """Every shard's kernel numbers its tiles from zero, so shards given
    the same seed would draw the same dropout masks. The interpreter
    cannot draw the TPU PRNG, so stand in for the kernel with a stub that
    returns the seed it was handed: four shards, four seeds — and none of
    them the seed the unsharded call derives from the same key."""
    from mxnet_tpu import parallel

    def seed_echo(q, k, v, bias, seed, causal, sm_scale, bq, bk, dropout):
        assert dropout == 0.25
        return jnp.zeros_like(q) + seed[0].astype(q.dtype)

    monkeypatch.setattr(fa, "_flash", seed_echo)
    q = jnp.zeros((4, 4, 128, 32), jnp.float32)
    key = jax.random.key(9)

    def attn(q):
        return fa.flash_attention(q, q, q, dropout=0.25, dropout_key=key)

    unsharded = {float(x) for x in np.unique(np.asarray(attn(q)))}
    assert len(unsharded) == 1
    parallel.make_mesh(devices=jax.devices()[:4], **axes)
    out = np.asarray(jax.jit(attn)(q))
    per_shard = {float(out[b, h, 0, 0])
                 for b in range(0, 4, 4 // axes["dp"])
                 for h in range(0, 4, 4 // axes.get("tp", 1))}
    assert len(per_shard) == 4, per_shard
    assert not (per_shard & unsharded)
    # and within a shard every element saw that shard's one seed
    assert len(np.unique(out)) == 4
