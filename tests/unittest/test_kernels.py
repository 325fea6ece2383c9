"""mx.kernels (pallas_ops) parity via the Pallas interpreter.

Same pattern as test_flash_interpret: MXNET_TPU_PALLAS_INTERPRET=1
routes every kernel through `pallas_call(interpret=True)` on CPU, so
the kernel CODE — int8 matmul epilogue fusion, the fused-update VMEM
passes, the MoE selection-tile matmuls and their custom VJPs — is
pinned against the jnp references in tier-1, not just on a real chip.

Also pinned here: kernels=off bit-identity (the fallback IS the
pre-kernel expression), the mx.zero per-shard composition of the
fused updates, the kernels=on strictness contract, and an mx.check
graph lint over each kernel's traced form (no baked constants, no
silent promotions, donation-safe).
"""
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import config

im = importlib.import_module("mxnet_tpu.pallas_ops.int8_matmul")
fu = importlib.import_module("mxnet_tpu.pallas_ops.fused_update")
mk = importlib.import_module("mxnet_tpu.pallas_ops.moe_kernels")
pa = importlib.import_module("mxnet_tpu.pallas_ops.paged_attention")
kw = importlib.import_module("mxnet_tpu.pallas_ops.kv_page_write")
_common = importlib.import_module("mxnet_tpu.pallas_ops._common")


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    config.set("kernels", "auto")
    config.set("kernels_min_elements", 1)
    yield
    config.reset("kernels")
    config.reset("kernels_min_elements")


# --------------------------------------------------------------------------
# int8 matmul
# --------------------------------------------------------------------------

def _int8_case(M=5, K=96, O=200, lead=(), seed=0):
    rng = np.random.RandomState(seed)
    shape = tuple(lead) + (M, K) if lead else (M, K)
    x_q = jnp.asarray(rng.randint(-127, 128, shape), jnp.int8)
    w_q = jnp.asarray(rng.randint(-127, 128, (K, O)), jnp.int8)
    w_scale = jnp.asarray((rng.rand(O) * 0.1 + 1e-3).astype(np.float32))
    bias = jnp.asarray(rng.randn(O).astype(np.float32))
    return x_q, w_q, jnp.float32(0.017), w_scale, bias


@pytest.mark.parametrize("relu", [False, True])
def test_int8_matmul_parity(relu):
    x_q, w_q, s_x, w_scale, bias = _int8_case()
    got = im.int8_matmul(x_q, w_q, s_x, w_scale, bias=bias, relu=relu)
    ref = im.int8_matmul_reference(x_q, w_q, s_x, w_scale, bias=bias,
                                   relu=relu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_int8_matmul_3d_and_no_bias():
    # the decode path shape: (B, 1, E) activations
    x_q, w_q, s_x, w_scale, _ = _int8_case(M=1, K=64, O=96, lead=(3,))
    got = im.int8_matmul(x_q, w_q, s_x, w_scale)
    ref = im.int8_matmul_reference(x_q, w_q, s_x, w_scale)
    assert got.shape == (3, 1, 96)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_int8_matmul_per_tensor_scale_broadcasts():
    x_q, w_q, s_x, _, _ = _int8_case(O=96)
    w_scale = jnp.asarray([0.05], jnp.float32)          # per-tensor caller
    got = im.int8_matmul(x_q, w_q, s_x, w_scale)
    ref = im.int8_matmul_reference(x_q, w_q, s_x, w_scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_int8_matmul_rejects_fp_operands():
    with pytest.raises(TypeError, match="int8"):
        im.int8_matmul(jnp.ones((4, 8), jnp.float32),
                       jnp.ones((8, 4), jnp.int8), 1.0,
                       jnp.ones((4,), jnp.float32))


def test_kernels_off_is_reference_path(monkeypatch):
    """kernels=off must dispatch the exact XLA fallback — same jaxpr as
    calling the reference directly (the bit-identity contract)."""
    config.set("kernels", "off")
    x_q, w_q, s_x, w_scale, bias = _int8_case()
    j1 = jax.make_jaxpr(
        lambda *a: im.int8_matmul(*a, relu=True))(x_q, w_q, s_x, w_scale,
                                                  bias)
    j2 = jax.make_jaxpr(
        lambda *a: im.int8_matmul_reference(*a, relu=True))(
            x_q, w_q, s_x, w_scale, bias)
    assert str(j1) == str(j2)


def test_kernels_on_raises_without_backend(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_PALLAS_INTERPRET", raising=False)
    config.set("kernels", "on")
    with pytest.raises(RuntimeError, match="kernels='on'"):
        _common.use_pallas()


# --------------------------------------------------------------------------
# fused optimizer update
# --------------------------------------------------------------------------

def _adam_case(n=3000, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(n).astype(np.float32)),
            jnp.asarray(rng.randn(n).astype(np.float32)),
            jnp.asarray(rng.randn(n).astype(np.float32) * 0.01),
            jnp.abs(jnp.asarray(rng.randn(n).astype(np.float32))) * 0.01)


@pytest.mark.parametrize("decoupled", [False, True])
@pytest.mark.parametrize("clip", [-1.0, 0.5])
def test_fused_adam_parity(decoupled, clip):
    w, g, m, v = _adam_case()
    kw = dict(beta1=0.9, beta2=0.999, epsilon=1e-8, wd=0.01,
              rescale_grad=0.5, clip_gradient=clip)
    got = fu.adam_update(w, g, m, v, 0.003, decoupled_wd=decoupled, **kw)
    ref = fu.adam_update_reference(w, g, m, v, 0.003,
                                   decoupled_wd=decoupled,
                                   **{k: kw[k] for k in
                                      ("beta1", "beta2", "epsilon", "wd",
                                       "rescale_grad", "clip_gradient")})
    assert fu.engaged(w.size)
    for a, b, name in zip(got, ref, ("w", "m", "v")):
        assert a.dtype == b.dtype, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-6, atol=1e-7, err_msg=name)


def test_fused_adam_2d_shape_preserved():
    w, g, m, v = (x.reshape(60, 50) for x in _adam_case())
    got = fu.adam_update(w, g, m, v, 0.01)
    assert all(o.shape == (60, 50) for o in got)


def test_fused_adam_below_min_elements_falls_back(monkeypatch):
    config.set("kernels_min_elements", 10_000)
    assert not fu.engaged(3000)


def test_fused_adam_multi_device_falls_back(monkeypatch):
    # compiled (non-interpret) multi-device SPMD steps keep the XLA
    # lowering — pallas_call has no GSPMD rule
    monkeypatch.delenv("MXNET_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(_common, "multi_device", lambda: True)
    monkeypatch.setattr(_common, "pallas_available", lambda: True)
    assert not fu.engaged(3000)


def test_fused_update_zero_shard_composition():
    """The mx.zero composition contract: applying the kernel per flat
    SHARD is bit-exact against the whole-vector kernel — the update is
    row-local, so a reduce-scattered gradient + per-shard apply (what a
    zero'd step runs) produces the same bytes as the replicated apply."""
    D = 4
    w, g, m, v = _adam_case(n=D * 1024)
    whole = fu.adam_update(w, g, m, v, 0.01, wd=0.01)
    shard = [
        fu.adam_update(*(x.reshape(D, -1)[d] for x in (w, g, m, v)),
                       0.01, wd=0.01)
        for d in range(D)
    ]
    for i, name in enumerate(("w", "m", "v")):
        merged = jnp.concatenate([s[i] for s in shard])
        np.testing.assert_array_equal(np.asarray(whole[i]),
                                      np.asarray(merged), err_msg=name)


@pytest.mark.parametrize("mdt", ["float32", "bfloat16"])
def test_fused_lamb_passes_parity(mdt):
    """FusedLamb.apply_flat: kernels path vs the XLA path, both moment
    storage dtypes, bias correction + clip + trust bounds live."""
    from mxnet_tpu.parallel.fused_lamb import FusedLamb
    rng = np.random.RandomState(1)
    shapes = [(64, 32), (100,), (7, 13), ()]
    fl = FusedLamb(shapes, [jnp.float32] * 4, wds=[0.01, 0.0, 0.01, 0.0],
                   beta1=0.9, beta2=0.999, epsilon=1e-6,
                   bias_correction=True, rescale_grad=1.0,
                   clip_gradient=1.0, lower_bound=0.0, upper_bound=10.0,
                   moments_dtype=mdt)

    def rand(s):
        return jnp.asarray(np.asarray(rng.randn(*s), np.float32))

    w = fl.flatten([rand(s) for s in shapes])
    g = fl.flatten([rand(s) for s in shapes])
    m = jnp.zeros_like(w).astype(jnp.dtype(mdt))
    v = jnp.zeros_like(w).astype(jnp.dtype(mdt))
    config.set("kernels", "off")
    ref = fl.apply_flat(w, g, m, v, jnp.float32(3.0), jnp.float32(0.01))
    config.set("kernels", "auto")
    got = fl.apply_flat(w, g, m, v, jnp.float32(3.0), jnp.float32(0.01))
    for a, b, name in zip(got, ref, ("w", "m", "v")):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=2e-6, atol=2e-7, err_msg=f"{mdt}/{name}")


def test_trainer_adam_step_parity():
    """End to end: a ShardedTrainer adam step with the kernel engaged
    matches the kernels=off trajectory (losses to printed precision)."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon import nn, loss as gloss

    parallel.make_mesh(dp=-1)

    def run():
        net = nn.Dense(16, in_units=32)
        mx.random.seed(0)
        net.initialize()
        lfn = gloss.L2Loss()
        tr = parallel.ShardedTrainer(net, lambda o, l: lfn(o, l), "adam",
                                     {"learning_rate": 0.01})
        x = nd.array(np.random.RandomState(0).randn(8, 32)
                     .astype(np.float32))
        y = nd.array(np.zeros((8, 16), np.float32))
        return [float(np.asarray(tr.step(x, y).asnumpy()))
                for _ in range(4)]

    config.set("kernels", "off")
    off = run()
    config.set("kernels", "auto")
    on = run()
    np.testing.assert_allclose(off, on, rtol=1e-6)


# --------------------------------------------------------------------------
# MoE dispatch/combine
# --------------------------------------------------------------------------

def _moe_case(N=50, D=40, E=4, C=16, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(N, D).astype(np.float32))
    expert = jnp.asarray(rng.randint(0, E, N), jnp.int32)
    # includes invalid (-1) and overflow (>= C) positions: both drop
    pos = jnp.asarray(rng.randint(-1, C + 2, N), jnp.int32)
    gate = jnp.asarray(rng.rand(N).astype(np.float32))
    return x, expert, pos, gate, E, C


def test_moe_dispatch_combine_parity():
    x, expert, pos, gate, E, C = _moe_case()
    buf = mk.dispatch_to_experts(x, expert, pos, E, C)
    bref = mk.dispatch_reference(x, expert, pos, E, C)
    np.testing.assert_allclose(np.asarray(buf), np.asarray(bref),
                               rtol=1e-6, atol=1e-6)
    y = mk.combine_from_experts(buf, expert, pos, gate)
    yref = mk.combine_reference(bref, expert, pos, gate)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref),
                               rtol=1e-6, atol=1e-6)


def test_moe_dispatch_gradient_parity():
    x, expert, pos, gate, E, C = _moe_case()

    def f(x_):
        return jnp.sum(mk.dispatch_to_experts(x_, expert, pos, E, C) ** 2)

    def fr(x_):
        return jnp.sum(mk.dispatch_reference(x_, expert, pos, E, C) ** 2)

    np.testing.assert_allclose(np.asarray(jax.grad(f)(x)),
                               np.asarray(jax.grad(fr)(x)),
                               rtol=1e-5, atol=1e-6)


def test_moe_combine_gradient_parity():
    x, expert, pos, gate, E, C = _moe_case()
    buf = mk.dispatch_reference(x, expert, pos, E, C)

    def f(b_, g_):
        return jnp.sum(mk.combine_from_experts(b_, expert, pos, g_) ** 2)

    def fr(b_, g_):
        return jnp.sum(mk.combine_reference(b_, expert, pos, g_) ** 2)

    ga, gb = jax.grad(f, argnums=(0, 1))(buf, gate)
    ra, rb = jax.grad(fr, argnums=(0, 1))(buf, gate)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(ra),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rb),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_moe_ffn_kernel_path_matches_einsum_path():
    """moe_ffn end to end (inside shard_map over a 1-extent ep axis):
    the fused dispatch/combine path reproduces the one-hot einsum path,
    forward and router/expert gradients. Slow-marked (grad through
    shard_map + interpreter, ~11s): ci/run.sh sanity runs it with the
    interpret kernel suite; tier-1 covers the same kernels via the
    direct dispatch/combine parity + VJP tests above."""
    from jax.sharding import Mesh
    from mxnet_tpu.parallel import moe as moe_mod

    rng = np.random.RandomState(0)
    N, D, Fh, E = 32, 16, 24, 4
    x = jnp.asarray(rng.randn(N, D).astype(np.float32))
    router = jnp.asarray(rng.randn(D, E).astype(np.float32))
    w1 = jnp.asarray(rng.randn(E, D, Fh).astype(np.float32) * 0.1)
    w2 = jnp.asarray(rng.randn(E, Fh, D).astype(np.float32) * 0.1)
    mesh = Mesh(np.array(jax.devices()[:1]), ("ep",))

    def loss(x_, r_, w1_, w2_):
        y, aux = moe_mod.moe_apply(x_, r_, w1_, w2_, mesh=mesh)
        return jnp.sum(y ** 2) + aux

    config.set("kernels", "off")
    ref = loss(x, router, w1, w2)
    ref_g = jax.grad(loss, argnums=(0, 1, 2))(x, router, w1, w2)
    config.set("kernels", "auto")
    assert mk.engaged()
    got = loss(x, router, w1, w2)
    got_g = jax.grad(loss, argnums=(0, 1, 2))(x, router, w1, w2)
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    for a, b in zip(got_g, ref_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------
# paged attention
# --------------------------------------------------------------------------

@pytest.fixture
def one_device():
    """A one-device mesh: the two paged kernels are global-view calls and
    stay off while the step spans the harness's eight virtual devices."""
    from mxnet_tpu.parallel import mesh as mesh_mod
    before = mesh_mod._current["mesh"]
    mesh_mod.make_mesh(devices=jax.devices()[:1])
    yield
    mesh_mod.set_mesh(before)


def _jaxpr(fn, *args):
    # a fresh callable each time: jax caches a traced function by
    # identity, and the kernel gate is read while tracing
    return str(jax.make_jaxpr(lambda *a: fn(*a))(*args))


def _holds_kernel(fn, *args):
    return "pallas_call" in _jaxpr(fn, *args)


def _paged_case(B=3, H=4, D=16, ps=8, n_pg=4, P=20, dtype=np.float32,
                seed=0):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, 1, D).astype(dtype))
    kp = jnp.asarray(rng.randn(P, H, ps, D).astype(dtype))
    vp = jnp.asarray(rng.randn(P, H, ps, D).astype(dtype))
    tables = jnp.asarray(rng.randint(0, P, (B, n_pg)).astype(np.int32))
    t = jnp.asarray(np.array([5, 17, n_pg * ps - 1], np.int32)[:B])
    return q, kp, vp, tables, t


def test_paged_attention_interpret_parity(one_device):
    q, kp, vp, tables, t = _paged_case()
    assert _holds_kernel(pa.paged_attention, q, kp, vp, tables, t)
    got = pa.paged_attention(q, kp, vp, tables, t)
    ref = pa.paged_attention_reference(q, kp, vp, tables, t)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-6, atol=2e-6)


def test_paged_attention_parity_bf16(one_device):
    q, kp, vp, tables, t = _paged_case(dtype=np.float32)
    q, kp, vp = (a.astype(jnp.bfloat16) for a in (q, kp, vp))
    got = pa.paged_attention(q, kp, vp, tables, t)
    assert got.dtype == jnp.bfloat16
    ref = pa.paged_attention_reference(q, kp, vp, tables, t)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def _walk_case(n_pg, lanes, dtype, B=5, H=4, D=16, ps=8, seed=3):
    """Rows at the positions where the walk's length changes — the first
    token, the last of a page, the first of the next, mid-page, the end
    of the bucket — each on pages of its own, in arenas `lanes` wide
    (zeros past D). Page 0 belongs to no row."""
    rng = np.random.RandomState(seed)
    P = 1 + B * n_pg
    pad = ((0, 0),) * 3 + ((0, lanes - D),)
    q = jnp.asarray(rng.randn(B, H, 1, D), dtype)
    kp = jnp.pad(jnp.asarray(rng.randn(P, H, ps, D), dtype), pad)
    vp = jnp.pad(jnp.asarray(rng.randn(P, H, ps, D), dtype), pad)
    tables = 1 + rng.permutation(B * n_pg).reshape(B, n_pg).astype(np.int32)
    t = np.array([0, ps - 1, ps, (n_pg // 2) * ps + 3, n_pg * ps - 1],
                 np.int32)
    return q, kp, vp, tables, t


def _assert_paged_parity(q, kp, vp, tables, t, dtype):
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(t))
    assert _holds_kernel(pa.paged_attention, *args)
    got = pa.paged_attention(*args)
    ref = pa.paged_attention_reference(*args)
    assert got.shape == ref.shape and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)
    return got


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("lanes", [16, 128], ids=["Dp=D", "Dp=lanes"])
@pytest.mark.parametrize("n_pg", [4, 16, 64])
def test_paged_attention_walk_parity(one_device, n_pg, lanes, dtype):
    """Kernel against reference with every length of walk in one batch,
    at three buckets, over bare and lane-wide arenas."""
    _assert_paged_parity(*_walk_case(n_pg, lanes, dtype), dtype)


@pytest.mark.parametrize("pages_a_wave", [1, 2, 3, 5])
def test_paged_attention_short_waves(one_device, monkeypatch, pages_a_wave):
    """Waves shorter than the walk, and of a length that does not divide
    it: the slots alternate across rows, the last wave of a row is
    short, and every bit matches the one-wave kernel."""
    q, kp, vp, tables, t = _walk_case(16, 128, jnp.float32)
    whole = _assert_paged_parity(q, kp, vp, tables, t, jnp.float32)
    page_bytes = kp[0].size * kp.dtype.itemsize
    assert pa._pages_per_wave(16, page_bytes, pa._WAVE_BYTES) == 16
    monkeypatch.setattr(pa, "_WAVE_BYTES", pages_a_wave * page_bytes)
    assert pa._pages_per_wave(16, page_bytes, pa._WAVE_BYTES) \
        == pages_a_wave
    got = _assert_paged_parity(q, kp, vp, tables, t, jnp.float32)
    assert np.array_equal(np.asarray(got), np.asarray(whole))


@pytest.mark.parametrize("pages_a_wave", [16, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_paged_attention_stops_at_the_position(one_device, monkeypatch,
                                               dtype, pages_a_wave):
    """Poison: every page past a row's position holds NaN, and the
    table's entries past it name a page of NaN. A kernel that walked on
    would multiply them by zero and return NaN; this one returns the
    clean case's bits."""
    q, kp, vp, tables, t = _walk_case(16, 128, dtype)
    ps = kp.shape[2]
    monkeypatch.setattr(pa, "_WAVE_BYTES",
                        pages_a_wave * kp[0].size * kp.dtype.itemsize)
    clean = _assert_paged_parity(q, kp, vp, tables, t, dtype)
    kp, vp, tables = np.array(kp), np.array(vp), tables.copy()
    for b in range(len(t)):
        past = tables[b, t[b] // ps + 1:]
        kp[past] = np.nan
        vp[past] = np.nan
        tables[b, t[b] // ps + 1:] = 0
    kp[0] = np.nan
    vp[0] = np.nan
    got = pa.paged_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                             jnp.asarray(tables), jnp.asarray(t))
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(clean, np.float32))


def test_paged_attention_idle_row_beside_live_rows(one_device):
    """A slot nobody holds: a table of zeros and t = 0. It reads page 0
    alone, and the live rows around it are what they are without it."""
    q, kp, vp, tables, t = _walk_case(16, 128, jnp.float32)
    alone = _assert_paged_parity(q, kp, vp, tables, t, jnp.float32)
    tables, t = tables.copy(), t.copy()
    tables[2], t[2] = 0, 0
    got = _assert_paged_parity(q, kp, vp, tables, t, jnp.float32)
    live = [0, 1, 3, 4]
    assert np.array_equal(np.asarray(got)[live], np.asarray(alone)[live])
    assert np.isfinite(np.asarray(got)[2]).all()


@pytest.mark.parametrize("pages_a_wave", [16, 2])
@pytest.mark.parametrize("where", ["first", "middle", "last", "runs", "all"])
def test_paged_attention_padding_rows_walk_no_page(one_device, monkeypatch,
                                                   where, pages_a_wave):
    """Rows at position -1 pad a pass to its width: they bring no page
    (everything their table names is NaN), come back as zeros, and the
    live rows around them are bit for bit what they are without them —
    wherever the padding sits, so the next live row's first wave is
    handed on across any run of it."""
    q, kp, vp, tables, t = _walk_case(16, 128, jnp.float32, B=7)
    t = np.array([0, 7, 8, 60, 127, 33, 90], np.int32)
    monkeypatch.setattr(pa, "_WAVE_BYTES",
                        pages_a_wave * kp[0].size * kp.dtype.itemsize)
    alone = _assert_paged_parity(q, kp, vp, tables, t, jnp.float32)
    pad = {"first": [0], "middle": [3], "last": [6], "runs": [0, 1, 4, 5],
           "all": list(range(7))}[where]
    kp, vp, t = np.array(kp), np.array(vp), t.copy()
    kp[tables[pad]] = np.nan
    vp[tables[pad]] = np.nan
    t[pad] = -1
    got = np.asarray(pa.paged_attention(
        q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
        jnp.asarray(t)))
    live = np.setdiff1d(np.arange(7), pad)
    assert np.array_equal(got[live], np.asarray(alone)[live])
    assert not got[pad].any()


def test_paged_attention_kernels_off_is_reference_path(one_device):
    config.set("kernels", "off")
    case = _paged_case()
    assert _jaxpr(pa.paged_attention, *case) \
        == _jaxpr(pa.paged_attention_reference, *case)


# --------------------------------------------------------------------------
# the arena write of the paged step
# --------------------------------------------------------------------------

def _write_case(dtype, P=24, H=4, D=16, ps=8, n_pg=3, seed=0, Dp=None):
    """Arenas (of last dimension Dp >= D, zeros past D), new vectors, and
    the targets `_paged_write_targets` makes for five rows: an active row
    mid-page, one at offset 0, one at offset ps-1, a masked row and a
    position past the table's range (both to the row's scratch page,
    offset 0)."""
    from mxnet_tpu.models.gpt import GPTForCausalLM
    rng = np.random.RandomState(seed)
    B = 5
    pad = ((0, 0),) * 3 + ((0, (Dp or D) - D),)
    kp = jnp.pad(jnp.asarray(rng.randn(P, H, ps, D), dtype), pad)
    vp = jnp.pad(jnp.asarray(rng.randn(P, H, ps, D), dtype), pad)
    kn = jnp.asarray(rng.randn(B, H, 1, D), jnp.float32)
    vn = jnp.asarray(rng.randn(B, H, 1, D), jnp.float32)
    tables = jnp.asarray(B + rng.permutation(P - B)[:B * n_pg]
                         .reshape(B, n_pg), jnp.int32)
    pos = jnp.asarray([ps + 3, 2 * ps, ps - 1, 5, n_pg * ps + 1], jnp.int32)
    active = jnp.asarray([True, True, True, False, True])
    wp, wo = GPTForCausalLM._paged_write_targets(None, pos, active, tables,
                                                 ps)
    assert wo.tolist() == [3, 0, ps - 1, 0, 0]
    assert wp.tolist()[3:] == [3, 4]
    return kp, vp, kn, vn, wp, wo


@pytest.mark.parametrize("Dp", [None, 128], ids=["head_dim", "lane_width"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kv_page_write_bit_equal_to_scatter(one_device, dtype, Dp):
    case = _write_case(dtype, Dp=Dp)
    assert _holds_kernel(kw.kv_page_write, *case)
    got = kw.kv_page_write(*case)
    ref = kw.kv_page_write_reference(*case)
    for g, r, before in zip(got, ref, case[:2]):
        assert g.dtype == dtype
        assert np.array_equal(np.asarray(g, np.float32),
                              np.asarray(r, np.float32))
        # pages no row names are as they were
        idle = np.setdiff1d(np.arange(before.shape[0]), np.asarray(case[4]))
        assert np.array_equal(np.asarray(g, np.float32)[idle],
                              np.asarray(before, np.float32)[idle])


@pytest.mark.parametrize("packing", ["pass", "slot_major"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("together", [2, 3, 5, 8])
def test_kv_page_write_rows_that_share_a_page(one_device, dtype, together,
                                              packing):
    """A pass's virtual rows: `together` consecutive positions of one
    request in ONE page, a span of eight across a page boundary (five
    rows at the end of a page, three at the start of the next), a
    decoding row on a page of its own, and padding rows that share a
    scratch cell — packed as `Server._pass` packs (the decoding row, the
    requests' runs, padding at the tail) and slot-major as the verify
    pass does (each slot's run, then its padding through its own scratch
    page). Either way the rows of one page are consecutive, the kernel's
    contract. Every page but the scratch ones equals the reference
    scatter, bit for bit."""
    rng = np.random.RandomState(together)
    P, H, ps, D = 24, 4, 8, 16
    n_scratch = 4
    pad = ((0, 0),) * 3 + ((0, 128 - D),)
    kp = jnp.pad(jnp.asarray(rng.randn(P, H, ps, D), dtype), pad)
    vp = jnp.pad(jnp.asarray(rng.randn(P, H, ps, D), dtype), pad)
    run, span = [7] * together, [10] * 5 + [11] * 3
    run_o, span_o = list(range(together)), [3, 4, 5, 6, 7, 0, 1, 2]
    if packing == "pass":
        wp = [9] + run + span + [0, 0, 0]
        wo = [6] + run_o + span_o + [0, 0, 0]
    else:
        wp = run + [1, 1] + span + [2] + [9] + [3, 3, 3]
        wo = run_o + [0, 0] + span_o + [0] + [6] + [0, 0, 0]
    # the contract: a page is named by one run of consecutive rows
    runs = [p for k, p in enumerate(wp) if k == 0 or wp[k - 1] != p]
    assert len(runs) == len(set(runs))
    wp, wo = jnp.asarray(wp, jnp.int32), jnp.asarray(wo, jnp.int32)
    kn = jnp.asarray(rng.randn(wp.shape[0], H, 1, D), jnp.float32)
    vn = jnp.asarray(rng.randn(wp.shape[0], H, 1, D), jnp.float32)
    assert _holds_kernel(kw.kv_page_write, kp, vp, kn, vn, wp, wo)
    got = jax.jit(kw.kv_page_write)(kp, vp, kn, vn, wp, wo)
    ref = kw.kv_page_write_reference(kp, vp, kn, vn, wp, wo)
    for g, r, before in zip(got, ref, (kp, vp)):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        before = np.asarray(before, np.float32)
        assert np.array_equal(g[n_scratch:], r[n_scratch:])
        # the scratch pages: one of the rows that named the cell, the
        # rest of the page as it was
        assert np.array_equal(g[:n_scratch, :, 1:], before[:n_scratch, :, 1:])
        assert not np.array_equal(g[7], before[7])


def test_kv_page_write_under_jit_with_donation(one_device):
    """The served form: arenas donated to a jitted step and threaded."""
    kp, vp, kn, vn, wp, wo = _write_case(jnp.bfloat16, seed=1)
    ref = kw.kv_page_write_reference(kp, vp, kn, vn, wp, wo)
    got = jax.jit(kw.kv_page_write, donate_argnums=(0, 1))(
        kp + 0, vp + 0, kn, vn, wp, wo)
    for g, r in zip(got, ref):
        assert np.array_equal(np.asarray(g, np.float32),
                              np.asarray(r, np.float32))


def test_kv_page_write_kernels_off_is_reference_path(one_device):
    """kernels=off: the write is the scatter, and the whole
    paged_attention_step holds no kernel — the pre-kernel program."""
    from mxnet_tpu import nd
    from mxnet_tpu.models._decode import paged_attention_step
    case = _write_case(jnp.float32)
    q, kp, vp, tables, t = _paged_case()
    B = q.shape[0]
    step_args = [nd.array(np.asarray(a)) for a in
                 (q, q, q, kp, vp, tables, jnp.arange(B), jnp.zeros(B), t)]

    def step(*a):
        return [o._data for o in paged_attention_step(
            *[nd.NDArray(x) for x in a])]

    raw = [a._data for a in step_args]
    assert _holds_kernel(step, *raw)
    config.set("kernels", "off")
    assert _jaxpr(kw.kv_page_write, *case) \
        == _jaxpr(kw.kv_page_write_reference, *case)
    text = _jaxpr(step, *raw)
    assert "pallas_call" not in text and text.count(" = scatter[") == 2


def test_kv_page_write_off_a_single_device_is_reference_path():
    """On a step that spans devices (the harness's eight, no mesh) the
    gate keeps the scatter, as it keeps `paged_attention` off, and the
    pool keeps the heads' own width."""
    assert _common.multi_device()
    assert not _holds_kernel(kw.kv_page_write, *_write_case(jnp.float32))
    assert kw.arena_head_dim(64) == 64


def test_arena_head_dim_follows_the_kernel_gate(one_device, monkeypatch):
    """The lane width where the kernels run; the heads' own elsewhere: a
    CPU without the interpreter, kernels=off, a step across devices. The
    pool allocates by it."""
    from mxnet_tpu import pages
    assert [kw.arena_head_dim(d) for d in (16, 64, 128, 160)] \
        == [128, 128, 128, 256]
    specs = {"target": [(2, 8, np.float32)] * 2}
    assert pages.PagePool(4, 8, 2, specs).state["target"][0].shape \
        == (10, 2, 4, 128)
    config.set("kernels", "off")
    assert kw.arena_head_dim(64) == 64
    assert pages.PagePool(4, 8, 2, specs).state["target"][0].shape \
        == (10, 2, 4, 8)
    config.set("kernels", "auto")
    monkeypatch.setattr(_common, "multi_device", lambda: True)
    assert kw.arena_head_dim(64) == 64
    monkeypatch.setattr(_common, "multi_device", lambda: False)
    monkeypatch.delenv("MXNET_TPU_PALLAS_INTERPRET")
    assert kw.arena_head_dim(64) == 64


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_paged_attention_over_lane_padded_arenas(one_device, dtype, tol):
    """Arenas wider than the heads (zeros past D): the kernel and the
    reference both give what the unpadded arenas give."""
    q, kp, vp, tables, t = _paged_case()
    q, kp, vp = (a.astype(dtype) for a in (q, kp, vp))
    pad = ((0, 0),) * 3 + ((0, 128 - kp.shape[3]),)
    wide = (q, jnp.pad(kp, pad), jnp.pad(vp, pad), tables, t)
    assert _holds_kernel(pa.paged_attention, *wide)
    ref = pa.paged_attention_reference(q, kp, vp, tables, t)
    assert np.array_equal(
        np.asarray(pa.paged_attention_reference(*wide), np.float32),
        np.asarray(ref, np.float32))
    got = pa.paged_attention(*wide)
    assert got.shape == ref.shape and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_paged_server_tokens_equal_on_kernel_and_scatter_write(
        one_device, monkeypatch):
    """Greedy tokens of a server whose steps write through the kernel
    equal those of the same server writing through the scatter (the
    attention kernel and lane-padded arenas on both sides): the write is
    bit-exact."""
    import mxnet_tpu as mx
    from mxnet_tpu import pallas_ops, serve
    from mxnet_tpu.models import gpt as gpt_mod

    model = gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config())
    mx.random.seed(0)
    model.initialize()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 128, (n,)).astype(np.int32)
               for n in (5, 9, 14, 17)]

    def tokens():
        srv = serve.Server(model, slots=4, page_size=4, prefill_chunk=4)
        reqs = [srv.submit(p, max_new_tokens=8) for p in prompts]
        srv.drain()
        srv.stop()
        assert all(r.verdict == "200 ok" for r in reqs)
        return [list(r.tokens) for r in reqs]

    try:
        on_kernel = tokens()
        monkeypatch.setattr(pallas_ops, "kv_page_write",
                            pallas_ops.kv_page_write_reference)
        on_scatter = tokens()
    finally:
        serve.disable()
    assert on_kernel == on_scatter


def test_paged_attention_reference_matches_dense_gather():
    """Tables laid out contiguously (page i of row b = pool row holding
    positions [i*ps, (i+1)*ps)) reduce the paged computation to the
    dense cached-attention expression — the identity that holds served
    tokens to `model.generate`'s."""
    rng = np.random.RandomState(3)
    B, H, D, ps, n_pg = 2, 4, 16, 8, 3
    L = n_pg * ps
    k = rng.randn(B, H, L, D).astype(np.float32)
    v = rng.randn(B, H, L, D).astype(np.float32)
    q = jnp.asarray(rng.randn(B, H, 1, D).astype(np.float32))
    t = jnp.asarray(np.array([7, 20], np.int32))
    # scatter the dense caches into pool pages, contiguous tables
    kp = np.zeros((B * n_pg, H, ps, D), np.float32)
    vp = np.zeros_like(kp)
    tables = np.zeros((B, n_pg), np.int32)
    for b in range(B):
        for i in range(n_pg):
            pid = b * n_pg + i
            tables[b, i] = pid
            kp[pid] = k[b, :, i * ps:(i + 1) * ps, :]
            vp[pid] = v[b, :, i * ps:(i + 1) * ps, :]
    got = pa.paged_attention_reference(q, jnp.asarray(kp),
                                       jnp.asarray(vp),
                                       jnp.asarray(tables), t)
    # dense masked attention, the decode_step math
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.asarray(k)) / (D ** 0.5)
    valid = jnp.arange(L)[None, None, None, :] <= t[:, None, None, None]
    s = jnp.where(valid, s, -1e30)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1),
                     jnp.asarray(v))
    assert np.array_equal(np.asarray(got), np.asarray(ref))


# --------------------------------------------------------------------------
# mx.check graph lint over the traced kernels
# --------------------------------------------------------------------------

def _assert_lint_clean(name, fn, args):
    from mxnet_tpu import check
    check.reset()
    config.set("check", "warn")
    check.enable()
    try:
        jitted = jax.jit(fn)
        check.check_jit(name, ("test_kernels", name), jitted, args)
        assert check.findings() == [], check.findings()
    finally:
        check.disable()
        config.reset("check")
        check.reset()


def test_check_lint_int8_kernel_clean():
    x_q, w_q, s_x, w_scale, bias = _int8_case()
    _assert_lint_clean(
        "kernels.int8_matmul",
        lambda *a: im.int8_matmul(*a, relu=True),
        (x_q, w_q, s_x, w_scale, bias))


def test_check_lint_fused_adam_clean():
    w, g, m, v = _adam_case()
    _assert_lint_clean(
        "kernels.fused_adam",
        lambda *a: fu.adam_update(*a, wd=0.01, clip_gradient=1.0),
        (w, g, m, v, jnp.float32(0.01)))


def test_check_lint_moe_kernels_clean():
    x, expert, pos, gate, E, C = _moe_case()

    def roundtrip(x_, e_, p_, g_):
        buf = mk.dispatch_to_experts(x_, e_, p_, E, C)
        return mk.combine_from_experts(buf, e_, p_, g_)

    _assert_lint_clean("kernels.moe_dispatch_combine", roundtrip,
                      (x, expert, pos, gate))


# --------------------------------------------------------------------------
# mx.inspect remediation hints
# --------------------------------------------------------------------------

def test_inspect_kernel_hint_names_applicable_kernel():
    """A memory-bound roofline verdict carries the applicable
    pallas_ops kernel (mirroring mx.check's degenerate-sharding rule
    naming mx.zero); compute-bound and unknown verdicts carry none."""
    from mxnet_tpu import inspect as mxi

    def rec(name, flops, bytes_accessed):
        r = mxi.CostRecord(name, "k")
        r.flops = flops
        r.bytes_accessed = bytes_accessed
        return r

    peak, bw = 100e12, 1e12          # ridge point at AI = 100
    low = rec("serve.decode(bucket=64)", 1e9, 1e9)       # AI 1: mem-bound
    assert low.roofline(peak, bw) == "memory-bound"
    hint = low.kernel_hint() if low.roofline() == "memory-bound" else None
    # drive via explicit peaks (CPU has none): patch the module lookups
    import unittest.mock as mock
    with mock.patch.object(mxi, "peak_flops_per_chip", lambda: peak), \
            mock.patch.object(mxi, "peak_bandwidth_per_chip", lambda: bw):
        assert "int8_matmul" in low.kernel_hint()
        assert "moe_kernels" in rec("moe_ffn(block3)", 1e9,
                                    1e9).kernel_hint()
        assert "fused_update" in rec("sharded_step(net)", 1e9,
                                     1e9).kernel_hint()
        # unmatched names still get the generic library pointer
        assert "pallas_ops" in rec("mystery_exec", 1e9, 1e9).kernel_hint()
        # compute-bound: no hint
        assert rec("serve.decode", 1e15, 1e9).kernel_hint() is None
        # snapshot surface carries the hint field
        d = low.as_dict()
        assert "int8_matmul" in d["kernel_hint"]


def test_inspect_report_renders_kernel_hint(tmp_path):
    import json
    import subprocess
    import sys as _sys
    import os as _os

    snap = {
        "backend": "TPU v5e",
        "peak_flops_per_chip": 197e12,
        "peak_bandwidth_per_chip": 819e9,
        "largest_peak_bytes_executable": "serve.decode",
        "records": [{
            "name": "serve.decode", "key": "k", "compiles": 1,
            "flops": 1e9, "bytes_accessed": 1e9, "peak_bytes": 1,
            "steps": 1, "avg_step_s": 0.001, "roofline": "memory-bound",
            "kernel_hint": "pallas_ops.int8_matmul via quantize_block",
        }],
    }
    p = tmp_path / "inspect.json"
    p.write_text(json.dumps(snap))
    root = _os.path.dirname(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))))
    out = subprocess.run(
        [_sys.executable, _os.path.join(root, "tools", "inspect_report.py"),
         str(p)], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "remediation: pallas_ops.int8_matmul" in out.stdout
