"""Pallas kernels against their references — the kernel phase of
`chip_smoke.py` (which is how this runs: every check here needs the chip,
because the CPU interpreter lowers a kernel to plain XLA ops and cannot
draw the TPU PRNG at all).

  1. flash attention, dropout=0: parity vs mha_reference (fwd + dq,
     plain / mask / causal)
  2. attention-dropout statistics (keep rate, inverted-scale mean,
     determinism)
  3. explicit-mask oracle check of the dropout path — the actual keep mask
     is EXTRACTED from the kernel (uniform-attention probe with v=I reads
     z_ij/(L(1-r)) back out), then fwd and all three grads are compared
     against XLA autodiff of softmax-then-mask with that fixed mask. This
     proves the forward, dq, and dkv kernels regenerate bit-identical masks
     AND that the dropout backward math is right.
  4. paged decode attention vs paged_attention_reference
  5. the in-place write of new keys and values into the page arenas vs the
     scatter it replaces
  6. the fused-LAMB kernel passes vs the same step with kernels=off
  7. int8 matmul vs int8_matmul_reference

Each function raises AssertionError on the first check that fails (the
smoke catches nothing) and prints one `agrees ...` line per check that
holds. Flash tolerances are calibrated to the MXU's reduced-precision f32
matmul (~1e-3 rel vs XLA), not to exact-f32 arithmetic.
"""
import collections
import re

import numpy as np
import jax
import jax.numpy as jnp

from mxnet_tpu import config
from mxnet_tpu.pallas_ops import (flash_attention, mha_reference,
                                  paged_attention, paged_attention_reference,
                                  kv_page_write, kv_page_write_reference,
                                  int8_matmul, int8_matmul_reference)
from mxnet_tpu.pallas_ops.kv_page_write import arena_head_dim


def check(name, ok, detail=""):
    if not ok:
        raise AssertionError(f"kernel check failed: {name} {detail}")
    print(f"  agrees: {name} {detail}", flush=True)


def rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-6))


def pallas_kernels(lowered):
    """{kernel name: count} of the Mosaic custom calls in a
    `jax.stages.Lowered` module — what the compiler is handed, and cannot
    drop while the outputs depend on it. Every pallas_call in pallas_ops/
    carries `name=`, which lowering keeps as the location's component
    before `pallas_call` (`.../lamb_pass1/pallas_call`, or wrapped by
    autodiff as `.../transpose(jvp(flash_dq))/pallas_call`). A custom
    call inside a function the module calls several times counts once a
    call (a stack of layers traced once, `models/_decode.layer_call`, is
    one function and a call a layer; XLA inlines them)."""
    text = lowered.as_text(debug_info=True)
    locs = dict(re.findall(r'^#loc(\d+) = loc\("([^"]*)"', text, re.M))
    # per function: its own custom calls, and the functions it calls
    own = collections.defaultdict(collections.Counter)
    calls = collections.defaultdict(collections.Counter)
    fn = "main"
    for line in text.splitlines():
        head = re.match(r'\s*func\.func \w+ @([\w.$-]+)\(', line)
        if head:
            fn = head.group(1)
        elif "@tpu_custom_call" in line:
            ref = re.search(r'loc\(#loc(\d+)\)\s*$', line)
            name = re.search(r'([A-Za-z0-9_]+)\)*/pallas_call',
                             locs.get(ref.group(1), "") if ref else "")
            own[fn][name.group(1) if name else "unnamed"] += 1
        else:
            for callee in re.findall(r'\bcall @([\w.$-]+)\(', line):
                calls[fn][callee] += 1

    def total(fn):
        found = collections.Counter(own[fn])
        for callee, n in calls[fn].items():
            for kernel, k in total(callee).items():
                found[kernel] += n * k
        return found

    return dict(total("main"))


def flash_parity(B=2, H=4, L=512, D=64):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, L, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, L, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, L, D), jnp.float32)
    mask = jnp.asarray(rng.rand(B, L) > 0.2)

    for name, kw in [("plain", {}), ("mask", {"mask": mask}),
                     ("causal", {"causal": True})]:
        bias = None
        if "mask" in kw:
            bias = jnp.where(mask, 0.0, -1e30)[:, None, None, :]
        out = flash_attention(q, k, v, block_q=128, block_k=128, **kw)
        ref = mha_reference(q, k, v, bias=bias, causal=kw.get("causal", False))
        check(f"flash fwd {name}", rel_err(out, ref) < 5e-3,
              f"rel={rel_err(out, ref):.2e}")
        g = jax.grad(lambda q: flash_attention(
            q, k, v, block_q=128, block_k=128, **kw).sum())(q)
        gr = jax.grad(lambda q: mha_reference(
            q, k, v, bias=bias, causal=kw.get("causal", False)).sum())(q)
        check(f"flash dq {name}", rel_err(g, gr) < 1e-2,
              f"rel={rel_err(g, gr):.2e}")


def flash_dropout_stats(B=2, H=4, L=512, D=64):
    q = jnp.zeros((B, H, L, D), jnp.float32)   # uniform probs = 1/L
    k = jnp.zeros((B, H, L, D), jnp.float32)
    v = jnp.asarray(np.eye(L)[None, None].repeat(H, 1).repeat(B, 0)
                    [..., :D], jnp.float32)
    key = jax.random.key(3)
    rate = 0.3
    out = flash_attention(q, k, v, block_q=128, block_k=128, dropout=rate,
                          dropout_key=key)
    # each output element is keep_ij/(L*(1-rate)); zeros ratio estimates rate
    zero_frac = float(jnp.mean(out == 0.0))
    check("dropout keep rate", abs(zero_frac - rate) < 0.02,
          f"dropped={zero_frac:.3f} want≈{rate}")
    clean = flash_attention(q, k, v, block_q=128, block_k=128)
    ratio = float(out.mean() / clean.mean())
    check("dropout inverted mean", abs(ratio - 1.0) < 0.05,
          f"ratio={ratio:.3f}")
    # determinism: same key → same output
    out2 = flash_attention(q, k, v, block_q=128, block_k=128, dropout=rate,
                           dropout_key=key)
    check("dropout deterministic", bool(jnp.all(out == out2)))


def keep_masks(B, H, L, rate, key):
    """The kernel's actual keep masks, (B, H, L, L) bool: uniform attention
    (q=k=0) with v=I makes out[b,h,i,j] = z_ij / (L*(1-rate)) — nonzero iff
    kept. The mask depends only on (seed, tile id), so the SAME mask
    applies to real tensors of the same L and block sizes."""
    probe = flash_attention(jnp.zeros((B, H, L, L)), jnp.zeros((B, H, L, L)),
                            jnp.broadcast_to(jnp.eye(L)[None, None],
                                             (B, H, L, L)),
                            block_q=128, block_k=128, dropout=rate,
                            dropout_key=key)
    return np.asarray(probe) > 0


def flash_dropout_oracle(B=1, H=2, L=512, D=64):
    rng = np.random.RandomState(2)
    key = jax.random.key(11)
    rate = 0.3
    Z = jnp.asarray(keep_masks(B, H, L, rate, key))
    frac = float(Z.mean())
    check("dropout keep-mask extraction", abs(frac - (1 - rate)) < 0.02,
          f"keep frac={frac:.3f}")

    q = jnp.asarray(rng.randn(B, H, L, D) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(B, H, L, D) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(B, H, L, D) * 0.5, jnp.float32)
    r = jnp.asarray(rng.randn(B, H, L, D), jnp.float32)

    def oracle(qq, kk, vv):
        s = jnp.einsum("bhqd,bhkd->bhqk", qq, kk,
                       preferred_element_type=jnp.float32) / np.sqrt(D)
        p = jnp.where(Z, jax.nn.softmax(s, -1) / (1 - rate), 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", p, vv)

    def pallas(qq, kk, vv):
        return flash_attention(qq, kk, vv, block_q=128, block_k=128,
                               dropout=rate, dropout_key=key)

    out_p, out_o = pallas(q, k, v), oracle(q, k, v)
    check("dropout fwd vs oracle", rel_err(out_p, out_o) < 5e-3,
          f"rel={rel_err(out_p, out_o):.2e}")
    for i, name in enumerate(("dq", "dk", "dv")):
        gp = jax.grad(lambda *a: jnp.vdot(pallas(*a), r), argnums=i)(q, k, v)
        go = jax.grad(lambda *a: jnp.vdot(oracle(*a), r), argnums=i)(q, k, v)
        check(f"dropout {name} vs oracle", rel_err(gp, go) < 1e-2,
              f"rel={rel_err(gp, go):.2e}")


def paged_parity(B=8, H=16, D=64, page_size=16, n_pg=32,
                 dtype=jnp.bfloat16, expect_kernel=True):
    """Paged decode attention at the served model's shapes: the kernel
    (kernels=auto) against paged_attention_reference on the same pool.
    Tolerance: both paths do f32 math on the same inputs and round once to
    `dtype`; the online softmax only reorders the sums, so one ulp of the
    output dtype at the output's scale is expected and two are allowed."""
    rng = np.random.RandomState(4)
    P = B * n_pg + B
    q = jnp.asarray(rng.randn(B, H, 1, D), dtype)
    k_pg = jnp.asarray(rng.randn(P, H, page_size, D), dtype)
    v_pg = jnp.asarray(rng.randn(P, H, page_size, D), dtype)
    tables = jnp.asarray(
        rng.permutation(P)[:B * n_pg].reshape(B, n_pg), jnp.int32)
    # positions from the first page to the last, the last row full
    t = jnp.asarray(np.linspace(3, n_pg * page_size - 1, B), jnp.int32)
    kern = jax.jit(paged_attention)
    if expect_kernel:
        found = pallas_kernels(kern.lower(q, k_pg, v_pg, tables, t))
        check("paged_attention kernel in the lowered call",
              found.get("paged_attention", 0) == 1, f"found={found}")
    got = kern(q, k_pg, v_pg, tables, t)
    ref = jax.jit(paged_attention_reference)(q, k_pg, v_pg, tables, t)
    tol = 2 * float(jnp.finfo(dtype).eps)
    err = rel_err(got, ref)
    check(f"paged attention B{B} H{H} D{D} page{page_size} n_pg{n_pg}",
          err <= tol, f"rel={err:.2e} tol={tol:.1e}")


def kv_write_parity(B=32, H=16, D=64, page_size=16, n_pages=2080,
                    dtype=jnp.bfloat16, expect_kernel=True):
    """The arena write of a paged step as the server runs it — arenas of
    the width the pool allocates (`arena_head_dim`), donated — against
    the scatter on the same pools: every element of both arenas equal,
    bit for bit, and the paged-attention kernel reading the written
    arenas against its reference. Rows as `paged_write_targets` makes
    them for a pass of virtual rows: every fourth row masked (its scratch
    page b, offset 0), the others on pages of their own at offsets that
    cover 0 and page_size-1 — and, where B allows, the rows of requests
    inside their prompt, side by side as a pass packs them (the write's
    contract: the rows of one page are consecutive): a span of eight
    consecutive positions across a page boundary (five rows at the end of
    one page, three at the start of the next) and two rows of one page."""
    rng = np.random.RandomState(7)
    Dp = arena_head_dim(D)
    pad = ((0, 0),) * 3 + ((0, Dp - D),)
    k_pg = jnp.pad(jnp.asarray(rng.randn(n_pages, H, page_size, D), dtype),
                   pad)
    v_pg = jnp.pad(jnp.asarray(rng.randn(n_pages, H, page_size, D), dtype),
                   pad)
    k_new = jnp.asarray(rng.randn(B, H, 1, D), dtype)
    v_new = jnp.asarray(rng.randn(B, H, 1, D), dtype)
    rows = np.arange(B)
    shares = B >= 16 and page_size >= 8
    masked = (rows % 4 == 3) & (rows >= (10 if shares else 0))
    real = B + rng.permutation(n_pages - B)[:B]
    wp = np.where(masked, rows, real)
    wo = np.where(masked, 0, (page_size - 1 - rows) % page_size)
    if shares:
        wp[:8] = [real[0]] * 5 + [real[1]] * 3
        wo[:8] = list(range(page_size - 5, page_size)) + [0, 1, 2]
        wp[8:10], wo[8:10] = real[8], [3, 4]
        shared = " (8 + 2 rows share 3 pages)"
    else:
        shared = ""
    wp, wo = jnp.asarray(wp, jnp.int32), jnp.asarray(wo, jnp.int32)
    want = jax.jit(kv_page_write_reference)(k_pg, v_pg, k_new, v_new, wp, wo)
    write = jax.jit(kv_page_write, donate_argnums=(0, 1))
    if expect_kernel:
        found = pallas_kernels(write.lower(k_pg, v_pg, k_new, v_new, wp, wo))
        check("kv_page_write kernel in the lowered call",
              found.get("kv_page_write", 0) == 1, f"found={found}")
        check("arenas are allocated at the lane width",
              Dp % 128 == 0, f"head_dim {D} -> {Dp}")
    got = write(k_pg, v_pg, k_new, v_new, wp, wo)
    for name, a, b in zip("KV", got, want):
        check(f"kv page write {name} B{B} H{H} D{D}->{Dp} page{page_size} "
              f"pool{n_pages}{shared}", bool(jnp.array_equal(a, b)),
              "every element equal")
    # the attention kernel over the written, lane-padded arenas
    n_pg = min(16, (n_pages - B) // B)
    tables = jnp.asarray(B + rng.permutation(n_pages - B)[:B * n_pg]
                         .reshape(B, n_pg), jnp.int32)
    q = jnp.asarray(rng.randn(B, H, 1, D), dtype)
    # a pass's rows: live ones at every depth, and padding (position -1)
    # at the start, in the middle and at the end, which walks no page
    t = np.linspace(1, n_pg * page_size - 1, B).astype(np.int32)
    t[[0, B // 2, B // 2 + 1, B - 1]] = -1
    live = t >= 0
    out = jax.jit(paged_attention)(q, *got, tables, jnp.asarray(t))
    ref = jax.jit(paged_attention_reference)(q, *got, tables, jnp.asarray(t))
    tol = 2 * float(jnp.finfo(dtype).eps)
    err = rel_err(out[live], ref[live])
    check(f"paged attention over arenas of width {Dp}, "
          f"{int((~live).sum())} padding rows among {B}", err <= tol,
          f"rel={err:.2e} tol={tol:.1e}")
    if expect_kernel:
        check("padding rows come back as zeros",
              not bool(jnp.any(out[~live])), "every element 0")


def lamb_parity(shapes, expect_kernel=True):
    """One fused-LAMB step (FusedLamb.apply_flat over the flat f32 master
    built from `shapes`) with kernels=auto against the same step with
    kernels=off. Tolerance: both are f32; the kernel sums each row's
    squares in a different order than XLA's reduction, which moves the
    trust ratios — and through them every weight — by a few f32 ulps."""
    from mxnet_tpu.parallel.fused_lamb import FusedLamb
    fl = FusedLamb(shapes, [jnp.float32] * len(shapes),
                   [0.01] * len(shapes), 0.9, 0.999, 1e-6, True, 1.0,
                   -1.0, -1.0, -1.0)
    rng = np.random.RandomState(5)
    n = fl.total
    w = jnp.asarray(rng.randn(n).astype(np.float32) * 0.02)
    g = jnp.asarray(rng.randn(n).astype(np.float32) * 1e-3)
    m = jnp.asarray(rng.randn(n).astype(np.float32) * 1e-4)
    v = jnp.asarray(np.square(rng.randn(n).astype(np.float32)) * 1e-6)
    t, lr = jnp.asarray(3.0), jnp.asarray(1e-3)
    outs = {}
    for knob in ("off", "auto"):
        config.set("kernels", knob)
        step = jax.jit(fl.apply_flat)
        found = pallas_kernels(step.lower(w, g, m, v, t, lr))
        if knob == "off":
            check("kernels=off LAMB step holds no kernel", not found,
                  f"found={found}")
        elif expect_kernel:
            check("lamb_pass1 + lamb_pass2 in the kernels=auto step",
                  found == {"lamb_pass1": 1, "lamb_pass2": 1},
                  f"found={found}")
        outs[knob] = step(w, g, m, v, t, lr)
    config.reset("kernels")
    for name, a, b in zip(("w", "m", "v"), outs["auto"], outs["off"]):
        check(f"LAMB new_{name} ({n / 1e6:.1f}M elements)",
              rel_err(a, b) < 1e-5, f"rel={rel_err(a, b):.2e}")


def int8_parity(M=8, K=768, O=3072):
    """int8 x int8 matmul with the fused per-channel rescale against the
    XLA lowering: int32 accumulation is exact on both sides, so only the
    f32 rescale can differ (by an ulp)."""
    rng = np.random.RandomState(6)
    xq = jnp.asarray(rng.randint(-127, 128, (M, K)), jnp.int8)
    wq = jnp.asarray(rng.randint(-127, 128, (K, O)), jnp.int8)
    ws = jnp.asarray(rng.rand(O).astype(np.float32) * 0.1)
    bias = jnp.asarray(rng.randn(O).astype(np.float32))
    got = jax.jit(int8_matmul)(xq, wq, jnp.float32(0.02), ws, bias)
    ref = jax.jit(int8_matmul_reference)(xq, wq, jnp.float32(0.02), ws, bias)
    check(f"int8 matmul M{M} K{K} O{O}", rel_err(got, ref) < 1e-5,
          f"rel={rel_err(got, ref):.2e}")
