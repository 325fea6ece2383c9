#!/usr/bin/env python
"""Compile for a TPU v5e without one: the step before any chip call.

libtpu ships a compile-only description of the chip
(`jax.experimental.topologies.get_topology_desc`), so this sandbox — CPUs
only — can still run Mosaic and the XLA:TPU compiler over every Pallas
kernel and over the whole train step, at the shapes `chip_smoke.py` uses,
on one device and on the four-device `v5e:2x2` mesh. What it catches is
what the compiler refuses (a dot Mosaic cannot parse, a block over the
scoped-VMEM limit, a kernel jit cannot partition, a step that does not fit
HBM); what it cannot catch is anything that only shows when the code RUNS
— numerics, hangs, real memory — and that is the chip's word.

    JAX_PLATFORMS=cpu python tools/aot_check.py            # everything
    JAX_PLATFORMS=cpu python tools/aot_check.py kernels    # or: train, serve, serve_glm, serve_laguna, serve_deepseek

Arguments are `jax.ShapeDtypeStruct`s whose shardings name the topology's
devices; the two places the package asks JAX what it runs on (the kernel
gate and the PRNG choice) are told "tpu" here, and the trainer's
`device_put`s are answered with abstract arrays, because nothing can be
placed on a device that is only a description. Exits non-zero on the
first refusal.
"""
import json
import os
import re
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import config, nd, parallel, serve  # noqa: E402
from mxnet_tpu.models import bert as bert_mod  # noqa: E402
from mxnet_tpu.models import gpt as gpt_mod  # noqa: E402
from mxnet_tpu.pallas_ops import _common  # noqa: E402
from mxnet_tpu import pallas_ops  # noqa: E402
from tools.tpu_validate import pallas_kernels  # noqa: E402

TOPOLOGY = "v5e:2x2"


def sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compile_(name, fn, *args):
    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    found = pallas_kernels(lowered)
    lowered.compile()
    print(f"  compiles: {name}  [{time.perf_counter() - t0:.1f}s]  "
          f"kernels={found or 'none'}", flush=True)
    return found


def check_kernels(devices):
    """Every kernel on the smoke's path at the smoke's shapes."""
    one = parallel.make_mesh(devices=devices[:1])
    s1 = NamedSharding(one, P())
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    key = jax.eval_shape(lambda: jax.random.key(0, impl="rbg"))

    def flash_loss(q, k, v, mask, key):
        return pallas_ops.flash_attention(
            q, k, v, mask=mask, dropout=0.1, dropout_key=key) \
            .astype(f32).sum()

    def flash_args(qs, ms, ks, B=32, H=12, L=512, D=64):
        return ([sds((B, H, L, D), bf, qs)] * 3
                + [sds((B, L), jnp.bool_, ms), sds(key.shape, key.dtype, ks)])

    grad = jax.value_and_grad(flash_loss, argnums=(0, 1, 2))
    compile_("flash fwd+bwd, mask + dropout, B32 H12 L512 D64, one device",
             grad, *flash_args(s1, s1, s1))
    compile_("flash fwd+bwd causal, B8 H16 L512 D64, one device",
             jax.grad(lambda q, k, v: pallas_ops.flash_attention(
                 q, k, v, causal=True).astype(f32).sum(), argnums=(0, 1, 2)),
             *[sds((8, 16, 512, 64), bf, s1)] * 3)

    # GPT-2 345M decode: 16 heads of 64, page 16, a 512 bucket, 8 slots
    B, H, D, ps, n_pg, n_pages = 8, 16, 64, 16, 32, 520
    compile_("paged_attention B8 H16 D64 page16 n_pg32",
             pallas_ops.paged_attention,
             sds((B, H, 1, D), bf, s1), sds((n_pages, H, ps, D), bf, s1),
             sds((n_pages, H, ps, D), bf, s1), sds((B, n_pg), i32, s1),
             sds((B,), i32, s1))

    # the write beside it, at the benchmark cell's 32 slots and 2,080
    # pages, arenas at the lane width as the pool allocates them
    B, n_pages, Dp = 32, 2080, 128
    compile_("kv_page_write B32 H16 D64->128 page16 pool2080",
             pallas_ops.kv_page_write,
             sds((n_pages, H, ps, Dp), bf, s1),
             sds((n_pages, H, ps, Dp), bf, s1),
             sds((B, H, 1, D), bf, s1), sds((B, H, 1, D), bf, s1),
             sds((B,), i32, s1), sds((B,), i32, s1))
    # the attention over those arenas at both buckets: at 1024 a row's
    # walk is up to 64 pages in one program
    for n_pg in (16, 64):
        compile_(f"paged_attention B32 H16 D64->128 page16 n_pg{n_pg}",
                 pallas_ops.paged_attention,
                 sds((B, H, 1, D), bf, s1),
                 sds((n_pages, H, ps, Dp), bf, s1),
                 sds((n_pages, H, ps, Dp), bf, s1),
                 sds((B, n_pg), i32, s1), sds((B,), i32, s1))
    # both at a pass's 64 and 128 virtual rows (a row a fed token, its
    # table row repeated), and the verify pass's 160 at bucket 1024
    for W, n_pg in ((64, 16), (64, 64), (128, 64), (160, 64)):
        compile_(f"kv_page_write + paged_attention W{W} n_pg{n_pg}",
                 lambda kp, vp, kn, vn, wp, wo, q, tb, t:
                 pallas_ops.paged_attention(
                     q, *pallas_ops.kv_page_write(kp, vp, kn, vn, wp, wo),
                     tb, t),
                 sds((n_pages, H, ps, Dp), bf, s1),
                 sds((n_pages, H, ps, Dp), bf, s1),
                 sds((W, H, 1, D), bf, s1), sds((W, H, 1, D), bf, s1),
                 sds((W,), i32, s1), sds((W,), i32, s1),
                 sds((W, H, 1, D), bf, s1), sds((W, n_pg), i32, s1),
                 sds((W,), i32, s1))

    # the latent cache's kernel at the DeepSeek-V2 cell's shapes: 128 heads
    # over rows of 576 in 640 lanes, a bucket of 17,408 (272 pages a row)
    for W in (32, 64):
        compile_(f"paged_latent_attention W{W} H128 576->640 page64 n_pg272",
                 lambda q, lat, tb, t: pallas_ops.paged_latent_attention(
                     q, lat, tb, t, 0.1147, 512),
                 sds((W, 128, 576), bf, s1), sds((4128, 64, 640), bf, s1),
                 sds((W, 272), i32, s1), sds((W,), i32, s1))

    # fused LAMB at BERT-base's flat size (110M f32), fused Adam f32 + bf16
    from mxnet_tpu.parallel.fused_lamb import FusedLamb
    shapes = [(1024, 1024)] * 84 + [(30522, 768), (768,)] * 2
    fl = FusedLamb(shapes, [f32] * len(shapes), [0.01] * len(shapes),
                   0.9, 0.999, 1e-6, True, 1.0, -1.0, -1.0, -1.0)
    flat = sds((fl.total,), f32, s1)
    compile_(f"lamb_pass1 + lamb_pass2, {fl.total / 1e6:.0f}M parameters",
             fl.apply_flat, flat, flat, flat, flat,
             sds((), f32, s1), sds((), f32, s1))
    for dt in (f32, bf):
        w = sds((8 << 20,), dt, s1)
        compile_(f"adam_update {jnp.dtype(dt).name}",
                 lambda w, g, m, v, lr: pallas_ops.fused_update.adam_update(
                     w, g, m, v, lr, wd=0.01),
                 w, w, sds(w.shape, f32, s1), sds(w.shape, f32, s1),
                 sds((), f32, s1))

    # int8 matmul at GPT-2 widths: MLP up-projection, vocabulary head
    for K, O in ((768, 3072), (768, 50257)):
        compile_(f"int8_matmul K{K} -> O{O}", pallas_ops.int8_matmul,
                 sds((8, K), jnp.int8, s1), sds((K, O), jnp.int8, s1),
                 sds((), f32, s1), sds((O,), f32, s1), sds((O,), f32, s1))

    # flash with its operands sharded over the four-device mesh: what jit
    # refuses to partition unless the kernel sits under shard_map
    if len(devices) >= 4:
        for axes in (dict(dp=4), dict(dp=2, tp=2)):
            mesh = parallel.make_mesh(devices=devices[:4], **axes)
            qs = NamedSharding(mesh, P(("dp",), "tp" if "tp" in axes
                                       else None))
            found = compile_(
                f"flash fwd+bwd, mask + dropout, sharded over {axes}", grad,
                *flash_args(qs, NamedSharding(mesh, P(("dp",))),
                            NamedSharding(mesh, P())))
            assert len(found) == 3, "flash kernels missing"


def check_train(devices):
    """The whole BERT-base bf16 LAMB step, as chip_smoke.py trains it, on
    one device and on four."""
    cfg = bert_mod.bert_base_config(dtype="bfloat16")
    b = bert_mod.make_synthetic_batch(cfg, 32, 512, 76, seed=0)
    data = [nd.array(b[k]) for k in
            ("input_ids", "token_types", "valid_length", "masked_positions")]
    labels = [nd.array(b[k]) for k in
              ("mlm_labels", "mlm_weights", "nsp_labels")]
    model = bert_mod.BERTForPretraining(cfg)
    mx.random.seed(0)
    model.initialize()

    # a topology device cannot hold an array: answer every placement with
    # the abstract array lowering needs (shape, dtype, sharding)
    real_put, real_state = jax.device_put, mx.random.get_state
    jax.device_put = lambda x, s=None, **kw: sds(
        tuple(x.shape), x.dtype, s)
    try:
        for n, want in ((1, 5), (4, 3)):
            if len(devices) < n:
                continue
            mesh = parallel.make_mesh(dp=-1, devices=devices[:n])
            key = real_state()
            mx.random.get_state = lambda: sds(
                key.shape, key.dtype, NamedSharding(mesh, P()))
            trainer = parallel.ShardedTrainer(
                model, bert_mod.bert_pretrain_loss, "lamb",
                {"learning_rate": 1e-3, "wd": 0.01})
            t0 = time.perf_counter()
            lowered = trainer.lower_step(data, labels)
            found = pallas_kernels(lowered)
            mem = lowered.compile().memory_analysis()
            print(f"  compiles: BERT-base b32 L512 bf16 LAMB step on "
                  f"{n} device(s)  [{time.perf_counter() - t0:.1f}s]  "
                  f"kernels={found}  temp="
                  f"{mem.temp_size_in_bytes / 2**30:.2f} GiB args="
                  f"{mem.argument_size_in_bytes / 2**30:.2f} GiB", flush=True)
            assert len(found) == want, \
                f"expected {want} distinct kernels in the step, got {found}"
    finally:
        jax.device_put, mx.random.get_state = real_put, real_state


def arena_copies_and_aliases(text, arenas):
    """(lines of the optimised HLO `text` that copy an operand shaped like
    one of `arenas`, parameters the module header aliases to outputs)."""
    shapes = {"[%s]" % ",".join(map(str, a.shape)) for a in arenas}
    copies = [line.strip()[:160] for line in text.splitlines()
              if any(re.search(r" = \w+%s\{[^}]*\} copy\(" % re.escape(sh),
                               line) for sh in shapes)]
    aliased = re.findall(r"\{\d+\}: \(\d+, \{\}, (?:may|must)-alias\)",
                         text.split("\n", 1)[0])
    return copies, aliased


def check_serve(devices):
    """The benchmark's serving cell (chipbench/configs/gpt2-medium-serve.json:
    GPT-2 medium, 32 slots over a pool of 2,080 pages of 16): the bucket-256
    step executables as the server builds them, one a rung of the ladder
    of pass widths (128, 64 and 32 virtual rows). Beyond compiling, each
    must be ONE pass
    that keeps the arenas where they are: both paged kernels once a layer
    (24 calls, in no loop body), every arena parameter aliased to an
    output, and no `copy` of an arena-shaped operand left anywhere. That
    is the counter that says the in-place write engaged."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "gpt2-medium-serve.json")) as f:
        cell = json.load(f)
    model_cfg = dict(cell["model"])
    model_cfg.pop("family")
    cfg = gpt_mod.gpt2_117m_config(**model_cfg)
    mesh = parallel.make_mesh(devices=devices[:1])
    s1 = NamedSharding(mesh, P())
    model = gpt_mod.GPTForCausalLM(cfg)
    mx.random.seed(0)
    model.initialize()
    n_arenas = 2 * cfg["num_layers"]
    bucket = min(cell["server"]["buckets"])

    srv = serve.Server(model, **cell["server"])
    for width in reversed(srv._rungs):
        run, avals = srv._runner(bucket, width), \
            srv._step_avals(bucket, width)
        arena = avals[-1][0]
        avals = jax.tree.map(lambda a: sds(a.shape, a.dtype, s1), avals)
        t0 = time.perf_counter()
        lowered = run.lower(*avals)
        found = pallas_kernels(lowered)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        shape = "[%s]" % ",".join(map(str, arena.shape))
        copies, aliased = arena_copies_and_aliases(text, [arena])
        loops = len(re.findall(r" while\(", text))
        print(f"  compiles: GPT-2 medium paged step, bucket {bucket} "
              f"width {width}  [{time.perf_counter() - t0:.1f}s]  "
              f"kernels={found}  loops: {loops}  "
              f"copies of {shape}: {len(copies)}  "
              f"aliased parameters: {len(aliased)}  temp="
              f"{mem.temp_size_in_bytes / 2**30:.2f} GiB args="
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB", flush=True)
        want = {"kv_page_write": cfg["num_layers"],
                "paged_attention": cfg["num_layers"]}
        assert found == want, f"expected {want} in the step, got {found}"
        assert not loops, f"{loops} loops in a step that is one pass"
        assert not copies, \
            f"{len(copies)} arena-shaped copies left, the first: " \
            f"{copies[0]}"
        assert len(aliased) == n_arenas, \
            f"{len(aliased)} of {n_arenas} arenas aliased to outputs"
    srv.stop()


def server_of_shapes(model, server_args, s1):
    """`serve.Server(model, **server_args)` with the model's parameters and
    the pool's arenas as SHAPES: nothing is drawn or placed, `lower` takes
    them as they are."""
    from mxnet_tpu.ndarray import NDArray
    for _, p in model._iter_params():
        p._data = NDArray(sds(p.shape, jnp.dtype(p.dtype), s1))
    real_zeros = jnp.zeros
    jnp.zeros = lambda shape, dtype: sds(shape, jnp.dtype(dtype), s1)
    try:
        return serve.Server(model, **server_args)
    finally:
        jnp.zeros = real_zeros


def check_serve_glm(devices):
    """The benchmark's GLM-5 cell (chipbench/configs/glm-5-serve-ep16.json:
    4.7 B parameters in bf16, 32 slots, one bucket of 6,272 over a pool of
    2,080 pages of 64): one executable a rung of the ladder of pass
    widths (128, 64 and 32 virtual rows) as the server builds them, from
    abstract parameters and arenas (nothing is
    drawn or placed; `lower` takes shapes). Each must fit the chip and
    keep its twelve arenas where they are: every arena aliased to an
    output, no `copy` of an arena-shaped operand."""
    from chipbench.kinds import serve_agent
    from mxnet_tpu.models import glm

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "glm-5-serve-ep16.json")) as f:
        cell = json.load(f)
    cfg = serve_agent.model_config(cell)
    mesh = parallel.make_mesh(devices=devices[:1])
    s1 = NamedSharding(mesh, P())
    model = glm.GLMForCausalLM(cfg)
    srv = server_of_shapes(model, cell["server"], s1)
    bucket = cell["server"]["buckets"][0]
    for width in reversed(srv._rungs):
        run, avals = srv._runner(bucket, width), \
            srv._step_avals(bucket, width)
        arenas = avals[-1]
        avals = jax.tree.map(lambda a: sds(a.shape, a.dtype, s1), avals)
        t0 = time.perf_counter()
        compiled = run.lower(*avals).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        copies, aliased = arena_copies_and_aliases(text, arenas)
        loops = len(re.findall(r" while\(", text))
        total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        print(f"  compiles: GLM-5 share ({glm.param_count(cfg) / 1e9:.3f} B "
              f"parameters) paged step, bucket {bucket} width {width}  "
              f"[{time.perf_counter() - t0:.1f}s]  loops: {loops}  "
              f"arena copies: "
              f"{len(copies)}  aliased parameters: {len(aliased)}  temp="
              f"{mem.temp_size_in_bytes / 2**30:.2f} GiB args="
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB", flush=True)
        # (XLA's own loops, the selection's top-k among them, are counted
        # for the reader; the pass itself is traced without one)
        assert not copies, f"arena-shaped copies left: {copies[0]}"
        assert len(aliased) == len(arenas), \
            f"{len(aliased)} of {len(arenas)} arenas aliased to outputs"
        assert total < 14 * 2**30, \
            f"{total / 2**30:.2f} GiB leaves no room on a 16 GB chip"
    srv.stop()


def check_serve_laguna(devices):
    """The benchmark's Laguna cell (chipbench/configs/
    laguna-xs2-serve-pp8.json: 3.87 B parameters in bf16, 32 slots, one
    bucket of 12,800 over a full class of 6,400 pages of 64 and a window
    class of 320): one executable a rung of the ladder of pass widths (256
    to 32 virtual rows) as the server builds them, from abstract
    parameters and arenas. Each
    must hold both paged kernels once a layer, keep its ten arenas of two
    page counts where they are (aliased, no arena-shaped copy) and fit the
    chip."""
    from chipbench.kinds import serve_mixed
    from mxnet_tpu.models import laguna

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "laguna-xs2-serve-pp8.json")) as f:
        cell = json.load(f)
    cfg = serve_mixed.model_config(cell)
    mesh = parallel.make_mesh(devices=devices[:1])
    s1 = NamedSharding(mesh, P())
    model = laguna.LagunaForCausalLM(cfg)
    srv = server_of_shapes(model, cell["server"], s1)
    bucket = cell["server"]["buckets"][0]
    n_l = cfg["num_hidden_layers"]
    for width in reversed(srv._rungs):
        run, avals = srv._runner(bucket, width), \
            srv._step_avals(bucket, width)
        arenas = avals[-1]
        avals = jax.tree.map(lambda a: sds(a.shape, a.dtype, s1), avals)
        t0 = time.perf_counter()
        lowered = run.lower(*avals)
        found = pallas_kernels(lowered)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        copies, aliased = arena_copies_and_aliases(text, arenas)
        total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        print(f"  compiles: Laguna stage ({laguna.param_count(cfg) / 1e9:.3f}"
              f" B parameters) paged step, bucket {bucket} width {width}  "
              f"[{time.perf_counter() - t0:.1f}s]  kernels={found}  "
              f"arena copies: {len(copies)}  aliased parameters: "
              f"{len(aliased)}  temp={mem.temp_size_in_bytes / 2**30:.2f} "
              f"GiB args={mem.argument_size_in_bytes / 2**30:.2f} GiB",
              flush=True)
        want = {"kv_page_write": n_l, "paged_attention": n_l}
        assert found == want, f"expected {want} in the step, got {found}"
        assert not copies, f"arena-shaped copies left: {copies[0]}"
        assert len(aliased) == len(arenas), \
            f"{len(aliased)} of {len(arenas)} arenas aliased to outputs"
        assert total < 14 * 2**30, \
            f"{total / 2**30:.2f} GiB leaves no room on a 16 GB chip"
    srv.stop()


def check_serve_deepseek(devices):
    """The benchmark's DeepSeek-V2 cell (chipbench/configs/
    deepseek-v2-serve-ep8.json: 3.81 B parameters in bf16, 32 slots, one
    bucket of 17,408 over a pool of 4,128 pages of 64): one executable a
    rung of the ladder of pass widths (256 to 32 virtual rows) as the
    server builds them, from abstract parameters and arenas. Each must hold
    the paged
    latent-attention kernel once a layer and NO gather of the bucket (a
    latent row has no head axis: `paged_attention` cannot read it, and the
    fallback would gather 17,408 rows for every virtual row), keep its six
    latent arenas where they are (aliased, no arena-shaped copy) and fit
    the chip."""
    from chipbench.kinds import serve_docs
    from mxnet_tpu.models import deepseek

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench", "configs",
                           "deepseek-v2-serve-ep8.json")) as f:
        cell = json.load(f)
    cfg = serve_docs.model_config(cell)
    mesh = parallel.make_mesh(devices=devices[:1])
    s1 = NamedSharding(mesh, P())
    model = deepseek.DeepseekForCausalLM(cfg)
    srv = server_of_shapes(model, cell["server"], s1)
    bucket = cell["server"]["buckets"][0]
    n_l = cfg["num_hidden_layers"]
    for width in reversed(srv._rungs):
        run, avals = srv._runner(bucket, width), \
            srv._step_avals(bucket, width)
        arenas = avals[-1]
        avals = jax.tree.map(lambda a: sds(a.shape, a.dtype, s1), avals)
        t0 = time.perf_counter()
        lowered = run.lower(*avals)
        found = pallas_kernels(lowered)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        copies, aliased = arena_copies_and_aliases(text, arenas)
        # the fallback's gather: (width, bucket, 640) rows of the arena
        gathered = re.findall(r"\[%d,%d,\d+\]" % (width, bucket), text)
        total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        print(f"  compiles: DeepSeek-V2 share "
              f"({deepseek.param_count(cfg) / 1e9:.3f} B parameters) paged "
              f"step, bucket {bucket} width {width}  "
              f"[{time.perf_counter() - t0:.1f}s]  kernels={found}  "
              f"arena copies: {len(copies)}  aliased parameters: "
              f"{len(aliased)}  bucket-wide gathers: {len(gathered)}  temp="
              f"{mem.temp_size_in_bytes / 2**30:.2f} GiB args="
              f"{mem.argument_size_in_bytes / 2**30:.2f} GiB", flush=True)
        want = {"paged_latent_attention": n_l}
        assert found == want, f"expected {want} in the step, got {found}"
        assert not gathered, f"a gather of the bucket: {gathered[0]}"
        assert not copies, f"arena-shaped copies left: {copies[0]}"
        assert len(aliased) == len(arenas) == n_l, \
            f"{len(aliased)} of {len(arenas)} arenas aliased to outputs"
        assert total < 14 * 2**30, \
            f"{total / 2**30:.2f} GiB leaves no room on a 16 GB chip"
    srv.stop()


def main():
    which = sys.argv[1:] or ["kernels", "train", "serve", "serve_glm",
                             "serve_laguna", "serve_deepseek"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=TOPOLOGY)
    devices = list(topo.devices)
    print(f"jax {jax.__version__}; compile-only topology {TOPOLOGY}: "
          f"{len(devices)} x {devices[0].device_kind!r}")
    # the two places the package asks what it runs on
    _common.pallas_available = lambda: True
    config.set("prng", "rbg")
    if os.environ.get("MXNET_TPU_PALLAS_INTERPRET") == "1":
        sys.exit("unset MXNET_TPU_PALLAS_INTERPRET: the interpreter "
                 "compiles nothing for the TPU")
    for name in which:
        print(f"== {name} ==")
        {"kernels": check_kernels, "train": check_train,
         "serve": check_serve, "serve_glm": check_serve_glm,
         "serve_laguna": check_serve_laguna,
         "serve_deepseek": check_serve_deepseek}[name](devices)
    print("aot_check: everything compiled for", devices[0].device_kind)


if __name__ == "__main__":
    main()
