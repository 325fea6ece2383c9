"""The DeepSeek-V2 decoder (models/deepseek.py) through serve.Server and
the page pool, at a small size on the CPU, against the plain reference
(chipbench/reference/deepseek_v2.py): chunked prefill, decoding through the
pool, a prefix-tree hit; the paged latent-attention kernel through the
interpreter against its fallback; the router's group limit; the share of
the experts; what the shared router still gives the other models; the
parameter count."""
import functools
import hashlib
import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from chipbench.reference import deepseek_v2 as reference  # noqa: E402
from chipbench.reference.glm5 import relative_errors  # noqa: E402
from mxnet_tpu import config, pages, parallel, serve  # noqa: E402
from mxnet_tpu.models import deepseek, glm  # noqa: E402
from mxnet_tpu.parallel import moe  # noqa: E402

pla = importlib.import_module("mxnet_tpu.pallas_ops.paged_latent_attention")

# (prompt, new): page 4, chunks of 8, YaRN's blend from position 16 on
LENGTHS = [(37, 20), (5, 30), (50, 9), (13, 24), (29, 11)]
PAGE = 4
ROPE_DIGEST = "3ee8d61643a6b7f0"      # glm.rope_interleaved on the parent


@pytest.fixture(autouse=True)
def _clean():
    from mxnet_tpu.parallel import mesh as mesh_mod
    before = mesh_mod._current["mesh"]
    yield
    serve.disable()
    config.reset()
    mesh_mod.set_mesh(before)


@functools.lru_cache(maxsize=None)      # weights are read, never written
def tiny(**keys):
    cfg = deepseek.deepseek_tiny_config(**keys)
    model = deepseek.DeepseekForCausalLM(cfg)
    mx.random.seed(5)
    model.initialize()
    return model, cfg


def server(model, **kw):
    parallel.make_mesh(devices=jax.devices()[:1])
    args = dict(slots=4, page_size=PAGE, buckets=[96], pool_pages=96,
                prefill_chunk=8)
    args.update(kw)
    return serve.Server(model, **args)


def expected(req, model, cfg, **kw):
    layers, top = model.layer_weights()
    seq = np.concatenate([req.prompt, req.tokens[:-1]])
    keys = kw.pop("keys", {})
    return np.asarray(reference.forward(
        seq, layers, top, dict(cfg, **keys), cfg["first_expert"],
        logits_from=req.prompt.size - 1, block=16, **kw))


def served(model, lengths=LENGTHS, **kw):
    srv = server(model, **kw)
    rng = np.random.RandomState(0)
    reqs = [srv.submit(rng.randint(0, 96, (n,)), max_new_tokens=m,
                       keep_logits=True) for n, m in lengths]
    srv.drain()
    assert all(r.state == serve.DONE for r in reqs), reqs
    return srv, reqs


# ---------------------------------------------------------------------------
# served logits against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernels", ["interpreter", "fallback"])
def test_served_logits_agree_with_the_reference(monkeypatch, kernels):
    """Prefill in chunks of 8, then decode through the pool, under load
    (five requests over four slots): every generated position's logits row
    is the reference's full forward pass with expanded heads and the
    public form of RoPE, through the Pallas kernel (interpreter) and
    through the XLA fallback."""
    if kernels == "interpreter":
        monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    model, cfg = tiny()
    srv, reqs = served(model)
    st = srv.stats()
    assert srv._rungs == (4, 8, 16)
    assert st["executables"] == len(srv._rungs)
    assert st["attn_sel_tokens"] == st["attn_ctx_tokens"] > 0
    assert st["sparse_tokens"] == 0
    for req in reqs:
        got, want = np.stack(req.logits), expected(req, model, cfg)
        assert got.shape == want.shape == (req.max_new_tokens, 96)
        assert relative_errors(got, want).max() < 1e-4
    srv.stop()


def test_a_tree_hit_serves_the_logits_of_a_full_prefill():
    """A request whose first 32 tokens (a document of whole pages) come
    from the tree gets the logits of the reference's full forward pass,
    and the pool drains to zero references."""
    model, cfg = tiny()
    rng = np.random.RandomState(2)
    doc = rng.randint(0, cfg["vocab_size"], (32,))
    first = np.concatenate([doc, rng.randint(0, cfg["vocab_size"], (3,))])
    second = np.concatenate([doc, rng.randint(0, cfg["vocab_size"], (6,))])
    srv = server(model)
    srv.submit(first, max_new_tokens=2)
    srv.drain()
    hit = srv.submit(second, max_new_tokens=12, keep_logits=True)
    srv.drain()
    st = srv.stats()
    assert st["prefix_hits"] == 1 and st["prefix_tokens"] == 32
    pool = srv._pool
    srv.stop()
    assert int(pool.refcount.sum()) == 0
    want = expected(hit, model, cfg)
    assert relative_errors(np.stack(hit.logits), want).max() < 1e-4


def test_reference_controls_fail_the_served_logits():
    """The comparison is no invariant check: the reference with mscale^2
    left out of the softmax scale, with the plain RoPE frequencies, with
    the group limit ignored or with the gates unscaled is far from what
    was served."""
    model, cfg = tiny()
    srv, reqs = served(model, lengths=LENGTHS[:1])
    srv.stop()
    req = reqs[0]
    got = np.stack(req.logits)
    assert relative_errors(got, expected(req, model, cfg)).max() < 1e-4
    for kw in ({"softmax_mscale": False}, {"yarn": False},
               {"keys": {"n_group": 1, "topk_group": 1}},
               {"keys": {"routed_scaling_factor": 1.0}}):
        err = relative_errors(got, expected(req, model, cfg, **kw))
        assert np.median(err) > 1e-2, (kw, np.median(err))


# ---------------------------------------------------------------------------
# the kernel through the interpreter against its fallback
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wave_bytes", [1280 * 1024, 3 * 4 * 128 * 4,
                                        4 * 128 * 4])
def test_kernel_against_its_fallback(monkeypatch, wave_bytes):
    """One program a virtual row, at waves of every page of the table, three and one:
    rows of one request at consecutive positions through the same table
    row, a row at position 0, a row that fills its table, and a padding
    row between live ones, which walks nothing and comes back as zeros."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(pla, "_WAVE_BYTES", wave_bytes)
    parallel.make_mesh(devices=jax.devices()[:1])
    rng = np.random.RandomState(0)
    P, ps, W, H, R, B, n_pg = 40, 4, 24, 6, 16, 8, 8
    lat = jnp.asarray(rng.randn(P, ps, W), jnp.float32)
    tables = rng.randint(0, P, (B, n_pg))
    tables[2] = tables[3] = tables[1]
    tables = jnp.asarray(tables, jnp.int32)
    t = jnp.asarray([5, 17, 18, 19, -1, 31, 0, -1], jnp.int32)
    q = jnp.asarray(rng.randn(B, H, W), jnp.float32)
    got = np.asarray(pla.paged_latent_attention(q, lat, tables, t, 0.3, R))
    want = np.asarray(pla.paged_latent_attention_reference(
        q, lat, tables, t, 0.3, R))
    live = np.asarray(t) >= 0
    assert got.shape == (B, H, R)
    assert np.abs(got[live] - want[live]).max() < 1e-5
    assert not got[~live].any()
    # the lanes of a row past the query's width are ignored by both
    narrow = np.asarray(pla.paged_latent_attention(
        q[..., :20], lat, tables, t, 0.3, R))
    want = np.asarray(pla.paged_latent_attention_reference(
        q[..., :20], lat, tables, t, 0.3, R))
    assert np.abs(narrow[live] - want[live]).max() < 1e-5


def test_kernel_is_off_under_a_mesh_of_several_devices(monkeypatch):
    """A global-view pallas_call has no partitioning rule: under a mesh of
    several devices the fallback runs, whatever the interpreter flag."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    parallel.make_mesh(dp=-1)
    called = []
    monkeypatch.setattr(pla, "_paged_call",
                        lambda *a, **k: called.append(1))
    lat = jnp.ones((6, 4, 24))
    out = pla.paged_latent_attention(
        jnp.ones((2, 3, 24)), lat, jnp.zeros((2, 2), jnp.int32),
        jnp.asarray([3, 5]), 1.0, 16)
    assert not called and out.shape == (2, 3, 16)


# ---------------------------------------------------------------------------
# the router and the share of the experts
# ---------------------------------------------------------------------------

def _digest(*arrays):
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("which,want", [
    ("glm", "09a521f16354d639"), ("laguna", "3d597ff4803ae742"),
    ("glm_bf16_jit", "4275131c721df058")])
def test_todays_arguments_route_as_they_did(which, want):
    """`moe_topk_route` as GLM-5 and Laguna call it (sigmoid, one group,
    a selection bias or zeros, normalised, times 2.5) gives the experts
    and gates it gave before it learned softmax and groups, bit for bit:
    the digests were taken on the parent commit."""
    k = jax.random.key(7)
    x = jax.random.normal(jax.random.fold_in(k, 0), (48, 64), jnp.float32)
    rw = jax.random.normal(jax.random.fold_in(k, 1), (64, 256),
                           jnp.float32) * 0.125
    bias = jax.random.normal(jax.random.fold_in(k, 2), (256,),
                             jnp.float32) * 0.05
    if which == "glm":
        out = moe.moe_topk_route(x, rw, bias, 8, 2.5, True)
    elif which == "laguna":
        out = moe.moe_topk_route(x, rw, jnp.zeros((256,), jnp.float32), 8,
                                 2.5, True)
    else:
        out = jax.jit(lambda x: moe.moe_topk_route(
            x.astype(jnp.bfloat16), rw, bias, 8, 2.5, True))(x)
    assert _digest(*out) == want


def test_group_limited_route_is_the_reference_s():
    """Softmax scores, the 3 best of 8 groups by their best expert, the 6
    best experts inside them, gates the scores times 16: the reference's
    choice and gates; every pick lies in at most 3 groups, and the limit
    changes the choice of some tokens."""
    cfg = dict(deepseek.DEEPSEEK_V2_PUBLISHED)
    k = jax.random.key(3)
    v = jax.random.normal(jax.random.fold_in(k, 0), (64, 32))
    rw = jax.random.normal(jax.random.fold_in(k, 1), (32, 160)) * 0.3
    expert, gate = moe.moe_topk_route(
        v, rw, None, 6, 16.0, False, scoring="softmax", n_group=8,
        topk_group=3)
    chosen, want = reference.route(v, rw, reference.signature(cfg))
    np.testing.assert_array_equal(np.asarray(expert), np.asarray(chosen))
    assert np.allclose(gate, want, rtol=1e-6)
    assert max(len(set(row // 20)) for row in np.asarray(expert)) <= 3
    free, _ = moe.moe_topk_route(v, rw, None, 6, 16.0, False,
                                 scoring="softmax")
    assert (np.sort(free, -1) != np.sort(expert, -1)).any()
    p = jax.nn.softmax(v @ rw, -1)
    assert np.allclose(gate, 16.0 * np.take_along_axis(
        np.asarray(p), np.asarray(expert), -1), rtol=1e-5)


def test_the_eight_groups_parts_add_up_to_the_uncut_layer():
    """The share test: eight chips, a group of experts each. The parts
    their `moe_share_ffn` give, plus the shared experts counted once, are
    the reference's uncut layer; the reference given one share equals the
    program's part for it; one share alone is not the layer."""
    model, cfg = tiny(experts_held=16)
    w = model.layers[1].weights()
    v = jax.random.normal(jax.random.key(1), (24, cfg["hidden_size"]))
    rnd = reference._rounder(23)
    sig = reference.signature(cfg)
    whole = reference.experts(v, w, sig, rnd)
    shared = reference.swiglu(v, w["shared_gate_proj"], w["shared_up_proj"],
                              w["shared_down_proj"], rnd)
    expert, gate = moe.moe_topk_route(
        v, w["router"], None, cfg["num_experts_per_tok"],
        cfg["routed_scaling_factor"], False, scoring="softmax",
        n_group=cfg["n_group"], topk_group=cfg["topk_group"])
    assert expert.shape == (24, 4)
    total = shared
    size = cfg["n_routed_experts"] // cfg["n_group"]
    for first in range(0, cfg["n_routed_experts"], size):
        held = slice(first, first + size)
        part = moe.moe_share_ffn(
            v, expert, gate, w["experts_gate_proj"][held],
            w["experts_up_proj"][held], w["experts_down_proj"][held], first)
        one = {k: (a[held] if k.startswith("experts_") else a)
               for k, a in w.items()}
        assert np.allclose(part, reference.experts(
            v, one, reference.signature(cfg, first_expert=first), rnd,
            shared=False), atol=1e-5)
        total = total + part
    assert np.allclose(total, whole, atol=2e-5)
    assert not np.allclose(shared + part, whole, atol=1e-3)


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

def test_param_count_is_the_published_size_and_the_cut_s():
    """235.7 B as published; the benchmark's cut (6 layers, 20 experts
    held, an eighth of the vocabulary) 3,814.6 M; and `param_count` counts
    what the model builds."""
    assert round(deepseek.param_count(deepseek.deepseek_v2_config()) / 1e8) \
        == 2357
    cut = deepseek.deepseek_v2_config(
        num_hidden_layers=6, experts_held=20, vocab_size=12800)
    assert round(deepseek.param_count(cut) / 1e5) == 38146
    model, cfg = tiny()
    built = sum(int(np.prod(p.shape)) for _, p in model._iter_params())
    assert built == deepseek.param_count(cfg)


def test_yarn_changes_the_frequencies_and_the_scale():
    """factor 40 over 4,096 positions: the fastest pairs keep theta^(-2i
    /d), the slowest are divided by 40, cos and sin are not scaled, and
    the softmax scale is 192^-0.5 times 1.2608^2."""
    inv, on_rope, scale = deepseek.rope_and_scale(
        deepseek.deepseek_v2_config())
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    assert inv.shape == (32,) and on_rope == 1.0
    assert np.allclose(inv[:8], plain[:8])
    assert np.allclose(inv[-4:], plain[-4:] / 40)
    m = 0.1 * 0.707 * np.log(40) + 1
    assert abs(m - 1.2608) < 1e-4 and abs(scale - 192 ** -0.5 * m * m) < 1e-9
    freqs, ref_scale = reference.rope_table(deepseek.DEEPSEEK_V2_PUBLISHED)
    assert np.allclose(freqs, inv, rtol=1e-6) and ref_scale == 1.0


def test_rope_pairs_is_glm_s_rotation():
    """`glm.rope_interleaved` is `rope_pairs` at the default frequencies,
    bit for bit what it computed before the two were split."""
    x = jax.random.normal(jax.random.key(0), (5, 3, 8))
    pos = jnp.asarray([0, 1, 7, 100, 4000])
    inv = 1e6 ** (-jnp.arange(0, 8, 2, dtype=jnp.float32) / 8)
    np.testing.assert_array_equal(
        np.asarray(glm.rope_interleaved(x, pos, 1e6)),
        np.asarray(glm.rope_pairs(x, pos, inv)))
    assert _digest(glm.rope_interleaved(x, pos, 1e6)) == ROPE_DIGEST



def test_the_model_is_served_through_the_pool_only():
    model, _ = tiny()
    with pytest.raises(NotImplementedError):
        model.forward(None)
    spec = model.serving_spec()
    assert spec.index_topk is None and spec.draft_step is None
    assert spec.streams == [(24, jnp.dtype("float32"))] * 3
    assert spec.windows is None


def test_tree_evicts_least_recently_used_leaves_first():
    """A pool under pressure gives back the tree's pages leaf by leaf, the
    least recently used first, a parent only after its children."""
    pool = pages.PagePool(4, 12, 0, {"target": [(8, jnp.float32)]})
    tree = pages.PrefixTree(pool)
    docs = [np.arange(12) + 100 * d for d in range(3)]
    for d in docs:
        owned = pool.alloc(3)
        tree.insert(d, owned)
        for p in owned:
            pool.decref(p)
    assert pool.free_pages() == 3
    got, _ = tree.match(docs[0])         # document 0 is now the newest
    for p in got:
        pool.decref(p)
    assert tree.evict(8) == 5 and pool.free_pages() == 8
    assert tree.match(docs[1])[1] == 0          # gone whole
    assert tree.match(docs[2])[1] == 4          # its last two pages went
    assert tree.match(docs[0])[1] == 12         # untouched
