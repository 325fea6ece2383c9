"""The Laguna-style decoder (models/laguna.py) through serve.Server and a
page pool with two page classes, at a small size on the CPU, against the
plain reference (chipbench/reference/laguna.py): prompts several windows
long fed in chunks, then decoding through both classes; the paged-attention
kernel with grouped queries and a window; when the pool returns a window
class's pages; what a one-class model keeps; the parameter count."""
import functools
import hashlib
import importlib
import json
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
from chipbench.reference import laguna as reference  # noqa: E402
from chipbench.reference.glm5 import relative_errors  # noqa: E402
from mxnet_tpu import config, pages, parallel, serve  # noqa: E402
from mxnet_tpu.models import gpt as gpt_mod  # noqa: E402
from mxnet_tpu.models import laguna  # noqa: E402

pa = importlib.import_module("mxnet_tpu.pallas_ops.paged_attention")

# (prompt, new): window 12, page 4, chunks of 8: prompts of up to four
# windows, every request crossing page and window boundaries
LENGTHS = [(37, 20), (5, 30), (50, 9), (13, 40), (29, 11)]
WINDOW, PAGE = 12, 4


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
    cfg = laguna.laguna_tiny_config(**keys)
    model = laguna.LagunaForCausalLM(cfg)
    mx.random.seed(3)
    model.initialize()
    return model, cfg


def server(model, **kw):
    parallel.make_mesh(devices=jax.devices()[:1])
    args = dict(slots=4, page_size=PAGE, buckets=[96], pool_pages=96,
                prefill_chunk=8)
    args.update(kw)
    return serve.Server(model, **args)


def served(model, lengths=LENGTHS, **kw):
    srv = server(model, **kw)
    rng = np.random.RandomState(0)
    reqs = [srv.submit(rng.randint(0, 96, (n,)), max_new_tokens=m,
                       keep_logits=True) for n, m in lengths]
    srv.drain()
    assert all(r.state == serve.DONE for r in reqs), reqs
    return srv, reqs


def expected(req, model, cfg, **kw):
    layers, top = model.layer_weights()
    seq = np.concatenate([req.prompt, req.tokens[:-1]])
    return np.asarray(reference.forward(
        seq, layers, top, cfg, logits_from=req.prompt.size - 1, block=16,
        **kw))


# ---------------------------------------------------------------------------
# served logits against the reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernels", ["interpreter", "fallback"])
def test_served_logits_agree_with_the_reference(monkeypatch, kernels):
    """Prefill in chunks of 8, then decode, through both page classes,
    under load (five requests over four slots): every generated position's
    logits row is the reference's full forward pass, through the Pallas
    kernels (interpreter) and through the XLA fallback."""
    if kernels == "interpreter":
        monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    model, cfg = tiny()
    srv, reqs = served(model)
    assert srv._rungs == (4, 8, 16)
    assert srv.stats()["executables"] == len(srv._rungs)
    for req in reqs:
        got, want = np.stack(req.logits), expected(req, model, cfg)
        assert got.shape == want.shape == (req.max_new_tokens, 96)
        assert relative_errors(got, want).max() < 1e-5
    srv.stop()


def test_only_an_audited_request_brings_logits_to_the_host():
    """Greedy requests of which one is audited emit what they emit when
    all are (`served`), several passes a step included, and only the
    audited request's rows are copied to the host."""
    model, _ = tiny()
    srv, every = served(model)
    srv.stop()
    srv = server(model)
    rng = np.random.RandomState(0)
    one = [srv.submit(rng.randint(0, 96, (n,)), max_new_tokens=m,
                      keep_logits=i == 3) for i, (n, m) in enumerate(LENGTHS)]
    srv.drain()
    st = srv.stats()
    srv.stop()
    assert [r.tokens for r in one] == [r.tokens for r in every]
    assert [r.logits is not None for r in one] == [i == 3 for i in range(5)]
    np.testing.assert_array_equal(np.stack(one[3].logits),
                                  np.stack(every[3].logits))
    assert all(int(row.argmax()) == t
               for row, t in zip(one[3].logits, one[3].tokens))
    new = LENGTHS[3][1]
    assert st["logit_rows_fetched"] == new
    assert st["rows_sampled_on_device"] == st["tokens"]
    assert st["fetched_bytes"] == 4 * 4 * st["chunk_dispatches"] + 4 * 96 * new


def test_reference_controls_fail_the_served_logits():
    """The comparison is no invariant check: the reference with the window
    ignored, with the queries grouped wrongly (h % Hkv), or with the full
    layers' RoPE over the whole head is far from what was served, and a
    model built with ONE head count for every layer is another model."""
    model, cfg = tiny()
    srv, reqs = served(model, lengths=LENGTHS[:2])
    full = cfg["rope_parameters"]["full_attention"]
    wrong_rope = dict(cfg["rope_parameters"], full_attention=dict(
        full, partial_rotary_factor=1))
    for req in reqs:
        got = np.stack(req.logits)
        for kw in ({"cfg": dict(cfg, sliding_window=10 ** 6)},
                   {"cfg": cfg, "group_interleaved": True},
                   {"cfg": dict(cfg, rope_parameters=wrong_rope)}):
            want = expected(req, model, kw.pop("cfg"), **kw)
            assert relative_errors(got, want).max() > 0.2, kw
    srv.stop()
    # the per-layer head count is in the weights' shapes
    heads = {layer.heads for layer in model.layers}
    assert heads == {6, 8}
    assert model.layers[0].w_q.shape == (64, 6 * 16)
    assert model.layers[1].w_q.shape == (64, 8 * 16)
    assert model.layers[1].w_g.shape == (64, 8)
    assert [layer.window for layer in model.layers] \
        == [None, 12, 12, 12, None]


def test_a_request_served_alone_gets_the_same_logits():
    model, cfg = tiny()
    _, crowd = served(model)
    _, alone = served(model, lengths=LENGTHS[2:3])
    # request 2 of the crowd drew other ids (the rng moved on): compare
    # each with the reference instead, and the alone one bit for bit with
    # a second run of itself
    _, again = served(model, lengths=LENGTHS[2:3])
    assert np.array_equal(np.stack(alone[0].logits),
                          np.stack(again[0].logits))
    for req in (crowd[2], alone[0]):
        assert relative_errors(np.stack(req.logits),
                               expected(req, model, cfg)).max() < 1e-5


# ---------------------------------------------------------------------------
# the kernel: grouped queries, a window
# ---------------------------------------------------------------------------

def _paged_case(B, Hq, Hkv, D, ps, n_pg, dtype, seed=0):
    rng = np.random.RandomState(seed)
    P = B * n_pg + B
    q = jnp.asarray(rng.randn(B, Hq, 1, D), dtype)
    k = jnp.asarray(rng.randn(P, Hkv, ps, D), dtype)
    v = jnp.asarray(rng.randn(P, Hkv, ps, D), dtype)
    tables = jnp.asarray(
        rng.permutation(P)[:B * n_pg].reshape(B, n_pg), jnp.int32)
    t = np.linspace(0, n_pg * ps - 1, B).astype(np.int32)
    t[1] = -1                                        # a padding row
    return q, k, v, tables, jnp.asarray(t)


@pytest.mark.parametrize("Hq,Hkv,window,wave_pages,dtype", [
    (12, 4, None, None, jnp.float32),   # groups of 3, everything
    (12, 4, 20, None, jnp.float32),     # and a window of 2.5 pages
    (8, 2, 20, 3, jnp.float32),         # waves of 3 pages, window mid-wave
    (6, 2, 9, 2, jnp.float32),
    (8, 8, 17, 2, jnp.float32),         # a window without groups
    (12, 4, 20, None, jnp.bfloat16),
])
def test_paged_attention_groups_and_window(monkeypatch, Hq, Hkv, window,
                                           wave_pages, dtype):
    """Interpreter against `paged_attention_reference`, and the reference
    against a plain dense computation."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    parallel.make_mesh(devices=jax.devices()[:1])
    ps, n_pg = 8, 12
    q, k, v, tables, t = _paged_case(8, Hq, Hkv, 16, ps, n_pg, dtype)
    if wave_pages:
        monkeypatch.setattr(
            pa, "_WAVE_BYTES", wave_pages * Hkv * ps * 128 * 4)
    jaxpr = str(jax.make_jaxpr(lambda *a: pa.paged_attention(*a, window))(
        q, k, v, tables, t))
    assert "pallas_call" in jaxpr           # the kernel, not the fallback
    got = np.asarray(pa.paged_attention(q, k, v, tables, t, window),
                     np.float32)
    ref = np.asarray(pa.paged_attention_reference(q, k, v, tables, t, window),
                     np.float32)
    live = np.asarray(t) >= 0
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[live], ref[live], rtol=tol, atol=tol)
    assert not got[~live].any()             # padding rows: zeros
    # the reference itself, row by row, in numpy
    kd = np.asarray(k, np.float64)[np.asarray(tables)]  # (B,n_pg,Hkv,ps,D)
    vd = np.asarray(v, np.float64)[np.asarray(tables)]
    for b in np.flatnonzero(live):
        tb = int(t[b])
        lo = 0 if window is None else max(0, tb - window + 1)
        keys = kd[b].transpose(1, 0, 2, 3).reshape(Hkv, n_pg * ps, -1)
        vals = vd[b].transpose(1, 0, 2, 3).reshape(Hkv, n_pg * ps, -1)
        for h in range(Hq):
            kv = h // (Hq // Hkv)
            s = keys[kv, lo:tb + 1] @ np.asarray(q, np.float64)[b, h, 0] / 4.0
            p = np.exp(s - s.max())
            want = (p / p.sum()) @ vals[kv, lo:tb + 1]
            np.testing.assert_allclose(ref[b, h, 0], want,
                                       rtol=20 * tol, atol=20 * tol)


def test_window_walk_reads_no_page_behind_the_window(monkeypatch):
    """Poison: every table entry behind a row's window names a page of
    NaN, as a freed page may hold anything. The kernel returns the clean
    case's bits; the walk never went there."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    parallel.make_mesh(devices=jax.devices()[:1])
    ps, n_pg, window = 8, 12, 20
    q, k, v, tables, t = _paged_case(8, 8, 2, 16, ps, n_pg, jnp.float32)
    clean = np.asarray(pa.paged_attention(q, k, v, tables, t, window))
    poison = k.shape[0]
    k2 = jnp.concatenate([k, jnp.full_like(k[:1], jnp.nan)])
    v2 = jnp.concatenate([v, jnp.full_like(v[:1], jnp.nan)])
    tb = np.asarray(tables).copy()
    for b, tt in enumerate(np.asarray(t)):
        tb[b, :max(0, tt - window + 1) // ps] = poison
    got = np.asarray(pa.paged_attention(q, k2, v2, jnp.asarray(tb), t,
                                        window))
    assert np.array_equal(got, clean)


@pytest.mark.parametrize("shape,dtype,seed,digest", [
    ((6, 4, 16, 8, 7), jnp.float32, 0, "600ede57c49b82ea"),
    ((5, 2, 64, 16, 5), jnp.bfloat16, 1, "c308dc1b80acbd65"),
])
def test_one_head_a_kv_head_and_no_window_is_the_kernel_it_was(
        monkeypatch, shape, dtype, seed, digest):
    """`Hq == Hkv, window=None`: the output's bits are those of the kernel
    before groups and windows (digests taken from the parent commit's
    kernel on these seeded inputs, through the interpreter)."""
    monkeypatch.setenv("MXNET_TPU_PALLAS_INTERPRET", "1")
    parallel.make_mesh(devices=jax.devices()[:1])
    B, H, D, ps, n_pg = shape
    q, k, v, tables, t = _paged_case(B, H, H, D, ps, n_pg, dtype, seed)
    out = pa.paged_attention(q, k, v, tables, t)
    got = hashlib.sha256(
        np.asarray(out.astype(jnp.float32)).tobytes()).hexdigest()[:16]
    assert got == digest


# ---------------------------------------------------------------------------
# the pool: page classes
# ---------------------------------------------------------------------------

def test_pool_classes_have_their_own_allocators_and_arenas():
    spec = (2, 16, jnp.float32)
    pool = pages.PagePool(4, 20, 3, {"target": [spec] * 4},
                          windows={"target": [None, 12, None, 12]},
                          window_pages={12: 6})
    assert list(pool.windows) == [12]
    win = pool.windows[12]
    assert (pool.num_pages, win.num_pages) == (23, 9)
    assert [a.shape[0] for a in pool.state["target"]] == [23, 9, 23, 9]
    assert pool.class_of(None) is pool and pool.class_of(12) is win
    got = win.alloc(6)
    assert min(got) >= 3 and win.free_pages() == 0
    assert pool.free_pages() == 20          # the other class is untouched
    with pytest.raises(pages.PagesExhausted):
        win.alloc(1)
    for p in got:
        win.decref(p)
    assert win.free_pages() == 6 and not win.refcount.any()
    # a copy on write moves rows of the full class only
    a, b = pool.state["target"][0], pool.state["target"][1]
    src = pool.alloc(1)[0]
    dst = pool.copy_page(src)
    assert pool.state["target"][1] is b and pool.state["target"][0] is not a
    assert dst != src


def test_window_pages_go_back_exactly_when_their_last_row_leaves(monkeypatch):
    """After every pass, for every seated request at position `pos` (its
    next row): the window class holds exactly the table entries from
    `(pos - window + 1) // page` to `(pos - 1) // page`, no page behind
    them and none missing; before a pass, every page a row of the pass
    reads or writes is held. Refcounts return to zero after `drain`."""
    model, _ = tiny()
    srv = server(model)
    win = srv._pool.windows[WINDOW]
    seen = {"passes": 0, "freed": 0}
    real_dispatch = serve.Server._dispatch

    def checked(self, grp, run, lead, tag):
        # `lead` = (toks, pos, slot, last, tables): what the pass reads
        _, pos, slot, _, _ = lead
        for p, i in zip(pos, slot):
            if p < 0:
                continue
            lo, hi = grp.wlo[WINDOW][i], grp.whi[WINDOW][i]
            first = max(p - WINDOW + 1, 0) // PAGE
            assert lo <= first and p // PAGE < hi, (p, lo, hi)
            row = grp.wtables[WINDOW][i]
            assert all(win.refcount[q] == 1 for q in row[lo:hi])
        seen["passes"] += 1
        return real_dispatch(self, grp, run, lead, tag)

    monkeypatch.setattr(serve.Server, "_dispatch", checked)
    rng = np.random.RandomState(1)
    for n, m in LENGTHS:
        srv.submit(rng.randint(0, 96, (n,)), max_new_tokens=m)
    held_most = 0
    while srv.busy():
        srv.step()
        for grp in srv._groups.values():
            for i in grp.active():
                pos = grp.pos[i]
                lo, hi = grp.wlo[WINDOW][i], grp.whi[WINDOW][i]
                assert lo == max(pos - WINDOW + 1, 0) // PAGE
                assert hi == (pos - 1) // PAGE + 1
                row = grp.wtables[WINDOW][i]
                assert not row[:lo].any() and not row[hi:].any()
                assert len(set(row[lo:hi])) == hi - lo
                held_most = max(held_most, hi - lo)
                # the full class keeps every page of the request
                assert grp.owned[i] == -(-(grp.slots[i].prompt.size
                                           + grp.slots[i].max_new_tokens)
                                         // PAGE)
        in_use = srv.stats()["pages_in_use"]
        assert in_use[f"window{WINDOW}"] == win.used_pages() \
            <= srv._slots * srv._window_need(WINDOW)
    st = srv.stats()
    assert seen["passes"] > 10 and held_most <= srv._window_need(WINDOW)
    # every page the requests ever took in the class came back
    assert st["window_pages_freed"] > 0
    assert win.stats["allocs"] == win.stats["frees"] > st["window_pages_freed"]
    assert not win.refcount.any() and not srv._pool.refcount.any()
    assert win.free_pages() == win.data_pages
    assert srv._pool.free_pages() == srv._pool.data_pages
    srv.stop()


def test_position_counters_cut_contexts_to_the_window():
    model, _ = tiny()
    srv, reqs = served(model, lengths=[(30, 6)])
    st = srv.stats()
    ctx = sum(q + 1 for q in range(30 + 5))
    cut = sum(min(q + 1, WINDOW) for q in range(30 + 5))
    assert (st["attn_tokens"], st["attn_ctx_tokens"],
            st["attn_window_tokens"]) == (35, ctx, cut)
    # positions 0..34: pages 0..5 fell behind position 35 - 12 + 1 = 24
    assert st["window_pages_freed"] == 24 // PAGE
    assert set(st["pages_in_use"]) == {"full", f"window{WINDOW}"}
    srv.stop()


def test_admission_sets_window_pages_aside_per_class():
    """A window class that cannot cover one more request refuses it like
    the full class does: the request waits for a slot's pages, and a
    server with nothing running rejects with the accounting."""
    model, _ = tiny()
    srv = server(model)
    need = srv._window_need(WINDOW)
    assert need == -(-(WINDOW + 8 - 1) // PAGE) + 1 == 6
    assert srv._pool.windows[WINDOW].data_pages == 4 * need
    assert srv._window_need(WINDOW, total=9) == 3      # a short request
    srv._pool.windows[WINDOW].num_pages -= 3 * need     # room for one
    a = srv.submit(np.arange(20), max_new_tokens=4)
    b = srv.submit(np.arange(20), max_new_tokens=4)
    srv.step()
    # one of them runs; the other waits (the ladder may have requeued the
    # first for the second, as it does when the full class is short)
    assert sorted((a.state, b.state)) == [serve.QUEUED, serve.RUNNING]
    assert srv._window_held(WINDOW) == need
    srv.drain()
    assert (a.state, b.state) == (serve.DONE, serve.DONE)
    assert srv._window_held(WINDOW) == 0
    srv.stop()


def test_a_model_with_a_window_class_shares_no_prefix():
    """The rule chosen: with a window class nothing is registered in the
    prefix tree and nothing matched, so no hit can start past rows whose
    window-class pages are gone. Two requests with one prompt prefill
    twice and get the same tokens."""
    model, _ = tiny()
    srv = server(model)
    prompt = np.random.RandomState(5).randint(0, 96, (40,))
    first = srv.submit(prompt, max_new_tokens=6)
    srv.drain()
    second = srv.submit(prompt, max_new_tokens=6)
    srv.drain()
    st = srv.stats()
    assert (st["tree_nodes"], st["prefix_hits"], st["prefix_tokens"]) \
        == (0, 0, 0)
    assert first.tokens == second.tokens
    srv.stop()


def test_a_drafter_beside_window_classes_is_refused():
    model, _ = tiny()
    drafter = gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config())
    drafter.initialize()
    with pytest.raises(ValueError, match="window page classes"):
        server(model, drafter=drafter)


def test_a_one_class_model_keeps_its_pool_tables_and_stats():
    """GPT through the same server: one allocator (the pool itself), 2-D
    page tables, and the `stats()` keys it had before page classes (and
    the three of the fetch, which every model has)."""
    parallel.make_mesh(devices=jax.devices()[:1])
    model = gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config())
    mx.random.seed(0)
    model.initialize()
    assert model.serving_spec().windows is None
    srv = serve.Server(model, slots=2, page_size=4, prefill_chunk=4)
    req = srv.submit(np.arange(9), max_new_tokens=5)
    srv.step()
    (grp,) = srv._groups.values()
    assert srv._pool.windows == {} and grp.wtables == {}
    assert grp.device_tables().shape == (2, grp.n_pg)
    assert len({a.shape[0] for a in srv._pool.state["target"]}) == 1
    srv.drain()
    assert req.state == serve.DONE
    assert set(srv.stats()) == {
        "submitted", "completed", "rejected", "shed", "expired",
        "cancelled", "failed", "tokens", "steps", "requeues", "degraded",
        "retries", "prompt_tokens", "prefix_tokens", "prefix_hits",
        "chunk_dispatches", "chunk_steps", "token_steps", "spec_rounds",
        "rows_dispatched", "rows_fed", "drafts_proposed", "drafts_accepted",
        "rows_sampled_on_device", "logit_rows_fetched", "fetched_bytes",
        "attn_tokens", "attn_ctx_tokens", "attn_sel_tokens", "sparse_tokens",
        "queued", "running", "buckets_allocated", "executables",
        "width_dispatches", "scheduler_steps", "pages", "page_size",
        "pool_pages_total", "pool_pages_free", "tree_nodes",
        "tree_evicted_pages", "cow_copies", "prefix_hit_rate", "accepted_draft_rate", "dispatches"}
    srv.stop()


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------

def test_param_count_is_the_published_size_and_the_file_s_bytes():
    published = laguna.laguna_config()
    assert laguna.param_count(published) / 1e9 == pytest.approx(33.44,
                                                                abs=0.005)
    model, cfg = tiny()
    built = sum(int(np.prod(p.shape)) for _, p in model._iter_params())
    assert laguna.param_count(cfg) == built
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "laguna-xs2-serve-pp8.json")) as f:
        file = json.load(f)
    from chipbench.kinds import serve_mixed
    stage = serve_mixed.model_config(file)
    assert laguna.param_count(stage) / 1e6 == pytest.approx(3869.8, abs=0.1)
    assert "3,869.8 M parameters = 7.74 GB" in file["bytes"]["weights"]
    assert laguna.param_count(stage) * 2 / 1e9 == pytest.approx(7.74,
                                                                abs=0.005)


def test_yarn_frequencies_blend_between_the_two_rotations():
    """Full layers: 32 pairs of the first 64 dims; the fast pairs keep
    theta^(-2i/64), the slow ones are divided by `factor`, and cos/sin
    carry 0.1 ln(factor) + 1. Sliding layers: 64 plain pairs."""
    rp = laguna.LAGUNA_XS2_PUBLISHED["rope_parameters"]
    inv, scale = laguna.rope_frequencies(rp["full_attention"], 128)
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    assert inv.shape == (32,) and scale == pytest.approx(
        0.1 * np.log(64) + 1)
    np.testing.assert_allclose(inv[:3], plain[:3], rtol=1e-6)
    np.testing.assert_allclose(inv[-3:], plain[-3:] / 64, rtol=1e-6)
    assert np.all(np.diff(inv / plain) <= 1e-6)     # a ramp, monotone
    inv, scale = laguna.rope_frequencies(rp["sliding_attention"], 128)
    assert inv.shape == (64,) and scale == 1.0
    np.testing.assert_allclose(inv, 10000.0 ** (-np.arange(0, 128, 2) / 128),
                               rtol=1e-6)
    # the reference computes its own table: the two agree
    for kind in rp:
        a, sa = laguna.rope_frequencies(rp[kind], 128)
        b, sb = reference.rope_table(rp[kind], 128)
        np.testing.assert_allclose(a, np.asarray(b, np.float32), rtol=1e-6)
        assert sa == pytest.approx(sb)
