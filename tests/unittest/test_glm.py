"""The GLM-5-style decoder (models/glm.py) through serve.Server and the page
pool, at a small size on the CPU, against the plain reference
(chipbench/reference/glm5.py): prompt then decoding against the
reference's full forward pass, the share of the experts, a prefix hit,
arenas of different widths under one allocator, and the server's
position counters. Selection is at work: index_topk 8 under contexts of
9 to 40."""
import functools
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
from chipbench.reference import glm5 as reference  # noqa: E402
from mxnet_tpu import config, pages, parallel, serve  # noqa: E402
from mxnet_tpu.models import glm  # noqa: E402
from mxnet_tpu.models import gpt as gpt_mod  # noqa: E402
from mxnet_tpu.parallel import moe  # noqa: E402

LENGTHS = [(20, 14), (11, 25), (24, 16), (9, 31)]      # (prompt, new)


@pytest.fixture(autouse=True)
def _clean():
    yield
    serve.disable()
    config.reset()


@functools.lru_cache(maxsize=None)      # weights are read, never written
def build(dtype, seed=3, **over):
    parallel.make_mesh(devices=jax.devices()[:1])
    cfg = glm.glm_tiny_config(dtype=dtype, **over)
    model = glm.GLMForCausalLM(cfg)
    mx.random.seed(seed)
    model.initialize()
    return model, cfg


def server(model, **over):
    args = dict(page_size=4, slots=4, buckets=[48],
                pool_pages=64, prefill_chunk=8)
    args.update(over)
    return serve.Server(model, **args)


def reference_logits(model, cfg, req, **kw):
    layers, top = model.layer_weights()
    seq = np.concatenate([req.prompt, req.tokens[:-1]])
    return np.asarray(reference.forward(
        seq, layers, top, cfg, cfg["first_expert"],
        logits_from=req.prompt.size - 1, block=16, pad_to=48, **kw))


def test_published_keys_add_up_to_the_published_total():
    """743.9 B without the multi-token-prediction layer; the chip's share
    of the benchmark's cut is 4.727 B (9.45 GB in bf16)."""
    assert glm.param_count(glm.glm5_config()) / 1e9 \
        == pytest.approx(743.9, abs=0.1)
    cut = glm.glm5_config(num_hidden_layers=6, first_k_dense_replace=1,
                          experts_held=16, vocab_size=19360)
    assert glm.param_count(cut) / 1e9 == pytest.approx(4.727, abs=0.001)
    model, cfg = build("float32")
    held = sum(int(np.prod(p.shape)) for _, p in model._iter_params())
    assert held == glm.param_count(cfg)
    assert all(p.data()._grad is None for _, p in model._iter_params())


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_served_logits_agree_with_the_reference(dtype):
    """(a) Prompt (chunked prefill), then decoding through the latent and
    indexer-key arenas, against the reference's one forward pass over
    prompt + generated tokens: the logits at every generated position.

    float32: both sides compute in float32 and differ by the order of
    their sums (absorbed against expanded heads): 1e-4 absolute, 50 times
    the 2e-6 measured. bfloat16: rounding shows in every row, and where it
    moves a discrete choice at this size (one of 8 picks, one of 3 of 8
    experts) a row is off by tenths; so the limit is on the MEDIAN row's
    relative error, 0.03: 3.5 times the 0.0085 measured over three
    seeds, a tenth of the 0.34-0.36 the reference itself gives with
    operands at an fp8 mantissa, which has to fail it, as has half the
    selection."""
    model, cfg = build(dtype)
    srv = server(model)
    rng = np.random.RandomState(0)
    reqs = [srv.submit(rng.randint(0, cfg["vocab_size"], (lp,)),
                       max_new_tokens=mn, keep_logits=True)
            for lp, mn in LENGTHS]
    srv.drain()
    st = srv.stats()
    srv.stop()
    assert st["sparse_tokens"] > 0 and st["chunk_steps"] > 0
    got, want = [], []
    for req, (lp, mn) in zip(reqs, LENGTHS):
        assert req.state == serve.DONE and len(req.logits) == mn
        assert all(int(row.argmax()) == t
                   for row, t in zip(req.logits, req.tokens))
        got.append(np.stack(req.logits))
        want.append(reference_logits(model, cfg, req))
    got, want = np.concatenate(got), np.concatenate(want)
    if dtype == "float32":
        assert np.abs(got - want).max() < 1e-4
        return
    limit = 0.03
    assert np.median(reference.relative_errors(got, want)) < limit
    lower = np.concatenate([reference_logits(model, cfg, r, mantissa_bits=3)
                            for r in reqs])
    assert np.median(reference.relative_errors(lower, want)) > 3 * limit
    fewer = dict(cfg, index_topk=cfg["index_topk"] // 2)
    half = np.concatenate([reference_logits(model, fewer, r) for r in reqs])
    assert np.median(reference.relative_errors(half, want)) > 3 * limit


def test_only_an_audited_request_brings_logits_to_the_host():
    """The benchmark's mix: greedy requests, some audited. All emit the
    tokens they emit with every request audited (the device's argmax is
    the kept row's), and only the audited ones' rows are copied."""
    model, cfg = build("float32")
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg["vocab_size"], (lp,)) for lp, _ in LENGTHS]
    runs = []
    for audited in ((0, 1, 2, 3), (1,)):
        srv = server(model)
        reqs = [srv.submit(p, max_new_tokens=mn, keep_logits=i in audited)
                for i, (p, (_, mn)) in enumerate(zip(prompts, LENGTHS))]
        srv.drain()
        runs.append((reqs, srv.stats()))
        srv.stop()
    (every, _), (one, st) = runs
    assert [r.tokens for r in one] == [r.tokens for r in every]
    assert [r.logits is None for r in one] == [True, False, True, True]
    np.testing.assert_array_equal(np.stack(one[1].logits),
                                  np.stack(every[1].logits))
    assert all(int(row.argmax()) == t
               for row, t in zip(one[1].logits, one[1].tokens))
    new = LENGTHS[1][1]
    assert st["logit_rows_fetched"] == new
    assert st["rows_sampled_on_device"] == st["tokens"]
    assert st["fetched_bytes"] == 4 * 4 * st["chunk_dispatches"] \
        + 4 * cfg["vocab_size"] * new


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """(b) Four chips of two experts each: the parts their
    `moe_share_ffn` give, plus the shared expert counted once, are the
    reference's uncut layer; and the reference given one share equals the
    program's part for it."""
    model, cfg = build("float32", experts_held=8)
    layer = model.layers[1]
    w = layer.weights()
    v = jax.random.normal(jax.random.key(1), (24, cfg["hidden_size"]))
    rnd = reference._rounder(23)
    whole = reference.experts(v, w, cfg, 0, rnd)
    shared = reference.swiglu(v, w["shared_gate_proj"], w["shared_up_proj"],
                              w["shared_down_proj"], rnd)
    expert, gate = moe.moe_topk_route(
        v, w["router"], w["router_select_offset"],
        cfg["num_experts_per_tok"], cfg["routed_scaling_factor"])
    assert expert.shape == (24, 3) and np.allclose(
        np.asarray(gate.sum(-1)), cfg["routed_scaling_factor"], atol=1e-5)
    total = shared
    for first in range(0, 8, 2):
        held = slice(first, first + 2)
        part = moe.moe_share_ffn(
            v, expert, gate, w["experts_gate_proj"][held],
            w["experts_up_proj"][held], w["experts_down_proj"][held], first)
        one = {k: (a[held] if k.startswith("experts_") else a)
               for k, a in w.items()}
        assert np.allclose(
            part, reference.experts(v, one, cfg, first, rnd, shared=False),
            atol=1e-5)
        total = total + part
    assert np.allclose(total, whole, atol=2e-5)
    # every token's gates are normalised over all its chosen experts, held
    # or not: a single share alone is NOT the layer
    assert not np.allclose(shared + part, whole, atol=1e-3)


def test_a_prefix_hit_serves_the_logits_of_a_full_prefill():
    """(c) A request whose first 16 tokens come from the tree (latents
    and indexer keys both) gets the logits of one that prefilled them
    itself, and the pool drains to zero references."""
    model, cfg = build("float32")
    rng = np.random.RandomState(2)
    prefix = rng.randint(0, cfg["vocab_size"], (16,))
    first = np.concatenate([prefix, rng.randint(0, cfg["vocab_size"], (3,))])
    second = np.concatenate([prefix, rng.randint(0, cfg["vocab_size"], (6,))])

    srv = server(model)
    srv.submit(first, max_new_tokens=2)
    srv.drain()
    hit = srv.submit(second, max_new_tokens=12, keep_logits=True)
    srv.drain()
    st = srv.stats()
    assert st["prefix_hits"] == 1 and st["prefix_tokens"] == 16
    assert st["cow_copies"] == 0
    pool = srv._pool
    srv.stop()
    assert int(pool.refcount.sum()) == 0
    assert pool.free_pages() == pool.data_pages

    cold = server(model)
    full = cold.submit(second, max_new_tokens=12, keep_logits=True)
    cold.drain()
    assert cold.stats()["prefix_hits"] == 0
    cold.stop()
    assert hit.tokens == full.tokens
    assert np.abs(np.stack(hit.logits) - np.stack(full.logits)).max() < 1e-5
    want = reference_logits(model, cfg, hit)
    assert np.abs(np.stack(hit.logits) - want).max() < 1e-4


def test_a_whole_prompt_hit_copies_both_arenas_on_write():
    """A prompt that is whole pages and all in the tree re-feeds its last
    token into a COPY of the shared page: latent and indexer-key rows
    travel together."""
    model, cfg = build("float32")
    prompt = np.random.RandomState(4).randint(0, cfg["vocab_size"], (16,))
    srv = server(model)
    a = srv.submit(prompt, max_new_tokens=10, keep_logits=True)
    srv.drain()
    b = srv.submit(prompt, max_new_tokens=10, keep_logits=True)
    srv.drain()
    assert srv.stats()["cow_copies"] == 1
    srv.stop()
    assert a.tokens == b.tokens
    assert np.abs(np.stack(a.logits) - np.stack(b.logits)).max() < 1e-5


def test_pool_holds_arenas_of_different_row_widths_alike():
    """(d) One allocator over a latent arena (no head axis), an indexer-
    key arena of another width and a K/V arena with heads: page p is row
    p of each, copy-on-write copies each, frees free each."""
    specs = {"target": [(18, np.float32), (6, np.float32)],
             "draft": [(2, 8, np.float32)]}
    pool = pages.PagePool(4, 6, 2, specs)
    shapes = [a.shape for a in pool.state["target"] + pool.state["draft"]]
    assert shapes == [(8, 4, 18), (8, 4, 6), (8, 2, 4, 8)]
    assert pool.pool_bytes() == 4 * 8 * 4 * (18 + 6 + 2 * 8)
    (src,) = pool.alloc(1)
    for tag in specs:
        pool.state[tag] = [a.at[src].set(float(i + 1))
                           for i, a in enumerate(pool.state[tag])]
    dst = pool.copy_page(src)
    assert dst != src and pool.refcount[dst] == 1
    for tag in specs:
        for i, a in enumerate(pool.state[tag]):
            assert jnp.all(a[dst] == float(i + 1)) and jnp.all(a[src] == a[dst])
    pool.decref(src)
    pool.decref(dst)
    assert pool.free_pages() == 6 and int(pool.refcount.sum()) == 0


def test_position_counters_are_what_a_walk_over_positions_counts():
    model, _ = build("float32")
    srv = server(model)
    srv._stats.update(attn_tokens=0, attn_ctx_tokens=0, attn_sel_tokens=0,
                      sparse_tokens=0)
    fed = [(0, 8), (5, 8), (8, 1), (7, 1), (3, 8), (40, 3), (0, 1)]
    for p, ni in fed:
        srv._note_fed(p, ni)
    contexts = [q + 1 for p, ni in fed for q in range(p, p + ni)]
    st = srv.stats()
    srv.stop()
    assert st["attn_tokens"] == len(contexts)
    assert st["attn_ctx_tokens"] == sum(contexts)
    assert st["attn_sel_tokens"] == sum(min(c, 8) for c in contexts)
    assert st["sparse_tokens"] == sum(c > 8 for c in contexts)


def _gpt_tiny():
    gpt = gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config())
    gpt.initialize()
    return gpt


@pytest.mark.parametrize("which", ["glm", "gpt"])
def test_the_model_is_served_through_the_pool_only(which):
    """Every model, GPT like GLM: the dense path's name is refused with
    an error that says it is gone; no keyword, or the "on" that
    configuration files still pass, is the pool."""
    model = build("float32")[0] if which == "glm" else _gpt_tiny()
    with pytest.raises(ValueError, match="dense per-bucket .* is gone"):
        serve.Server(model, pages="off")
    for kw in ({}, {"pages": "on"}):
        srv = serve.Server(model, page_size=4, slots=2, **kw)
        assert srv.stats()["pages"] == "on" and srv._pool.data_pages > 0
        srv.stop()


def test_a_model_without_a_draft_step_cannot_draft():
    with pytest.raises(ValueError, match="cannot draft"):
        serve.Server(_gpt_tiny(), drafter=build("float32")[0])


def test_gpt_answers_the_serving_spec_with_what_the_server_read():
    """The Server asks `serving_spec()` and no longer reaches into
    `model.gpt`: GPT's answer is the values it read there before."""
    cfg = gpt_mod.gpt_tiny_config()
    gpt = gpt_mod.GPTForCausalLM(cfg)
    gpt.initialize()
    spec = gpt.serving_spec()
    heads, d = cfg["num_heads"], cfg["units"] // cfg["num_heads"]
    assert spec.vocab_size == cfg["vocab_size"]
    assert spec.max_length == cfg["max_length"]
    assert spec.streams == [(heads, d, jnp.float32)] * (2 * cfg["num_layers"])
    assert spec.index_topk is None
    srv = serve.Server(gpt, page_size=8, slots=4)
    arenas = srv._pool.state["target"]
    assert len(arenas) == 2 * cfg["num_layers"]
    assert arenas[0].shape == (4 + 4 * (cfg["max_length"] // 8), heads, 8, d)
    assert srv._pool.pool_bytes() == 2 * cfg["num_layers"] * 4 \
        * (32 + 4 * cfg["max_length"]) * cfg["units"]
    srv.stop()
