"""mx.pages tests: page pool alloc/free/refcount invariants, the
content-hashed prefix tree (collision tolerance, partial-block tails,
LRU leaf eviction returning pages under pressure), copy-on-write on a
whole-prompt match, and the serve integration contracts — the server
emits BIT-IDENTICAL tokens to `model.generate` (shared-prefix reuse
included), speculative decoding is bit-identical to plain greedy
(exact acceptance, weak drafters included), admission under page
exhaustion walks the degradation ladder, a server built with no
keyword has a pool, and mx.check's `degenerate-paging` lint flags the
configurations that silently void the feature."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, pages, parallel, serve
from mxnet_tpu import check as mxcheck
from mxnet_tpu.models import gpt as gpt_mod

_VOCAB = 128


@pytest.fixture(autouse=True)
def _clean():
    yield
    serve.disable()
    mxcheck.disable()
    mxcheck.reset()
    config.reset()


@pytest.fixture(scope="module")
def model():
    parallel.make_mesh(dp=-1)
    cfg = gpt_mod.gpt_tiny_config()
    m = gpt_mod.GPTForCausalLM(cfg)
    mx.random.seed(0)
    m.initialize()
    return m


@pytest.fixture(scope="module")
def drafter():
    parallel.make_mesh(dp=-1)
    cfg = gpt_mod.gpt_tiny_config(num_layers=1)
    d = gpt_mod.GPTForCausalLM(cfg)
    mx.random.seed(7)
    d.initialize()
    return d


def _prompt(n, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, _VOCAB, (n,)).astype(np.int32)


def _pool(ps=4, data=8, scratch=2, streams=1):
    specs = [(2, 8, np.float32)] * (2 * streams)
    return pages.PagePool(ps, data, scratch,
                          {"target": specs})


# -- PagePool ---------------------------------------------------------------

def test_pool_alloc_free_refcount_invariants():
    pool = _pool(data=6, scratch=3)
    assert pool.data_pages == 6 and pool.free_pages() == 6
    got = pool.alloc(4)
    assert len(got) == 4 and min(got) >= pool.scratch
    assert pool.free_pages() == 2 and pool.used_pages() == 4
    assert all(pool.refcount[p] == 1 for p in got)
    pool.incref(got[0])
    pool.decref(got[0])
    assert pool.refcount[got[0]] == 1     # still held once
    assert pool.free_pages() == 2
    for p in got:
        pool.decref(p)
    assert pool.free_pages() == 6 and pool.used_pages() == 0
    assert pool.stats["allocs"] == 4 and pool.stats["frees"] == 4
    assert pool.stats["peak_used"] == 4
    # freed pages recycle through the free list
    again = pool.alloc(6)
    assert sorted(again) == sorted(range(3, 9))


def test_pool_exhaustion_is_atomic_and_accounted():
    pool = _pool(data=3)
    pool.alloc(2)
    with pytest.raises(pages.PagesExhausted) as ei:
        pool.alloc(2)
    assert ei.value.need == 2 and ei.value.free == 1
    assert pool.free_pages() == 1          # nothing half-allocated


def test_pool_refcount_errors_on_free_pages():
    pool = _pool()
    (p,) = pool.alloc(1)
    pool.decref(p)
    with pytest.raises(RuntimeError):
        pool.decref(p)
    with pytest.raises(RuntimeError):
        pool.incref(p)


def test_copy_page_copies_every_stream():
    import jax.numpy as jnp
    specs = [(2, 8, np.float32)] * 2
    pool = pages.PagePool(4, 6, 1, {"target": specs, "draft": specs})
    (src,) = pool.alloc(1)
    for tag in ("target", "draft"):
        pool.state[tag] = [a.at[src].set(float(i + 1))
                           for i, a in enumerate(pool.state[tag])]
    dst = pool.copy_page(src)
    assert dst != src and pool.refcount[dst] == 1
    for tag in ("target", "draft"):
        for i, a in enumerate(pool.state[tag]):
            assert jnp.all(a[dst] == float(i + 1))
    assert pool.stats["cow_copies"] == 1


# -- PrefixTree -------------------------------------------------------------

def test_tree_match_insert_and_partial_tail():
    pool = _pool(ps=4, data=8)
    tree = pages.PrefixTree(pool)
    prompt = _prompt(11)                   # 2 full blocks + 3-token tail
    own = pool.alloc(2)
    tree.insert(prompt, own)
    assert len(tree) == 2                  # the partial tail is NOT shared
    assert all(pool.refcount[p] == 2 for p in own)   # owner + tree
    got, matched = tree.match(prompt)
    assert got == own and matched == 8
    assert all(pool.refcount[p] == 3 for p in own)   # + the match
    # a prompt diverging after block 1 matches exactly one block
    other = prompt.copy()
    other[5] = (other[5] + 1) % _VOCAB
    got2, matched2 = tree.match(other)
    assert got2 == own[:1] and matched2 == 4
    assert tree.stats["hits"] == 2


def test_tree_hash_collision_is_detected(monkeypatch):
    pool = _pool(ps=4, data=8)
    tree = pages.PrefixTree(pool)
    monkeypatch.setattr(pages, "_block_digest",
                        lambda parent, block: b"same-digest")
    a, b = _prompt(4, seed=1), _prompt(4, seed=2)
    pa = pool.alloc(1)
    tree.insert(a, pa)
    # b collides with a's digest but stores different tokens: the walk
    # verifies content and refuses the match, and insert refuses to
    # overwrite the colliding node
    got, matched = tree.match(b)
    assert got == [] and matched == 0
    tree.insert(b, pool.alloc(1))
    assert len(tree) == 1
    got_a, matched_a = tree.match(a)
    assert got_a == pa and matched_a == 4


def test_tree_evict_lru_leaves_returns_pages():
    pool = _pool(ps=4, data=4)
    tree = pages.PrefixTree(pool)
    first, second = _prompt(8, seed=1), _prompt(8, seed=2)
    p1 = pool.alloc(2)
    tree.insert(first, p1)
    for p in p1:
        pool.decref(p)                     # request drained; tree holds them
    p2 = pool.alloc(2)
    tree.insert(second, p2)
    for p in p2:
        pool.decref(p)
    assert pool.free_pages() == 0
    tree.match(second)                     # refresh: second is now MRU
    for p in p2:
        pool.decref(p)
    n = tree.evict(2)
    assert n == 2 and pool.free_pages() == 2
    # LRU order: the first chain (stale) went, the refreshed survived
    assert tree.match(first) == ([], 0)
    got, matched = tree.match(second)
    assert matched == 8
    assert tree.stats["evicted_pages"] == 2


def test_tree_clear_drains_every_reference():
    pool = _pool(ps=4, data=6)
    tree = pages.PrefixTree(pool)
    own = pool.alloc(3)
    tree.insert(_prompt(12), own)
    for p in own:
        pool.decref(p)
    assert pool.free_pages() == 3
    assert tree.clear() == 3
    assert pool.free_pages() == 6 and len(tree) == 0


# -- serve integration: bit-identity ---------------------------------------

def _generate_tokens(model, prompts, max_new=8):
    """The reference: greedy `model.generate` on a dense cache, one
    prompt at a time — code the server shares nothing with past the
    block's token body."""
    return [model.generate(p[None], max_new_tokens=max_new,
                           on_device=False)[0].tolist() for p in prompts]


def test_served_bit_identical_to_generate(model):
    prompts = [_prompt(n, seed=n) for n in (5, 9, 14, 17)]
    ref = _generate_tokens(model, prompts)
    srv = serve.Server(model, slots=4, page_size=4, prefill_chunk=4)
    reqs = [srv.submit(p, max_new_tokens=8) for p in prompts]
    srv.drain()
    out = [list(r.tokens) for r in reqs]
    st = srv.stats()
    srv.stop()
    assert out == ref
    assert all(r.verdict == "200 ok" for r in reqs)
    # batched prefill engaged (chunked dispatches, not one per token)
    assert st["chunk_dispatches"] < sum(p.size for p in prompts)
    assert st["pages"] == "on" and st["pool_pages_total"] > 0


def test_prefix_reuse_skips_prefill_bit_identical(model):
    rng = np.random.RandomState(3)
    shared = rng.randint(0, _VOCAB, (12,)).astype(np.int32)
    prompts = [np.concatenate([shared,
                               rng.randint(0, _VOCAB, (3,))
                               .astype(np.int32)])
               for _ in range(4)]
    ref = _generate_tokens(model, prompts, max_new=6)
    srv = serve.Server(model, slots=2, page_size=4,
                       prefill_chunk=4)
    out = []
    for p in prompts:                      # sequential: the tree is warm
        r = srv.submit(p, max_new_tokens=6)
        srv.drain()
        out.append(list(r.tokens))
    st = srv.stats()
    srv.stop()
    assert out == ref
    assert st["prefix_hits"] >= 3          # every follower hit the tree
    assert st["prefix_hit_rate"] > 0.4     # 12 of 15 tokens per follower
    assert st["tree_nodes"] > 0


def test_cow_on_whole_prompt_match(model):
    p = _prompt(16, seed=5)                # lp a page multiple: full match
    ref = _generate_tokens(model, [p], max_new=4)[0]
    srv = serve.Server(model, slots=2, page_size=4,
                       prefill_chunk=4)
    r1 = srv.submit(p, max_new_tokens=4)
    srv.drain()
    r2 = srv.submit(p, max_new_tokens=4)
    srv.drain()
    st = srv.stats()
    srv.stop()
    assert list(r1.tokens) == ref and list(r2.tokens) == ref
    # the second request matched the WHOLE prompt: its first write
    # (the re-fed last token) landed inside a shared page -> CoW
    assert st["cow_copies"] >= 1
    assert st["prefix_tokens"] >= p.size - 1


@pytest.mark.slow  # ~13s spec-decode drive; ci pages stage runs it by name
def test_speculative_bit_identical_to_plain_greedy(model):
    prompts = [_prompt(n, seed=n) for n in (5, 9, 17)]
    ref = _generate_tokens(model, prompts, max_new=16)
    # the target drafting for itself: near-total acceptance
    srv = serve.Server(model, slots=4, page_size=4,
                       prefill_chunk=4, drafter=model, spec_k=3)
    reqs = [srv.submit(p, max_new_tokens=16) for p in prompts]
    srv.drain()
    out = [list(r.tokens) for r in reqs]
    st = srv.stats()
    srv.stop()
    assert out == ref
    assert st["spec_rounds"] > 0 and st["drafts_proposed"] > 0
    assert st["accepted_draft_rate"] > 0.5


def test_weak_drafter_still_bit_identical(model, drafter):
    prompts = [_prompt(n, seed=100 + n) for n in (6, 11)]
    ref = _generate_tokens(model, prompts, max_new=10)
    srv = serve.Server(model, slots=2, page_size=4,
                       prefill_chunk=4, drafter=drafter, spec_k=3)
    reqs = [srv.submit(p, max_new_tokens=10) for p in prompts]
    srv.drain()
    out = [list(r.tokens) for r in reqs]
    srv.stop()
    # a drafter with different weights/depth mostly guesses wrong —
    # exact acceptance makes that a speed question, never correctness
    assert out == ref


def test_spec_round_carries_sampled_rows(model):
    p1, p2 = _prompt(7, seed=21), _prompt(9, seed=22)
    # the reference for a SAMPLED row is a drafter-less server (its rng
    # is the request's own); the greedy row is also held to generate
    srv0 = serve.Server(model, slots=4, page_size=4, prefill_chunk=4)
    a = srv0.submit(p1, max_new_tokens=8, temperature=0.8, top_k=8, seed=3)
    b = srv0.submit(p2, max_new_tokens=8)
    srv0.drain()
    ref = [list(a.tokens), list(b.tokens)]
    srv0.stop()
    srv = serve.Server(model, slots=4, page_size=4,
                       prefill_chunk=4, drafter=model, spec_k=3)
    a = srv.submit(p1, max_new_tokens=8, temperature=0.8, top_k=8, seed=3)
    b = srv.submit(p2, max_new_tokens=8)
    srv.drain()
    out = [list(a.tokens), list(b.tokens)]
    srv.stop()
    assert out == ref
    assert out[1] == _generate_tokens(model, [p2])[0]


# -- serve integration: pressure, eviction, rejection -----------------------

def test_page_pressure_evicts_tree_and_completes(model):
    # each request needs ceil(14/4) = 4 pages exactly; a 5-page pool
    # leaves no room for the previous prompt's 2 tree-held blocks, so
    # every later distinct prompt must evict them to run
    prompts = [_prompt(10, seed=31), _prompt(10, seed=32),
               _prompt(10, seed=33)]
    ref = _generate_tokens(model, prompts, max_new=4)
    srv = serve.Server(model, slots=1, page_size=4,
                       prefill_chunk=4, pool_pages=5)
    out = []
    for p in prompts:
        r = srv.submit(p, max_new_tokens=4)
        srv.drain()
        out.append(list(r.tokens))
    tree_stats = dict(srv._tree.stats)
    st = srv.stats()
    srv.stop()
    assert out == ref
    assert tree_stats["evicted_pages"] > 0
    assert st["completed"] == 3


def test_page_exhaustion_rejects_when_nothing_running(model):
    # a pool smaller than one table: the request can never fit
    srv = serve.Server(model, slots=1, page_size=4,
                       prefill_chunk=4, pool_pages=3)
    r = srv.submit(_prompt(20, seed=41), max_new_tokens=8)
    srv.drain()
    srv.stop()
    assert r.state == serve.REJECTED
    assert "page pool exhausted" in r.verdict


def test_vacate_returns_exclusive_pages(model):
    srv = serve.Server(model, slots=2, page_size=4,
                       prefill_chunk=4)
    total = srv._pool.free_pages()
    r = srv.submit(_prompt(9, seed=51), max_new_tokens=4)
    srv.drain()
    srv.stop()                             # clears the tree too
    assert r.state == serve.DONE
    assert srv._pool.free_pages() == total
    assert int(srv._pool.refcount.sum()) == 0


# -- fast path + lint -------------------------------------------------------

def test_server_without_keyword_has_a_pool(model):
    """There is one cache manager: `Server(model)` builds the pool at
    construction (every slot at the longest servable length) and reports
    the pool's keys; `pages="on"`, which configuration files still pass,
    builds the same server, and the dense path's name is refused."""
    srv = serve.Server(model, slots=2)
    st = srv.stats()
    assert st["pages"] == "on" and st["prefix_hit_rate"] == 0.0
    assert st["pool_pages_total"] == 2 * (srv._max_len // st["page_size"])
    assert st["pool_pages_free"] == st["pool_pages_total"]
    assert serve.Server(model, slots=2, pages="on").stats()[
        "pool_pages_total"] == st["pool_pages_total"]
    with pytest.raises(KeyError):
        config.get("pages")             # the switch is not an option
    r = srv.submit(_prompt(5), max_new_tokens=4)
    srv.drain()
    srv.stop()
    assert r.state == serve.DONE
    assert r.tokens == _generate_tokens(model, [_prompt(5)], 4)[0]


def test_degenerate_paging_page_size_finding(model):
    mxcheck.enable()
    srv = serve.Server(model, slots=1, page_size=64,
                       buckets=[32, 64])
    srv.stop()
    found = [f for f in mxcheck.findings()
             if f["rule"] == "degenerate-paging"]
    assert found and "32" in found[0]["message"]


def test_degenerate_paging_drafter_vocab_finding(model):
    parallel.make_mesh(dp=-1)
    cfg = gpt_mod.gpt_tiny_config(vocab_size=96, num_layers=1)
    mism = gpt_mod.GPTForCausalLM(cfg)
    mx.random.seed(9)
    mism.initialize()
    mxcheck.enable()
    srv = serve.Server(model, slots=1, page_size=4,
                       drafter=mism)
    srv.stop()
    found = [f for f in mxcheck.findings()
             if f["rule"] == "degenerate-paging"]
    assert found
    assert any("vocabulary" in f["message"] for f in found)


def test_clean_paged_config_no_finding(model):
    mxcheck.enable()
    srv = serve.Server(model, slots=1, page_size=4)
    srv.stop()
    assert [f for f in mxcheck.findings()
            if f["rule"] == "degenerate-paging"] == []
