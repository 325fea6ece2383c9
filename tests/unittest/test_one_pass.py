"""A serving step is ONE pass over its tokens (PR 31): the step's tokens
go through the layer stack once, as virtual rows (token, position, the
slot whose page-table row the row reads and writes through).

(a) the pass's logits against feeding the same tokens one dispatch at a
    time, by tolerance, for both models, both widths and `full`;
(c) a burst of 3 x slots prompts: no pass feeds more than its width,
    every request inside its prompt feeds `prefill_chunk` tokens (or what
    is left) every step, every request DONE with `model.generate`'s
    tokens, prefix hits and a copy on write included;
(d) after the step of the first admission into a bucket
    `stats()["executables"]` does not grow; a bucket the byte budget
    refuses is neither compiled nor dispatched, and a bucket the device
    refuses when it is first compiled is an admission refusal;
(e) every live `serve.decode_step` span names an executable that
    `mx.trace.scope_map` knows, and `serve.step`'s `chunk` is > 1 exactly
    when the wide executable ran.
(The arena write with several rows of one page is in test_kernels.py.)"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import config, pages, parallel, serve, trace
from mxnet_tpu.models import glm as glm_mod
from mxnet_tpu.models import gpt as gpt_mod
from mxnet_tpu.ndarray import NDArray

_VOCAB = 128


@pytest.fixture(autouse=True)
def _clean():
    yield
    serve.disable()
    trace.disable()
    trace.reset()
    config.reset()


def _model(family):
    parallel.make_mesh(dp=-1)
    if family == "gpt":
        m = gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config())
    else:
        m = glm_mod.GLMForCausalLM(glm_mod.glm_tiny_config())
    mx.random.seed(3)
    m.initialize()
    return m


@pytest.fixture(scope="module")
def gpt():
    return _model("gpt")


@pytest.fixture(scope="module", params=["gpt", "glm"])
def family_model(request):
    return request.param, _model(request.param)


# -- (a) one pass against one token a dispatch ------------------------------

SLOTS, PS, N_PG = 4, 4, 6


def _pass(spec, arenas, tables, rows, width, full=False):
    """Run `chunk_step` on `rows` = [(token, position, slot)], padded to
    `width`. Returns (logits, new arenas)."""
    toks = np.zeros((width,), np.int32)
    pos = np.full((width,), -1, np.int32)
    slot = np.zeros((width,), np.int32)
    last = np.zeros((SLOTS,), np.int32)
    for w, (tok, p, s) in enumerate(rows):
        toks[w], pos[w], slot[w] = tok, p, s
        last[s] = w
    lg, new = spec.chunk_step(
        *[NDArray(np.asarray(a)) for a in (toks, pos, slot, last, tables)],
        [NDArray(a) for a in arenas], PS, full=full)
    return np.asarray(lg._data), [a._data for a in new]


@pytest.mark.parametrize("full", [False, True], ids=["last", "full"])
@pytest.mark.parametrize("width", [SLOTS, 2 * SLOTS], ids=["slots", "wide"])
def test_one_pass_logits_equal_one_token_a_dispatch(family_model, width,
                                                    full):
    """Slot 0 decodes at position 9; slot 2 is inside its prompt from
    position 3 on (its span crosses a page boundary); in the wide pass
    slot 1 feeds a prompt from position 0 too. Slot 3 is empty. Every
    fed token's logits (or each slot's last, without `full`) and every
    written arena row equal what one token a dispatch gives, to
    rounding."""
    family, model = family_model
    spec = model.serving_spec()
    rng = np.random.RandomState(5)
    pool = pages.PagePool(PS, SLOTS * N_PG, SLOTS, {"target": spec.streams})
    tables = np.zeros((SLOTS, N_PG), np.int32)
    for s in range(SLOTS):
        tables[s] = pool.alloc(N_PG)
    ids = rng.randint(0, spec.vocab_size if family == "glm" else _VOCAB,
                      (SLOTS, 16))
    arenas = pool.state["target"]
    # what is cached before the step: slot 0's positions 0..8, slot 2's 0..2
    for s, upto in ((0, 9), (2, 3)):
        for p in range(upto):
            _, arenas = _pass(spec, arenas, tables, [(ids[s, p], p, s)],
                              SLOTS)
    rows = [(ids[0, 9], 9, 0)] + [(ids[2, p], p, 2) for p in range(3, 6)]
    if width > SLOTS:
        rows += [(ids[2, 6], 6, 2)] + [(ids[1, p], p, 1) for p in range(3)]
    assert len(rows) <= width and (width == SLOTS or len(rows) > SLOTS)
    # one token a dispatch, in the pass's order
    one, single = [], arenas
    for row in rows:
        lg, single = _pass(spec, single, tables, [row], SLOTS)
        one.append(lg[row[2]])
    got, packed = _pass(spec, arenas, tables, rows, width, full=full)
    if full:
        assert got.shape[0] == width
        want, got = np.stack(one), got[:len(rows)]
    else:
        assert got.shape[0] == SLOTS
        fed = sorted({s for _, _, s in rows})
        want = np.stack([[lg for lg, r in zip(one, rows) if r[2] == s][-1]
                         for s in fed])
        got = got[fed]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert (got.argmax(-1) == want.argmax(-1)).all()
    # the cache: every page but the scratch ones
    for a, b in zip(packed, single):
        np.testing.assert_allclose(np.asarray(a)[SLOTS:],
                                   np.asarray(b)[SLOTS:],
                                   rtol=2e-4, atol=2e-4)


# -- (c) a burst -------------------------------------------------------------

def _prompt(n, seed):
    return np.random.RandomState(seed).randint(0, _VOCAB, (n,)) \
        .astype(np.int32)


def _watch_passes(srv):
    """Record, for every chunk round, its passes as [(request, first
    position, tokens)] with the request's prompt length."""
    seen, passes = [], srv._passes

    def watched(feeds):
        out = passes(feeds)
        grp = next(iter(srv._groups.values()))
        seen.append([[(grp.slots[i].id, p, len(ids),
                       grp.slots[i].prompt.size) for i, p, ids in rows]
                     for rows in out])
        return out

    srv._passes = watched
    return seen


@pytest.mark.parametrize("chunk,rungs", [(4, (4, 8)), (6, (4, 8, 16))],
                         ids=["two-rungs", "three"])
def test_a_burst_of_three_times_the_slots(gpt, chunk, rungs):
    """The burst owes more than the top rung holds and takes several
    passes a step, whatever the ladder: `slots` and `2 * slots` (all a
    server of 4 slots and chunks of 4 has), or a third rung."""
    slots = 4
    rng = np.random.RandomState(9)
    shared = _prompt(12, 1)
    prompts = [_prompt(n, 20 + n) for n in (5, 9, 14, 17, 3, 11, 6)] \
        + [np.concatenate([shared, rng.randint(0, _VOCAB, (k,))
                           .astype(np.int32)]) for k in (2, 5, 1)] \
        + [shared, shared]                  # whole-prompt hits: copy on write
    assert len(prompts) == 3 * slots
    ref = [gpt.generate(p[None], max_new_tokens=6, on_device=False)[0]
           .tolist() for p in prompts]
    srv = serve.Server(gpt, slots=slots, page_size=4, prefill_chunk=chunk,
                       queue_depth=64, buckets=[32])
    first = srv.submit(shared, max_new_tokens=6)     # warms the tree
    srv.drain()
    seen = _watch_passes(srv)
    alone = srv.stats()["chunk_dispatches"]
    reqs = [srv.submit(p, max_new_tokens=6) for p in prompts]
    srv.drain()
    st = srv.stats()
    srv.stop()
    assert list(first.tokens) == ref[-1]
    assert all(r.state == serve.DONE for r in reqs)
    assert [list(r.tokens) for r in reqs] == ref
    assert st["prefix_hits"] >= 5 and st["cow_copies"] >= 2
    wide = srv._wide()
    assert srv._rungs == rungs and wide == rungs[-1]
    assert max(len(step) for step in seen) > 1, "the burst took two passes"
    for step in seen:
        fed = {}
        for k, rows in enumerate(step):
            n = sum(ni for _, _, ni, _ in rows)
            # every pass but a step's last is full, none is over its width
            assert 0 < n <= wide and (n == wide or k == len(step) - 1)
            for rid, p, ni, lp in rows:
                if p < lp:
                    fed[rid] = fed.get(rid, 0) + ni
                    assert p + ni <= lp
                else:
                    assert ni == 1 and k == 0 and rid not in fed
        # every request inside its prompt fed its whole chunk this step
        for rows in step:
            for rid, p, ni, lp in rows:
                if rid in fed and fed[rid] is not None:
                    start = min(q for r2 in step for i2, q, _, _ in r2
                                if i2 == rid)
                    assert fed[rid] == min(chunk, lp - start)
                    fed[rid] = None
        # prompt tokens in admission order
        order = [rid for rows in step for rid, p, _, lp in rows if p < lp]
        assert order == sorted(order)
    # the counter the mechanism brings
    assert st["rows_fed"] == st["attn_tokens"]
    assert st["rows_dispatched"] == sum(
        w * n for w, n in st["width_dispatches"].items())
    assert set(st["width_dispatches"]) <= set(srv._rungs)
    assert sum(n for w, n in st["width_dispatches"].items()
               if w > slots) == st["chunk_steps"] > 0
    assert st["width_dispatches"][wide] > 0
    assert st["width_dispatches"][slots] == st["token_steps"] > 0
    assert st["rows_fed"] <= st["rows_dispatched"]
    assert st["chunk_dispatches"] - alone == sum(len(step) for step in seen)


# -- (d) executables ---------------------------------------------------------

@pytest.mark.parametrize("drafter", [False, True], ids=["plain", "drafter"])
def test_executables_are_built_at_the_first_admission(gpt, drafter):
    srv = serve.Server(gpt, slots=2, page_size=4, prefill_chunk=4,
                       buckets=[32], drafter=gpt if drafter else None,
                       spec_k=2)
    assert srv.stats()["executables"] == 0
    srv.submit(_prompt(2, 1), max_new_tokens=3)     # fits the narrow pass
    srv.step()
    built = srv.stats()["executables"]
    # one a rung of the ladder; with a drafter its mirrors, the verify
    # pass and the draft chain
    assert srv._rungs == (2, 4)
    assert built == (6 if drafter else 2)
    reqs = [srv.submit(_prompt(n, n), max_new_tokens=5) for n in (9, 11, 3)]
    srv.drain()
    st = srv.stats()
    srv.stop()
    assert all(r.state == serve.DONE for r in reqs)
    assert st["chunk_steps"] > 0 and st["token_steps"] > 0
    assert st["executables"] == built


def _refuse_bucket_64(srv):
    """Bucket 32's step fits beside parameters and pool, bucket 64's does
    not (forced exec peaks, as test_serve's overload smoke does)."""
    srv._exec_peaks.update({32: 4096, 64: 1 << 20})
    config.set("device_bytes_limit",
               srv._params_bytes + srv._pool.pool_bytes() + (1 << 19))


def test_a_refused_bucket_is_neither_compiled_nor_dispatched(gpt):
    """mx.memsafe's contract: a bucket whose step is predicted to overrun
    the device is refused BEFORE anything of it is built or run. Its
    request is shrunk into the bucket that fits, and only that bucket's
    executables exist afterwards."""
    srv = serve.Server(gpt, slots=2, page_size=4, prefill_chunk=4,
                       buckets=[32, 64])
    _refuse_bucket_64(srv)
    calls = []
    dispatch = srv._dispatch
    srv._dispatch = lambda grp, *a: (calls.append(grp.bucket),
                                     dispatch(grp, *a))[1]
    big = srv.submit(_prompt(10, 7), max_new_tokens=40)    # wants 64
    over = srv.submit(_prompt(40, 8), max_new_tokens=20)   # nothing under 64
    srv.step()
    assert srv._warmed == {32} and srv.stats()["executables"] == 2
    srv.drain()
    st = srv.stats()
    srv.stop()
    assert big.state == serve.DONE and big.degraded \
        and big.max_new_tokens == 22
    assert over.state == serve.REJECTED and "429" in over.verdict
    assert srv._warmed == {32} and st["executables"] == len(srv._rungs)
    assert set(calls) == {32}
    assert not [key for key in srv._runners if key[1] == 64]
    assert 64 not in st["buckets_allocated"]


@pytest.mark.parametrize("oom", [True, False], ids=["oom", "other"])
def test_a_bucket_the_device_refuses_at_warm_up(gpt, oom):
    """The budget's prediction can miss: the device refuses a bucket's
    executables when they are first compiled and run on padding. An
    out-of-memory refusal with the pool intact is an admission refusal
    (429 for the seated request, the bucket struck, later requests
    shrunk below it) and does not raise out of `step()`; anything else
    is a scheduler error."""
    srv = serve.Server(gpt, slots=2, page_size=4, prefill_chunk=4,
                       buckets=[32, 64])
    dispatch = srv._dispatch
    words = "RESOURCE_EXHAUSTED: out of memory" if oom else "bad lowering"

    def refusing(grp, *a):
        if grp.bucket == 64:
            raise RuntimeError(words)
        return dispatch(grp, *a)

    srv._dispatch = refusing
    first = srv.submit(_prompt(10, 7), max_new_tokens=40)      # bucket 64
    small = srv.submit(_prompt(5, 2), max_new_tokens=5)        # bucket 32
    if not oom:
        with pytest.raises(RuntimeError, match="bad lowering"):
            srv.step()
        srv.stop()
        return
    srv.step()
    assert first.state == serve.REJECTED
    assert "429 over capacity" in first.verdict \
        and "RESOURCE_EXHAUSTED" in first.verdict
    assert 64 in srv._unfit and 64 not in srv._warmed
    later = srv.submit(_prompt(10, 7), max_new_tokens=40)      # shrunk
    srv.drain()
    st = srv.stats()
    srv.stop()
    assert small.state == serve.DONE and not small.degraded
    assert later.state == serve.DONE and later.max_new_tokens == 22
    # the refused request's pages went back: what is held, the tree holds
    srv._tree.evict(st["pool_pages_total"])
    assert srv._pool.free_pages() == st["pool_pages_total"]
    assert srv._warmed == {32}


# -- (e) spans ---------------------------------------------------------------

def test_spans_name_the_executable_that_ran(gpt):
    label = "serve.paged/bucket={bucket}/chunk={chunk}"
    trace.enable()
    srv = serve.Server(gpt, slots=2, page_size=4, prefill_chunk=4,
                       buckets=[32])
    for n in (9, 3, 6):
        srv.submit(_prompt(n, n), max_new_tokens=4)
    srv.drain()
    st = srv.stats()
    srv.stop()
    spans = trace.spans()
    steps = {s["step"]: s for s in spans if s["name"] == "serve.step"}
    rounds = [s for s in spans if s["name"] == "serve.decode_step"]
    assert len(rounds) == st["steps"] > 0
    maps = trace.scope_map()
    ran_wide = 0
    chunk_of = {2: 1, 4: 4}             # a rung's name: one value each
    assert srv._rungs == tuple(chunk_of)
    for s in rounds:
        assert s["chunk"] == chunk_of[s["width"]] and s["fed"] <= s["width"]
        # the narrowest rung that holds the pass's rows
        assert s["width"] == min(w for w in srv._rungs if w >= s["fed"])
        names = maps[label.format(**s)]
        assert any("/kv_arena_update/" in path for path in names.values())
        assert steps[s["step"]]["chunk"] == max(
            r["chunk"] for r in rounds if r["step"] == s["step"])
        ran_wide += s["chunk"] > 1
    assert ran_wide == st["chunk_steps"] > 0
    assert len(rounds) - ran_wide == st["token_steps"] > 0
    assert sum(s["width"] for s in rounds) == st["rows_dispatched"]
    assert sum(s["fed"] for s in rounds) == st["rows_fed"]
