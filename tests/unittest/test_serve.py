"""mx.serve tests: continuous-batching scheduler correctness (tokens
equal `model.generate`'s, bit-identical under load, bucket-bounded
executables), admission control (429 budget rejections riding
mx.memsafe over parameters + page pool + the step's peak), bounded-queue
backpressure and both shed policies, per-request deadlines with
mid-generation eviction, the graceful-degradation ladder (shrink,
evict-and-requeue), transient-dispatch retry, serving fault injection
(slow_client / burst / cancel), streaming, trace spans + the
queue-bound/decode-bound verdict, guard heartbeats, telemetry, the
serve=off zero-overhead fast path, and the overload acceptance smoke."""
import os
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import (config, dataflow, guard, memsafe, parallel,
                       resilience, serve, telemetry, trace)
from mxnet_tpu import check as mxcheck
from mxnet_tpu.models import gpt as gpt_mod

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACE_REPORT = os.path.join(ROOT, "tools", "trace_report.py")

_VOCAB = 128


@pytest.fixture(autouse=True)
def _clean_serve():
    yield
    serve.disable()
    resilience.uninstall()
    mxcheck.disable()
    mxcheck.reset()
    trace.disable()
    trace.reset()
    guard.disable()
    memsafe.reset()
    memsafe.disable()
    telemetry.reset()
    telemetry.disable()
    config.reset()


@pytest.fixture(scope="module")
def model():
    parallel.make_mesh(dp=-1)
    cfg = gpt_mod.gpt_tiny_config()
    m = gpt_mod.GPTForCausalLM(cfg)
    mx.random.seed(0)
    m.initialize()
    return m


def _prompt(n, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, _VOCAB, (n,)).astype(np.int32)


class _FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t


# -- core scheduler ----------------------------------------------------------

def test_single_request_matches_generate(model):
    p = _prompt(5)
    ref = model.generate(p[None], max_new_tokens=8, on_device=False)
    srv = serve.Server(model, slots=3)
    r = srv.submit(p, max_new_tokens=8)
    srv.drain()
    assert r.state == serve.DONE and r.verdict == "200 ok"
    assert r.tokens == ref[0].tolist()
    assert np.array_equal(r.result(timeout=1), ref[0])


def test_bit_identical_under_load(model):
    """The acceptance property: a request's tokens must not depend on
    what else shares the batch. Requests join mid-flight (continuous
    batching), lengths differ, and every completed output must equal the
    same request run ALONE on an unloaded server — bit-identical."""
    specs = [(3, 6, 1), (7, 9, 2), (5, 4, 3), (11, 7, 4), (4, 12, 5)]
    srv = serve.Server(model, slots=3)
    reqs = []
    for i, (lp, new, seed) in enumerate(specs):
        reqs.append(srv.submit(_prompt(lp, seed), max_new_tokens=new))
        srv.step()          # stagger: later requests join a running batch
    srv.drain()
    assert all(r.state == serve.DONE for r in reqs)
    solo = serve.Server(model, slots=3)      # one at a time: never loaded
    for (lp, new, seed), r in zip(specs, reqs):
        sr = solo.submit(_prompt(lp, seed), max_new_tokens=new)
        solo.drain()
        assert sr.tokens == r.tokens, f"load-dependent output for {r}"


def test_eos_stops_row(model):
    srv = serve.Server(model, slots=2)
    p = _prompt(5)
    ref = model.generate(p[None], max_new_tokens=16, on_device=False)
    hit = int(ref[0][0])            # greedy emits this first: early stop
    miss = next(v for v in range(_VOCAB) if v not in set(ref[0].tolist()))
    r_hit = srv.submit(p, max_new_tokens=16, eos=hit)
    r_miss = srv.submit(p, max_new_tokens=16, eos=miss)
    srv.drain()
    assert r_hit.state == r_miss.state == serve.DONE
    assert r_hit.tokens == [hit]    # stopped at eos, eos kept
    assert r_miss.tokens == ref[0].tolist()   # never saw eos: full budget


def test_temperature_sampling_deterministic_per_request(model):
    kwargs = dict(max_new_tokens=6, temperature=0.8, top_k=5, seed=42)
    solo = serve.Server(model, slots=3)
    a = solo.submit(_prompt(4), **kwargs)
    solo.drain()
    srv = serve.Server(model, slots=3)
    others = [srv.submit(_prompt(6, s), max_new_tokens=8) for s in (1, 2)]
    b = srv.submit(_prompt(4), **kwargs)
    srv.drain()
    assert a.state == b.state == serve.DONE
    # per-request seeded rng: the sampled stream ignores batch neighbors
    assert a.tokens == b.tokens
    assert all(o.state == serve.DONE for o in others)


# -- where a token is chosen: ids from the device, logits rows on request -----

_MIXED = [
    dict(max_new_tokens=7),
    dict(max_new_tokens=6, temperature=0.8, top_k=5, seed=42),
    dict(max_new_tokens=8, keep_logits=True),
    dict(max_new_tokens=5, temperature=1.3, seed=7, keep_logits=True),
    dict(max_new_tokens=9),
]


def test_mixed_batch_emits_what_each_request_emits_alone(model):
    """Greedy rows take the ids the executable chose, sampling and
    `keep_logits` rows get their logits row: in one batch, token for token
    and row for row what each request gets served alone."""
    prompts = [_prompt(4 + 3 * i, seed=30 + i) for i in range(len(_MIXED))]
    alone = []
    for p, kw in zip(prompts, _MIXED):
        solo = serve.Server(model, slots=3, prefill_chunk=4)
        alone.append(solo.submit(p, **kw))
        solo.drain()
    srv = serve.Server(model, slots=3, prefill_chunk=4)
    reqs = [srv.submit(p, **kw) for p, kw in zip(prompts, _MIXED)]
    srv.drain()
    st = srv.stats()
    for r, a, kw in zip(reqs, alone, _MIXED):
        assert r.state == a.state == serve.DONE
        assert r.tokens == a.tokens and len(r.tokens) == kw["max_new_tokens"]
        if not kw.get("keep_logits"):
            assert r.logits is None
            continue
        assert len(r.logits) == len(r.tokens)
        for row, solo_row, tok in zip(r.logits, a.logits, r.tokens):
            assert row.shape == (_VOCAB,) and row.dtype == np.float32
            np.testing.assert_array_equal(row, solo_row)
            if not kw.get("temperature"):
                assert int(np.argmax(row)) == tok    # the row argmax read
    greedy = sum(kw["max_new_tokens"] for kw in _MIXED
                 if not kw.get("temperature"))
    assert st["rows_sampled_on_device"] == greedy
    assert st["tokens"] == sum(kw["max_new_tokens"] for kw in _MIXED)
    # every sampled or kept token brought its row, and no other row came
    assert st["logit_rows_fetched"] == st["tokens"] - 7 - 9
    assert st["fetched_bytes"] == 4 * 3 * st["chunk_dispatches"] \
        + 4 * _VOCAB * st["logit_rows_fetched"]


@pytest.mark.parametrize("special", [None, "keep_logits", "temperature"])
def test_a_pass_fetches_ids_and_only_the_rows_asked_for(model, special):
    """A pass of greedy rows copies 4 bytes a row of the pass and not one
    logit; a request that keeps its logits (or samples) adds its own row,
    in the passes it emits in, and no other."""
    slots = 4
    srv = serve.Server(model, slots=slots, prefill_chunk=4)
    for i in range(3):
        srv.submit(_prompt(5 + i, seed=i), max_new_tokens=6)
    odd = None
    if special:
        kw = {"keep_logits": True} if special == "keep_logits" \
            else {"temperature": 0.7, "seed": 1}
        odd = srv.submit(_prompt(9, seed=9), max_new_tokens=4, **kw)
    before = srv.stats()
    while srv.busy():
        emitted = len(odd.tokens) if odd else 0
        srv.step()
        st = srv.stats()
        passes = st["chunk_dispatches"] - before["chunk_dispatches"]
        rows = len(odd.tokens) - emitted if odd else 0
        assert passes >= 1 and rows in (0, 1)
        assert st["logit_rows_fetched"] - before["logit_rows_fetched"] == rows
        assert st["fetched_bytes"] - before["fetched_bytes"] \
            == 4 * slots * passes + 4 * _VOCAB * rows
        before = st
    assert st["logit_rows_fetched"] == (4 if odd else 0)
    assert st["rows_sampled_on_device"] \
        == st["tokens"] - (4 if special == "temperature" else 0)


def test_a_tie_in_the_logits_emits_the_lowest_id(model):
    """`jnp.argmax` in the executable and `np.argmax` on the host both take
    the first index on a tie, so the move changes no token."""
    import jax.numpy as jnp
    from mxnet_tpu.ndarray import NDArray
    srv = serve.Server(model, slots=2)
    real = srv._spec.chunk_step

    def tied(*args, **kw):
        lg, new = real(*args, **kw)
        d = lg._data
        top = jnp.broadcast_to(d.max(-1, keepdims=True) + 1.0,
                               (d.shape[0], 3))
        return NDArray(d.at[:, jnp.array([9, 4, 100])].set(top)), new

    srv._spec = srv._spec._replace(chunk_step=tied)
    plain = srv.submit(_prompt(5), max_new_tokens=5)
    kept = srv.submit(_prompt(6, seed=1), max_new_tokens=5, keep_logits=True)
    srv.drain()
    assert plain.tokens == kept.tokens == [4] * 5
    for row in kept.logits:
        assert row[4] == row[9] == row[100] == row.max()
        assert int(np.argmax(row)) == 4


@pytest.mark.parametrize("kept", [False, True], ids=["plain", "kept"])
def test_spec_round_takes_its_tokens_from_the_ids_of_the_verify_pass(
        model, kept):
    """Accepted and bonus tokens are plain greedy decode's, read from the
    verify pass's ids: no logits row crosses unless a request keeps them."""
    slots, k = 2, 3
    prompts = [_prompt(6, seed=41), _prompt(9, seed=42)]
    ref = [model.generate(p[None], max_new_tokens=10,
                          on_device=False)[0].tolist() for p in prompts]
    srv = serve.Server(model, slots=slots, page_size=4, prefill_chunk=4,
                       drafter=model, spec_k=k)
    reqs = [srv.submit(p, max_new_tokens=10, keep_logits=kept and i == 0)
            for i, p in enumerate(prompts)]
    srv.drain()
    st = srv.stats()
    srv.stop()
    assert [r.tokens for r in reqs] == ref
    assert st["spec_rounds"] > 0 and st["drafts_accepted"] > 0
    assert st["rows_sampled_on_device"] == st["tokens"] == 20
    ids = 4 * slots * st["chunk_dispatches"] \
        + 4 * slots * (k + 1) * 2 * st["spec_rounds"]   # drafts, verify ids
    if not kept:
        assert st["logit_rows_fetched"] == 0
        assert st["fetched_bytes"] == ids
        return
    assert reqs[1].logits is None and len(reqs[0].logits) == 10
    assert all(int(np.argmax(row)) == t
               for row, t in zip(reqs[0].logits, reqs[0].tokens))
    # a verify pass brings the kept request's k + 1 rows: which of them
    # it emits is known only once the ids are here
    assert 10 <= st["logit_rows_fetched"] <= 10 + k * st["spec_rounds"]
    assert st["fetched_bytes"] == ids + 4 * _VOCAB * st["logit_rows_fetched"]


def test_streaming_tokens_arrive_incrementally(model):
    srv = serve.Server(model, slots=2)
    r = srv.submit(_prompt(4), max_new_tokens=6)
    seen = []
    it = r.stream()
    while not r.done:
        srv.step()
        if not r.done and r._stream_q.qsize():
            seen.append(next(it))
    assert seen, "no token was observable mid-generation"
    assert seen == r.tokens[:len(seen)]
    assert seen + list(it) == r.tokens          # sentinel ends the stream


def test_bucketing_bounds_executables_and_check_quiet(model):
    """A stream of novel prompt/generation lengths compiles at most one
    executable a rung of the ladder per bucket (two pow2 buckets here),
    and mx.check's retrace-hazard rule stays quiet on the bucketed
    stream."""
    import jax
    mxcheck.enable("warn")
    srv = serve.Server(model, slots=2)
    jits = {"n": 0}
    real_jit = jax.jit

    def counting_jit(*a, **k):
        jits["n"] += 1
        return real_jit(*a, **k)

    jax.jit = counting_jit
    try:
        lengths = [(3, 5), (7, 9), (5, 11), (13, 4), (9, 30), (17, 40),
                   (21, 30), (6, 50)]       # needs: <=32 and 33..64
        reqs = [srv.submit(_prompt(lp, i), max_new_tokens=new)
                for i, (lp, new) in enumerate(lengths)]
        srv.drain()
    finally:
        jax.jit = real_jit
    assert all(r.state == serve.DONE for r in reqs)
    st = srv.stats()
    # one executable a rung and bucket, however many lengths
    assert st["executables"] <= 2 * len(srv._rungs), st
    assert jits["n"] <= 2 * len(srv._rungs), jits
    assert {key[1] for key in srv._runners} == {32, 64}
    bad = [f for f in mxcheck.findings()
           if f["rule"] in ("retrace-hazard", "donation-miss")]
    assert bad == [], bad


def test_bucket_length_shared_policy():
    assert dataflow.bucket_length(5) == max(
        32, int(config.get("bucket_pad_min")))
    assert dataflow.bucket_length(33) == 64
    assert dataflow.bucket_length(40, [16, 48, 96]) == 48
    assert dataflow.bucket_length(200, [16, 48, 96]) == 200  # raw outlier
    bp = dataflow.BucketPad()
    assert bp._bucket(33, "pow2") == dataflow.bucket_length(33)


# -- backpressure & shedding -------------------------------------------------

def test_queue_backpressure_reject(model):
    srv = serve.Server(model, slots=1, queue_depth=2, shed="reject")
    reqs = [srv.submit(_prompt(4), max_new_tokens=4) for _ in range(5)]
    shed = [r for r in reqs if r.state == serve.SHED]
    assert len(shed) == 3
    assert all("503" in r.verdict and "queue full" in r.verdict
               for r in shed)
    srv.drain()
    assert all(r.state == serve.DONE for r in reqs if r not in shed)
    assert srv.stats()["shed"] == 3


def test_queue_shed_oldest(model):
    srv = serve.Server(model, slots=1, queue_depth=2, shed="oldest")
    reqs = [srv.submit(_prompt(4), max_new_tokens=4) for _ in range(4)]
    # the two oldest were displaced by the two newest
    assert [r.state for r in reqs[:2]] == [serve.SHED, serve.SHED]
    assert all("displaced" in r.verdict for r in reqs[:2])
    srv.drain()
    assert all(r.state == serve.DONE for r in reqs[2:])


# -- admission control -------------------------------------------------------

def test_admission_rejects_over_budget_429(model):
    srv = serve.Server(model, slots=2)
    cap = srv._params_bytes + srv._pool.pool_bytes() // 2
    config.set("device_bytes_limit", cap)
    r = srv.submit(_prompt(8), max_new_tokens=16)
    assert r.state == serve.REJECTED
    assert "429" in r.verdict and "capacity" in r.verdict
    srv.drain()                    # nothing dispatched, nothing raises
    assert srv.stats()["rejected"] == 1
    assert srv._groups == {}


def test_admission_budget_rides_memsafe(model):
    """The admission check IS memsafe's check_budget: a rejection leaves
    the accounting in memsafe.last_check and raises nothing out of the
    scheduler."""
    srv = serve.Server(model, slots=2)
    pred32 = srv._params_bytes + srv._pool.pool_bytes() \
        + (srv._exec_peak(32) or 0)
    config.set("device_bytes_limit", pred32 + 1)
    r = srv.submit(_prompt(4), max_new_tokens=4)
    srv.drain()
    assert r.state == serve.DONE
    chk = memsafe.last_check()
    assert chk is not None
    assert chk["executable"].startswith("serve.decode(bucket=32")
    assert chk["headroom_bytes"] >= 0


def test_prompt_too_long_rejected_413(model):
    srv = serve.Server(model, slots=2)
    r = srv.submit(_prompt(60), max_new_tokens=10)   # 70 > max_length 64
    assert r.state == serve.REJECTED and "413" in r.verdict


def test_submit_validation_raises(model):
    srv = serve.Server(model, slots=2)
    with pytest.raises(ValueError):
        srv.submit(np.zeros((0,), np.int32), max_new_tokens=4)
    with pytest.raises(ValueError):
        srv.submit(_prompt(4), max_new_tokens=0)


# -- deadlines ---------------------------------------------------------------

def test_deadline_expires_mid_generation(model):
    clk = _FakeClock()
    telemetry.enable()
    srv = serve.Server(model, slots=2, clock=clk)
    r = srv.submit(_prompt(3), max_new_tokens=30, deadline_ms=100)
    while srv.busy():
        srv.step()
        clk.t += 0.02          # the deadline passes mid-generation
    assert r.state == serve.EXPIRED
    assert "504" in r.verdict and "mid-generation" in r.verdict
    assert 0 < len(r.tokens) < 30      # partial tokens stay delivered
    assert srv._groups == {}           # KV pages reclaimed
    assert srv.stats()["expired"] == 1
    assert telemetry.get("serve_deadline_missed_total").value == 1


def test_deadline_expires_in_queue(model):
    clk = _FakeClock()
    srv = serve.Server(model, slots=1, clock=clk)
    a = srv.submit(_prompt(3), max_new_tokens=20)
    b = srv.submit(_prompt(3), max_new_tokens=4, deadline_ms=50)
    srv.step()                 # a takes the only slot; b waits
    clk.t = 1.0
    srv.step()
    assert b.state == serve.EXPIRED and "queue" in b.verdict
    srv.drain()
    assert a.state == serve.DONE and len(a.tokens) == 20


def test_default_deadline_knob(model):
    clk = _FakeClock()
    config.set("serve_deadline_ms", 80.0)
    srv = serve.Server(model, slots=2, clock=clk)
    r = srv.submit(_prompt(3), max_new_tokens=30)
    assert r.deadline == pytest.approx(0.08)
    clk.t = 1.0
    srv.step()
    assert r.state == serve.EXPIRED


# -- graceful degradation ----------------------------------------------------

@pytest.mark.parametrize("short_of", ["bytes", "pages"])
def test_degrade_shrink_max_new(model, short_of):
    """One ladder, whichever refusal starts it. `bytes`: bucket 64's
    step executable is over the byte budget beside parameters and pool,
    bucket 32's fits (and `pages="on"`, the keyword two configuration
    files still pass, is accepted: at PR 29 this server seated the
    shrunk request in a DENSE group and the next step died with
    AttributeError: '_Group' object has no attribute 'n_pg').
    `pages`: the pool holds 32 tokens, the request wants 50."""
    telemetry.enable()
    if short_of == "bytes":
        srv = serve.Server(model, slots=2, pages="on", page_size=4)
        # pinned: XLA's CPU temp bytes hardly differ between the buckets
        srv._exec_peaks.update({32: 4096, 64: 1 << 20})
        config.set("device_bytes_limit",
                   srv._params_bytes + srv._pool.pool_bytes() + (1 << 19))
    else:
        srv = serve.Server(model, slots=2, page_size=4, pool_pages=8)
    r = srv.submit(_prompt(10), max_new_tokens=40)    # wants bucket 64
    srv.drain()
    assert r.state == serve.DONE
    assert r.max_new_tokens == 22 and len(r.tokens) == 22
    assert r.degraded == "shrink_max_new:40->22"
    assert r.tokens == model.generate(
        _prompt(10)[None], max_new_tokens=22, on_device=False)[0].tolist()
    assert srv.stats()["degraded"] == 1
    evs = [e for e in telemetry.events("serve")
           if e.get("action") == "shrink_max_new"]
    assert evs and evs[0]["req"] == r.id


def test_degrade_evict_requeues_youngest_bit_exact_replay(model):
    solo = serve.Server(model, slots=1)
    ref = solo.submit(_prompt(4), max_new_tokens=50)
    solo.drain()

    # 14 pages of 4: the 54 positions of `a`, nothing more
    srv = serve.Server(model, slots=1, page_size=4, pool_pages=14)
    a = srv.submit(_prompt(4), max_new_tokens=50)     # bucket 64
    srv.step()
    assert a.state == serve.RUNNING
    assert srv._pool.free_pages() == 0
    b = srv.submit(_prompt(4), max_new_tokens=4)      # bucket 32: pressure
    srv.drain()
    assert b.state == serve.DONE
    # a was evicted (youngest running), requeued, and replayed to the
    # SAME tokens as the unloaded run — deterministic replay
    assert a.state == serve.DONE and a.requeues == 1
    assert a.degraded is None          # requeued requests are never shrunk
    assert a.tokens == ref.tokens
    st = srv.stats()
    assert st["requeues"] == 1 and st["degraded"] >= 1


def test_byte_refusal_evicts_nobody_and_rejects_at_once(model):
    """An eviction frees pages, not bytes: a request whose step
    executable does not fit beside parameters and pool, and that no
    smaller bucket can hold, is rejected with the accounting while the
    running request keeps its slot."""
    srv = serve.Server(model, slots=2, page_size=4)
    srv._exec_peaks.update({32: 4096, 64: 1 << 20})
    config.set("device_bytes_limit",
               srv._params_bytes + srv._pool.pool_bytes() + (1 << 19))
    a = srv.submit(_prompt(4), max_new_tokens=20)     # bucket 32: fits
    srv.step()
    assert a.state == serve.RUNNING
    b = srv.submit(_prompt(40), max_new_tokens=20)    # 64 or nothing
    srv.step()
    assert b.state == serve.REJECTED
    assert "429" in b.verdict and "capacity" in b.verdict
    assert a.state == serve.RUNNING and a.requeues == 0
    srv.drain()
    assert a.state == serve.DONE and srv.stats()["requeues"] == 0


def test_pages_freed_by_expiry_admit_same_step(model):
    """Pages returned by an eviction must be reusable by admission in
    the SAME scheduler step — an expired request's pages still counting
    against the pool would spuriously 429 (or shrink) a request the very
    next line would have had room for."""
    clk = _FakeClock()
    # 6 pages of 4: exactly the 24 positions of `a`
    srv = serve.Server(model, slots=1, clock=clk, page_size=4, pool_pages=6)
    a = srv.submit(_prompt(4), max_new_tokens=20, deadline_ms=50)
    srv.step()
    assert a.state == serve.RUNNING and srv._pool.free_pages() == 0
    clk.t = 1.0                                # a's deadline passes
    b = srv.submit(_prompt(4, seed=1), max_new_tokens=4)
    srv.step()              # one step: evict a AND admit b
    assert a.state == serve.EXPIRED
    assert b.state == serve.RUNNING, (b.state, b.verdict)
    srv.drain()
    assert b.state == serve.DONE and b.degraded is None


def test_by_id_pruned_after_terminal(model):
    srv = serve.Server(model, slots=2)
    reqs = [srv.submit(_prompt(4, i), max_new_tokens=4) for i in range(3)]
    srv.drain()
    assert all(r.state == serve.DONE for r in reqs)
    assert srv._by_id == {}     # no per-request leak in a long-lived server


def test_cancel_spec_waits_for_target(model):
    """A step-less cancel@req:N must stay armed until request N exists —
    an idling background scheduler tick must not burn it as a no-op."""
    config.set("fault_inject", "cancel@req:0")
    resilience.install()
    srv = serve.Server(model, slots=2)
    for _ in range(3):
        srv.step()              # idle ticks before any submission
    r = srv.submit(_prompt(4), max_new_tokens=8)
    srv.drain()
    assert r.state == serve.CANCELLED and "499" in r.verdict


# -- dispatch retry & scheduler failure --------------------------------------

def _flaky(srv, fails, exc=OSError("transient fabric glitch")):
    orig = srv._runner

    def runner(*key, **kw):
        run = orig(*key, **kw)

        def wrapped(*args):
            if fails["n"] > 0:
                fails["n"] -= 1
                raise exc
            return run(*args)

        wrapped.lower = run.lower
        return wrapped

    srv._runner = runner


def test_retry_transient_dispatch(model):
    srv = serve.Server(model, slots=2, retry=resilience.RetryPolicy(
        max_attempts=3, backoff_s=0.001))
    _flaky(srv, {"n": 2})
    r = srv.submit(_prompt(4), max_new_tokens=4)
    srv.drain()
    assert r.state == serve.DONE and len(r.tokens) == 4
    assert srv.stats()["retries"] == 2


def test_scheduler_error_fails_requests_not_clients(model):
    """A non-transient dispatch error in the background scheduler must
    surface as a 500 verdict on every live request — a client blocked in
    result() must never wedge on a dead scheduler."""
    srv = serve.Server(model, slots=2, retry=resilience.RetryPolicy(
        max_attempts=1))
    _flaky(srv, {"n": 100}, exc=ValueError("wedged runtime"))
    srv.start()
    r = srv.submit(_prompt(4), max_new_tokens=4)
    toks = r.result(timeout=10)
    assert r.state == serve.FAILED and "500" in r.verdict
    assert toks.size == 0
    with pytest.raises(ValueError):
        srv.raise_if_failed()
    # a submit AFTER the failure fails fast instead of enqueueing a
    # request no thread will ever drive
    r2 = srv.submit(_prompt(4), max_new_tokens=4)
    assert r2.state == serve.FAILED and "500" in r2.verdict
    srv.stop()


def test_stop_finishes_outstanding(model):
    srv = serve.Server(model, slots=1)
    reqs = [srv.submit(_prompt(4), max_new_tokens=30) for _ in range(3)]
    srv.step()
    srv.stop()
    assert all(r.done for r in reqs)
    assert any(r.state == serve.CANCELLED and "server stopped" in r.verdict
               for r in reqs)
    # a submit AFTER stop() is shed immediately, never silently queued
    r2 = srv.submit(_prompt(4), max_new_tokens=4)
    assert r2.state == serve.SHED and "server stopped" in r2.verdict


# -- fault injection ---------------------------------------------------------

def test_fault_cancel_spec_mid_generation(model):
    config.set("fault_inject", "cancel@req:0@step:4")
    resilience.install()
    srv = serve.Server(model, slots=2)
    r = srv.submit(_prompt(3), max_new_tokens=20)
    srv.drain()
    assert r.state == serve.CANCELLED and "499" in r.verdict
    assert 0 < len(r.tokens) < 20        # cancelled between decode steps
    assert srv._groups == {}             # slot evicted, pages reclaimed


def test_fault_burst_spec(model):
    config.set("fault_inject", "burst:3@step:2")
    resilience.install()
    srv = serve.Server(model, slots=4, queue_depth=2, shed="reject")
    extra = []
    srv.on_burst = lambda n: extra.extend(
        srv.submit(_prompt(5, i), max_new_tokens=6) for i in range(n))
    r = srv.submit(_prompt(4), max_new_tokens=10)
    srv.drain()
    assert len(extra) == 3
    assert r.state == serve.DONE
    assert all(e.done for e in extra)


def test_fault_slow_client_does_not_wedge_scheduler(model):
    config.set("fault_inject", "slow_client:20")
    resilience.install()
    srv = serve.Server(model, slots=2)
    r = srv.submit(_prompt(3), max_new_tokens=10)
    got = []
    th = threading.Thread(target=lambda: got.extend(r.stream()))
    th.start()
    t0 = time.perf_counter()
    srv.drain()
    drained = time.perf_counter() - t0
    assert r.state == serve.DONE          # scheduler finished regardless
    th.join(timeout=10)
    assert got == r.tokens                # slow client still got everything
    # the consumer stalled ~20ms * 10 tokens; the scheduler did not
    assert drained < 0.2 * len(r.tokens)


# -- zero-overhead fast path -------------------------------------------------

def test_serve_off_zero_overhead(model):
    serve.disable()
    assert not serve.enabled()
    calls = {"n": 0}
    real = serve.note_dispatch
    serve.note_dispatch = lambda *a, **k: (
        calls.__setitem__("n", calls["n"] + 1), real(*a, **k))[1]
    try:
        model.generate(_prompt(4)[None], max_new_tokens=4, on_device=False)
    finally:
        serve.note_dispatch = real
    assert calls["n"] == 0, "decode hook ran while serve disabled"
    serve.Server(model)          # constructing a Server arms it
    assert serve.enabled()


# -- observability -----------------------------------------------------------

def test_telemetry_counters(model):
    telemetry.enable()
    srv = serve.Server(model, slots=2, queue_depth=2, shed="reject")
    a = srv.submit(_prompt(4), max_new_tokens=5)
    b = srv.submit(_prompt(4), max_new_tokens=5)   # queued
    c = srv.submit(_prompt(4), max_new_tokens=5)   # shed: queue holds 2
    srv.drain()
    assert c.state == serve.SHED
    m = telemetry.get("serve_requests_total")
    assert m.labels(outcome="completed").value == 2
    assert m.labels(outcome="shed").value == 1
    assert telemetry.get("serve_tokens_total").value == 10
    assert telemetry.get("serve_ttft_seconds").count == 2
    assert telemetry.get("serve_queue_wait_seconds").count == 2


def test_guard_heartbeat_serve_phase(model, tmp_path):
    guard.enable(guard_dir=str(tmp_path))
    srv = serve.Server(model, slots=2)
    srv.submit(_prompt(4), max_new_tokens=4)
    srv.drain()
    assert guard._beat is not None
    assert guard._beat["phase"] == "serve"


def test_trace_spans_cover_lifecycle(model):
    trace.enable(sample_every=1)
    srv = serve.Server(model, slots=2)
    r = srv.submit(_prompt(4), max_new_tokens=5)
    srv.drain()
    assert r.state == serve.DONE
    spans = trace.spans()
    names = {s["name"] for s in spans}
    assert {"serve.admit", "serve.queue_wait", "serve.decode_step",
            "serve.stream"} <= names
    # (`phase`: the spans that enclose or subdivide the older ones, kept
    # out of the category sums so no second is counted twice)
    assert all(s["cat"] in ("serve", "phase") for s in spans
               if s["name"].startswith("serve."))
    cp = trace.critical_path()
    assert cp is not None and cp["cat"] == "serve"


def _trace_report_module():
    import importlib.util
    spec = importlib.util.spec_from_file_location("_trace_report_serve_ut",
                                                  TRACE_REPORT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_report_serve_verdicts():
    tr = _trace_report_module()
    queue_bound = {0: {"by_cat": {"serve": 300e3},
                       "by_span": {"serve.queue_wait": 250e3,
                                   "serve.decode_step": 50e3},
                       "steps": []}}
    kind, rank, dom, _detail = tr._verdict(queue_bound, [])
    assert (kind, rank, dom) == ("queue-bound", 0, "serve.queue_wait")
    decode_bound = {0: {"by_cat": {"serve": 300e3},
                        "by_span": {"serve.queue_wait": 40e3,
                                    "serve.decode_step": 260e3},
                        "steps": [],
                        "rungs": {32: [2, 40], 64: [1, 56]},
                        "fetched": [1536, 2, 2]}}
    kind, rank, dom, detail = tr._verdict(decode_bound, [])
    assert (kind, rank, dom) == ("decode-bound", 0, "serve.decode_step")
    assert "passes 75.0% full" in detail
    assert "1.50 passes a step, by rung: 32 x 2 62% full, 64 x 1 88% full" \
        in detail
    assert "fetched 768 bytes a step (2 logits rows in 2 steps)" in detail
    # a TRAINING window with step spans keeps its old verdicts even if a
    # serve span leaked into it
    train = {0: {"by_cat": {"step": 100e3, "serve": 10e3},
                 "by_span": {"step.dispatch": 90e3, "step.fence": 10e3},
                 "steps": [100e3]}}
    kind, _rank, _dom, _detail = tr._verdict(train, [])
    assert kind == "compute-bound"


def test_trace_report_end_to_end_serve_window(model, tmp_path):
    trace.enable(trace_dir=str(tmp_path), rank=0, sample_every=1)
    srv = serve.Server(model, slots=2)
    for i in range(3):
        srv.submit(_prompt(4, i), max_new_tokens=4)
    srv.drain()
    trace.flush()
    tr = _trace_report_module()
    files = tr.discover([str(tmp_path)])
    ranks = {rank: tr.load(path) for rank, path in files}
    offsets, _ref = tr._offsets_us(ranks)
    text = tr.report(ranks, offsets)
    assert "verdict: decode-bound" in text or "verdict: queue-bound" in text
    assert "passes a step, by rung: " in text


# -- overload acceptance smoke ----------------------------------------------

@pytest.mark.slow
def test_overload_acceptance_smoke(model):
    """The ISSUE acceptance scenario in one run: queue full + slow
    client + deadline expiry + forced MemoryBudgetError at admission +
    an injected burst + a mid-generation cancel. The server never
    raises out of the scheduler loop, never dispatches a
    predicted-overrun batch, evicts expired requests between decode
    steps, and every COMPLETED request's tokens are bit-identical to
    its unloaded single-request generation."""
    config.set("fault_inject",
               "slow_client:10,burst:2@step:6,cancel@req:1@step:5")
    resilience.install()
    telemetry.enable()
    clk = _FakeClock()
    srv = serve.Server(model, slots=3, queue_depth=3, shed="reject",
                       clock=clk)
    # bucket 32's step fits beside parameters and pool, bucket 64's not
    srv._exec_peaks.update({32: 4096, 64: 1 << 20})
    config.set("device_bytes_limit",
               srv._params_bytes + srv._pool.pool_bytes() + (1 << 19))
    extra = []
    srv.on_burst = lambda n: extra.extend(
        srv.submit(_prompt(5, 50 + i), max_new_tokens=5) for i in range(n))

    reqs = [srv.submit(_prompt(3 + i, i), max_new_tokens=6 + i)
            for i in range(2)]                        # r0=id0, r1=id1
    srv.step()                                        # both take slots
    # id2: wants bucket 64 -> MemoryBudgetError at admission; the shrink
    # rung clamps it into the free slot of the affordable 32 bucket
    big = srv.submit(_prompt(10, 7), max_new_tokens=40)
    # id3: no bucket under 64 holds it -> 429 at its first admission
    over = srv.submit(_prompt(40, 8), max_new_tokens=20)
    late = srv.submit(_prompt(3, 9), max_new_tokens=25, deadline_ms=300)
    flood = [srv.submit(_prompt(4, 20 + i), max_new_tokens=4)
             for i in range(4)]                       # overflows the queue

    consumer = threading.Thread(target=lambda: list(reqs[0].stream()))
    consumer.start()
    while srv.busy():
        srv.step()
        clk.t += 0.02
    consumer.join(timeout=10)

    assert srv._error is None                 # nothing escaped the loop
    # forced memory rejection at admission -> 429 verdict, and the
    # over-budget bucket was never allocated, much less dispatched
    assert over.state == serve.REJECTED and "429" in over.verdict
    assert 64 not in srv.stats()["buckets_allocated"]
    assert 64 not in srv._groups
    # the pressured request was admitted DEGRADED, not crashed
    assert big.state == serve.DONE
    assert big.degraded and big.max_new_tokens == 22
    # deadline-expired request evicted BETWEEN decode steps, mid-flight
    assert late.state == serve.EXPIRED and "504" in late.verdict
    assert "mid-generation" in late.verdict
    # queue overflow shed with the policy's verdict
    assert any(f.state == serve.SHED and "503" in f.verdict
               for f in flood)
    # injected cancel landed mid-generation
    assert reqs[1].state == serve.CANCELLED
    assert 0 < len(reqs[1].tokens) < reqs[1].max_new_tokens
    # everything reached a terminal state: no wedged clients
    for r in reqs + extra + flood + [big, over, late]:
        assert r.done, r
    # bit-identical to unloaded single-request generation
    completed = [r for r in reqs + extra + flood + [big]
                 if r.state == serve.DONE]
    assert completed, "overload run completed nothing"
    config.reset("device_bytes_limit")
    for r in completed:
        solo = serve.Server(model, slots=3)
        sr = solo.submit(r.prompt, max_new_tokens=r.max_new_tokens)
        solo.drain()
        assert sr.tokens == r.tokens, f"load-dependent output for {r}"
    st = srv.stats()
    assert st["expired"] >= 1 and st["shed"] >= 1 and st["degraded"] >= 1
    assert telemetry.get("serve_deadline_missed_total").value >= 1


# ---------------------------------------------------------------------------
# int8 decode path (mx.kernels: pallas_ops.int8_matmul via QuantizedDense)
# ---------------------------------------------------------------------------

def _quantized_models():
    """Two copies of the same seeded model: one on the int8 decode path,
    one dequantize-then-fp (the reference oracle) — identical int8
    weights by construction."""
    from mxnet_tpu.contrib import quantization as quant

    parallel.make_mesh(dp=-1)
    cfg = gpt_mod.gpt_tiny_config()
    q = gpt_mod.GPTForCausalLM(cfg)
    mx.random.seed(0)
    q.initialize()
    quant.quantize_block(q)
    s = gpt_mod.GPTForCausalLM(cfg)
    mx.random.seed(0)
    s.initialize()
    quant.quantize_block(s, simulate=True)
    return q, s


def test_serve_int8_tokens_match_dequantized_reference():
    """The acceptance gate: the int8 serving decode (int8xint8->int32
    matmul with fused per-channel rescale) produces IDENTICAL tokens to
    the dequantized-fp reference on a fixed seed, through the real
    continuous-batching scheduler."""
    qmodel, smodel = _quantized_models()
    prompts = [_prompt(5, seed=3), _prompt(9, seed=4), _prompt(3, seed=5)]

    def serve_all(mdl):
        # greedy decode: the int8 accumulator differs from the fp
        # reference only in last-ulp rounding, which argmax absorbs; a
        # sampled comparison would test the sampler's tie-breaks, not
        # the decode path
        srv = serve.Server(mdl, slots=2)
        reqs = [srv.submit(p, max_new_tokens=6, seed=17 + i)
                for i, p in enumerate(prompts)]
        srv.drain()
        assert all(r.state == serve.DONE for r in reqs)
        return [list(r.tokens) for r in reqs]

    assert serve_all(qmodel) == serve_all(smodel)


def test_serve_int8_memory_accounting_stays_correct():
    """Per-request KV/memory accounting on the quantized server: the
    resident-params measurement sees the int8 footprint (smaller than
    fp32), the pool's bytes are unchanged (arenas stay in the model
    dtype), and the admission budget check still runs pre-dispatch."""
    qmodel, _ = _quantized_models()
    fp = model_fp = gpt_mod.GPTForCausalLM(gpt_mod.gpt_tiny_config())
    mx.random.seed(0)
    model_fp.initialize()
    srv_fp = serve.Server(fp, slots=2)
    srv_q = serve.Server(qmodel, slots=2)
    assert 0 < srv_q._params_bytes < srv_fp._params_bytes
    assert srv_q._pool.pool_bytes() == srv_fp._pool.pool_bytes() > 0
    # the budget path still produces a verdict under a tiny simulated
    # capacity: a request that cannot fit is 429'd, never dispatched
    config.set("device_bytes_limit", srv_q._params_bytes + 1)
    memsafe.enable()
    try:
        r = srv_q.submit(_prompt(5), max_new_tokens=4)
        srv_q.drain()
        assert r.state == serve.REJECTED, (r.state, r.verdict)
        assert "429" in (r.verdict or "")
    finally:
        config.reset("device_bytes_limit")
        memsafe.disable()


def test_serve_int8_decode_check_lint_quiet():
    """The quantized decode executable's traced form is finding-free:
    int8 weights ride as jit arguments (Constants), not baked closure
    constants — mx.check's large-constant rule must stay quiet and the
    KV caches stay donated."""
    qmodel, _ = _quantized_models()
    mxcheck.reset()
    config.set("check", "warn")
    mxcheck.enable()
    try:
        srv = serve.Server(qmodel, slots=2)
        r = srv.submit(_prompt(6), max_new_tokens=4)
        srv.drain()
        assert r.state == serve.DONE
        assert mxcheck.findings() == [], mxcheck.findings()
    finally:
        mxcheck.disable()
        config.reset("check")
        mxcheck.reset()
